"""Smoke run of the served path on the TPU, through the user entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded mesh path on four chips

One chip: a synthetic msmarco-splade corpus (dim 30522, 119 nonzeros
per document, 43 per query — ``data.synthetic.splade_config``), drawn
on the device from ``--seed``, served by

* ``flat`` + ``dotvbyte`` over ``FLAT_DOCS`` documents, with
  ``backend="pallas"`` (the fused rows kernel, compiled by Mosaic) and
  ``backend="jnp"`` (XLA on the chip): both top-k lists must equal the
  host exact reference, and the scores must agree to f16 tolerance;
* ``seismic`` + ``dotvbyte`` and ``seismic`` + ``streamvbyte`` over the
  first ``SEISMIC_DOCS`` documents: recall@10 against the exact
  reference is printed, and pallas and jnp must return the same ids;
* the micro-batching pipeline (``submit``/``poll``/``flush``) over the
  flat pallas index: every response must equal direct search.

Four chips: ``flat`` + ``dotvbyte`` built with ``n_shards=4`` and
``use_mesh=True``, whose top-k must equal the unsharded build's byte
for byte, with each shard's arrays on their own device.

It runs in this one process and starts none. It exits non-zero on any
failed check, and before any work when JAX finds no TPU or the
kernels would not lower through Mosaic. The lines before the last
report one run on the named device; they are not benchmark results.
The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

K = 10
#: the flat corpus: the largest the host builds and checks in minutes
FLAT_DOCS = 200_000
#: the Seismic corpus (its host build costs a few ms per document)
SEISMIC_DOCS = 20_000
N_QUERIES = 32


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_checks(chips: int) -> dict:
    """The run needs a TPU and the Mosaic lowering; anything else fails
    here, before any work."""
    import jax

    from repro.kernels.modes import resolve_lowering

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"JAX found no TPU (platform {d.platform!r})")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)} device(s)")
    lowering = resolve_lowering(None)
    check(lowering == "mosaic", f"the kernels would lower through {lowering!r}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def make_corpus(n_docs: int, n_queries: int, seed: int):
    from repro.data.synthetic import generate_collection_device, splade_config

    t = time.perf_counter()
    col = generate_collection_device(
        splade_config(n_docs=n_docs, n_queries=n_queries, seed=seed), "f16"
    )
    Q = np.stack([col.query_dense(i) for i in range(col.n_queries)])
    nnz = np.diff(col.fwd.offsets)
    log(f"# corpus: {col.fwd.n_docs} docs x dim {col.fwd.dim}, "
        f"{nnz.mean():.1f} nnz/doc, {len(Q)} queries x "
        f"{np.mean([len(c) for c in col.query_comps]):.1f} nnz/query, "
        f"generated in {time.perf_counter() - t:.1f} s")
    return col.fwd, Q


def exact_reference(fwd, Q):
    """Host numpy ground truth (``ForwardIndex.exact_scores``): per
    query (top-k ids, top-k scores, all scores)."""
    t = time.perf_counter()
    ref = []
    for q in Q:
        full = fwd.exact_scores(q)
        ids = np.argsort(-full, kind="stable")[:K]
        ref.append((ids, full[ids], full))
    log(f"# exact reference over {fwd.n_docs} docs: {time.perf_counter() - t:.1f} s")
    return ref


def check_exact(name: str, ids, ref) -> None:
    """Every query's top-k is an exact top-k: the reference's ids, or —
    where scores tie — ids whose exact scores equal the reference's."""
    for i, (rid, rs, full) in enumerate(ref):
        got = np.asarray(ids[i])
        if np.array_equal(got, rid):
            continue
        check(len(set(got.tolist())) == len(got)
              and np.allclose(full[got], rs, rtol=1e-6, atol=1e-6),
              f"{name}: query {i} top-{K} {got.tolist()} != exact {rid.tolist()}")


def index_bytes(r) -> int:
    return int(sum(a.nbytes for a in r.arrays.values()))


def with_backend(r, backend: str):
    """A second handle over the same device arrays, on another backend."""
    from repro.serve.api import Retriever

    return Retriever(
        r.cfg.replace(backend=backend), r.arrays, n_docs=r.n_docs, dim=r.dim,
        value_scale=r.value_scale, value_format=r.value_format,
    )


def serve(name: str, r, Q, want_kernel: bool, batch: int | None = None):
    """Compile the plan for ``batch`` queries, run ``Q`` through it in
    batches cold and warm → host (ids, scores); checks the kernel is in
    (or out of) the program."""
    batch = batch or len(Q)
    plan = r.plans.get(r.plans.bucket_for(batch))
    t = time.perf_counter()
    plan.warm(r.dim)
    compile_s = time.perf_counter() - t
    has_kernel = "tpu_custom_call" in plan.executable.as_text()
    check(has_kernel == want_kernel,
          f"{name}: tpu_custom_call {'missing from' if want_kernel else 'in'} "
          f"the compiled search")

    def run():
        out = [r.search(Q[i : i + batch]) for i in range(0, len(Q), batch)]
        return (np.concatenate([np.asarray(o[0]) for o in out]),
                np.concatenate([np.asarray(o[1]) for o in out]))

    run()
    t = time.perf_counter()
    ids, scores = run()
    warm_s = time.perf_counter() - t
    log(f"# {name}: index {index_bytes(r) / 2**20:.1f} MiB on device, "
        f"compile {compile_s:.1f} s, warm search {1e3 * warm_s:.2f} ms "
        f"for {len(Q)} queries in batches of {batch}")
    return ids, scores


def flat_phase(fwd, Q, ref, backend: str = "pallas"):
    from repro.serve.api import Retriever, RetrieverConfig

    t = time.perf_counter()
    rp = Retriever.build(
        fwd, RetrieverConfig(engine="flat", codec="dotvbyte", backend=backend, k=K)
    )
    log(f"# flat+dotvbyte: build {time.perf_counter() - t:.1f} s")
    ip, sp = serve(f"flat+dotvbyte/{backend}", rp, Q, want_kernel=backend == "pallas")
    # the jnp chain materialises every row's gathered query values per
    # query ([batch, N, L] f32), so it runs in small batches at this size
    ij, sj = serve("flat+dotvbyte/jnp", with_backend(rp, "jnp"), Q, want_kernel=False,
                   batch=4)
    check_exact(f"flat/{backend}", ip, ref)
    check_exact("flat/jnp", ij, ref)
    check(np.allclose(sp, sj, rtol=1e-3, atol=2e-3),
          f"flat: {backend} and jnp scores differ by {np.abs(sp - sj).max()}")
    log(f"# flat: top-{K} equals the exact reference on all {len(Q)} queries "
        f"(pallas and jnp); max |pallas - jnp| score {np.abs(sp - sj).max():.3g}")
    return rp, ip, sp


def seismic_phase(fwd, Q, ref, backend: str = "pallas"):
    from repro.core.seismic import recall_at_k
    from repro.serve.api import Retriever, RetrieverConfig, get_engine

    cfg = RetrieverConfig(engine="seismic", codec="dotvbyte", backend=backend, k=K)
    t = time.perf_counter()
    index = get_engine("seismic").host_index(fwd, cfg)
    log(f"# seismic: host build {time.perf_counter() - t:.1f} s over {fwd.n_docs} docs")
    for codec in ("dotvbyte", "streamvbyte"):
        rp = Retriever.from_host_index(index, cfg.replace(codec=codec))
        ip, _ = serve(f"seismic+{codec}/{backend}", rp, Q, want_kernel=backend == "pallas")
        ij, _ = serve(f"seismic+{codec}/jnp", with_backend(rp, "jnp"), Q, want_kernel=False)
        check(np.array_equal(ip, ij), f"seismic+{codec}: {backend} and jnp top-{K} ids differ")
        rec = np.mean([recall_at_k(r[0], ip[i]) for i, r in enumerate(ref)])
        log(f"# seismic+{codec}: recall@{K} {rec:.3f} against the exact reference "
            f"(pallas and jnp ids identical)")


def pipeline_phase(r, Q, ids, scores) -> None:
    """Requests through submit/poll/flush, then repeats served from the
    result cache: every response equals direct search."""
    pipe = r.pipeline(deadline_us=60e6)
    tickets = [pipe.submit(q) for q in Q]
    pipe.poll()
    pipe.flush()
    repeats = [(i, pipe.submit(Q[i])) for i in range(0, len(Q), 2)]
    for i, t in list(enumerate(tickets)) + repeats:
        got_ids, got_scores = t.result()
        check(np.array_equal(got_ids, ids[i]) and np.array_equal(got_scores, scores[i]),
              f"pipeline: response {i} differs from direct search")
    hits = sum(t.from_cache for _, t in repeats)
    log(f"# pipeline: {len(tickets) + len(repeats)} requests "
        f"({hits} from the result cache) equal direct search")


def mesh_phase(fwd, Q, n_shards: int, backend: str = "pallas") -> None:
    """Sharded flat over a device mesh vs the unsharded build."""
    from repro.serve.api import Retriever, RetrieverConfig

    cfg = RetrieverConfig(engine="flat", codec="dotvbyte", backend=backend, k=K)
    ru = Retriever.build(fwd, cfg)
    iu, su = serve("flat+dotvbyte unsharded", ru, Q, want_kernel=backend == "pallas")
    t = time.perf_counter()
    rs = Retriever.build(fwd, cfg.replace(n_shards=n_shards))
    rs.use_mesh = True  # no quiet fallback to the sequential rotation
    log(f"# sharded build: {n_shards} shards in {time.perf_counter() - t:.1f} s")
    np.asarray(rs.search(Q)[0])
    t = time.perf_counter()
    ids, scores = rs.search(Q)
    ids, scores = np.asarray(ids), np.asarray(scores)
    log(f"# mesh search over {n_shards} devices: warm {1e3 * (time.perf_counter() - t):.2f} ms "
        f"for {len(Q)} queries")
    check(np.array_equal(ids, iu) and np.array_equal(scores, su),
          "mesh top-k differs from the unsharded top-k")
    placement = rs.shard_devices()
    check(placement, "the sharded retriever is not on the mesh path")
    for name, devs in placement.items():
        check(len(set(devs)) == n_shards,
              f"{name}: shards share devices {devs}, want {n_shards} distinct")
    log(f"# mesh top-{K} byte-identical to unsharded on all {len(Q)} queries; "
        f"shard s of every array on device ids {placement['nnz_rows']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch import compile_cache

    compile_cache.enable()
    device = device_checks(args.chips)
    log(f"# device: {device['kind']} x{device['count']} ({device['platform']}); "
        f"one smoke run on this device, not a benchmark result")

    fwd, Q = make_corpus(FLAT_DOCS, N_QUERIES, args.seed)
    if args.chips > 1:
        mesh_phase(fwd, Q, args.chips)
    else:
        rp, ids, scores = flat_phase(fwd, Q, exact_reference(fwd, Q))
        small = fwd.slice(0, SEISMIC_DOCS)
        seismic_phase(small, Q, exact_reference(small, Q))
        pipeline_phase(rp, Q, ids, scores)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
