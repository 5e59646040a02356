"""Benchmark of the served retrieval path on one accelerator.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run is one process that holds the chip. It resolves the cell
``<name>`` of ``BENCHMARK.json`` to its configuration, traffic mix and
per-layer metric files (``bench/spec.py``), draws the corpus and the
queries from ``--seed`` (``bench/corpus.py``), builds the index through
the program's ``Retriever``, warms every shape the mix uses, measures
for ``--seconds``, and then checks the served answers against the
plain reference (``bench/reference.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the same window and reports its per-layer
metrics. ``--control`` serves the configuration's lower-precision
control instead (its ``control`` entry); it must come out not correct.

It refuses to run (exit 2, no result) when JAX finds no TPU, fewer
chips than the cell asks for, or a kernel lowering other than Mosaic.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its
limit. The same numbers are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import corpus, loadgen, reference, roofline, spec  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

#: the jax.monitoring events that mark a trace or a compile
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoChip(RuntimeError):
    """JAX found no chip to measure on."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, require_chip: bool = True) -> dict:
    """The device as JAX names it; raises ``NoChip`` where the cell
    cannot be measured (no TPU, too few chips, no Mosaic lowering)."""
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
        from repro.kernels.modes import resolve_lowering

        lowering = resolve_lowering(None)
        if lowering != "mosaic":
            raise NoChip(f"the kernels would lower through {lowering!r}, not Mosaic")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def seeds(seed: int):
    """(corpus, traffic, sample) seeds, each 32 bits, from any ``--seed``."""
    return [int(s) for s in np.random.SeedSequence(seed).generate_state(3)]


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def profile(config: dict, n_queries: int, seed: int) -> corpus.Profile:
    """The configuration's corpus profile with ``n_queries`` queries."""
    c = config["corpus"]
    return corpus.Profile(
        dim=c["dim"], n_docs=c["n_docs"], n_queries=n_queries,
        doc_nnz_mean=c["doc_nnz_mean"], query_nnz_mean=c["query_nnz_mean"],
        n_topics=c["n_topics"], topic_concentration=c["topic_concentration"],
        zipf_a=c["zipf_a"], value_shape=c["value_shape"], value_scale=c["value_scale"],
        seed=seed,
    )


def build(config: dict, corp):
    """The program's index over ``corp``, as the configuration states."""
    from repro.core.forward_index import VALUE_FORMATS, ForwardIndex
    from repro.serve.api import Retriever, RetrieverConfig

    fwd = ForwardIndex(
        components=corp.components, values=corp.values, offsets=corp.offsets,
        dim=corp.dim, value_format=VALUE_FORMATS[config["corpus"]["value_format"]],
    )
    return Retriever.build(fwd, RetrieverConfig(**config["retriever"]))


def warm(r, config: dict, traffic: dict, Q: np.ndarray):
    """Compile and run every shape the mix will use; returns the
    pipeline an open mix is served through (None for a closed one)."""
    if traffic["loop"] == "closed":
        batch = int(traffic["batch"])
        for b in range(2):  # the bucket's plan, compiled, then once warm
            np.asarray(r.search(Q[b * batch:(b + 1) * batch])[0])
        return None
    p = config["pipeline"]
    pipe = r.pipeline(buckets=p["buckets"], deadline_us=p["deadline_us"],
                      cache_size=p["cache_size"])
    zeros = np.zeros((max(pipe.plans.buckets), Q.shape[1]), np.float32)
    for n in range(1, len(zeros) + 1):  # every batch size a dispatch can take
        np.asarray(pipe.plans.get(pipe.plans.bucket_for(n))(zeros[:n])[0])
    return pipe


@contextlib.contextmanager
def count_compiles():
    """Counts traces and compiles inside the block."""
    import jax

    seen = []

    def listener(event, duration, **kw):
        if event in COMPILE_EVENTS:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, control: bool = False, t_start: float | None = None,
             out=print) -> dict:
    """One run of ``cell`` → the result object (also printed via ``out``)."""
    import jax

    from repro.launch import compile_cache

    t_start = T_START if t_start is None else t_start
    chips = int(cell.workload["chips"])
    device = device_info(chips, require_chip)
    compile_cache.enable()
    log(f"# device: {device} at {time.perf_counter() - t_start:.1f} s")
    config = _merge(cell.config, cell.config["control"]) if control else cell.config
    traffic = cell.traffic
    corpus_seed, traffic_seed, sample_seed = seeds(seed)
    n_q = loadgen.n_requests(traffic, seconds)
    t = time.perf_counter()
    corp = corpus.generate(profile(config, n_q, corpus_seed))
    Q = corp.queries_dense()
    log(f"# corpus: {corp.n_docs} docs, {len(Q)} queries in {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    r = build(config, corp)
    index_bytes = sum(int(a.nbytes) for a in r.arrays.values())
    log(f"# build: {config['retriever']} in {time.perf_counter() - t:.1f} s, "
        f"{index_bytes} B on the device")
    t = time.perf_counter()

    pipe = warm(r, config, traffic, Q)
    if pipe is not None:
        due = loadgen.schedule(traffic, seconds, np.random.default_rng(traffic_seed))
    setup_s = time.perf_counter() - t_start
    log(f"# setup: {setup_s:.2f} s (warm-up {time.perf_counter() - t:.1f} s)")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = jax.profiler.TraceAnnotation if trace else loadgen.no_span
    with count_compiles() as compiles:
        if trace:
            jax.profiler.start_trace(trace_dir)
        with span("bench.window"):
            if pipe is None:
                res = loadgen.closed(r.search, Q, traffic["batch"], seconds, span=span)
            else:
                res = loadgen.open_loop(pipe, Q, due, span=span)
        if trace:
            jax.profiler.stop_trace()
    log(f"# window: {res['completed']}/{res['attempted']} requests in "
        f"{res['elapsed_s']:.3f} s; traces or compiles inside it: {len(compiles)}")
    if "batch_s" in res:
        b = res["batch_s"]
        log(f"# {len(b)} batches, seconds each: min {b.min():.4f} median "
            f"{np.median(b):.4f} max {b.max():.4f}")
    peak = memory_peak(chips)
    fill = (pipe.stats.dispatches, pipe.stats.occupancy) if pipe is not None else None
    k = int(config["retriever"]["k"])
    del r, pipe
    gc.collect()
    jax.clear_caches()

    # the served answers against the plain reference
    rng = np.random.default_rng(sample_seed)
    m = len(res["query_index"])
    pick = np.sort(rng.choice(m, size=min(m, int(traffic["check_sample"])), replace=False))
    t = time.perf_counter()
    got = reference.compare(corp, Q[res["query_index"][pick]], res["ids"][pick],
                            res["scores"][pick], k)
    log(f"# reference over {len(pick)} answers: {time.perf_counter() - t:.1f} s")
    failed = int(res["attempted"] - res["completed"])
    got["failed_requests"] = failed
    checks = {name: {"value": float(got[name]), "limit": float(lim)}
              for name, lim in {**config["checks"], "failed_requests": 0}.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    values = {
        "setup_s": setup_s,
        "index_bytes_per_doc": index_bytes / corp.n_docs,
        "recall_at_10": got["recall_at_k"],
    }
    if traffic["loop"] == "closed":
        values["qps"] = res["completed"] / res["elapsed_s"]
    if traffic["loop"] == "open":
        values["p95_ms"] = 1e3 * loadgen.nearest_rank(res["latency_s"], 0.95)
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": int(res["attempted"]), "failed": failed}
    if trace:
        red = trace_mod.reduce(trace_mod.find_xplane(trace_dir), n_chips=chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red.update(loop=traffic["loop"], completed=res["completed"], device_kind=device["kind"])
        if traffic["loop"] == "closed":
            batch = traffic["batch"]
            red.update(calls=len(res["ids"]) // batch,
                       work=roofline.batch_call_work(corp, config["retriever"]["codec"],
                                                     config["retriever"]["vq"], batch))
            got_share = roofline.share(red["work"], red["calls"], red.get("kernel_s", 0.0),
                                       device["kind"])
            if got_share is not None:
                log(f"# rows kernel: {red['work']['bytes']} B and {red['work']['flops']} "
                    f"operations per call; {got_share[0]:.6g} % of its roofline, "
                    f"{got_share[1]}-bound")
        else:
            dispatches, occupancy = fill
            red.update(gen_lag_s=res["gen_lag_s"],
                       slots=sum(b * n for b, n in dispatches.items()),
                       real=sum(occupancy.values()))
        metrics = {}
        for m_ in cell.per_layer:  # each reader returns None where it finds nothing
            v = spec.metric_reader(m_["name"])(red)
            if v is not None:
                metrics[m_["name"]] = {"value": float(v), "unit": m_["unit"]}
        device.update(busy_s=red.get("busy_s", 0.0), window_s=red.get("window_s", 0.0))
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": red.get("device_ops", []),
                                 "idle_gaps": red.get("idle_gaps", [])})
    else:
        result.update(metrics={m_["name"]: {"value": float(values[m_["name"]]),
                                            "unit": m_["unit"]} for m_ in cell.end_to_end},
                      device=device)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} <= {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    out(json.dumps(result))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve the configuration's lower-precision control")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    try:
        run_cell(cell, args.seed, args.seconds, bool(args.trace), control=args.control,
                 out=lambda s: print(s, flush=True))
    except NoChip as e:
        log(f"refused: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
