"""Serving launcher: build (or load) an ANNS index over a synthetic
MsMarco-like collection and serve batched queries through the unified
``repro.serve.api`` Retriever surface.

``python -m repro.launch.serve --engine seismic --codec dotvbyte
--n-docs 20000 --n-queries 64`` builds the collection + index, runs
batched searches, and reports recall@10 + latency. Engine choices come
straight from the registry (plus ``both`` = seismic+hnsw and ``all`` =
every registered engine, ``flat`` included); codec choices come from
``repro.core.layout.available_layouts()``, so a newly registered
engine or codec reaches this CLI with zero edits. ``--compare-codecs``
sweeps every serving codec over the same host index.

The build/serve split (DESIGN.md §7): ``--save-index DIR`` writes one
artifact per engine×codec under ``DIR/<engine>-<codec>/`` (manifest +
packed arrays + the top-k of this run); ``--load-index DIR`` skips the
build, serves from the artifacts, and — when the saved top-k is
present — verifies the reopened index returns byte-identical results
(the ``make serve-roundtrip`` smoke).

``--pipeline`` switches to the online-serving load generator
(DESIGN.md §8): a seeded synthetic traffic trace (one request at a
time, a Zipf-ish repeat-heavy head to exercise the result cache,
optional ``--trace-qps`` pacing) is driven through the micro-batching
scheduler; every response is verified byte-identical to a direct
``Retriever.search`` of the same query, then the ServeStats block
(QPS, p50/p95/p99, hit rate, bucket occupancy, recompiles) is
reported — the ``make pipeline-smoke`` gate.

``--mutate`` is the live-mutation load generator (DESIGN.md §10): a
seeded insert/delete/update stream interleaved with the query trace,
served through the micro-batching pipeline over a ``MutableRetriever``
(delta segments + tombstones). At every checkpoint — after each
mutation round and again after the final merge/compaction — EVERY
response since the previous checkpoint is verified byte-identical to a
freshly built oracle index over the post-mutation corpus, and the
ResultCache must show an epoch invalidation per round (a cached answer
never survives a mutation). Engine budgets are forced exhaustive so
parity is byte-exact — keep ``--n-docs`` small (≲ 200) in this mode.

The HNSW host build is a few ms per document — prefer ``--n-docs``
in the low thousands when sweeping the graph engine interactively.
"""

from __future__ import annotations

import argparse
import pathlib
import time

import numpy as np


def _device_label() -> str:
    """The device the timings below ran on, as JAX reports it."""
    import jax

    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind} x{jax.device_count()}"


def _report(name, codec, k, recs, dt_us, col, extra=""):
    comp_bytes = col.fwd.storage_bytes(codec)["components"]
    raw_bytes = col.fwd.storage_bytes("uncompressed")["components"]
    print(
        f"{name:8s} codec={codec:13s} recall@{k}={np.mean(recs):.3f} "
        f"latency={dt_us:7.0f}µs/q ({_device_label()}) "
        f"components={comp_bytes/2**20:.1f}MiB ({8*comp_bytes/col.fwd.total_nnz:.1f} "
        f"bits/comp vs 16.0 raw, {100*(1-comp_bytes/raw_bytes):.0f}% saved){extra}"
    )


def _pipeline_loadgen(retriever, Q, args, rng) -> str:
    """Drive a synthetic traffic trace through the micro-batching
    scheduler and verify every response against direct search.

    The trace is repeat-heavy (``--repeat-frac`` of requests re-ask one
    of a few head queries — the shape of real query logs) so the
    result cache sees hits; ``--trace-qps`` > 0 paces arrivals in real
    time, 0 means closed-loop back-to-back (deadline dispatches then
    fire while previous batches compute). Returns the stats summary;
    raises AssertionError on any parity violation."""
    from repro.serve.pipeline import ServeStats, synthetic_trace

    trace = synthetic_trace(rng, args.requests, Q.shape[0],
                            repeat_frac=args.repeat_frac)
    direct_ids, direct_scores = retriever.search(Q)
    direct_ids, direct_scores = np.asarray(direct_ids), np.asarray(direct_scores)

    pipe = retriever.pipeline(deadline_us=args.deadline_us,
                              cache_size=args.cache_size)
    # compile cost out of the measured trace (benchmarks/common.py
    # warmup discipline): p50/p95/p99 below cover warm dispatches only
    warm = pipe.warm()
    gap = 1.0 / args.trace_qps if args.trace_qps > 0 else 0.0
    tickets = []
    for qi in trace:
        if gap:
            time.sleep(gap)
        pipe.poll()  # fire expired deadlines before admitting
        tickets.append(pipe.submit(Q[qi]))
    pipe.flush()

    for qi, t in zip(trace, tickets):
        assert np.array_equal(t.ids, direct_ids[qi]), (
            f"pipeline top-k ids diverge from direct search (query {qi})")
        assert np.array_equal(t.scores, direct_scores[qi]), (
            f"pipeline top-k scores diverge from direct search (query {qi})")
    snap = pipe.snapshot()
    return (f"{ServeStats.summary(snap)} "
            f"warm_compiles={warm} "
            f"trace_recompiles={snap['recompiles'] - warm}")


def _mutate_loadgen(col, name, codec, args, rng) -> None:
    """Live-mutation load generator (DESIGN.md §10).

    Base index over the leading ~60% of the collection; the rest is
    the insert pool. ``--mutations`` seeded events (insert / delete /
    update) run in three rounds, each followed by a query burst
    through the micro-batching pipeline and a CHECKPOINT: a fresh
    oracle ``Retriever.build`` over the current live corpus must match
    every burst response byte-for-byte (stable id ``live_ids[pos]`` ↔
    oracle position ``pos``). The final merge runs in the BACKGROUND
    (DESIGN.md §11) with queries streaming through the commit; those
    during-merge responses join the post-merge checkpoint (compaction
    does not change the live corpus, so one oracle covers both sides
    of the flip). Raises AssertionError on any divergence."""
    from repro.serve.api import Retriever, RetrieverConfig
    from repro.serve.pipeline import ServeStats, synthetic_trace
    from repro.serve.segments import MutableRetriever

    fwd = col.fwd
    n_docs = fwd.n_docs
    # budgets exhaustive for the whole mutated corpus: candidate sets
    # must be identical mutable vs oracle for byte parity
    exhaustive = {
        "seismic": dict(cut=16, block_budget=1024, n_probe=1024,
                        n_postings=100000, block_size=8),
        "hnsw": dict(beam=n_docs + 8, iters=n_docs + 8, n_seeds=4, m=8,
                     ef_construction=48),
        "flat": {},
    }
    cfg = RetrieverConfig(engine=name, codec=codec, k=args.k,
                          backend=args.backend or "jnp",
                          n_shards=args.n_shards,
                          params=exhaustive.get(name, {}))
    n_base = max(args.k + 4, (2 * n_docs) // 3)
    pool = list(range(n_base, n_docs))  # un-inserted doc pool
    m = MutableRetriever.create(fwd.slice(0, n_base), cfg)
    pipe = m.pipeline(deadline_us=args.deadline_us,
                      cache_size=args.cache_size)
    Q = np.stack([col.query_dense(i) for i in range(col.n_queries)])

    def mutate_one() -> str:
        live = m.live_ids()
        ops = ["delete", "update"] + (["insert"] if pool else [])
        # never shrink below k + margin (the oracle needs k live docs)
        if len(live) <= args.k + 2:
            ops = ["insert"] if pool else ["update"]
        op = ops[int(rng.integers(len(ops)))]
        if op == "insert":
            take = [pool.pop(0) for _ in range(min(len(pool),
                                                   int(rng.integers(1, 4))))]
            m.insert([fwd.doc(i) for i in take])
        elif op == "delete":
            m.delete(int(live[int(rng.integers(len(live)))]))
        else:  # update-in-place: new content under the same stable id
            victim = int(live[int(rng.integers(len(live)))])
            c, v = fwd.doc(int(rng.integers(n_docs)))
            m.update([(c, v)], ids=[victim])
        return op

    def burst_and_checkpoint(label: str, pre=()) -> int:
        # fresh segment/part plans compile on first touch — warm them
        # out of the burst (same discipline as the --pipeline trace)
        pipe.warm()
        trace = synthetic_trace(rng, max(8, args.requests // 4),
                                Q.shape[0], repeat_frac=args.repeat_frac)
        tickets = []
        for qi in trace:
            pipe.poll()
            tickets.append(pipe.submit(Q[qi]))
        pipe.flush()
        live_fwd, live = m.live_corpus()
        oracle = Retriever.build(live_fwd, cfg.replace(n_shards=1))
        oids, osc = map(np.asarray, oracle.search(Q))
        for qi, t in list(pre) + list(zip(trace, tickets)):
            assert np.array_equal(np.asarray(t.ids), live[oids[qi]]), (
                f"{name}/{codec} {label}: mutable top-k ids diverge from "
                f"the post-mutation oracle (query {qi})")
            assert np.array_equal(np.asarray(t.scores), osc[qi]), (
                f"{name}/{codec} {label}: mutable top-k scores diverge "
                f"from the post-mutation oracle (query {qi})")
        return len(pre) + len(trace)

    served = burst_and_checkpoint("pre-mutation")
    rounds, ops = 3, []
    for r in range(rounds):
        lo = (args.mutations * r) // rounds
        hi = (args.mutations * (r + 1)) // rounds
        ops += [mutate_one() for _ in range(lo, hi)]
        served += burst_and_checkpoint(f"round {r + 1}")
    # background compaction with queries streaming THROUGH the commit
    # (DESIGN.md §11): responses served while the merge builds + flips
    # join the post-merge parity set — compaction must not perturb them
    handle = m.merge(background=True)
    during = []
    while not handle.done() and len(during) < 4 * args.requests:
        pipe.poll()
        qi = int(rng.integers(Q.shape[0]))
        during.append((qi, pipe.submit(Q[qi])))
    pipe.flush()
    handle.result()
    served += burst_and_checkpoint("post-merge", pre=during)
    snap = pipe.snapshot()
    # one epoch invalidation per mutated round + one for the merge
    rounds = min(args.mutations, rounds)
    assert snap["cache_invalidations"] >= rounds + 1, (
        f"{name}/{codec}: ResultCache survived a mutation "
        f"(invalidations={snap['cache_invalidations']})")
    from collections import Counter

    mix = ",".join(f"{k}={v}" for k, v in sorted(Counter(ops).items()))
    print(f"{name:8s} codec={codec:13s} mutation parity OK "
          f"({served} responses, {len(during)} during background merge, "
          f"{args.mutations} mutations [{mix}], "
          f"{len(m.base_ids)} docs after merge, gen={m.generation}) "
          f"[{ServeStats.summary(snap)}]")


def main() -> None:
    from repro.core.layout import available_layouts
    from repro.launch import compile_cache
    from repro.serve.api import available_engines

    engines_known = available_engines()
    codecs_known = available_layouts()

    ap = argparse.ArgumentParser()
    ap.add_argument("--encoder", choices=["splade", "lilsr"], default="splade")
    ap.add_argument("--engine", choices=[*engines_known, "both", "all"],
                    default="seismic",
                    help="a registered engine, 'both' (seismic+hnsw) or 'all'")
    ap.add_argument("--codec", default="dotvbyte", choices=codecs_known)
    ap.add_argument("--backend", default=None,
                    choices=["jnp", "pallas", "pallas_interpret",
                             "pallas_compiled"],
                    help="candidate-rescoring path: jnp reference or the "
                         "fused kernel registry (DESIGN.md §3); 'pallas' = "
                         "the kernels' default compiled mode, or pin the "
                         "mode explicitly; default jnp, or the artifact's "
                         "saved backend under --load-index")
    ap.add_argument("--compare-codecs", action="store_true",
                    help="sweep every registered serving codec over the same index")
    ap.add_argument("--pipeline", action="store_true",
                    help="online-serving load generator (DESIGN.md §8): "
                         "drive a synthetic traffic trace through the "
                         "micro-batching scheduler, verify parity vs "
                         "direct search, report ServeStats")
    ap.add_argument("--mutate", action="store_true",
                    help="live-mutation load generator (DESIGN.md §10): "
                         "seeded insert/delete/update stream interleaved "
                         "with the query trace over a MutableRetriever, "
                         "per-response parity vs a fresh oracle at every "
                         "checkpoint, then merge + parity again; "
                         "exhaustive budgets — keep --n-docs small")
    ap.add_argument("--mutations", type=int, default=12,
                    help="--mutate stream length (events across 3 rounds)")
    ap.add_argument("--requests", type=int, default=256,
                    help="trace length for --pipeline")
    ap.add_argument("--deadline-us", type=float, default=1000.0,
                    help="--pipeline batch-filling deadline (µs)")
    ap.add_argument("--trace-qps", type=float, default=0.0,
                    help="--pipeline arrival pacing; 0 = closed-loop")
    ap.add_argument("--repeat-frac", type=float, default=0.25,
                    help="--pipeline fraction of requests re-asking a "
                         "head query (result-cache exercise)")
    ap.add_argument("--cache-size", type=int, default=1024,
                    help="--pipeline result-cache capacity (0 disables)")
    ap.add_argument("--save-index", metavar="DIR", default=None,
                    help="save each built index artifact under DIR/<engine>-<codec>/")
    ap.add_argument("--load-index", metavar="DIR", default=None,
                    help="serve from artifacts under DIR instead of building")
    ap.add_argument("--n-shards", type=int, default=1,
                    help="index shards (DESIGN.md §9): > 1 builds/serves "
                         "a sharded artifact tree — per-shard sub-indexes "
                         "over contiguous doc ranges, memory-mapped on "
                         "--load-index, searched over a device mesh when "
                         "devices ≥ shards else via the out-of-core "
                         "resident-shard LRU")
    ap.add_argument("--max-resident", type=int, default=None,
                    help="bound on simultaneously-resident shards "
                         "(sequential sharded path; default: all)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the background shard prefetcher on "
                         "the sequential sharded path (DESIGN.md §11); "
                         "every rotation then pages in on the hot path")
    ap.add_argument("--n-docs", type=int, default=20000)
    ap.add_argument("--n-queries", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--cut", type=int, default=8)
    ap.add_argument("--n-probe", type=int, default=64)
    ap.add_argument("--beam", type=int, default=64, help="HNSW beam width (static ef)")
    ap.add_argument("--iters", type=int, default=64, help="HNSW nodes expanded per query")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()
    if args.save_index and args.load_index:
        ap.error("--save-index and --load-index are mutually exclusive")
    if args.pipeline and (args.save_index or args.load_index):
        ap.error("--pipeline is a serving-loop mode; run it without "
                 "--save-index/--load-index")
    if args.mutate and (args.pipeline or args.save_index or args.load_index):
        ap.error("--mutate is a serving-loop mode; run it without "
                 "--pipeline/--save-index/--load-index")

    from repro.core.seismic import exact_top_k, recall_at_k
    from repro.data.synthetic import generate_collection, lilsr_config, splade_config
    from repro.serve.api import Retriever, RetrieverConfig, open_retriever

    cfg_fn = splade_config if args.encoder == "splade" else lilsr_config
    print(f"generating {args.n_docs}-doc synthetic {args.encoder} collection…")
    col = generate_collection(cfg_fn(args.n_docs, args.n_queries, args.seed),
                              value_format="f16")
    print(f"(nnz/doc={col.fwd.total_nnz/col.fwd.n_docs:.0f})")

    if args.engine == "both":
        engines = ("seismic", "hnsw")
    elif args.engine == "all":
        engines = tuple(engines_known)
    else:
        engines = (args.engine,)
    codecs = codecs_known if args.compare_codecs else (args.codec,)

    if args.mutate:
        for name in engines:
            for codec in codecs:
                _mutate_loadgen(col, name, codec, args,
                                np.random.default_rng(args.seed + 2))
        return

    search_params = {
        "seismic": dict(cut=args.cut, block_budget=512, n_probe=args.n_probe,
                        n_postings=2000, block_size=64),
        "hnsw": dict(beam=args.beam, iters=args.iters, n_seeds=8,
                     m=16, ef_construction=48),
        "flat": {},
    }

    # host indexes build once per engine; codecs sweep over them
    # (a sharded build constructs per-range sub-indexes instead)
    host_indexes: dict[str, object] = {}
    if not args.load_index and args.n_shards == 1:
        from repro.serve.api import get_engine

        for name in engines:
            impl = get_engine(name)
            if not hasattr(impl, "host_index"):
                continue
            t0 = time.time()
            cfg = RetrieverConfig(engine=name, k=args.k,
                                  params=search_params.get(name, {}))
            host_indexes[name] = impl.host_index(col.fwd, cfg)
            print(f"{name}: host index built in {time.time()-t0:.1f}s")

    Q = np.stack([col.query_dense(i) for i in range(col.n_queries)])
    truth = [exact_top_k(col.fwd, Q[i], args.k)[0] for i in range(col.n_queries)]

    roundtrip_checked = 0
    for name in engines:
        for codec in codecs:
            cfg = RetrieverConfig(engine=name, codec=codec, k=args.k,
                                  backend=args.backend or "jnp",
                                  n_shards=args.n_shards,
                                  params=search_params.get(name, {}))
            backend_overridden = False
            if args.load_index:
                art = pathlib.Path(args.load_index) / f"{name}-{codec}"
                retriever = open_retriever(art)
                if args.max_resident is not None and hasattr(
                    retriever, "max_resident"
                ):
                    retriever.max_resident = args.max_resident
                if args.no_prefetch and hasattr(retriever, "prefetch"):
                    retriever.prefetch = False
                # the backend is a serving choice, not an index format
                # (DESIGN.md §7): an explicit --backend re-wraps the
                # loaded arrays under the requested path (monolithic
                # artifacts; a sharded tree serves its saved backend)
                if (args.backend and args.backend != retriever.cfg.backend
                        and not hasattr(retriever, "shards")):
                    backend_overridden = True
                    retriever = Retriever(
                        retriever.cfg.replace(backend=args.backend),
                        retriever.arrays,
                        n_docs=retriever.n_docs,
                        dim=retriever.dim,
                        value_scale=retriever.value_scale,
                        value_format=retriever.value_format,
                    )
            elif name in host_indexes:
                retriever = Retriever.from_host_index(host_indexes[name], cfg)
            else:
                retriever = Retriever.build(col.fwd, cfg)
                if args.max_resident is not None and hasattr(
                    retriever, "max_resident"
                ):
                    retriever.max_resident = args.max_resident
                if args.no_prefetch and hasattr(retriever, "prefetch"):
                    retriever.prefetch = False
            if args.pipeline:
                rng = np.random.default_rng(args.seed + 1)
                summary = _pipeline_loadgen(retriever, Q, args, rng)
                print(f"{name:8s} codec={codec:13s} pipeline parity OK "
                      f"({args.requests} requests) [{summary}]")
                continue
            ids, scores = retriever.search(Q)  # compile
            t0 = time.time()
            ids, scores = retriever.search(Q)
            ids = np.asarray(ids)
            dt = time.time() - t0

            recs = [recall_at_k(truth[i], ids[i]) for i in range(col.n_queries)]
            extra = ""
            if args.save_index:
                art = pathlib.Path(args.save_index) / f"{name}-{codec}"
                retriever.save(art)
                np.savez(art / "topk.npz", ids=ids, scores=np.asarray(scores))
                extra = f" saved→{art}"
            if args.load_index:
                ref = pathlib.Path(args.load_index) / f"{name}-{codec}" / "topk.npz"
                if ref.is_file():
                    with np.load(ref) as npz:
                        assert np.array_equal(npz["ids"], ids), (
                            f"{name}/{codec}: reopened top-k ids differ from the "
                            f"build-time run")
                        if backend_overridden:
                            # cross-backend scores agree to rounding, not bytes
                            assert np.allclose(npz["scores"], np.asarray(scores),
                                               rtol=1e-5, atol=1e-6), (
                                f"{name}/{codec}: cross-backend top-k scores differ")
                            extra = " roundtrip=ids-identical (backend overridden)"
                        else:
                            assert np.array_equal(npz["scores"], np.asarray(scores)), (
                                f"{name}/{codec}: reopened top-k scores differ")
                            extra = " roundtrip=byte-identical"
                    roundtrip_checked += 1
            _report(name, codec, args.k, recs, 1e6 * dt / col.n_queries, col, extra)
    if args.load_index:
        print(f"serve-roundtrip OK: {roundtrip_checked} artifact(s) verified "
              f"against their build-time top-k")


if __name__ == "__main__":
    main()
