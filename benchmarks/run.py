"""Benchmark entry point — one section per paper table + kernel/roofline
extras. Prints ``name,us_per_call,derived`` CSV (benchmarks/common.py)
and snapshots the kernel + serving + pipeline + scale + mutation +
overlap families to machine-readable ``BENCH_kernels.json`` /
``BENCH_serve.json`` / ``BENCH_pipeline.json`` /
``BENCH_roofline.json`` / ``BENCH_scale.json`` /
``BENCH_mutation.json`` / ``BENCH_overlap.json`` at the repo root
(schema: name, µs, structured mode/codec, parsed derived metrics, git
sha — see ``common.write_bench_json``) so the perf trajectory is
diffable across PRs.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --fast     # reduced sizes
    PYTHONPATH=src python -m benchmarks.run --only table1
    PYTHONPATH=src python -m benchmarks.run --quick    # CI smoke: tier-1
                                                       # pytest + tiny
                                                       # Table-1/2/3 +
                                                       # kernel pass
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

from .common import emit, write_bench_json

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _snapshot(kernel_rows, serve_rows, mode: str, pipeline_rows=None,
              n_docs: int | None = None, scale_rows=None,
              mutation_rows=None, overlap_rows=None) -> None:
    """Write the committed snapshots. ``mode`` (quick/fast/full) is
    recorded in the payload so the perf trajectory is only compared
    like-for-like (``n_docs`` likewise, for the kernel family — the
    perf gate re-measures at the committed size); a family is only
    (over)written when its sections ran completely — a partial
    ``--only`` run never drops rows from a committed file."""
    if kernel_rows:
        kmeta = {"mode": mode}
        if n_docs is not None:
            kmeta["n_docs"] = n_docs
        write_bench_json(os.path.join(_ROOT, "BENCH_kernels.json"), kernel_rows,
                         meta=kmeta)
        # the roofline placement derives entirely from the kernel rows
        # (+ any dry-run records on disk), so it snapshots with them
        from . import roofline

        write_bench_json(
            os.path.join(_ROOT, "BENCH_roofline.json"),
            roofline.run() + roofline.kernel_roofline(kernel_rows),
            meta={"mode": mode},
        )
    if serve_rows:
        write_bench_json(os.path.join(_ROOT, "BENCH_serve.json"), serve_rows,
                         meta={"mode": mode})
    if pipeline_rows:
        write_bench_json(os.path.join(_ROOT, "BENCH_pipeline.json"),
                         pipeline_rows, meta={"mode": mode})
    if scale_rows:
        write_bench_json(os.path.join(_ROOT, "BENCH_scale.json"),
                         scale_rows, meta={"mode": mode})
    if mutation_rows:
        write_bench_json(os.path.join(_ROOT, "BENCH_mutation.json"),
                         mutation_rows, meta={"mode": mode})
    if overlap_rows:
        write_bench_json(os.path.join(_ROOT, "BENCH_overlap.json"),
                         overlap_rows, meta={"mode": mode})


def _quick_smoke() -> int:
    """One-command regression gate (``make check``): the tier-1 test
    suite plus a miniature Table-1/2/3 benchmark pass, so codec, layout
    or engine regressions surface even when they only bend a curve."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    print("# tier-1 pytest…", file=sys.stderr, flush=True)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q"], cwd=root, env=env
    )
    if proc.returncode:
        return proc.returncode

    # JAX only after the child above has exited: one process owns a chip
    from repro.launch import compile_cache

    compile_cache.enable()
    from . import (kernel_bench, table1_codecs, table2_seismic, table3_graph,
                   table4_pipeline, table5_scale, table6_mutation,
                   table7_overlap)

    print("# tiny table1/table2/table3/table4/table5/table6/table7 + kernels…",
          file=sys.stderr, flush=True)
    rows = table1_codecs.run(n_docs=400, n_queries=2, rgb_iters=2)
    serve_rows = table2_seismic.run(n_docs=400, n_queries=4)
    serve_rows += table3_graph.run(n_docs=400, n_queries=4)
    kernel_rows = kernel_bench.run(n_docs=300)
    pipeline_rows = table4_pipeline.run(n_docs=400, n_queries=8, n_requests=64)
    scale_rows = table5_scale.run(n_docs_sweep=(2000,), n_queries=16,
                                  n_requests=32)
    mutation_rows = table6_mutation.run(n_docs=1000, n_queries=16,
                                        n_requests=32)
    overlap_rows = table7_overlap.run(n_docs=1000, n_queries=16,
                                      n_requests=8)
    rows += serve_rows + kernel_rows + pipeline_rows + scale_rows
    rows += mutation_rows + overlap_rows
    emit(rows)
    # a NaN latency means no sweep point reached the accuracy level —
    # or, for the pipeline/amortized-gate rows, that bucketed serving
    # failed to beat per-query dispatch — the regression classes this
    # gate exists to catch (a healthy build produces zero NaN rows)
    bad = [r.name for r in rows if r.us != r.us]
    if bad:
        print(f"# quick smoke FAILED: unmet accuracy rows: {bad}", file=sys.stderr)
        return 1
    # snapshot only after the gate passes — a failing run must not
    # overwrite the committed trajectory with regression numbers
    _snapshot(kernel_rows, serve_rows, mode="quick", pipeline_rows=pipeline_rows,
              n_docs=300, scale_rows=scale_rows, mutation_rows=mutation_rows,
              overlap_rows=overlap_rows)
    print(f"# quick smoke OK ({len(rows)} rows)", file=sys.stderr)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced collection sizes")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tier-1 pytest + tiny table1/table2/table3")
    ap.add_argument("--only", default=None,
                    choices=["table1", "table2", "table3", "table4", "table5",
                             "table6", "table7", "kernel", "roofline"])
    args = ap.parse_args()

    if args.quick:
        sys.exit(_quick_smoke())
    from repro.launch import compile_cache

    compile_cache.enable()

    rows = []
    by_section: dict[str, list] = {}
    t0 = time.time()

    def section(name, fn):
        if args.only and args.only != name:
            return
        print(f"# running {name}…", file=sys.stderr, flush=True)
        got = fn()
        by_section[name] = got
        rows.extend(got)

    from . import (kernel_bench, roofline, table1_codecs, table2_seismic,
                   table3_graph, table4_pipeline, table5_scale,
                   table6_mutation, table7_overlap)

    if args.fast:
        section("table1", lambda: table1_codecs.run(n_docs=1500, n_queries=2, rgb_iters=3))
        section("table2", lambda: table2_seismic.run(n_docs=1200, n_queries=6))
        section("table3", lambda: table3_graph.run(n_docs=800, n_queries=6))
        section("table4", lambda: table4_pipeline.run(n_docs=800, n_queries=16,
                                                      n_requests=128))
        section("table5", lambda: table5_scale.run(n_docs_sweep=(2000,),
                                                   n_queries=16, n_requests=64))
        section("table6", lambda: table6_mutation.run(n_docs=1500,
                                                      n_queries=16,
                                                      n_requests=64))
        section("table7", lambda: table7_overlap.run(n_docs=1200,
                                                     n_queries=16,
                                                     n_requests=8))
        section("kernel", lambda: kernel_bench.run(n_docs=800))
    else:
        section("table1", lambda: table1_codecs.run())
        section("table2", lambda: table2_seismic.run())
        section("table3", lambda: table3_graph.run())
        section("table4", lambda: table4_pipeline.run())
        section("table5", lambda: table5_scale.run())
        section("table6", lambda: table6_mutation.run())
        section("table7", lambda: table7_overlap.run())
        section("kernel", lambda: kernel_bench.run())
    section("roofline", roofline.run)

    serve_complete = "table2" in by_section and "table3" in by_section
    _snapshot(
        by_section.get("kernel", []),
        by_section.get("table2", []) + by_section.get("table3", [])
        if serve_complete else [],
        mode="fast" if args.fast else "full",
        pipeline_rows=by_section.get("table4", []),
        n_docs=800 if args.fast else 2000,
        scale_rows=by_section.get("table5", []),
        mutation_rows=by_section.get("table6", []),
        overlap_rows=by_section.get("table7", []),
    )
    emit(rows)
    print(f"# total {time.time()-t0:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
