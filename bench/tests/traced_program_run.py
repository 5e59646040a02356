"""One run of a benchmark cell with the program's spans and counters read.

    python3 bench/tests/traced_program_run.py --workload <cell> --seed <n> \
        --seconds <s> [--trace 0|1] [--gaps] [--small] [--out traced_out]

``bench/run.py`` does not read what the program records about itself
yet (PERF.md §7, item 0). This script runs its ``run_cell`` with that
wiring added from outside, as the benchmark would add it:

* under ``--trace 1``, ``repro.serve.obs.enable()`` before the window;
* ``program_trace.counters`` read after warm-up and after the window,
  and their gain (``program_trace.since``) added to the reduction;
* ``program_trace.reduce`` in place of ``bench.trace.reduce``;
* the six per-layer metrics of ``bench/metrics`` that read these, for
  the cell's loop, appended to the cell's own.

After the cell's result line it prints one ``program {...}`` line: the
window's end-to-end numbers (``qps``, or ``p50_ms``/``p95_ms``) and, in
a traced run, device time by scope, the executables run, the ten
longest idle gaps named by program spans, the counters, each span's
count and medians, and for an open window the host time per dispatch
by bucket and fill. The spans go to ``<out>/spans_<cell>_<seed>.json``;
``--gaps`` also writes the ops, executables and spans around the
fifteen longest device gaps to ``<out>/gaps_<cell>_<seed>.json``.
``--small`` runs the CPU size of ``bench/tests/harness.py``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import loadgen, run, spec  # noqa: E402
from bench import program_trace as pt  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.tests import harness  # noqa: E402

#: the per-layer metrics that read the program's spans and counters
NEW_METRICS = {
    "closed": [("dispatch_host_ms.batch", "ms"), ("h2d_bytes_per_q.batch", "B/query"),
               ("topk_ms_per_batch.batch", "ms")],
    "open": [("queue_wait_ms.open", "ms"), ("dispatch_host_ms.open", "ms"),
             ("phase1_ms_per_q.open", "ms")],
}


def _ms(xs) -> float:
    return 1e3 * statistics.median(xs)


def span_stats(spans: dict) -> dict:
    """name → count, median and p95 length (ms), median self time (ms)."""
    out = {}
    for name, got in spans.items():
        d = sorted(e - s for s, e, _ in got)
        out[name] = {"n": len(d), "median_ms": _ms(d),
                     "p95_ms": 1e3 * loadgen.nearest_rank(d, 0.95),
                     "self_median_ms": _ms(pt.self_times(spans, name))}
    return out


def dispatch_split(spans: dict) -> dict:
    """Host time per pipeline dispatch, by bucket and fill: median ms of
    the ``plan.dispatch`` inside it, of its ``serve.wait`` and
    ``serve.demux``, and of its length less the wait."""
    plans = spans.get("plan.dispatch", [])
    by_did = {}
    for name in ("serve.wait", "serve.demux"):
        for s, e, a in spans.get(name, []):
            by_did.setdefault(a.get("did"), {})[name] = e - s
    rows = {}
    for s, e, a in spans.get("serve.dispatch", []):
        got = by_did.get(a.get("did"), {})
        plan = sum(pe - ps for ps, pe, _ in plans if ps >= s and pe <= e)
        fill = "full" if a.get("n_real") == a.get("bucket") else "padded"
        row = rows.setdefault(f"bucket {a.get('bucket')}, {fill}", [])
        row.append((plan, got.get("serve.wait", 0.0), got.get("serve.demux", 0.0),
                    (e - s) - got.get("serve.wait", 0.0)))
    return {k: {"n": len(v), **{col: _ms([r[i] for r in v]) for i, col in enumerate(
        ("plan.dispatch", "serve.wait", "serve.demux", "dispatch-wait"))}}
        for k, v in sorted(rows.items())}


def gaps(path: str, n: int = 15) -> list:
    """The ``n`` longest device gaps in the window, each with the
    executables and host spans overlapping it and the device ops on
    either side, in ms from the gap's start."""
    devices, host, modules = pt.read_events(path)
    w0, w1 = [(s, e) for name, s, e, _ in host if name == trace_mod.WINDOW_SPAN][0]
    ops = sorted((s, e, name) for name, s, e, _ in devices.get(0, []) if e > w0 and s < w1)
    edges, t = [], w0
    for s, e, _ in ops:
        if s > t:
            edges.append((t, s))
        t = max(t, e)
    if w1 > t:
        edges.append((t, w1))
    rel = lambda x, g0: round((x - g0) / 1e6, 3)  # noqa: E731
    out = []
    for g0, g1 in sorted(edges, key=lambda g: g[0] - g[1])[:n]:
        before = [o for o in ops if o[1] <= g0]
        after = [o for o in ops if o[0] >= g1]
        out.append({
            "ms": (g1 - g0) / 1e6, "at_s": (g0 - w0) / 1e9,
            "prev_op": before[-1][2][:80] if before else None,
            "next_op": after[0][2][:80] if after else None,
            "modules": [(m, rel(s, g0), rel(e, g0)) for m, s, e in modules if s < g1 and e > g0],
            "host": [(h, rel(s, g0), rel(e, g0), a) for h, s, e, a in host
                     if s < g1 and e > g0 and h != trace_mod.WINDOW_SPAN],
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--gaps", action="store_true")
    ap.add_argument("--small", action="store_true", help="the CPU size of bench/tests/harness.py")
    ap.add_argument("--out", default=str(REPO / "traced_out"))
    a = ap.parse_args(argv)
    out_dir = pathlib.Path(a.out)

    cell = (harness.small_cell(a.workload) if a.small
            else spec.resolve(harness.benchmark(), a.workload))
    cell.per_layer = cell.per_layer + [
        {"name": n, "unit": u} for n, u in NEW_METRICS[cell.traffic["loop"]]]
    state = {}
    orig_warm, orig_loops = run.warm, {n: getattr(loadgen, n) for n in ("closed", "open_loop")}

    def warm(r, config, traffic, Q):
        pipe = orig_warm(r, config, traffic, Q)
        plans, stats = (r.plans, None) if pipe is None else (pipe.plans, pipe.stats)
        state.update(plans=plans, stats=stats, before=pt.counters(plans, stats))
        return pipe

    def loop(name):
        def wrapped(*args, **kw):
            state["res"] = orig_loops[name](*args, **kw)
            state["after"] = pt.counters(state["plans"], state["stats"])
            return state["res"]
        return wrapped

    def reduce(path, n_chips=1):
        if a.gaps:
            out_dir.mkdir(parents=True, exist_ok=True)
            (out_dir / f"gaps_{a.workload}_{a.seed}.json").write_text(
                json.dumps(gaps(path), indent=1))
        red = pt.reduce(path, n_chips)
        red.update(pt.since(state["before"], state["after"]))
        state["red"] = red
        return red

    run.warm = warm
    for name in orig_loops:
        setattr(loadgen, name, loop(name))
    orig_trace = run.trace_mod
    run.trace_mod = types.SimpleNamespace(reduce=reduce, find_xplane=trace_mod.find_xplane)
    from repro.launch import compile_cache

    try:
        from repro.serve import obs
    except ImportError:  # a program from before its spans: it records none
        obs = None
    orig_cache = compile_cache.enable
    if a.small:  # write nothing to the checkout
        compile_cache.enable = lambda: None
    if a.trace and obs is not None:
        obs.enable()
    try:
        run.run_cell(cell, a.seed, a.seconds, bool(a.trace), require_chip=not a.small,
                     t_start=time.perf_counter() if a.small else T0,
                     out=lambda s: print(s, flush=True))
    finally:
        if obs is not None:
            obs.disable()
        compile_cache.enable = orig_cache
        run.warm, run.trace_mod = orig_warm, orig_trace
        for name, f in orig_loops.items():
            setattr(loadgen, name, f)

    res = state["res"]
    extra = {"workload": a.workload, "seed": a.seed, "trace": a.trace}
    if "batch_s" in res:
        extra["qps"] = res["completed"] / res["elapsed_s"]
    else:
        extra["p50_ms"] = 1e3 * loadgen.nearest_rank(res["latency_s"], 0.50)
        extra["p95_ms"] = 1e3 * loadgen.nearest_rank(res["latency_s"], 0.95)
    if "red" in state:
        red = state["red"]
        spans = red.get("spans", {})
        counters = pt.since(state["before"], state["after"])
        if "queue_wait_us" in counters:
            counters["queue_wait_us"] = len(counters["queue_wait_us"])
        extra.update(scope_s=red.get("scope_s"), modules=red.get("modules"),
                     idle_gaps_program=red.get("idle_gaps_program"), counters=counters,
                     span_stats=span_stats(spans))
        if spans.get("serve.dispatch"):
            extra["dispatch_split"] = dispatch_split(spans)
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"spans_{a.workload}_{a.seed}.json").write_text(json.dumps(spans))
    print("program " + json.dumps(extra), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
