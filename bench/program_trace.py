"""What the program records about itself in a profiler trace, reduced.

``bench/trace.py`` reduces a trace to device busy, idle and kernel
time, and names idle gaps by the benchmark's own ``bench.*`` spans.
This module adds what the program under test puts in the same trace:

* host spans ``serve.*`` and ``plan.*`` (``repro.serve.obs``, on while
  ``obs.enable()`` is in force), each with its args (``rid``, ``did``,
  ``bucket``, ...), as the profiler's event stats;
* the ``jax.named_scope`` path of every device operation. The profiler
  keeps it in the ``tf_op`` stat of the operation's event metadata,
  which ``jax.profiler.ProfileData`` does not expose, so ``op_paths``
  reads those fields of the ``.xplane.pb`` (an ``XSpace`` protobuf)
  with a small wire-format reader;
* the executables' names (the ``XLA Modules`` line):
  ``jit_search_<engine>(<id>)``.

``reduce`` gives ``bench.trace.reduce``'s keys and, clipped to the
``bench.window`` span and averaged over the chips used:

* ``scope_s`` — scope → device self time (s), each operation counted
  under the first ``flat.*``/``seismic.*`` scope of its path, else
  under ``unscoped``;
* ``spans`` — span name → [start, end, args] in seconds from the
  window's start;
* ``idle_gaps_program`` — the ten longest device gaps, each named by
  what took most of it: the program or ``bench.*`` spans of one name,
  each counted by its own time (less the spans nested in it, so the
  innermost span names its part), or ``idle``, where no span was open
  (the host waiting for work);
* ``modules`` — executable name → number of runs in the window.

``counters`` and ``since`` read the program's own counters around a
window (``PlanCache.upload_bytes``, the pipeline's queue-wait
samples); both give nothing for a program without them.
"""

from __future__ import annotations

import re

from bench import trace

#: host spans of the program (``repro.serve.obs``)
PROGRAM_PREFIXES = ("serve.", "plan.")
#: named scopes of the engines (``repro.serve.engines``)
SCOPE_PREFIXES = ("flat.", "seismic.")
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:TPU:"

# -- protobuf wire format: just enough of XSpace to reach op metadata ----
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
# key = 1, value = 2), .stat_metadata = 5 (same); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7 (the id of a stat metadata naming it).


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo=0, hi=None):
    """(field number, value) of one message in ``buf[lo:hi]``: an int
    for a scalar, a (start, end) slice for a length-delimited field."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, sl) -> str:
    return bytes(buf[sl[0]:sl[1]]).decode("utf-8", "replace")


def _map_values(buf, entries):
    for entry in entries:
        for f, v in _fields(buf, *entry):
            if f == 2:
                yield v


def op_paths(path: str, n_chips: int = 1) -> dict:
    """chip → {operation name as the trace's events give it (its HLO
    text) → its ``tf_op`` path}, for the first ``n_chips`` chips."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, ev_meta, st_meta = "", [], []
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf == 4:
                ev_meta.append(v)
            elif pf == 5:
                st_meta.append(v)
        idx = name[len(DEVICE_PLANE):]
        if not name.startswith(DEVICE_PLANE) or not idx.isdigit() or int(idx) >= n_chips:
            continue
        stat_names = {}
        for meta in _map_values(buf, st_meta):
            d = dict(_fields(buf, *meta))
            stat_names[d.get(1, 0)] = _text(buf, d[2]) if 2 in d else ""
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        paths = {}
        for meta in _map_values(buf, ev_meta):
            op, got = "", None
            for mf, mv in _fields(buf, *meta):
                if mf == 2:
                    op = _text(buf, mv)
                elif mf == 5:
                    stat = dict(_fields(buf, *mv))
                    if stat.get(1) in tf_op:
                        got = (_text(buf, stat[5]) if 5 in stat
                               else stat_names.get(stat.get(7), ""))
            if got is not None:
                paths.setdefault(op, got)
        out[int(idx)] = paths
    return out


# -- reading and reducing -------------------------------------------------

_WRAPPED = re.compile(r"^(?:[\w.-]+\()*(.*?)\)*$")


def scope_of(tf_op: str) -> str:
    """The first ``flat.*``/``seismic.*`` component of an op-name path
    (transforms unwrapped: ``vmap(seismic.phase1)``), else ``unscoped``."""
    for part in (tf_op or "").split("/"):
        name = _WRAPPED.match(part).group(1)
        if name.startswith(SCOPE_PREFIXES):
            return name
    return UNSCOPED


def read_events(path: str, n_chips: int = 1):
    """(device ops per chip as (hlo, start_ns, end_ns, tf_op), host
    ``bench.*``/``serve.*``/``plan.*`` spans as (name, start_ns,
    end_ns, args), executables run as (name, start_ns, end_ns))."""
    from jax.profiler import ProfileData

    paths = op_paths(path, n_chips)
    pd = ProfileData.from_file(path)
    devices, host, modules = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            idx = plane.name[len(DEVICE_PLANE):]
            if not idx.isdigit() or int(idx) >= n_chips:
                continue
            ops = paths.get(int(idx), {})
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    devices[int(idx)] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns, ops.get(e.name, ""))
                        for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    modules += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.",) + PROGRAM_PREFIXES):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                     dict(e.stats)))
    return devices, host, modules


def _module_name(name: str) -> str:
    """``jit_search_flat(4242)`` -> ``jit_search_flat``."""
    return name.split("(", 1)[0]


def _own_intervals(spans):
    """Each (start, end) span's own time: its interval less the union of
    the other spans nested in it."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][0], -spans[i][1]))
    out = [None] * len(spans)
    for k, i in enumerate(order):
        s, e = spans[i]
        inner, m = [], k + 1
        while m < len(order) and spans[order[m]][0] < e:  # only spans starting inside nest
            a, b = spans[order[m]]
            if b <= e and (a, b) != (s, e):
                inner.append((a, b))
            m += 1
        pieces, t = [], s
        for a, b in trace._union(inner):
            if a > t:
                pieces.append((t, a))
            t = max(t, b)
        if e > t:
            pieces.append((t, e))
        out[i] = pieces
    return out


def reduce_events(devices: dict, host: list, modules: list = ()) -> dict:
    """The program's part of the reduction from already-read events;
    {} without a ``bench.window`` span or device operations."""
    windows = [(s, e) for n, s, e, *_ in host if n == trace.WINDOW_SPAN]
    if not windows or not devices:
        return {}
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    program = [h for h in host if h[0].startswith(PROGRAM_PREFIXES) and h[2] > w0 and h[1] < w1]
    bench_spans = [h for h in host if h[0].startswith("bench.") and h[0] != trace.WINDOW_SPAN]
    by_scope, gaps = {}, []
    for ops in devices.values():
        clipped = [(n, max(s, w0), min(e, w1), p) for n, s, e, p in ops if e > w0 and s < w1]
        # self time by scope: the scope stands in for each op's name
        for scope, t in trace.self_times([(scope_of(p), s, e) for _, s, e, p in clipped]).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + t
        merged = trace._union([(s, e) for _, s, e, _ in clipped])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
    gaps.sort(key=lambda g: g[0] - g[1])

    pool = program + bench_spans
    own = _own_intervals([(s, e) for _, s, e, _ in pool])

    def name_gap(g0, g1):
        # what took most of the gap: the spans of one name, each counted by
        # its own time (less the spans nested in it), or no span at all
        took = {}
        for (name, *_), iv in zip(pool, own):
            t = sum(trace._overlap(g0, g1, a, b) for a, b in iv)
            if t > 0:
                took[name] = took.get(name, 0.0) + t
        open_ = trace._union([(max(s, g0), min(e, g1)) for _, s, e, _ in pool if e > g0 and s < g1])
        took["idle"] = (g1 - g0) - sum(e - s for s, e in open_)
        return max(took, key=took.get)

    spans = {}
    for name, s, e, args in sorted(program, key=lambda h: h[1]):
        spans.setdefault(name, []).append(
            [(max(s, w0) - w0) * 1e-9, (min(e, w1) - w0) * 1e-9, dict(args)])
    ran = {}
    for name, s, e in modules:
        if e > w0 and s < w1:
            ran[_module_name(name)] = ran.get(_module_name(name), 0) + 1
    n = len(devices)
    return {
        "scope_s": {k: v / n * 1e-9 for k, v in sorted(by_scope.items())},
        "spans": spans,
        "idle_gaps_program": [[name_gap(g0, g1), (g1 - g0) * 1e-9] for g0, g1 in gaps[:10]],
        "modules": ran,
    }


def reduce(path: str, n_chips: int = 1) -> dict:
    """``bench.trace.reduce`` of the trace at ``path`` with the program's
    part added; {} where it holds no window or no device operations."""
    out = trace.reduce(path, n_chips)
    if out:
        out.update(reduce_events(*read_events(path, n_chips)))
    return out


def self_times(spans: dict, name: str) -> list:
    """Self time (s) of each ``name`` span in a reduction's ``spans``:
    its length less the union of the program spans nested in it."""
    every = [(s, e) for got in spans.values() for s, e, _ in got]
    own = dict(zip(every, _own_intervals(every)))
    return [sum(b - a for a, b in own[(s, e)]) for s, e, _ in spans.get(name, [])]


# -- the program's counters ------------------------------------------------


def counters(plans, stats=None) -> dict:
    """The plan cache's ``upload_bytes`` and, given the pipeline's
    ``ServeStats``, its queue-wait samples (µs); {} for a program that
    keeps no such counters."""
    out = {}
    if hasattr(plans, "upload_bytes"):
        out["upload_bytes"] = int(plans.upload_bytes)
    if stats is not None and hasattr(stats, "queue_wait_us"):
        out["queue_wait_us"] = list(stats.queue_wait_us)
    return out


def since(before: dict, after: dict) -> dict:
    """What the counters gained between two ``counters`` readings: the
    differences of the sums, and the samples added after ``before``."""
    out = {}
    if "upload_bytes" in before and "upload_bytes" in after:
        out["upload_bytes"] = after["upload_bytes"] - before["upload_bytes"]
    if "queue_wait_us" in after:
        out["queue_wait_us"] = after["queue_wait_us"][len(before.get("queue_wait_us", ())):]
    return out
