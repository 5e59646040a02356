"""Reduction of a profiler trace to device busy, idle and kernel time.

The benchmark wraps its traced window in a host span ``bench.window``
and its calls into the program in ``bench.*`` spans
(``jax.profiler.TraceAnnotation``). The profiler writes an
``.xplane.pb``; ``jax.profiler.ProfileData`` reads it. On each device
plane (``/device:TPU:<n>``) the ``XLA Ops`` line holds one event per
operation the device ran, named by its HLO text
(``%name = shape op(...)``), with a start and a duration in ns on the
same clock as the host spans. Operations nest: a ``while`` loop's
event spans the kernel calls of its body.

A kernel event is a Mosaic custom call: its HLO names the target
``tpu_custom_call``. The rows kernels are the only ones on the served
path; their instruction is ``%rows_<codec>_<vq>.<n>``, or
``%closed_call.<n>`` where ``vmap`` wrapped the call.

``reduce`` gives, for the chips used and clipped to the window, each
averaged over those chips:

* ``busy_s`` — the union of all operation intervals;
* ``window_s`` — the length of ``bench.window``;
* ``kernel_s`` — the union of the kernel events' intervals;
* ``other_s`` — ``busy_s - kernel_s``: device time in which something
  other than a kernel ran;
* ``device_ops`` — the ten instructions that took most self time (an
  event's duration less that of the events nested in it);
* ``idle_gaps`` — the ten longest gaps between busy intervals, each
  named by the ``bench.*`` span that overlaps it most (``idle`` where
  none does).
"""

from __future__ import annotations

import glob
import os

#: the device line that holds one event per operation
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def read_events(path: str, n_chips: int = 1):
    """(device op events per chip, host ``bench.*`` spans) of a trace,
    each event a (name, start_ns, end_ns) tuple."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = plane.name[len("/device:TPU:"):]
            if not idx.isdigit() or int(idx) >= n_chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(idx)] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return devices, host


def is_kernel(hlo: str) -> bool:
    return KERNEL_TARGET in hlo or hlo.startswith("%rows_")


def op_name(hlo: str) -> str:
    """``%fusion.6 = f32[...] fusion(...)`` -> ``fusion.6``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(ops) -> dict:
    """Instruction name -> summed self time (ns) of (hlo, start, end) events."""
    out, stack = {}, []  # open events: [name, end, direct children's time, duration]

    def close(top):
        out[top[0]] = out.get(top[0], 0.0) + top[3] - top[2]

    for hlo, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(e, stack[-1][1]) - s
        stack.append([op_name(hlo), e, 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def reduce_events(devices: dict, host: list) -> dict:
    """The reduction above from already-read events."""
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not windows or not devices:
        return {}
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    spans = [h for h in host if h[0] != WINDOW_SPAN]
    busy = kernel = 0.0
    by_op, gaps = {}, []
    for ops in devices.values():
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        merged = _union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged)
        kernel += sum(e - s for s, e in _union([(s, e) for n, s, e in clipped if is_kernel(n)]))
        for name, t in self_times(clipped).items():
            by_op[name] = by_op.get(name, 0.0) + t
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for g0, g1 in gaps[:10]:
        best = max(spans, key=lambda h: _overlap(g0, g1, h[1], h[2]), default=None)
        name = best[0] if best and _overlap(g0, g1, best[1], best[2]) > 0 else "idle"
        named.append([name, (g1 - g0) * 1e-9])
    n = len(devices)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "kernel_s": kernel / n * 1e-9,
        "other_s": (busy - kernel) / n * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in top],
        "idle_gaps": named,
    }


def reduce(path: str, n_chips: int = 1) -> dict:
    """Read the trace at ``path`` and reduce it; {} where it holds no
    window or no device operations."""
    devices, host = read_events(path, n_chips)
    return reduce_events(devices, host)
