"""Median host time (ms) of one ``plan.dispatch`` span in a closed batch
window: its self time, from the program's span (``repro.serve.obs``) —
the query batch's upload, its padding and the enqueue of the compiled
plan, not the wait for its results."""

import statistics

from bench.program_trace import self_times


def read(r):
    got = self_times(r.get("spans") or {}, "plan.dispatch")
    if r.get("loop") != "closed" or not got:
        return None
    return 1e3 * statistics.median(got)
