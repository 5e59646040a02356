"""Traced device time (ms) of every operation other than the rows
kernel in an open window (the engine's block gather, summary scoring,
dedupe and top-k), per completed query."""


def read(r):
    if r.get("loop") != "open" or not r.get("other_s") or not r.get("completed"):
        return None
    return 1e3 * r["other_s"] / r["completed"]
