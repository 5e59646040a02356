"""Traced device time (ms) of the ``rows_*`` kernel events in an open
window, per completed query."""


def read(r):
    if r.get("loop") != "open" or not r.get("kernel_s") or not r.get("completed"):
        return None
    return 1e3 * r["kernel_s"] / r["completed"]
