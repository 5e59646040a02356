"""Traced device time (ms) under Seismic's ``seismic.phase1`` named
scope (summary gather, block estimates, top-``n_probe``) in an open
window, per completed query."""


def read(r):
    t = (r.get("scope_s") or {}).get("seismic.phase1")
    if r.get("loop") != "open" or not t or not r.get("completed"):
        return None
    return 1e3 * t / r["completed"]
