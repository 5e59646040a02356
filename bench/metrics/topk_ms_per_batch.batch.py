"""Traced device time (ms) under the flat engine's ``flat.topk`` named
scope in a closed batch window, per search call."""


def read(r):
    t = (r.get("scope_s") or {}).get("flat.topk")
    if r.get("loop") != "closed" or not t or not r.get("calls"):
        return None
    return 1e3 * t / r["calls"]
