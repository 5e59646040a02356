"""Share (%) of the bucket slots dispatched in an open window that
carried a real query, from the pipeline's ``ServeStats`` counters."""


def read(r):
    if r.get("loop") != "open" or not r.get("slots"):
        return None
    return 100.0 * r["real"] / r["slots"]
