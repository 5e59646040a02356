"""Share (%) of a closed batch window in which no operation ran on the
device: 1 - union of the traced op intervals / window."""


def read(r):
    if r.get("loop") != "closed" or not r.get("window_s"):
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
