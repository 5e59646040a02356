"""95th percentile (ms) of how late the load generator sent each
request of an open window after it was due."""

from bench.loadgen import nearest_rank


def read(r):
    lag = r.get("gen_lag_s")
    if r.get("loop") != "open" or lag is None or not len(lag):
        return None
    return 1e3 * nearest_rank(lag, 0.95)
