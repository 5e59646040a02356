"""95th percentile (ms) of the queue wait of an open window's requests:
submit to the start of the dispatch that carried them
(``ServeStats.queue_wait_us``, one sample per dispatched request)."""

from bench.loadgen import nearest_rank


def read(r):
    waits = r.get("queue_wait_us")
    if r.get("loop") != "open" or not waits:
        return None
    return 1e-3 * nearest_rank(waits, 0.95)
