"""The load generator: one general generator for every traffic mix.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

* ``"loop": "closed"`` — back-to-back batches of ``batch`` distinct
  queries from a pool of ``query_pool``, each batch's results fetched
  to the host before the next is sent;
* ``"loop": "open"`` — ``rate_qps`` × seconds requests due on a
  schedule of Poisson arrivals (``schedule``), one distinct query
  each, sent whether or not earlier ones have completed.

Both record, per request, when it was due, sent and completed on the
host clock, so a stalled server or a late generator shows.
"""

from __future__ import annotations

import contextlib
import math
import time

import numpy as np


def n_requests(traffic: dict, seconds: float) -> int:
    """Distinct queries a run of this mix draws."""
    if traffic["loop"] == "closed":
        return int(traffic["query_pool"])
    return max(1, int(round(traffic["rate_qps"] * seconds)))


def schedule(traffic: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of an open mix. Poisson
    arrivals: exponential gaps, here the same set for every seed (the
    exponential's quantiles at (i + 1/2) / n, scaled to fill the window)
    in an order drawn from the seed, so seeds differ in order only."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"no generator for arrivals {traffic['arrivals']!r}")
    n = n_requests(traffic, seconds)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def no_span(name):
    """The span of an untraced run: nothing."""
    return contextlib.nullcontext()


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile of all values, by nearest rank."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def closed(search, Q: np.ndarray, batch: int, seconds: float, span=no_span,
           clock=time.perf_counter) -> dict:
    """Back-to-back batches through ``search`` (dense [batch, dim] →
    (ids, scores)) until the window passes ``seconds``; the window ends
    when the last batch's results are on the host."""
    n_pool = len(Q) // batch
    batches = Q[: n_pool * batch].reshape(n_pool, batch, -1)
    served = []  # (pool batch index, ids, scores)
    ends = []
    t0 = clock()
    while True:
        b = len(served) % n_pool
        with span("bench.search"):
            ids, scores = search(batches[b])
        with span("bench.fetch"):
            served.append((b, np.asarray(ids), np.asarray(scores)))
        ends.append(clock() - t0)
        if ends[-1] >= seconds:
            break
    return {
        "elapsed_s": ends[-1],
        "batch_s": np.diff(ends, prepend=0.0),
        "attempted": len(served) * batch,
        "completed": len(served) * batch,
        "query_index": np.concatenate([b * batch + np.arange(batch) for b, _, _ in served]),
        "ids": np.concatenate([i for _, i, _ in served]),
        "scores": np.concatenate([s for _, _, s in served]),
    }


def open_loop(pipe, Q: np.ndarray, due: np.ndarray, span=no_span,
              clock=time.perf_counter, sleep=time.sleep, grace_s: float = 60.0) -> dict:
    """Send ``Q[i]`` through ``pipe.submit`` at ``due[i]``, poll the
    pipeline's deadline between arrivals, and stamp each request when
    its result is on the host. Requests not completed ``grace_s`` after
    the last is due count as failed."""
    n = len(due)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    tickets = [None] * n
    waiting: list[int] = []
    deadline_s = pipe.deadline_us * 1e-6
    t0 = clock()

    def reap():
        now = clock() - t0
        still = []
        for j in waiting:
            if tickets[j].done:
                done[j] = now
            else:
                still.append(j)
        waiting[:] = still

    i = 0
    while i < n or waiting:
        now = clock() - t0
        if now > due[-1] + grace_s:
            break
        while i < n and due[i] <= now:
            sent[i] = now
            with span("bench.submit"):
                tickets[i] = pipe.submit(Q[i])
            waiting.append(i)
            i += 1
            reap()
            now = clock() - t0
        with span("bench.poll"):
            pipe.poll()
        reap()
        if i >= n and not waiting:
            break
        nxt = due[i] if i < n else math.inf
        if waiting:
            nxt = min(nxt, sent[waiting[0]] + deadline_s)
        wait = nxt - (clock() - t0)
        if wait > 5e-4:
            sleep(wait - 2e-4)
    end = clock() - t0
    ok = ~np.isnan(done)
    ids = np.stack([t.ids if t is not None and t.done else np.full(pipe.retriever.cfg.k, -1)
                    for t in tickets])
    scores = np.stack([t.scores if t is not None and t.done
                       else np.full(pipe.retriever.cfg.k, np.nan) for t in tickets])
    return {
        "elapsed_s": float(np.nanmax(done)) if ok.any() else end,
        "attempted": n,
        "completed": int(ok.sum()),
        "latency_s": np.where(ok, done, end) - due,  # a failed request waited to the end
        "gen_lag_s": np.where(np.isnan(sent), end, sent) - due,
        "query_index": np.arange(n),
        "ids": ids,
        "scores": scores,
    }
