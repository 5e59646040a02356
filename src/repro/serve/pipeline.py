"""Online serving pipeline (DESIGN.md §8): compiled-plan cache,
bucketed micro-batching scheduler, result cache, and serving metrics.

The paper's claim is that forward-index compression must not
compromise inner-product latency; this module is where that claim
meets *traffic* instead of one frozen batch. Four layers, stacked:

* ``PlanCache`` — the compile layer extracted from
  ``Retriever.__init__``: ONE executable per
  ``(engine, codec, backend, k, bucket)`` key. Arbitrary query-batch
  sizes are padded up to the smallest covering bucket (default
  ``DEFAULT_BUCKETS``, extended by the ``RetrieverConfig.batch_size``
  hint), so steady-state traffic always hits a warm compiled plan —
  a fresh batch shape costs a bucket-pad, not an XLA recompile.
  ``compiles`` counts plan creations (the recompile metric).

* ``Pipeline`` — the host-side micro-batching scheduler: ``submit``
  admits one query at a time, the queue coalesces into the smallest
  covering bucket (padded slots carry the zero query and are sliced
  away on the way out), a full largest-bucket queue dispatches
  immediately, and ``deadline_us`` bounds how long a lone query waits
  for batch-mates — latency-sensitive traffic is never starved by
  batch-filling. Batched work dispatches through the plan cache into
  the engines' ``search_batch`` (the kernel registry's ``*_batch``
  rows entries under ``backend="pallas"``), and per-query top-k is
  de-multiplexed back to each ticket in submission order.

* ``ResultCache`` — an LRU over the *quantized sparse query* (nonzero
  component ids + values rounded to the index's storage dtype): the
  repeat-heavy head of real query logs short-circuits dispatch
  entirely and replays the exact top-k previously served. A cached
  answer is only valid for the index state that produced it:
  ``invalidate()`` flushes every entry, and the ``epoch`` tag lets the
  pipeline invalidate automatically whenever the owning retriever's
  ``epoch`` attribute moves (a ``MutableRetriever`` bumps it on every
  insert/delete/update and on each generation flip — DESIGN.md §10),
  so a mutation can never replay a pre-mutation top-k.

* ``ServeStats`` — the metrics contract: QPS, p50/p95/p99 end-to-end
  latency, result-cache hit rate, per-bucket dispatch counts and
  occupancy (real queries / bucket capacity), the plan-cache recompile
  count, and the result-cache invalidation counters (flushes and
  entries dropped).

Determinism contract (tests/test_pipeline.py, ``make pipeline-smoke``):
bucketed/padded/cached serving returns byte-identical top-k ids and
scores to a direct ``Retriever.search`` of the same queries, for every
engine × codec × backend.

The wall clock is injectable (``clock=``) so deadline semantics are
testable with a fake clock; production uses ``time.perf_counter``.

Threading model (DESIGN.md §11): every layer here is safe to drive
from multiple threads — ``PlanCache.get`` creates plans under a lock,
``ResultCache`` serializes get/put/invalidate, ``ServeStats`` guards
its counters, and ``Pipeline`` holds one scheduler lock across
admission/dispatch (one dispatcher at a time; submitters from other
threads queue on the lock, never on a torn queue). The overlap
counters (``prefetch_hits``/``prefetch_misses``/``merge_wall_us``/
``blocked_swap_us``) are synced off the serving stack at snapshot
time, so the prefetch and background-merge wins are observable, not
just benchmarked.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict, deque
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # import cycle: api.py imports this module at runtime
    from .api import Retriever

__all__ = [
    "DEFAULT_BUCKETS",
    "plan_buckets",
    "PlanKey",
    "SearchPlan",
    "PlanCache",
    "ResultCache",
    "ServeStats",
    "Pipeline",
    "quantized_query_key",
    "synthetic_trace",
]

#: default padding buckets — arbitrary batch sizes round up to the
#: smallest covering entry; power-of-two spacing bounds pad waste < 2×
DEFAULT_BUCKETS: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64, 128)


def plan_buckets(
    batch_size: Optional[int] = None,
    buckets: Optional[Sequence[int]] = None,
) -> Tuple[int, ...]:
    """The sorted bucket set: an explicit ``buckets`` sequence (used
    verbatim), or ``DEFAULT_BUCKETS`` extended by the
    ``RetrieverConfig.batch_size`` hint (the expected steady-state
    batch gets an exact-fit plan)."""
    if buckets is not None:
        out = set(buckets)
    else:
        out = set(DEFAULT_BUCKETS)
        if batch_size is not None:
            out.add(int(batch_size))
    if not out or any(
        not isinstance(b, (int, np.integer)) or isinstance(b, bool) or b < 1
        for b in out
    ):
        raise ValueError(
            f"buckets must be a non-empty set of positive ints, got "
            f"{sorted(out)}"
        )
    return tuple(sorted(int(b) for b in out))


def synthetic_trace(
    rng: np.random.Generator,
    n_requests: int,
    n_queries: int,
    repeat_frac: float = 0.25,
) -> np.ndarray:
    """Repeat-heavy query-id trace — the ONE synthetic workload shape
    the load generator (``launch/serve.py --pipeline``) and the
    Table-4 scheduler benchmark share, so both gates measure the same
    traffic: ``repeat_frac`` of requests re-ask one of a small head
    (``n_queries // 4`` hot queries, the skew of real query logs), the
    rest draw uniformly. Returns i64 [n_requests] query indices."""
    n_head = max(1, n_queries // 4)
    return np.where(
        rng.random(n_requests) < repeat_frac,
        rng.integers(0, n_head, size=n_requests),
        rng.integers(0, n_queries, size=n_requests),
    )


# ---------------------------------------------------------------------------
# plan cache — the compile layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Identity of one compiled search executable.

    ``mode`` is the RESOLVED kernel execution mode
    (``repro.kernels.modes.MODES``) the backend string maps to — the
    auto ``backend="pallas"`` resolves to ``"pallas_compiled"`` here, so
    plan identity tracks what actually compiles, not how it was asked
    for.

    ``shard`` is the shard-topology component (DESIGN.md §9): ``""``
    for a monolithic index, ``"<shard>/<n_shards>"`` for a per-shard
    sub-retriever inside a ``ShardedRetriever`` — shards of one tree
    (whose array shapes may differ, e.g. the ragged last shard) never
    collide on a plan key.

    ``gen`` is the index-generation component (DESIGN.md §10): ``""``
    for an immutable index, ``"g<generation>"`` for the fan-out facade
    of a ``MutableRetriever`` — a generation flip (merge/compaction
    commit) changes the component, so stale facade plans are retired
    rather than silently reused against the new base."""

    engine: str
    codec: str
    backend: str
    mode: str
    k: int
    bucket: int
    shard: str = ""
    gen: str = ""
    #: value codec (DESIGN.md §12) — the traced dequant stage differs
    #: per vq, so executables must not collide across value codecs
    vq: str = "f16"


class SearchPlan:
    """One warm executable: pad a ``[n ≤ bucket, dim]`` query batch to
    the bucket shape, run the jit'd engine ``search_batch``, slice the
    padding back off. Padded slots carry the zero query — ``vmap``
    keeps per-query results independent, so padding never perturbs the
    real rows (asserted by the parity suite).

    ``warm(dim)`` ahead-of-time compiles the bucket-shaped executable
    (``jit.lower(...).compile()``) without running a search — the
    prefetcher (DESIGN.md §11) stages compiles off the serving hot
    path. Calls whose padded batch matches the warmed shape/dtype run
    the AOT executable directly; anything else falls back to ordinary
    jit dispatch (which shares XLA's compilation cache, so nothing
    compiles twice)."""

    __slots__ = ("key", "_fn", "_compiled", "_warm_sig", "_lock")

    def __init__(self, key: PlanKey, fn: Callable):
        self.key = key
        self._fn = fn
        self._compiled: Optional[Callable] = None
        self._warm_sig: Optional[Tuple[int, int, np.dtype]] = None
        self._lock = threading.Lock()

    def warm(self, dim: int, dtype=jnp.float32) -> bool:
        """AOT-compile this plan for ``[bucket, dim]`` batches of
        ``dtype``. Idempotent; returns True iff a compile happened.
        Only jit-backed plans can lower — facade plans (sharded /
        mutable fan-out dispatch through sub-plans) return False and
        are warmed by executing instead (``Pipeline.warm``)."""
        if not hasattr(self._fn, "lower"):
            return False
        with self._lock:
            if self._compiled is not None:
                return False
            spec = jax.ShapeDtypeStruct((self.key.bucket, int(dim)), dtype)
            compiled = self._fn.lower(spec).compile()
            self._warm_sig = (self.key.bucket, int(dim), np.dtype(dtype))
            self._compiled = compiled
            return True

    @property
    def executable(self):
        """The AOT-compiled executable ``warm`` built (None before)."""
        return self._compiled

    def __call__(self, Q) -> Tuple[jnp.ndarray, jnp.ndarray]:
        Q = jnp.asarray(Q)
        n, bucket = Q.shape[0], self.key.bucket
        if n > bucket:
            raise ValueError(f"batch of {n} exceeds plan bucket {bucket}")
        if n < bucket:
            Q = jnp.concatenate(
                [Q, jnp.zeros((bucket - n, Q.shape[1]), Q.dtype)]
            )
        fn = self._fn
        if (self._compiled is not None
                and (bucket, Q.shape[1], np.dtype(Q.dtype)) == self._warm_sig):
            fn = self._compiled
        ids, scores = fn(Q)
        return ids[:n], scores[:n]


class PlanCache:
    """Compiled executables of ONE retriever, keyed by padding bucket.

    Holds the jit'd ``impl.search_batch`` (the compile logic that used
    to live inline in ``Retriever.__init__``) and hands out
    ``SearchPlan``s per bucket; jax's executable cache is keyed by the
    padded shape, so plan keys and compiled programs are 1:1.
    ``compiles`` counts plan creations — the serving-metrics recompile
    counter. Batches beyond the largest bucket round up to the next
    power of two, which joins the bucket set (counted as a compile)."""

    def __init__(self, retriever: "Retriever", buckets: Optional[Sequence[int]] = None):
        import jax
        from functools import partial

        from repro.kernels.modes import backend_mode, resolve_mode

        cfg = retriever.cfg
        self.buckets = plan_buckets(cfg.batch_size, buckets)
        self.k = cfg.k
        mode = resolve_mode(backend_mode(cfg.backend))
        self._key = partial(
            PlanKey, cfg.engine, cfg.codec, cfg.backend, mode, cfg.k,
            shard=getattr(retriever, "shard", ""), vq=cfg.vq,
        )
        self._dispatch = jax.jit(
            partial(
                retriever.impl.search_batch,
                cfg,
                retriever.n_docs,
                retriever.value_scale,
                retriever.arrays,
            )
        )
        self._plans: Dict[int, SearchPlan] = {}
        self.compiles = 0
        self._lock = threading.Lock()

    def bucket_for(self, n: int) -> int:
        """Smallest covering bucket; beyond the largest, the next power
        of two (one dispatch, never a silent truncation)."""
        if n < 1:
            raise ValueError(f"batch size must be ≥ 1, got {n}")
        for b in self.buckets:
            if b >= n:
                return b
        return 1 << (n - 1).bit_length()

    def get(self, bucket: int) -> SearchPlan:
        """The plan for ``bucket``, compiled on first request. Ad hoc
        beyond-the-largest buckets get a cached plan too, but the
        configured bucket SET stays fixed — a one-off oversized batch
        must not raise the scheduler's dispatch threshold. Thread-safe:
        concurrent first requests for one bucket create one plan."""
        with self._lock:
            plan = self._plans.get(bucket)
            if plan is None:
                plan = SearchPlan(self._key(bucket=bucket), self._dispatch)
                self._plans[bucket] = plan
                self.compiles += 1
            return plan

    def search(self, Q) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Pad ``Q`` to its covering bucket and run the warm plan.
        An empty batch short-circuits to empty ``(0, k)`` results."""
        Q = jnp.asarray(Q)
        if Q.shape[0] == 0:
            return (jnp.zeros((0, self.k), jnp.int32),
                    jnp.zeros((0, self.k), jnp.float32))
        return self.get(self.bucket_for(Q.shape[0]))(Q)


# ---------------------------------------------------------------------------
# result cache — quantized-query LRU
# ---------------------------------------------------------------------------


def quantized_query_key(q, value_dtype=np.float16) -> bytes:
    """Cache key of one dense query: the *quantized sparse* form —
    nonzero component ids + values rounded to ``value_dtype``.

    Sub-f32 keying is a DELIBERATE tolerance, not an exactness claim:
    scoring uses the full-precision query, so two queries that collide
    after rounding can have (slightly) different true scores. That is
    why ``Pipeline`` only defaults to an f16 key when the index itself
    stores f16 values — the collapse then treats queries within one
    f16 ulp per component as the same ask, an error of the same order
    as the value quantization the index already accepts — and keys
    exactly (f32, identity rounding) otherwise. Exact replays of a
    served query always hit their own byte-identical entry."""
    qv = np.asarray(q, dtype=value_dtype)
    nz = np.flatnonzero(qv).astype(np.int32)
    return nz.tobytes() + qv[nz].tobytes()


class ResultCache:
    """Bounded LRU of per-query top-k results.

    Keys come from ``quantized_query_key``; values are the
    ``(ids [k], scores [k])`` numpy pair exactly as served, so a hit
    replays byte-identical results. Entries are stored as read-only
    COPIES: a caller mutating the arrays it was handed can never
    corrupt later replays (and cached rows don't pin whole dispatch
    batches alive). ``capacity=0`` disables caching (every lookup
    misses, nothing is stored).

    A cached result is a statement about ONE index state.
    ``invalidate()`` flushes the cache when that state changes (the
    index mutated, a merge committed a new generation); the ``epoch``
    attribute tags which index epoch the current entries belong to, so
    the pipeline can compare it against the owning retriever's
    ``epoch`` and invalidate lazily on the next admission
    (DESIGN.md §10). ``invalidations`` / ``invalidated_entries`` count
    flushes and the entries they dropped — surfaced in
    ``ServeStats.snapshot`` as the staleness-hygiene metric."""

    def __init__(self, capacity: int = 1024):
        if capacity < 0:
            raise ValueError(f"capacity must be ≥ 0, got {capacity}")
        self.capacity = int(capacity)
        self._items: "OrderedDict[bytes, Tuple[np.ndarray, np.ndarray]]" = OrderedDict()
        self.hits = 0
        self.lookups = 0
        #: index epoch the current entries were computed against
        self.epoch: int = 0
        self.invalidations = 0
        self.invalidated_entries = 0
        # get/put/invalidate race between serving threads and a
        # background-merge commit (DESIGN.md §11); RLock so a holder
        # can re-enter through the property accessors
        self._lock = threading.RLock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)

    def get(self, key: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        with self._lock:
            self.lookups += 1
            got = self._items.get(key)
            if got is None:
                return None
            self._items.move_to_end(key)
            self.hits += 1
            return got

    def put(self, key: bytes, ids: np.ndarray, scores: np.ndarray) -> None:
        if self.capacity == 0:
            return
        ids, scores = np.array(ids), np.array(scores)  # own the memory
        ids.flags.writeable = scores.flags.writeable = False
        with self._lock:
            self._items[key] = (ids, scores)
            self._items.move_to_end(key)
            while len(self._items) > self.capacity:
                self._items.popitem(last=False)

    def invalidate(self, epoch: Optional[int] = None) -> int:
        """Flush every entry; returns how many were dropped.

        ``epoch`` (when given) records the index epoch the cache is now
        current for — the pipeline passes the retriever's epoch so the
        flush happens exactly once per index change, not per lookup.
        An empty flush still counts as an invalidation: the caller
        declared the previous state dead, whether or not anything was
        cached under it. Atomic: a concurrent ``get`` sees either the
        pre-flush entries (tagged stale by the epoch check upstream) or
        an empty cache, never a torn map."""
        with self._lock:
            n = len(self._items)
            self._items.clear()
            self.invalidations += 1
            self.invalidated_entries += n
            if epoch is not None:
                self.epoch = int(epoch)
            return n

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0


# ---------------------------------------------------------------------------
# serving metrics
# ---------------------------------------------------------------------------


class ServeStats:
    """The pipeline metrics block (DESIGN.md §8 metrics contract).

    Latency samples are end-to-end per query (submit → result
    de-multiplexed), in µs under the pipeline's clock, kept in a
    bounded sliding window (``window`` most recent — a long-lived
    pipeline must not grow without bound, and recent percentiles are
    the ones that matter operationally). ``snapshot()`` returns one
    flat dict: qps, p50/p95/p99_us, cache_hit_rate, n_queries,
    dispatches + occupancy per bucket, recompiles, and the overlap
    counters (``prefetch_hits/prefetch_misses`` from the sharded
    prefetcher, ``merge_wall_us/blocked_swap_us`` from background
    compaction — DESIGN.md §11). Recording is lock-guarded so serving
    threads and a background merge can feed one stats block."""

    def __init__(self, clock: Callable[[], float], window: int = 8192):
        self._clock = clock
        self.t_start = clock()
        self.n_queries = 0  # completed (cache hits included)
        self.latencies_us = deque(maxlen=window)
        self.dispatches: Dict[int, int] = {}  # bucket → dispatch count
        self.occupancy: Dict[int, int] = {}  # bucket → Σ real queries
        # overlap counters (DESIGN.md §11) — synced off the serving
        # stack by ``sync_overlap`` / set directly by owners
        self.prefetch_hits = 0       # shard rotations served from the staged buffer
        self.prefetch_misses = 0     # rotations that paid page-in on the hot path
        self.merge_wall_us = 0.0     # Σ background-merge build wall-clock
        self.blocked_swap_us = 0.0   # Σ time queries were blocked on commit swaps
        self._lock = threading.RLock()

    def reset_clock(self) -> None:
        """Restart the QPS clock (e.g. after ``Pipeline.warm`` so the
        warmup wall-clock doesn't dilute the measured trace)."""
        with self._lock:
            self.t_start = self._clock()

    def record_dispatch(self, bucket: int, n_real: int) -> None:
        with self._lock:
            self.dispatches[bucket] = self.dispatches.get(bucket, 0) + 1
            self.occupancy[bucket] = self.occupancy.get(bucket, 0) + n_real

    def record_query(self, latency_us: float) -> None:
        with self._lock:
            self.n_queries += 1
            self.latencies_us.append(latency_us)

    def percentile(self, p: float) -> float:
        with self._lock:
            if not self.latencies_us:
                return float("nan")
            samples = np.asarray(list(self.latencies_us))
        return float(np.percentile(samples, p))

    def sync_overlap(self, retriever) -> None:
        """Pull the overlap counters off the serving stack: prefetch
        hits/misses live on a ``ShardedRetriever`` (possibly the base
        of a ``MutableRetriever``), merge/swap timings on a
        ``MutableRetriever``. Objects without the attributes contribute
        zero, so this is safe over any retriever."""
        srcs = [retriever, getattr(retriever, "base", None)]
        srcs = [r for r in srcs if r is not None]
        with self._lock:
            self.prefetch_hits = sum(
                int(getattr(r, "prefetch_hits", 0)) for r in srcs)
            self.prefetch_misses = sum(
                int(getattr(r, "prefetch_misses", 0)) for r in srcs)
            self.merge_wall_us = sum(
                float(getattr(r, "merge_wall_us", 0.0)) for r in srcs)
            self.blocked_swap_us = sum(
                float(getattr(r, "blocked_swap_us", 0.0)) for r in srcs)

    def snapshot(self, cache: Optional[ResultCache] = None,
                 plans: Optional[PlanCache] = None) -> dict:
        with self._lock:
            elapsed = max(self._clock() - self.t_start, 1e-9)
            dispatches = dict(sorted(self.dispatches.items()))
            occ = {
                b: self.occupancy[b] / (b * dispatches[b])
                for b in dispatches
            }
            overlap = {
                "prefetch_hits": self.prefetch_hits,
                "prefetch_misses": self.prefetch_misses,
                "merge_wall_us": self.merge_wall_us,
                "blocked_swap_us": self.blocked_swap_us,
            }
            n_queries = self.n_queries
        return {
            "n_queries": n_queries,
            "qps": n_queries / elapsed,
            "p50_us": self.percentile(50),
            "p95_us": self.percentile(95),
            "p99_us": self.percentile(99),
            "cache_hit_rate": cache.hit_rate if cache is not None else 0.0,
            "cache_invalidations": (
                cache.invalidations if cache is not None else 0
            ),
            "cache_invalidated_entries": (
                cache.invalidated_entries if cache is not None else 0
            ),
            "dispatches": dispatches,
            "bucket_occupancy": occ,
            "recompiles": plans.compiles if plans is not None else 0,
            **overlap,
        }

    @staticmethod
    def summary(snap: dict) -> str:
        occ = " ".join(
            f"b{b}×{snap['dispatches'][b]}@{snap['bucket_occupancy'][b]:.0%}"
            for b in snap["dispatches"]
        )
        out = (
            f"served={snap['n_queries']} qps={snap['qps']:.0f} "
            f"p50={snap['p50_us']:.0f}µs p95={snap['p95_us']:.0f}µs "
            f"p99={snap['p99_us']:.0f}µs hit_rate={snap['cache_hit_rate']:.0%} "
            f"invalidations={snap.get('cache_invalidations', 0)} "
            f"recompiles={snap['recompiles']} buckets[{occ}]"
        )
        pf = snap.get("prefetch_hits", 0) + snap.get("prefetch_misses", 0)
        if pf:
            out += (f" prefetch={snap['prefetch_hits']}h/"
                    f"{snap['prefetch_misses']}m")
        if snap.get("merge_wall_us", 0.0):
            out += (f" merge_wall={snap['merge_wall_us'] / 1e3:.0f}ms"
                    f" blocked_swap={snap['blocked_swap_us']:.0f}µs")
        return out


# ---------------------------------------------------------------------------
# micro-batching scheduler
# ---------------------------------------------------------------------------


class PendingQuery:
    """Ticket returned by ``Pipeline.submit``; ``result()`` flushes the
    owning pipeline if the query is still queued (closed-loop callers
    never deadlock on an under-filled bucket)."""

    __slots__ = ("q", "key", "t_submit", "done", "ids", "scores", "from_cache",
                 "_pipeline")

    def __init__(self, pipeline: "Pipeline", q: np.ndarray, key: bytes,
                 t_submit: float):
        self._pipeline = pipeline
        self.q = q
        self.key = key
        self.t_submit = t_submit
        self.done = False
        self.from_cache = False
        self.ids: Optional[np.ndarray] = None
        self.scores: Optional[np.ndarray] = None

    def result(self) -> Tuple[np.ndarray, np.ndarray]:
        if not self.done:
            self._pipeline.flush()
        assert self.done, "flush() must complete every queued ticket"
        return self.ids, self.scores

    def _complete(self, ids: np.ndarray, scores: np.ndarray, now: float,
                  stats: ServeStats) -> None:
        self.ids, self.scores = ids, scores
        self.done = True
        stats.record_query(1e6 * (now - self.t_submit))


class Pipeline:
    """Host-side micro-batching scheduler over one ``Retriever``.

    Admission → coalescing → dispatch → de-multiplex:

    * ``submit(q)`` checks the result cache (a hit completes the
      ticket immediately), else enqueues; a queue at the largest
      bucket's capacity dispatches at once.
    * ``poll()`` fires the deadline: once the OLDEST queued query has
      waited ``deadline_us``, the queue dispatches into its smallest
      covering bucket rather than waiting for batch-mates. Call it on
      every scheduler turn (the load generator calls it before each
      arrival).
    * ``flush()`` dispatches whatever is queued (end of trace /
      ``result()`` on a queued ticket).
    * ``search_batch(Q)`` is the synchronous convenience loop:
      submit every row, flush, return results stacked in submission
      order — the surface ``Retriever.search_batch`` reroutes to.

    The plan cache is shared with the owning retriever (a direct
    ``retriever.search`` and the pipeline warm the same executables).
    """

    def __init__(
        self,
        retriever: "Retriever",
        *,
        buckets: Optional[Sequence[int]] = None,
        deadline_us: float = 1000.0,
        cache_size: int = 1024,
        key_dtype=None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if deadline_us < 0:
            raise ValueError(f"deadline_us must be ≥ 0, got {deadline_us}")
        self.retriever = retriever
        # ask the retriever for its plan surface rather than building a
        # PlanCache directly: a ShardedRetriever answers with its
        # shard-fanning facade (same bucket_for/get/search/compiles
        # contract), so the scheduler works unmodified over shards
        self.plans = (
            retriever.plans if buckets is None
            else retriever.make_plans(buckets)
        )
        self.deadline_us = float(deadline_us)
        self.cache = ResultCache(cache_size)
        if key_dtype is None:
            # match the cache tolerance to the index's own value
            # quantization: f16 keys for f16-valued rows, exact (f32)
            # keys for everything else — see quantized_query_key
            key_dtype = (
                np.float16
                if getattr(retriever, "value_format", None) == "f16"
                else np.float32
            )
        self.key_dtype = key_dtype  # result-cache tolerance knob
        self._clock = clock
        self.stats = ServeStats(clock)
        self._queue: List[PendingQuery] = []
        # one scheduler lock across admission + dispatch: submitters
        # from other threads serialize here, so the queue is never torn
        # and at most one dispatch runs at a time (DESIGN.md §11);
        # RLock because submit → _dispatch re-enters
        self._lock = threading.RLock()

    # -- warmup ---------------------------------------------------------
    def warm(self) -> int:
        """Pre-build every configured bucket's plan by executing a
        zero-query batch through it — compile cost moves out of the
        measured trace, the same discipline as ``benchmarks/common.py``
        ``timeit_us(warmup=…)``. Bypasses stats and the result cache
        (the zero query would otherwise pollute both) and restarts the
        QPS clock. Returns the number of plans the warmup created
        (recompiles during the subsequent trace stay visible in
        ``snapshot()['recompiles']`` on top of this baseline)."""
        dim = int(self.retriever.dim)
        before = self.plans.compiles
        for b in self.plans.buckets:
            plan = self.plans.get(b)
            np.asarray(plan(np.zeros((1, dim), np.float32))[0])
        self.stats.reset_clock()
        return self.plans.compiles - before

    # -- admission ------------------------------------------------------
    def submit(self, q) -> PendingQuery:
        q = np.asarray(q, dtype=np.float32)
        now = self._clock()
        with self._lock:
            # epoch sync: a mutable retriever bumps ``epoch`` on every
            # index change (insert/delete/merge commit); any cached
            # answer predating the bump is stale and must not be served
            # (DESIGN.md §10) — under the scheduler lock, so a commit
            # landing mid-admission can't interleave a stale hit
            ep = getattr(self.retriever, "epoch", None)
            if ep is not None and ep != self.cache.epoch:
                self.cache.invalidate(epoch=ep)
            # key computation is an O(dim) scan — skip it entirely when
            # the cache is disabled (the strict-exactness path stays lean)
            caching = self.cache.capacity > 0
            key = quantized_query_key(q, self.key_dtype) if caching else b""
            ticket = PendingQuery(self, q, key, now)
            if caching:
                hit = self.cache.get(ticket.key)
                if hit is not None:
                    ticket.from_cache = True
                    ticket._complete(hit[0], hit[1], self._clock(), self.stats)
                    return ticket
            self._queue.append(ticket)
            if len(self._queue) >= self.plans.buckets[-1]:
                self._dispatch()
            return ticket

    # -- scheduling -----------------------------------------------------
    def poll(self) -> int:
        """Fire the deadline if the oldest queued query has expired;
        returns how many queries were dispatched."""
        with self._lock:
            if not self._queue:
                return 0
            waited_us = 1e6 * (self._clock() - self._queue[0].t_submit)
            if waited_us >= self.deadline_us:
                return self._dispatch()
            return 0

    def flush(self) -> int:
        """Dispatch every queued query (possibly several buckets)."""
        with self._lock:
            n = 0
            while self._queue:
                n += self._dispatch()
            return n

    def _dispatch(self) -> int:
        """Coalesce the queue head into its smallest covering bucket,
        run the plan, de-multiplex per-query top-k, feed the cache.
        Callers hold ``_lock``."""
        if not self._queue:
            return 0
        cap = self.plans.buckets[-1]
        batch, self._queue = self._queue[:cap], self._queue[cap:]
        bucket = self.plans.bucket_for(len(batch))
        Q = np.stack([t.q for t in batch])
        ids, scores = self.plans.get(bucket)(Q)
        ids, scores = np.asarray(ids), np.asarray(scores)
        now = self._clock()
        self.stats.record_dispatch(bucket, len(batch))
        caching = self.cache.capacity > 0
        for i, t in enumerate(batch):
            t._complete(ids[i], scores[i], now, self.stats)
            if caching:
                self.cache.put(t.key, ids[i], scores[i])
        return len(batch)

    # -- synchronous convenience surface --------------------------------
    def search_batch(self, Q) -> Tuple[np.ndarray, np.ndarray]:
        """Serve a whole query batch through the scheduler: results
        stacked in submission order, byte-identical to a direct
        ``Retriever.search`` of the same rows (the parity invariant)."""
        Q = np.asarray(Q)
        if Q.shape[0] == 0:
            k = self.retriever.cfg.k
            return np.zeros((0, k), np.int32), np.zeros((0, k), np.float32)
        tickets = [self.submit(q) for q in Q]
        self.flush()
        ids = np.stack([t.ids for t in tickets])
        scores = np.stack([t.scores for t in tickets])
        return ids, scores

    def snapshot(self) -> dict:
        self.stats.sync_overlap(self.retriever)
        return self.stats.snapshot(cache=self.cache, plans=self.plans)
