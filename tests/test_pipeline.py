"""Online serving pipeline tests (DESIGN.md §8): plan cache, bucketed
micro-batching scheduler, result cache, serving metrics.

The ISSUE-5 acceptance criterion lives here: bucketed/padded batch
search through the pipeline returns byte-identical top-k (ids AND
scores) to a direct ``Retriever.search`` for every engine × codec ×
backend combination — including ragged final batches and cache-hit
replays. Scheduler semantics (deadline firing, full-bucket dispatch,
LRU eviction, recompile counting) are tested with an injected fake
clock, so nothing here sleeps."""

import numpy as np
import pytest

from repro.core.layout import available_layouts
from repro.data.synthetic import SyntheticConfig, generate_collection
from repro.serve.api import Retriever, RetrieverConfig, get_engine, open_retriever
from repro.serve.pipeline import (
    DEFAULT_BUCKETS,
    Pipeline,
    PlanCache,
    ResultCache,
    ServeStats,
    plan_buckets,
    quantized_query_key,
)

#: per-engine knobs sized for the tiny test collection
ENGINE_PARAMS = {
    "seismic": dict(cut=8, block_budget=128, n_probe=24, n_postings=200,
                    block_size=16),
    "hnsw": dict(beam=16, iters=16, n_seeds=4, m=8, ef_construction=24),
    "flat": {},
}


class FakeClock:
    """Deterministic injectable clock (seconds)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance_us(self, us: float) -> None:
        self.t += us * 1e-6


@pytest.fixture(scope="module")
def collection():
    cfg = SyntheticConfig(
        name="pipe", dim=1024, n_docs=240, n_queries=7,
        doc_nnz_mean=35.0, query_nnz_mean=10.0, seed=3,
    )
    return generate_collection(cfg, value_format="f16")


@pytest.fixture(scope="module")
def queries(collection):
    return np.stack([collection.query_dense(i) for i in range(collection.n_queries)])


@pytest.fixture(scope="module")
def host_indexes(collection):
    out = {}
    for name in ("seismic", "hnsw"):
        impl = get_engine(name)
        cfg = RetrieverConfig(engine=name, params=ENGINE_PARAMS[name])
        out[name] = impl.host_index(collection.fwd, cfg)
    return out


def _retriever(collection, host_indexes, engine, codec, backend="jnp", **kw):
    cfg = RetrieverConfig(engine=engine, codec=codec, k=5, backend=backend,
                          params=ENGINE_PARAMS[engine], **kw)
    if engine in host_indexes:
        return Retriever.from_host_index(host_indexes[engine], cfg)
    return Retriever.build(collection.fwd, cfg)


# ---------------------------------------------------------------------------
# the acceptance criterion: pipeline ≡ direct search, all combinations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("codec", available_layouts())
@pytest.mark.parametrize("engine", ["seismic", "hnsw", "flat"])
def test_pipeline_matches_direct_search(collection, queries, host_indexes,
                                        engine, codec, backend):
    """Bucketed/padded scheduler dispatch — AND a cache-hit replay —
    return byte-identical top-k ids and scores to direct search, for
    every engine × codec × backend."""
    r = _retriever(collection, host_indexes, engine, codec, backend)
    ids_d, sc_d = r.search(queries)  # direct: pads 7 → bucket 8
    ids_p, sc_p = r.search_batch(queries)  # pipeline: same plan, queued
    assert np.array_equal(np.asarray(ids_d), ids_p)
    assert np.array_equal(np.asarray(sc_d), sc_p)
    # replay: every query now hits the result cache; results identical
    ids_c, sc_c = r.search_batch(queries)
    assert np.array_equal(ids_p, ids_c)
    assert np.array_equal(sc_p, sc_c)
    snap = r.pipeline().snapshot()
    assert snap["cache_hit_rate"] == pytest.approx(0.5)
    assert snap["n_queries"] == 2 * collection.n_queries


def test_ragged_batches_and_custom_buckets(collection, queries, host_indexes):
    """A 7-query stream over buckets (2, 4) coalesces into a full
    4-bucket plus a ragged 3-in-4 final batch — same bytes as direct
    search either way."""
    r = _retriever(collection, host_indexes, "flat", "streamvbyte")
    ids_d, sc_d = r.search(queries)
    pipe = Pipeline(r, buckets=(2, 4), cache_size=0)
    ids_p, sc_p = pipe.search_batch(queries)
    assert np.array_equal(np.asarray(ids_d), ids_p)
    assert np.array_equal(np.asarray(sc_d), sc_p)
    snap = pipe.snapshot()
    assert snap["dispatches"] == {4: 2}  # 4 full + 3 padded to 4
    assert snap["bucket_occupancy"][4] == pytest.approx(7 / 8)


def test_batch_beyond_largest_bucket(collection, queries, host_indexes):
    """Streams longer than the largest bucket split across dispatches
    (scheduler) / round up to a power-of-two plan (direct search) —
    results identical to per-query truth in both paths."""
    r = _retriever(collection, host_indexes, "flat", "dotvbyte")
    Q = np.concatenate([queries, queries[:3]])  # 10 queries
    ids_d, sc_d = r.search(Q)
    pipe = Pipeline(r, buckets=(4,), cache_size=0)
    ids_p, sc_p = pipe.search_batch(Q)
    assert np.array_equal(np.asarray(ids_d), ids_p)
    assert np.array_equal(np.asarray(sc_d), sc_p)
    assert pipe.snapshot()["dispatches"] == {4: 3}


# ---------------------------------------------------------------------------
# scheduler semantics (fake clock — no sleeping)
# ---------------------------------------------------------------------------


def test_deadline_fires_undersized_batch(collection, queries, host_indexes):
    clock = FakeClock()
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    pipe = Pipeline(r, buckets=(8,), deadline_us=1000.0, cache_size=0,
                    clock=clock)
    t0 = pipe.submit(queries[0])
    t1 = pipe.submit(queries[1])
    assert not t0.done and pipe.poll() == 0  # deadline not reached
    clock.advance_us(999.0)
    assert pipe.poll() == 0
    clock.advance_us(2.0)  # oldest query now past its deadline
    assert pipe.poll() == 2
    assert t0.done and t1.done
    ids_d, _ = r.search(queries[:2])
    assert np.array_equal(np.asarray(ids_d)[0], t0.ids)
    assert np.array_equal(np.asarray(ids_d)[1], t1.ids)
    assert pipe.snapshot()["dispatches"] == {8: 1}
    # end-to-end latency saw the deadline wait
    assert pipe.stats.percentile(50) >= 1000.0


def test_full_bucket_dispatches_immediately(collection, queries, host_indexes):
    clock = FakeClock()
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    pipe = Pipeline(r, buckets=(1, 2, 4), deadline_us=1e9, cache_size=0,
                    clock=clock)
    tickets = [pipe.submit(q) for q in queries[:4]]
    assert all(t.done for t in tickets)  # queue hit the largest bucket
    assert pipe.snapshot()["dispatches"] == {4: 1}


def test_queue_wait_samples_under_a_fake_clock(collection, queries, host_indexes):
    """One sample per dispatched ticket: dispatch start − submit; cache
    hits are never queued, so they add none."""
    clock = FakeClock()
    r = _retriever(collection, host_indexes, "flat", "dotvbyte")
    pipe = Pipeline(r, buckets=(1, 4), deadline_us=1000.0, cache_size=8, clock=clock)
    pipe.submit(queries[0])
    clock.advance_us(300.0)
    pipe.submit(queries[1])
    clock.advance_us(800.0)
    assert pipe.poll() == 2  # the oldest waited past the deadline
    assert list(pipe.stats.queue_wait_us) == pytest.approx([1100.0, 800.0])
    t = pipe.submit(queries[0])  # a hit: never queued
    assert t.done and len(pipe.stats.queue_wait_us) == 2
    snap = pipe.snapshot()
    assert snap["queue_p50_us"] == pytest.approx(950.0)
    assert snap["queue_p95_us"] == pytest.approx(1085.0)


def test_queue_wait_window_is_bounded():
    stats = ServeStats(clock=lambda: 0.0, window=3)
    stats.record_queue_wait([1.0, 2.0, 3.0, 4.0])
    assert list(stats.queue_wait_us) == [2.0, 3.0, 4.0]
    assert np.isnan(ServeStats(clock=lambda: 0.0).snapshot()["queue_p50_us"])


def test_ticket_result_flushes(collection, queries, host_indexes):
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    pipe = Pipeline(r, buckets=(8,), deadline_us=1e9, cache_size=0)
    t = pipe.submit(queries[0])
    assert not t.done
    ids, scores = t.result()  # blocks on a flush, never deadlocks
    assert t.done and ids.shape == (5,) and scores.shape == (5,)


# ---------------------------------------------------------------------------
# result cache
# ---------------------------------------------------------------------------


def test_result_cache_lru_eviction_and_keys():
    c = ResultCache(capacity=2)
    ids = np.arange(3)
    k1, k2, k3 = b"a", b"b", b"c"
    c.put(k1, ids, ids)
    c.put(k2, ids, ids)
    assert c.get(k1) is not None  # k1 now most-recent
    c.put(k3, ids, ids)  # evicts k2 (LRU)
    assert c.get(k2) is None
    assert c.get(k1) is not None and c.get(k3) is not None
    assert len(c) == 2
    # quantized key: f16-identical queries share one entry, distinct
    # queries do not
    q = np.zeros(64, np.float32)
    q[7], q[20] = 1.25, 3.5
    q_jitter = q.copy()
    q_jitter[q > 0] += 1e-5  # below f16 resolution at these magnitudes
    q_other = q.copy()
    q_other[20] = 3.75
    assert quantized_query_key(q) == quantized_query_key(q_jitter)
    assert quantized_query_key(q) != quantized_query_key(q_other)


def test_cache_replays_survive_caller_mutation(collection, queries,
                                               host_indexes):
    """Cached entries are read-only copies of what was served: a
    caller scribbling on the arrays it was handed cannot corrupt later
    replays (dispatch results are read-only jax-buffer views already;
    the cache owns its own immutable copies either way)."""
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    pipe = Pipeline(r, buckets=(2,))
    t1 = pipe.submit(queries[0])
    t2 = pipe.submit(queries[1])  # fills bucket 2 → dispatched
    assert t2.done
    ref = t1.ids.copy()
    with pytest.raises(ValueError):  # dispatch view: immutable
        t1.ids[:] = -1
    t3 = pipe.submit(queries[0])  # cache hit
    assert t3.from_cache
    assert np.array_equal(t3.ids, ref)
    assert t3.ids is not t1.ids  # the cache owns a copy, not a view
    with pytest.raises(ValueError):  # replayed arrays: immutable too
        t3.ids[:] = -1


def test_cache_key_dtype_matches_index_quantization(collection, host_indexes):
    """The default cache tolerance follows the index: f16 keys for an
    f16-valued index (collapse error ≤ the index's own quantization
    noise), with an explicit exact override available."""
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    assert Pipeline(r).key_dtype == np.float16  # f16 value_format
    assert Pipeline(r, key_dtype=np.float32).key_dtype == np.float32


def test_cache_disabled(collection, queries, host_indexes):
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    pipe = Pipeline(r, cache_size=0)
    pipe.search_batch(queries[:2])
    pipe.search_batch(queries[:2])
    snap = pipe.snapshot()
    assert snap["cache_hit_rate"] == 0.0
    assert len(pipe.cache) == 0


# ---------------------------------------------------------------------------
# plan cache + batch_size wiring
# ---------------------------------------------------------------------------


def test_plan_buckets_and_bucket_for(collection, host_indexes):
    assert plan_buckets() == DEFAULT_BUCKETS
    assert 24 in plan_buckets(24)
    # an explicit bucket sequence is used verbatim — the batch_size
    # hint must not leak into it (the caller asked for exactly these)
    assert plan_buckets(128, buckets=(2, 4)) == (2, 4)
    with pytest.raises(ValueError, match="positive"):
        plan_buckets(buckets=(0, 4))
    with pytest.raises(ValueError, match="positive ints"):
        plan_buckets(buckets=(2.5, 8))
    with pytest.raises(ValueError, match="non-empty"):
        plan_buckets(buckets=())
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    assert r.plans.bucket_for(1) == 1
    assert r.plans.bucket_for(7) == 8
    assert r.plans.bucket_for(128) == 128
    assert r.plans.bucket_for(129) == 256  # beyond max → next pow2
    with pytest.raises(ValueError, match="≥ 1"):
        r.plans.bucket_for(0)


def test_oversized_search_keeps_bucket_set_stable(collection, queries,
                                                  host_indexes):
    """A one-off beyond-the-largest batch gets an ad hoc plan but must
    NOT grow the configured bucket set — otherwise one oversized
    direct search would permanently raise the scheduler's full-queue
    dispatch threshold."""
    r = _retriever(collection, host_indexes, "flat", "uncompressed",
                   batch_size=3)
    pipe = Pipeline(r, buckets=(2,), cache_size=0)
    r.search(np.repeat(queries, 1 + 2 // len(queries), axis=0)[:3])
    buckets_before = r.plans.buckets
    Qbig = np.repeat(queries, 20, axis=0)  # 140 > max bucket 128
    ids_d, _ = r.search(Qbig)
    assert ids_d.shape[0] == 140
    assert r.plans.buckets == buckets_before  # 256 plan cached, set unchanged
    assert pipe.plans.buckets == (2,)


def test_empty_batch(collection, host_indexes):
    """Zero queries is a valid (if degenerate) batch: empty (0, k)
    results from both the direct and the scheduler path."""
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    ids, scores = r.search(np.zeros((0, collection.fwd.dim), np.float32))
    assert ids.shape == scores.shape == (0, 5)
    ids_p, scores_p = r.search_batch(np.zeros((0, collection.fwd.dim)))
    assert ids_p.shape == scores_p.shape == (0, 5)


def test_batch_size_hint_gets_exact_plan(collection, queries, host_indexes):
    """The once-dead RetrieverConfig.batch_size: the hinted shape joins
    the bucket set, so the steady-state batch is served un-padded."""
    r = _retriever(collection, host_indexes, "flat", "streamvbyte",
                   batch_size=7)
    assert 7 in r.plans.buckets
    assert r.plans.bucket_for(7) == 7
    ids_h, sc_h = r.search(queries)  # exact-fit plan
    r8 = _retriever(collection, host_indexes, "flat", "streamvbyte")
    ids_8, sc_8 = r8.search(queries)  # padded to bucket 8
    assert np.array_equal(np.asarray(ids_h), np.asarray(ids_8))
    assert np.array_equal(np.asarray(sc_h), np.asarray(sc_8))


@pytest.mark.parametrize("bad", [0, -3, 2.5, True, "8"])
def test_invalid_batch_size_rejected(collection, bad):
    with pytest.raises(ValueError, match="batch_size"):
        Retriever.build(collection.fwd,
                        RetrieverConfig(engine="flat", batch_size=bad))


def test_recompile_counting(collection, queries, host_indexes):
    """Warm traffic never recompiles: every batch size within one
    bucket reuses the same plan; a new bucket is one compile."""
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    assert r.plans.compiles == 0
    r.search(queries[:5])  # bucket 8
    assert r.plans.compiles == 1
    r.search(queries[:7])  # same bucket — warm
    r.search(queries[:6])
    assert r.plans.compiles == 1
    r.search(queries[:2])  # bucket 2 — one more plan
    assert r.plans.compiles == 2
    assert r.pipeline().snapshot()["recompiles"] == 2


def test_plan_cache_shared_between_search_and_pipeline(collection, queries,
                                                      host_indexes):
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    r.search(queries)  # warms bucket 8
    n = r.plans.compiles
    r.search_batch(queries)  # scheduler dispatch reuses the warm plan
    assert r.plans.compiles == n
    # an explicit bucket override compiles its own cache
    pipe = Pipeline(r, buckets=(2,))
    assert pipe.plans is not r.plans


def test_oversized_batch_rejected_by_plan(collection, host_indexes, queries):
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    plan = r.plans.get(4)
    with pytest.raises(ValueError, match="exceeds plan bucket"):
        plan(queries)  # 7 queries into a 4-bucket plan


# ---------------------------------------------------------------------------
# artifacts + metrics
# ---------------------------------------------------------------------------


def test_artifact_round_trips_batch_size(collection, host_indexes, tmp_path):
    r = _retriever(collection, host_indexes, "flat", "streamvbyte",
                   batch_size=24)
    art = r.save(tmp_path / "bs")
    r2 = open_retriever(art)
    assert r2.cfg.batch_size == 24
    assert 24 in r2.plans.buckets


def test_stats_snapshot_contract(collection, queries, host_indexes):
    clock = FakeClock()
    r = _retriever(collection, host_indexes, "flat", "uncompressed")
    pipe = Pipeline(r, buckets=(4,), deadline_us=1e9, clock=clock)
    clock.advance_us(1e6)  # 1 s window
    pipe.search_batch(queries)  # 4 + 3-padded-to-4, then replay 2 hits
    pipe.search_batch(queries[:2])
    snap = pipe.snapshot()
    assert snap["n_queries"] == 9
    assert snap["qps"] == pytest.approx(9.0)  # clock frozen after 1 s
    assert snap["dispatches"] == {4: 2}
    assert snap["bucket_occupancy"][4] == pytest.approx(7 / 8)
    assert snap["cache_hit_rate"] == pytest.approx(2 / 9)
    assert snap["recompiles"] == 1
    for key in ("p50_us", "p95_us", "p99_us"):
        assert np.isfinite(snap[key])
