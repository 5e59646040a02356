"""Spans, counters, named scopes and named executables of the serving
path (``repro.serve.obs``; DESIGN.md §8 metrics contract).

Spans are checked by replacing ``jax.profiler.TraceAnnotation`` with a
recorder (off: it must never be built; on: names, args and nesting),
the plan cache's counters, and scopes and executable names in the
compiled HLO text (queue-wait samples: ``test_pipeline.py``). That results stay bitwise
those of a plan without scopes is the parity suites' job
(``test_pipeline.py``, ``test_serve_api.py``)."""

import jax
import numpy as np
import pytest

from repro.data.synthetic import SyntheticConfig, generate_collection
from repro.serve import obs
from repro.serve.api import Retriever, RetrieverConfig
from repro.serve.pipeline import Pipeline

SEISMIC = dict(cut=8, block_budget=128, n_probe=24, n_postings=200, block_size=16)
DIM = 1024


class Recorder:
    """Stands in for ``TraceAnnotation``: logs (event, name, args)."""

    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        Recorder.log.append(("enter", self.name, self.args))

    def __exit__(self, *exc):
        Recorder.log.append(("exit", self.name, self.args))


@pytest.fixture(scope="module")
def collection():
    cfg = SyntheticConfig(name="obs", dim=DIM, n_docs=200, n_queries=9,
                          doc_nnz_mean=30.0, query_nnz_mean=10.0, seed=5)
    return generate_collection(cfg, value_format="f16")


@pytest.fixture(scope="module")
def queries(collection):
    return np.stack([collection.query_dense(i) for i in range(collection.n_queries)])


def _retriever(collection, engine, **kw):
    cfg = RetrieverConfig(engine=engine, codec="dotvbyte", k=5, backend="jnp",
                          params=SEISMIC if engine == "seismic" else {}, **kw)
    return Retriever.build(collection.fwd, cfg)


@pytest.fixture(scope="module")
def flat(collection):
    return _retriever(collection, "flat")


@pytest.fixture(scope="module")
def seismic(collection):
    return _retriever(collection, "seismic")


@pytest.fixture
def recorded(monkeypatch):
    Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    obs.enable()
    yield Recorder.log
    obs.disable()


@pytest.mark.parametrize("engine", ["flat", "seismic"])
def test_off_builds_no_span(monkeypatch, request, queries, engine):
    """With spans off (the default), serving constructs no profiler
    annotation at all: direct search and the pipeline."""

    def boom(*a, **k):
        raise AssertionError("a span was built while obs is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    r = request.getfixturevalue(engine)
    np.asarray(r.search(queries[:3])[0])
    pipe = Pipeline(r, buckets=(1, 4), cache_size=4)
    ids, _ = pipe.search_batch(queries[:5])
    assert ids.shape == (5, 5)


def test_on_emits_spans_tied_by_request_and_dispatch(flat, queries, recorded):
    """Each layer's span, with the ids that tie one request together:
    ``serve.admit`` per ticket (``rid``), one ``serve.dispatch`` per
    batch (``did``, bucket, real count, first and last ``rid``) holding
    ``plan.dispatch``, ``serve.wait`` and ``serve.demux`` in that order."""
    pipe = Pipeline(flat, buckets=(1, 4), cache_size=0)
    pipe.search_batch(queries[:6])  # 4 dispatch when bucket 4 fills, 2 at flush
    enters = [(n, a) for ev, n, a in recorded if ev == "enter"]
    admits = [a["rid"] for n, a in enters if n == "serve.admit"]
    assert admits == list(range(6))
    dispatches = [a for n, a in enters if n == "serve.dispatch"]
    assert dispatches == [
        dict(did=0, bucket=4, n_real=4, rid_first=0, rid_last=3),
        dict(did=1, bucket=4, n_real=2, rid_first=4, rid_last=5),
    ]
    # inside each dispatch: plan, wait, demux, in order, tagged with its did
    for d in (0, 1):
        lo = recorded.index(("enter", "serve.dispatch", dispatches[d]))
        hi = recorded.index(("exit", "serve.dispatch", dispatches[d]))
        inner = [(n, a) for ev, n, a in recorded[lo + 1:hi] if ev == "enter"]
        assert [n for n, _ in inner] == ["plan.dispatch", "serve.wait", "serve.demux"]
        assert inner[0][1] == {"bucket": 4, "first": int(d == 0)}
        assert all(a == {"did": d} for _, a in inner[1:])
    # every span closes, and admission never holds a dispatch
    assert sum(ev == "enter" for ev, _, _ in recorded) == len(recorded) // 2
    lo = recorded.index(("enter", "serve.admit", {"rid": 3}))
    assert recorded[lo + 1] == ("exit", "serve.admit", {"rid": 3})


def test_direct_search_spans_only_the_plan(flat, queries, recorded):
    np.asarray(flat.search(queries[:3])[0])
    assert [n for ev, n, _ in recorded if ev == "enter"] == ["plan.dispatch"]


def test_plan_cache_counts_upload_bytes_before_padding(collection, queries):
    """``upload_bytes`` counts the host batch each plan call uploads,
    not the zero rows padded onto it on the device, as ``snapshot``
    reports it; a batch already on the device uploads nothing."""
    r = _retriever(collection, "flat")
    assert r.plans.upload_bytes == 0
    np.asarray(r.search(queries[:3])[0])  # bucket 4
    np.asarray(r.search(queries[:1])[0])  # bucket 1
    assert r.plans.upload_bytes == (3 + 1) * DIM * 4
    np.asarray(r.search(jax.numpy.asarray(queries[:2]))[0])
    assert r.plans.upload_bytes == (3 + 1) * DIM * 4
    pipe = Pipeline(r, buckets=(8,), cache_size=0)
    pipe.search_batch(queries[:5])
    snap = pipe.snapshot()
    assert snap["upload_bytes"] == pipe.plans.upload_bytes == 5 * DIM * 4


@pytest.mark.parametrize("n_shards", [2, 3])
def test_sharded_facade_counts_each_batch_once(collection, queries, n_shards):
    """A sharded index uploads a batch once, whatever its shard count:
    the facade's plans count it; each shard's plan is handed the
    uploaded batch and counts nothing."""
    r = _retriever(collection, "flat", n_shards=n_shards)
    np.asarray(r.search(queries[:3])[0])
    assert r.plans.upload_bytes == 3 * DIM * 4
    shards = [r._shard_retriever(s).plans for s in range(n_shards)]
    assert [p.upload_bytes for p in shards] == [0] * n_shards
    assert [p.compiles for p in shards] == [1] * n_shards  # each shard ran


@pytest.mark.parametrize("engine,scopes", [
    ("flat", ("flat.score", "flat.topk")),
    ("seismic", ("seismic.select", "seismic.phase1", "seismic.dedupe",
                 "seismic.phase2", "seismic.topk")),
])
def test_executables_carry_engine_name_and_scopes(request, engine, scopes):
    """The compiled plan is ``jit_search_<engine>`` and its operations
    carry the engine's named scopes in their op-name metadata."""
    r = request.getfixturevalue(engine)
    plan = r.make_plans((2,)).get(2)
    plan.warm(DIM)
    hlo = plan.executable.as_text()
    assert f"jit_search_{engine}" in hlo
    for scope in scopes:
        assert scope in hlo, scope


def test_mutable_facade_counts_survive_a_generation_flip(collection, queries):
    """The mutable facade counts its own plans' batches, and keeps them
    when a merge retires those plans."""
    from repro.serve.segments import MutableRetriever

    cfg = RetrieverConfig(engine="flat", codec="dotvbyte", k=5, backend="jnp")
    m = MutableRetriever.create(collection.fwd.slice(0, 150), cfg)
    np.asarray(m.search(queries[:3])[0])
    m.insert([collection.fwd.doc(i) for i in range(150, 160)])
    m.merge()
    np.asarray(m.search(queries[:4])[0])  # bucket 4 again: a fresh plan
    assert m.plans.retired == 1
    assert m.plans.upload_bytes == (3 + 4) * DIM * 4


def test_counters_lose_no_update_across_threads(collection, queries):
    """Plan calls race from many threads; every upload is counted."""
    import sys
    import threading

    r = _retriever(collection, "flat")
    np.asarray(r.search(queries[:2])[0])  # compile outside the race
    n_threads, n_calls = 8, 25

    def work():
        for _ in range(n_calls):
            r.search(queries[:2])

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert r.plans.upload_bytes == (1 + n_threads * n_calls) * 2 * DIM * 4


def test_request_serials_follow_queue_order_across_threads(flat, queries, recorded):
    """Submitters on many threads: each dispatch's ``rid_first`` to
    ``rid_last`` is exactly the requests it carried, because a request
    takes its serial in the order it joins the queue."""
    import sys
    import threading

    pipe = Pipeline(flat, buckets=(1, 4), cache_size=0)
    pipe.search_batch(queries[:4])  # compile outside the race
    recorded.clear()
    n_threads, n_each = 6, 10
    tickets = [[] for _ in range(n_threads)]

    def work(i):
        for j in range(n_each):
            tickets[i].append(pipe.submit(queries[(i + j) % len(queries)]))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    pipe.flush()
    done = [t for got in tickets for t in got]
    assert all(t.done for t in done)
    dispatches = [a for ev, n, a in recorded if ev == "enter" and n == "serve.dispatch"]
    first = min(t.rid for t in done)
    for d in dispatches:  # contiguous, in order, each exactly its real count
        assert (d["rid_first"], d["rid_last"]) == (first, first + d["n_real"] - 1)
        first += d["n_real"]
    assert first == max(t.rid for t in done) + 1
