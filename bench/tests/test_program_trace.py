"""The program's part of a trace (``bench/program_trace.py``) and the
per-layer metrics that read it, on traces recorded on a TPU v5e and on
events made by hand.

``flat_program_20k.xplane.pb`` and ``seismic_program_20k.xplane.pb``
were recorded with the program's spans on by
``record_program_traces.py`` (its docstring says what each holds);
``seismic_bucket4_20k.xplane.pb`` is the older trace of
``test_trace.py``, from before the program named its scopes."""

import pathlib

import pytest

from bench import program_trace as pt
from bench import spec, trace
from bench.tests.record_program_traces import strip_planes

DATA = pathlib.Path(__file__).parent / "data"
FLAT = str(DATA / "flat_program_20k.xplane.pb")
SEISMIC = str(DATA / "seismic_program_20k.xplane.pb")
MS = 1_000_000  # ns


# -- traces recorded on the chip -------------------------------------------


def _outermost(path):
    """The outermost name of every op path with a name stack (parameters
    and compiler-made operations have none)."""
    return {p.split("/")[0] for p in pt.op_paths(path)[0].values() if "/" in p}


@pytest.mark.parametrize("path,engine", [(FLAT, "flat"), (SEISMIC, "seismic")])
def test_executables_are_named_per_engine(path, engine):
    r = pt.reduce(path)
    assert set(r["modules"]) == {f"jit_search_{engine}"}
    assert _outermost(path) == {f"jit(search_{engine})"}


def test_flat_device_time_by_scope():
    r = pt.reduce(FLAT)
    scopes = r["scope_s"]
    assert scopes["flat.topk"] > 0 and scopes["flat.score"] > 0
    assert set(scopes) <= {"flat.score", "flat.topk", pt.UNSCOPED}
    # scopes split the device's self time, which sums to its busy time
    assert sum(scopes.values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert scopes["flat.score"] >= r["kernel_s"] * (1 - 1e-6)  # the kernel is scoring


def test_seismic_device_time_by_scope():
    r = pt.reduce(SEISMIC)
    scopes = r["scope_s"]
    for s in ("seismic.select", "seismic.phase1", "seismic.dedupe", "seismic.phase2",
              "seismic.topk"):
        assert scopes.get(s, 0) > 0, s
    assert sum(scopes.values()) == pytest.approx(r["busy_s"], rel=1e-6)


@pytest.mark.parametrize("path,n_dispatch", [(FLAT, 3), (SEISMIC, 3)])
def test_each_plan_dispatch_starts_before_the_module_it_launched(path, n_dispatch):
    """On the profiler's shared clock: the k-th ``plan.dispatch`` span
    starts before the k-th executable run on the device."""
    devices, host, modules = pt.read_events(path)
    window = [(s, e) for n, s, e, _ in host if n == trace.WINDOW_SPAN][0]
    plans = sorted(s for n, s, e, _ in host if n == "plan.dispatch" and s >= window[0])
    runs = sorted(s for _, s, _ in modules if s >= window[0])
    assert len(plans) == len(runs) == n_dispatch
    assert all(p < m for p, m in zip(plans, runs))


def test_seismic_spans_tie_requests_to_dispatches():
    spans = pt.reduce(SEISMIC)["spans"]
    assert [a["rid"] for _, _, a in spans["serve.admit"]] == list(range(10))
    assert [(a["bucket"], a["n_real"], a["rid_first"], a["rid_last"])
            for _, _, a in spans["serve.dispatch"]] == [(1, 1, 0, 0), (8, 8, 1, 8), (1, 1, 9, 9)]
    assert [a["did"] for _, _, a in spans["serve.wait"]] == [0, 1, 2]
    assert [a["bucket"] for _, _, a in spans["plan.dispatch"]] == [1, 8, 1]


@pytest.mark.parametrize("path", [FLAT, SEISMIC])
def test_idle_gaps_are_named_by_program_spans(path):
    r = pt.reduce(path)
    names = {n for n, _ in r["idle_gaps_program"]}
    assert names & {"plan.dispatch", "serve.wait", "serve.admit", "serve.demux",
                    "serve.dispatch"}
    assert [t for _, t in r["idle_gaps_program"]] == [t for _, t in r["idle_gaps"]]


def test_trace_reduction_keys_keep_their_values():
    """The program's part only adds keys to ``bench.trace.reduce``."""
    base = trace.reduce(FLAT)
    r = pt.reduce(FLAT)
    assert {k: r[k] for k in base} == base


def test_older_trace_reads_as_unscoped():
    path = str(DATA / "seismic_bucket4_20k.xplane.pb")
    r = pt.reduce(path)
    assert set(r["scope_s"]) == {pt.UNSCOPED} and r["spans"] == {}
    assert r["modules"] == {"jit__unknown": 3}
    assert _outermost(path) == {"jit(<unknown>)"}


def test_stripping_the_metadata_plane_keeps_the_reduction(tmp_path):
    path = DATA / "seismic_bucket4_20k.xplane.pb"
    out = tmp_path / "stripped.xplane.pb"
    out.write_bytes(strip_planes(path.read_bytes()))
    assert out.stat().st_size < path.stat().st_size
    assert pt.reduce(str(out)) == pt.reduce(str(path))


# -- events made by hand ---------------------------------------------------


@pytest.mark.parametrize("path,scope", [
    ("jit(search_flat)/flat.topk/top_k", "flat.topk"),
    ("jit(search_seismic)/vmap(seismic.phase1)/vmap(jit(_take))/gather:", "seismic.phase1"),
    ("jit(search_seismic)/vmap(seismic.phase2)/vmap(jit(rows_scores_batch))/while",
     "seismic.phase2"),
    ("jit(search_flat)/add", pt.UNSCOPED),
    ("", pt.UNSCOPED),
])
def test_scope_of(path, scope):
    assert pt.scope_of(path) == scope


def _hand_made():
    ops = [
        ("%while.1 = while()", 0 * MS, 10 * MS, "jit(search_flat)/flat.score/while"),
        ('%rows.2 = custom-call(), custom_call_target="tpu_custom_call"', 1 * MS, 9 * MS,
         "jit(search_flat)/flat.score/while/body/rows"),
        ("%fusion.3 = fusion()", 10 * MS, 12 * MS, "jit(search_flat)/flat.topk/top_k"),
        ("%copy.4 = copy()", 16 * MS, 17 * MS, ""),
    ]
    host = [
        ("bench.window", 0, 20 * MS, {}),
        ("bench.search", 12 * MS, 14 * MS, {}),
        ("serve.dispatch", 12 * MS, 16 * MS, {"did": 0}),
        ("plan.dispatch", 12 * MS, 13 * MS, {"bucket": 8, "first": 0}),
        ("serve.wait", 13 * MS, 16 * MS, {"did": 0}),
        ("bench.fetch", 17 * MS, 19 * MS, {}),
        ("serve.admit", 30 * MS, 31 * MS, {"rid": 0}),  # outside the window
    ]
    modules = [("jit_search_flat(42)", 0, 12 * MS), ("jit_search_flat(42)", 25 * MS, 26 * MS)]
    return ops, host, modules


def test_reduction_by_hand():
    ops, host, modules = _hand_made()
    r = pt.reduce_events({0: ops}, host, modules)
    assert r["scope_s"] == pytest.approx(
        {"flat.score": 0.010, "flat.topk": 0.002, pt.UNSCOPED: 0.001})
    assert r["modules"] == {"jit_search_flat": 1}
    assert r["spans"] == {
        "serve.dispatch": [[pytest.approx(0.012), pytest.approx(0.016), {"did": 0}]],
        "plan.dispatch": [[pytest.approx(0.012), pytest.approx(0.013), {"bucket": 8, "first": 0}]],
        "serve.wait": [[pytest.approx(0.013), pytest.approx(0.016), {"did": 0}]],
    }
    # [12, 16]: serve.dispatch covers it, but its own time there is nil;
    # serve.wait's own time overlaps most. [17, 20]: bench.fetch
    assert r["idle_gaps_program"] == [["serve.wait", pytest.approx(0.004)],
                                      ["bench.fetch", pytest.approx(0.003)]]


def test_self_times_leave_out_nested_spans():
    _, host, _ = _hand_made()
    spans = pt.reduce_events({0: [("%f = fusion()", 0, 1, "")]}, host)["spans"]
    assert pt.self_times(spans, "serve.dispatch") == [pytest.approx(0.0)]
    assert pt.self_times(spans, "plan.dispatch") == [pytest.approx(0.001)]
    assert pt.self_times(spans, "serve.demux") == []


def test_no_window_or_no_device_reduces_to_nothing():
    ops, host, _ = _hand_made()
    assert pt.reduce_events({0: ops}, host[1:]) == {}
    assert pt.reduce_events({}, host) == {}


class _Plans:
    upload_bytes = 300


class _Stats:
    queue_wait_us = [5.0, 7.0]


def test_counters_and_their_window():
    before = pt.counters(_Plans(), _Stats())
    assert before == {"upload_bytes": 300, "queue_wait_us": [5.0, 7.0]}
    after = {"upload_bytes": 700, "queue_wait_us": [5.0, 7.0, 9.0]}
    assert pt.since(before, after) == {"upload_bytes": 400, "queue_wait_us": [9.0]}
    # a program without the counters gives nothing to read
    assert pt.counters(object(), object()) == {}
    assert pt.since({}, {}) == {}


# -- the per-layer metrics that read them ------------------------------------


def _spans(**named):
    return {k: [[s, e, a] for s, e, a in v] for k, v in named.items()}


CLOSED = {
    "loop": "closed", "completed": 64, "calls": 2, "upload_bytes": 64 * 122_088,
    "scope_s": {"flat.topk": 0.003, "flat.score": 2.5},
    "spans": _spans(**{"plan.dispatch": [(0.0, 0.004, {}), (1.0, 1.002, {}), (2.0, 2.003, {})]}),
}
OPEN = {
    "loop": "open", "completed": 10,
    "queue_wait_us": [1000.0 * i for i in range(1, 21)],
    "scope_s": {"seismic.phase1": 0.05},
    "spans": _spans(**{
        "serve.dispatch": [(0.0, 0.010, {"did": 0}), (1.0, 1.120, {"did": 1}),
                           (2.0, 2.020, {"did": 2})],
        "serve.wait": [(0.002, 0.009, {"did": 0}), (1.005, 1.110, {"did": 1}),
                       (2.004, 2.016, {"did": 2})],
    }),
}


@pytest.mark.parametrize("name,red,value", [
    ("dispatch_host_ms.batch", CLOSED, 3.0),
    ("h2d_bytes_per_q.batch", CLOSED, 122_088.0),
    ("topk_ms_per_batch.batch", CLOSED, 1.5),
    ("queue_wait_ms.open", OPEN, 19.0),
    ("dispatch_host_ms.open", OPEN, 8.0),  # 3, 15 and 8 ms outside serve.wait
    ("phase1_ms_per_q.open", OPEN, 5.0),
])
def test_reader_on_a_reduction(name, red, value):
    assert spec.metric_reader(name)(red) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "dispatch_host_ms.batch", "h2d_bytes_per_q.batch", "topk_ms_per_batch.batch",
    "queue_wait_ms.open", "dispatch_host_ms.open", "phase1_ms_per_q.open",
])
def test_reader_finds_nothing_without_its_input(name):
    """Nothing where the program recorded nothing (as before it had
    spans and counters), nor in the other loop's reduction."""
    read = spec.metric_reader(name)
    bare = {"loop": "closed" if name.endswith(".batch") else "open", "completed": 64,
            "calls": 2, "busy_s": 1.0, "window_s": 1.0}
    assert read(bare) is None
    other = OPEN if name.endswith(".batch") else CLOSED
    assert read(other) is None


def test_gap_with_no_span_open_is_idle():
    """A gap mostly outside every span (the host waiting for work) is
    ``idle``, though a span's tail overlaps it."""
    ops = [("%a = fusion()", 0, 10 * MS, ""), ("%b = fusion()", 90 * MS, 100 * MS, "")]
    host = [("bench.window", 0, 100 * MS, {}), ("serve.wait", 5 * MS, 15 * MS, {"did": 0}),
            ("bench.submit", 85 * MS, 89 * MS, {}), ("serve.admit", 85 * MS, 88 * MS, {"rid": 1})]
    r = pt.reduce_events({0: ops}, host)
    assert r["idle_gaps_program"] == [["idle", pytest.approx(0.080)]]


# -- the measurement script that wires them into a run ---------------------


def test_traced_run_summaries_on_the_seismic_fixture():
    """``traced_program_run.py``'s summaries of a traced window: span
    counts and medians, the host time per dispatch by bucket and fill,
    and the device gaps with what ran around them."""
    from bench.tests import traced_program_run as tpr

    spans = pt.reduce(SEISMIC)["spans"]
    stats = tpr.span_stats(spans)
    assert stats["serve.admit"]["n"] == 10 and stats["serve.dispatch"]["n"] == 3
    assert 0 < stats["plan.dispatch"]["self_median_ms"] <= stats["plan.dispatch"]["median_ms"]
    split = tpr.dispatch_split(spans)
    assert {k: v["n"] for k, v in split.items()} == {"bucket 1, full": 2, "bucket 8, full": 1}
    for row in split.values():
        assert 0 < row["plan.dispatch"] <= row["dispatch-wait"]
        assert row["serve.wait"] > 0
    got = tpr.gaps(SEISMIC, n=4)
    assert len(got) == 4 and got == sorted(got, key=lambda g: -g["ms"])
    assert any(h[0].startswith(pt.PROGRAM_PREFIXES) for g in got for h in g["host"])


def test_traced_run_reads_the_counters_on_the_cpu(tmp_path, capsys):
    """A small CPU run through the script: the plan cache's upload count
    reaches ``h2d_bytes_per_q.batch`` (a CPU trace has no device plane,
    so the span metrics read nothing), and the script puts back what it
    wrapped."""
    import json

    from bench import loadgen, run
    from bench.tests import traced_program_run as tpr
    from repro.serve import obs

    wrapped = (run.warm, run.trace_mod, loadgen.closed, loadgen.open_loop)
    assert tpr.main(["--workload", "splade-flat.batch32", "--seed", str(2**31 + 77),
                     "--seconds", "0.5", "--trace", "1", "--small",
                     "--out", str(tmp_path)]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result, program = json.loads(lines[-2]), json.loads(lines[-1].split(" ", 1)[1])
    assert result["correct"]
    assert result["metrics"]["h2d_bytes_per_q.batch"]["value"] == 30_522 * 4
    assert program["counters"]["upload_bytes"] > 0 and program["qps"] > 0
    assert (run.warm, run.trace_mod, loadgen.closed, loadgen.open_loop) == wrapped
    assert obs.span("x") is obs.span("y")  # spans are off again
