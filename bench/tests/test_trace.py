"""The trace reduction, on traces recorded on a TPU v5e and on events
made by hand.

``flat_batch32_20k.xplane.pb``: three searches of 32 queries through a
flat DotVByte index of 20,000 documents (the batched rows kernel,
``%rows_dotvbyte_f16``, inside a ``while`` loop). ``seismic_bucket4_20k``:
three bucket-4 searches of a Seismic index over the same documents
(the kernel wrapped by ``vmap`` as ``%closed_call``). Each window is a
``bench.window`` span with ``bench.search`` spans inside."""

import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


def test_flat_trace():
    r = trace.reduce(str(DATA / "flat_batch32_20k.xplane.pb"))
    assert r["window_s"] == pytest.approx(0.461522372, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.452462486, rel=1e-6)
    assert r["kernel_s"] == pytest.approx(0.450891517, rel=1e-6)
    assert r["other_s"] == pytest.approx(r["busy_s"] - r["kernel_s"])
    name, t = r["device_ops"][0]
    assert name == "rows_dotvbyte_f16.7" and t == pytest.approx(r["kernel_s"], rel=1e-6)
    assert "while.9" not in dict(r["device_ops"])  # its body is all kernel: no self time
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.search", "bench.fetch", "idle"}
    assert r["idle_gaps"][0][0] == "bench.fetch"


def test_vmapped_kernel_is_found_by_its_custom_call_target():
    r = trace.reduce(str(DATA / "seismic_bucket4_20k.xplane.pb"))
    ops = dict(r["device_ops"])
    assert r["kernel_s"] == pytest.approx(ops["closed_call.13"], rel=1e-6)
    assert r["kernel_s"] == pytest.approx(0.096844503, rel=1e-6)
    assert "fusion.6" in ops  # phase 1's summary gather, outside the kernel
    assert r["other_s"] > 0.08
    assert 0 < r["busy_s"] <= r["window_s"]


def test_reduction_by_hand():
    ns = 1_000_000
    ops = [
        ("%while.1 = (...) while(...)", 0 * ns, 10 * ns),
        ('%rows_dotvbyte_f16.2 = f32[] custom-call(), custom_call_target="tpu_custom_call"',
         1 * ns, 5 * ns),
        ('%closed_call.3 = f32[] custom-call(), custom_call_target="tpu_custom_call"',
         5 * ns, 8 * ns),
        ("%fusion.4 = f32[] fusion()", 14 * ns, 16 * ns),
        ("%fusion.5 = f32[] fusion()", 30 * ns, 40 * ns),  # outside the window
    ]
    host = [("bench.window", 0, 20 * ns), ("bench.fetch", 10 * ns, 13 * ns),
            ("bench.search", 13 * ns, 14 * ns)]
    r = trace.reduce_events({0: ops}, host)
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.012)  # [0, 10] and [14, 16]
    assert r["kernel_s"] == pytest.approx(0.007)  # [1, 5] and [5, 8]
    assert r["other_s"] == pytest.approx(0.005)
    assert dict(r["device_ops"]) == pytest.approx(
        {"rows_dotvbyte_f16.2": 0.004, "closed_call.3": 0.003, "while.1": 0.003,
         "fusion.4": 0.002})
    assert r["idle_gaps"] == [["bench.fetch", pytest.approx(0.004)],
                              ["idle", pytest.approx(0.004)]]


def test_no_window_or_no_device_reduces_to_nothing():
    ops = [("%fusion.1 = f32[] fusion()", 0, 10)]
    assert trace.reduce_events({0: ops}, [("bench.search", 0, 10)]) == {}
    assert trace.reduce_events({}, [("bench.window", 0, 10)]) == {}
