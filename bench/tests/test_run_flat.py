"""A CPU run of ``splade-flat.batch32`` at a small size: sound, its
control, and the faults its timed path can have."""

import pytest

from bench.tests import harness as h


def test_sound_run_is_correct():
    res = h.run_small("splade-flat.batch32")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 32
    assert set(res["metrics"]) == {"setup_s", "qps", "index_bytes_per_doc"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["score_err"]["value"] < 1e-5


def test_control_is_not_correct():
    res = h.run_small("splade-flat.batch32", control=True)
    assert not res["correct"]
    assert res["checks"]["score_err"]["value"] > res["checks"]["score_err"]["limit"]


@pytest.mark.parametrize("fault", [h.alter_one_answer, h.drop_half_batch()], ids=["altered", "half_left_out"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    h.break_plans(monkeypatch, fault)
    assert not h.run_small("splade-flat.batch32")["correct"]
