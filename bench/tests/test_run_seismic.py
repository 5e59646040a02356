"""A CPU run of ``splade-seismic.open`` at a small size: sound, and its
control (``test_run_seismic_faults.py`` breaks its timed path)."""

from bench.tests import harness as h


def test_sound_run_is_correct():
    res = h.run_small("splade-seismic.open")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 16
    assert set(res["metrics"]) == {"setup_s", "p95_ms", "recall_at_10", "index_bytes_per_doc"}
    assert 0.9 <= res["metrics"]["recall_at_10"]["value"] <= 1.0


def test_control_is_not_correct():
    res = h.run_small("splade-seismic.open", control=True)
    assert not res["correct"]
    assert res["checks"]["score_err"]["value"] > res["checks"]["score_err"]["limit"]
