"""A CPU run of ``splade-seismic.open`` at a small size with its timed
path broken: each fault it can have comes out not correct."""

import pytest

from bench.tests import harness as h


@pytest.mark.parametrize("fault", [h.alter_one_answer, h.drop_half_batch(), h.swap_tickets()],
                         ids=["altered", "half_left_out", "swapped"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    h.break_plans(monkeypatch, fault)
    assert not h.run_small("splade-seismic.open")["correct"]
