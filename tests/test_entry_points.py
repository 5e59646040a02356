"""Entry-point plumbing: the compile-cache location and the chip
smoke run's refusal to run without a TPU."""

from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

from repro.launch import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_env(monkeypatch, cache_config, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache
    and nothing else is configured in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    """Without it, the cache is the fixed ``<checkout>/.cache/jax``."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_ROOT, ".cache", "jax")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)


def _chip_smoke():
    sys.path.insert(0, _ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(_ROOT)
    return chip_smoke


def test_chip_smoke_refuses_a_host_without_tpu():
    """On the CPU the smoke run fails its first check, before any work."""
    cs = _chip_smoke()
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(cs.SmokeFailure, match="no TPU"):
        cs.device_checks(1)


def test_chip_smoke_exact_check_allows_only_true_ties():
    """A top-k differing from the reference passes only where the
    exact scores tie; a wrong id fails."""
    cs = _chip_smoke()
    full = np.array([5.0, 3.0, 3.0, 1.0, 0.5] + [0.0] * 10, np.float32)
    ref = [(np.array([0, 1, 2] + list(range(3, 10))), full[[0, 1, 2] + list(range(3, 10))], full)]
    tie_swap = np.array([[0, 2, 1] + list(range(3, 10))])
    cs.check_exact("tie", tie_swap, ref)
    wrong = np.array([[0, 1, 3] + list(range(4, 10)) + [10]])
    with pytest.raises(cs.SmokeFailure, match="exact"):
        cs.check_exact("wrong", wrong, ref)
