"""Synthetic data statistics + sparse-encoder training signal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import (
    SyntheticConfig,
    generate_collection,
    generate_collection_device,
    lilsr_config,
    splade_config,
)
from repro.models.sparse_encoder import (
    SparseEncoderConfig,
    contrastive_loss,
    encode,
    encoder_init,
)


def test_splade_statistics_match_paper():
    col = generate_collection(splade_config(n_docs=400, n_queries=40, seed=0))
    nnz_doc = col.fwd.total_nnz / col.fwd.n_docs
    nnz_q = np.mean([len(c) for c in col.query_comps])
    assert abs(nnz_doc - 119) < 12, nnz_doc  # paper: 119 nnz/doc
    assert abs(nnz_q - 43) < 8, nnz_q  # paper: 43 nnz/query


def test_lilsr_statistics_match_paper():
    col = generate_collection(lilsr_config(n_docs=200, n_queries=40, seed=1))
    nnz_doc = col.fwd.total_nnz / col.fwd.n_docs
    nnz_q = np.mean([len(c) for c in col.query_comps])
    assert abs(nnz_doc - 387) < 30, nnz_doc
    assert abs(nnz_q - 6) < 3, nnz_q


def test_queries_retrieve_related_docs():
    """Topic structure: a query's exact top-10 must beat random recall."""
    col = generate_collection(
        SyntheticConfig(name="t", dim=2048, n_docs=500, n_queries=10,
                        doc_nnz_mean=60, query_nnz_mean=20, seed=2)
    )
    scores = np.stack([col.fwd.exact_scores(col.query_dense(i)) for i in range(10)])
    top = scores.max(axis=1)
    med = np.median(scores, axis=1)
    assert (top > 4 * np.maximum(med, 1e-3)).mean() >= 0.8


def test_device_generator_statistics_match_paper():
    """The bulk device generator draws the same model: SPLADE nonzeros,
    sorted unique in-vocabulary ids, and one collection per seed."""
    cfg = splade_config(n_docs=300, n_queries=40, seed=3)
    col = generate_collection_device(cfg, "f16", batch=128)
    fwd = col.fwd
    assert fwd.n_docs == 300 and fwd.dim == 30522
    assert abs(fwd.total_nnz / fwd.n_docs - 119) < 12
    assert abs(np.mean([len(c) for c in col.query_comps]) - 43) < 8
    assert int(fwd.components.max()) < fwd.dim
    for d in range(fwd.n_docs):
        c = fwd.components[fwd.offsets[d] : fwd.offsets[d + 1]].astype(np.int64)
        assert len(c) >= 4 and (np.diff(c) > 0).all()
    assert (fwd.values > 0).all()
    again = generate_collection_device(cfg, "f16", batch=128).fwd
    np.testing.assert_array_equal(again.components, fwd.components)
    np.testing.assert_array_equal(again.values, fwd.values)


def test_device_generator_queries_retrieve_related_docs():
    """Topic structure survives the device draw: a query's exact top
    score stands well above the median."""
    col = generate_collection_device(
        SyntheticConfig(name="t", dim=2048, n_docs=500, n_queries=10,
                        doc_nnz_mean=60, query_nnz_mean=20, seed=2),
        batch=256,
    )
    scores = np.stack([col.fwd.exact_scores(col.query_dense(i)) for i in range(10)])
    top = scores.max(axis=1)
    med = np.median(scores, axis=1)
    assert (top > 4 * np.maximum(med, 1e-3)).mean() >= 0.8


def _tok_batch(key, cfg, B=8, S=16):
    ks = jax.random.split(key, 4)
    return {
        "q_tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab),
        "q_mask": jnp.ones((B, S), bool),
        "d_tokens": jax.random.randint(ks[1], (B, S), 0, cfg.vocab),
        "d_mask": jnp.ones((B, S), bool),
    }


def test_sparse_encoder_shapes_and_sparsity():
    cfg = SparseEncoderConfig(vocab=512, n_layers=2, d_model=32, n_heads=4, d_ff=64,
                              max_len=16)
    key = jax.random.PRNGKey(0)
    p = encoder_init(key, cfg)
    batch = _tok_batch(key, cfg)
    emb = encode(p, cfg, batch["d_tokens"], batch["d_mask"])
    assert emb.shape == (8, 512)
    assert bool((emb >= 0).all())  # log1p(relu) ≥ 0


def test_sparse_encoder_trains():
    cfg = SparseEncoderConfig(vocab=512, n_layers=2, d_model=32, n_heads=4, d_ff=64,
                              max_len=16, flops_lambda=1e-4)
    key = jax.random.PRNGKey(1)
    p = encoder_init(key, cfg)
    from repro.train.optimizer import OptimizerConfig, make_optimizer
    from repro.train.train_step import init_train_state, make_train_step

    oinit, oupd = make_optimizer(OptimizerConfig(lr=2e-3, warmup_steps=5, total_steps=60))
    step = jax.jit(make_train_step(lambda pp, b: contrastive_loss(pp, cfg, b), oupd))
    state = init_train_state(p, oinit)
    losses = []
    batch = _tok_batch(key, cfg)  # overfit one batch
    for i in range(30):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], (losses[0], losses[-1])
