"""Synthetic learned-sparse corpus and queries for the benchmark.

A copy of the program's bulk device generator
(``repro.data.synthetic.generate_collection_device`` with
``splade_config``), kept here so that later changes to the program
cannot change the benchmark's data. ``bench/tests/test_corpus.py``
shows that the copy reproduces the original at a fixed seed.

The model: Zipf component popularity over the vocabulary, documents
that mix three of ``n_topics`` latent topics (each topic boosts a
random set of components), Gumbel top-k sampling without replacement
of each row's components, a random relabelling of component ids, and
gamma-distributed activations. Queries share the topics of a focus
document. Document rows are drawn on the default JAX device in
``[batch, dim]`` steps; queries and values are drawn on the host.

The result is plain CSR arrays (component ids u32, values in the
stored dtype, offsets i64) and the queries' sparse rows: it imports
nothing of the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Profile:
    """Sparsity profile of one encoder over one vocabulary."""

    dim: int = 30522
    n_docs: int = 20000
    n_queries: int = 100
    doc_nnz_mean: float = 119.0
    query_nnz_mean: float = 43.0
    n_topics: int = 64
    topic_concentration: float = 6.0
    zipf_a: float = 1.1
    value_shape: float = 2.0
    value_scale: float = 0.5
    seed: int = 0


@dataclasses.dataclass
class Corpus:
    """CSR documents and sparse queries."""

    dim: int
    components: np.ndarray  # u32 [nnz], sorted within each document
    values: np.ndarray  # stored dtype [nnz]
    offsets: np.ndarray  # i64 [n_docs + 1]
    query_comps: list  # per query: sorted u32 ids
    query_vals: list  # per query: f32 values

    @property
    def n_docs(self) -> int:
        return len(self.offsets) - 1

    def queries_dense(self, idx=None) -> np.ndarray:
        """Dense f32 [n, dim] rows of the queries ``idx`` (all by default)."""
        idx = range(len(self.query_comps)) if idx is None else idx
        idx = list(idx)
        out = np.zeros((len(idx), self.dim), np.float32)
        for r, i in enumerate(idx):
            out[r, self.query_comps[i]] = self.query_vals[i]
        return out


def _topic_logits(p: Profile, rng: np.random.Generator):
    ranks = np.arange(1, p.dim + 1, dtype=np.float64)
    background = -p.zipf_a * np.log(ranks)
    topic_size = max(p.dim // p.n_topics, 8)
    topic_comps = np.stack(
        [rng.choice(p.dim, size=topic_size, replace=False) for _ in range(p.n_topics)]
    )
    return background.astype(np.float32), topic_comps


def _sample_rows(logits: np.ndarray, nnz: np.ndarray, rng: np.random.Generator):
    out = []
    g = rng.gumbel(size=logits.shape).astype(np.float32)
    keys = logits + g
    for i in range(logits.shape[0]):
        k = int(nnz[i])
        idx = np.argpartition(-keys[i], k)[:k]
        out.append(np.sort(idx).astype(np.uint32))
    return out


def _mixture_logits_fn(p: Profile, background, topic_comps):
    def mixture_logits(n_rows: int, doc_topics: np.ndarray) -> np.ndarray:
        lg = np.tile(background, (n_rows, 1))
        for r in range(n_rows):
            for t in doc_topics[r]:
                lg[r, topic_comps[t]] += p.topic_concentration
        return lg

    return mixture_logits


def _queries(p: Profile, rng, mixture_logits, doc_topic_sets, relabel):
    q_comps, q_vals = [], []
    focus = rng.integers(0, p.n_docs, size=p.n_queries)
    qnnz = np.clip(rng.poisson(p.query_nnz_mean, size=p.n_queries), 2, p.dim // 8)
    lg = mixture_logits(p.n_queries, doc_topic_sets[focus])
    rows = _sample_rows(lg, qnnz, rng)
    for comps in rows:
        vals = rng.gamma(p.value_shape, p.value_scale, size=len(comps)).astype(
            np.float32
        ) + np.float32(0.05)
        q_comps.append(np.sort(relabel[comps]))
        q_vals.append(vals)
    return q_comps, q_vals


def generate(p: Profile, value_dtype=np.float16, batch: int = 2048) -> Corpus:
    """Draw the corpus and queries of ``p`` from ``p.seed`` (a seed
    that fits 32 bits: ``jax.random.key`` wraps larger ones)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(p.seed)
    background, topic_comps = _topic_logits(p, rng)
    relabel = rng.permutation(p.dim).astype(np.uint32)
    doc_topic_sets = rng.integers(0, p.n_topics, size=(p.n_docs, 3))
    nnz = np.clip(rng.poisson(p.doc_nnz_mean, size=p.n_docs), 4, p.dim // 4)
    k_max = int(nnz.max(initial=4))
    bg, tc = jnp.asarray(background), jnp.asarray(topic_comps)

    @jax.jit
    def draw(key, topics):
        rows = jnp.arange(topics.shape[0])[:, None]
        boost = jnp.zeros((topics.shape[0], p.dim), jnp.float32).at[
            rows, tc[topics].reshape(topics.shape[0], -1)
        ].add(p.topic_concentration)
        keys = bg + boost + jax.random.gumbel(key, boost.shape, jnp.float32)
        return jax.lax.top_k(keys, k_max)[1]

    key = jax.random.key(p.seed)
    comps = []
    for lo in range(0, p.n_docs, batch):
        topics = np.zeros((batch, 3), np.int64)
        topics[: min(batch, p.n_docs - lo)] = doc_topic_sets[lo : lo + batch]
        idx = np.asarray(draw(jax.random.fold_in(key, lo), jnp.asarray(topics)))
        idx = idx[: min(batch, p.n_docs - lo)]
        live = np.arange(k_max)[None, :] < nnz[lo : lo + batch, None]
        ids = np.sort(np.where(live, relabel[idx], p.dim), axis=1)
        comps.append(ids[live])
    vals = rng.gamma(p.value_shape, p.value_scale, size=int(nnz.sum()))
    values = (vals.astype(np.float32) + np.float32(0.05)).astype(value_dtype)
    mixture_logits = _mixture_logits_fn(p, background, topic_comps)
    q_comps, q_vals = _queries(p, rng, mixture_logits, doc_topic_sets, relabel)
    return Corpus(
        dim=p.dim,
        components=np.concatenate(comps).astype(np.uint32),
        values=values,
        offsets=np.concatenate([[0], np.cumsum(nnz)]).astype(np.int64),
        query_comps=q_comps,
        query_vals=q_vals,
    )
