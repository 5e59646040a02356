"""Flat registry entry: exact full scan over the packed row form.

The trivial third engine (DESIGN.md §7): no pruning structure at all —
every query scores every document's row and takes the global top-k.
It exists for two reasons: it proves the ``register_engine`` registry
is open (an engine is just arrays + a pure ``search_one``), and it is
the *recall oracle* — its top-k under any codec is the exact answer
the approximate engines are measured against, computed on device
through the very same decode path they use.

O(N·L) per query, so serve it on small collections (tests, smoke
gates, truth generation) — that is its job.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import layout
from repro.core.forward_index import ForwardIndex
from repro.core.scoring import score_all_rows_batch

from ..api import EngineImpl, RetrieverConfig, register_engine, row_array_specs

__all__ = ["FlatEngine"]


@register_engine("flat")
class FlatEngine(EngineImpl):
    name = "flat"
    dedupe_merge = False  # contiguous doc ranges are disjoint
    defaults: dict = {}  # nothing to tune — that is the point

    # -- host-side build ------------------------------------------------
    def build_arrays(self, fwd: ForwardIndex, cfg: RetrieverConfig):
        return layout.pack_rows(fwd, codec=cfg.codec, vq=cfg.vq).arrays()

    # -- serving --------------------------------------------------------
    def search_one(self, cfg: RetrieverConfig, n_docs: int, value_scale: float, arrays, q):
        """One dense query → (ids [k], scores [k]): score ALL rows, by
        the same every-row path as ``search_batch``."""
        docs = jnp.arange(arrays["nnz_rows"].shape[0], dtype=jnp.int32)
        scores = score_all_rows_batch(
            cfg.codec, arrays, q[None], value_scale, backend=cfg.backend
        )[0]
        scores = jnp.where(docs < n_docs, scores, -jnp.inf)
        top_s, idx = jax.lax.top_k(scores, cfg.k)
        return jnp.take(docs, idx), top_s

    def search_batch(self, cfg: RetrieverConfig, n_docs: int, value_scale: float, arrays, Q):
        """Genuinely batched full scan (DESIGN.md §8): every query
        shares the one candidate set (all rows), so the pipeline's
        bucketed dispatch decodes each row ONCE and scores the whole
        resident query batch — ``score_all_rows_batch``, the range
        form of the rows kernel (``rows_scan_batch``: contiguous row
        blocks, no candidate ids) under ``backend="pallas"``. Per-query
        results are bitwise those of ``vmap(search_one)`` (the parity
        suite). Named scopes
        ``flat.score`` and ``flat.topk`` label its device operations in
        profiler traces."""
        docs = jnp.arange(arrays["nnz_rows"].shape[0], dtype=jnp.int32)
        with jax.named_scope("flat.score"):
            scores = score_all_rows_batch(
                cfg.codec, arrays, Q, value_scale, backend=cfg.backend
            )
            scores = jnp.where(docs[None, :] < n_docs, scores, -jnp.inf)
        with jax.named_scope("flat.topk"):
            top_s, idx = jax.lax.top_k(scores, cfg.k)
            return jnp.take(docs, idx), top_s

    def array_specs(
        self,
        cfg: RetrieverConfig,
        *,
        n_docs: int,
        l_max: int,
        d_max: int,
        value_dtype=jnp.float16,
        **_ignored,
    ):
        return row_array_specs(
            cfg.codec, n_docs=n_docs, l_max=l_max, d_max=d_max,
            value_dtype=value_dtype, vq=cfg.vq,
        )

    # -- sharded build --------------------------------------------------
    def build_shard(self, fwd: ForwardIndex, cfg: RetrieverConfig, lo: int, hi: int):
        """One artifact shard (DESIGN.md §9): rows packed straight from
        the per-shard pack offsets (``pack_rows`` over the CSR slice,
        shard-local row ids) — no sub-index structure to rebuild, and
        row bytes identical to the same docs' rows in a monolithic
        pack at equal row capacity."""
        return layout.pack_rows(
            fwd, codec=cfg.codec, doc_range=(lo, hi), vq=cfg.vq
        ).arrays()

    def shard_build(self, fwd: ForwardIndex, cfg: RetrieverConfig, n_shards: int):
        """Contiguous doc ranges, rows padded to a common local size."""
        import numpy as np

        n = fwd.n_docs
        docs_local = (n + n_shards - 1) // n_shards
        dicts, idmaps = [], []
        for s in range(n_shards):
            lo, hi = s * docs_local, min((s + 1) * docs_local, n)
            sub = fwd.slice(lo, hi).padded(docs_local)
            dicts.append(layout.pack_rows(sub, codec=cfg.codec, vq=cfg.vq).arrays())
            idmap = np.full(docs_local + 1, n, dtype=np.int32)
            idmap[: hi - lo] = np.arange(lo, hi, dtype=np.int32)
            idmaps.append(idmap)
        return dicts, idmaps, docs_local, {}
