"""Seismic registry entry: batched, static-shape two-phase search
(TPU adaptation of Bruch et al.'s heap-and-early-exit engine).

The host-side reference (repro.core.seismic) has faithful heap
semantics but data-dependent control flow. TPUs want static shapes and
batches, so serving uses the standard two-phase static relaxation:

  phase 1  for each query: gather the blocks of its top-``cut``
           components (≤ ``block_budget``), score every summary
           (gather + FMA), take the top-``n_probe`` blocks — this
           replaces the heap_factor pruning test with a fixed probe
           budget (the Seismic papers' own batching trick);
  phase 2  gather the ≤ n_probe·block_size candidate documents, dedupe
           (sort by id, mask repeats), re-score *exactly* against the
           packed forward-index rows under any codec registered in
           core/layout.py — the paper's hot path — and take the
           global top-k.

``search_one`` is a *pure* function of (arrays, query) so the same
code serves the jit'd production path, the multi-pod dry-run
(ShapeDtypeStruct arrays via ``array_specs``), and the generic sharded
driver (``api.make_sharded_search``). A document's blocks scatter
across shards, so this engine declares ``dedupe_merge``.

Batched dispatch (DESIGN.md §8): each query probes its OWN block set,
so there is no shared candidate set to decode once — the pipeline's
bucketed plans compile the inherited ``EngineImpl.search_batch``
(``vmap(search_one)``), and under ``backend="pallas"`` the vmap
batching rule lifts the query axis into the rows-kernel grid, which
amortises the per-dispatch host overhead the bucket exists to kill.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout
from repro.core.scoring import score_candidate_rows
from repro.core.seismic import SeismicIndex, SeismicParams

from ..api import EngineImpl, RetrieverConfig, register_engine, row_array_specs

__all__ = ["SeismicEngine"]


@register_engine("seismic")
class SeismicEngine(EngineImpl):
    name = "seismic"
    dedupe_merge = True
    defaults = {
        # search-time (phase budgets)
        "cut": 8,  # query components probed
        "block_budget": 512,  # max candidate blocks per query (phase 1)
        "n_probe": 64,  # blocks exactly re-scored (phase 2)
        # build-time (host SeismicIndex, used by build/shard_build)
        "n_postings": 4000,
        "block_size": 64,
        "summary_mass": 0.5,
        "summary_scale": 1.0 / 32.0,
        "proj_dims": 1,
        "seed": 0,
    }

    # -- host-side build ------------------------------------------------
    def host_index(self, fwd, cfg: RetrieverConfig) -> SeismicIndex:
        p = self.params(cfg)
        return SeismicIndex.build(
            fwd,
            SeismicParams(
                n_postings=p["n_postings"],
                block_size=p["block_size"],
                summary_mass=p["summary_mass"],
                summary_scale=p["summary_scale"],
                proj_dims=p["proj_dims"],
                seed=p["seed"],
            ),
        )

    def build_arrays(self, fwd, cfg: RetrieverConfig):
        return self.arrays_from_index(self.host_index(fwd, cfg), cfg)

    def arrays_from_index(self, index: SeismicIndex, cfg: RetrieverConfig):
        """SeismicIndex → static engine arrays (numpy): inverted block
        ranges, padded summaries, block→doc lists, plus the shared
        packed row form for phase-2 rescoring."""
        fwd = index.fwd
        n_docs, real_blocks = fwd.n_docs, index.n_blocks
        # an all-empty doc range (a sharded-build corner) yields ZERO
        # blocks, which would zero-size the static search arrays on
        # axis 0; pad to one sentinel block — empty summary, no real
        # docs — that phase 1 can harmlessly gather
        n_blocks = max(real_blocks, 1)

        s_len = np.diff(index.summary_indptr)
        s_max = int(max(s_len.max(initial=1), 1))
        sum_comps = np.zeros((n_blocks, s_max), dtype=np.int32)
        sum_vals = np.zeros((n_blocks, s_max), dtype=np.float32)
        for b in range(real_blocks):
            s, e = int(index.summary_indptr[b]), int(index.summary_indptr[b + 1])
            sum_comps[b, : e - s] = index.summary_comps[s:e]
            sum_vals[b, : e - s] = (
                index.summary_vals[s:e].astype(np.float32) * index.params.summary_scale
            )

        b_len = np.diff(index.block_doc_indptr)
        bs_max = int(max(b_len.max(initial=1), 1))
        block_docs = np.full((n_blocks, bs_max), n_docs, dtype=np.int32)
        for b in range(real_blocks):
            s, e = int(index.block_doc_indptr[b]), int(index.block_doc_indptr[b + 1])
            block_docs[b, : e - s] = index.block_docs[s:e]

        arrays = {
            "cbs": index.comp_block_indptr[:-1].astype(np.int32),
            "cbl": np.diff(index.comp_block_indptr).astype(np.int32),
            "sum_comps": sum_comps,
            "sum_vals": sum_vals,
            "block_docs": block_docs,
        }
        arrays.update(layout.pack_rows(fwd, codec=cfg.codec, vq=cfg.vq).arrays())
        return arrays

    # -- serving --------------------------------------------------------
    def search_one(self, cfg: RetrieverConfig, n_docs: int, value_scale: float, arrays, q):
        """One dense query → (ids [k], scores [k]). Pure and static-shape.

        arrays: cbs/cbl [dim], sum_comps/sum_vals [n_blocks, s_max],
        block_docs [n_blocks, bs_max], plus the packed row form. Each
        step runs under a named scope (``seismic.select``, ``.phase1``,
        ``.dedupe``, ``.phase2``, ``.topk``) that labels its device
        operations in profiler traces."""
        p = self.params(cfg)
        cut, block_budget, n_probe = p["cut"], p["block_budget"], p["n_probe"]
        with jax.named_scope("seismic.select"):
            # top-cut query components
            qv, qc = jax.lax.top_k(jnp.abs(q), cut)
            live = qv > 0
            # candidate blocks: fixed budget round-robin over the cut comps
            starts = arrays["cbs"][qc]  # [cut]
            lens = jnp.where(live, arrays["cbl"][qc], 0)
            per = block_budget // cut
            offs = jnp.arange(per)[None, :]  # [1, per]
            cand = starts[:, None] + offs  # [cut, per]
            valid = offs < lens[:, None]
            cand = jnp.where(valid, cand, -1).reshape(-1)  # [budget]

        with jax.named_scope("seismic.phase1"):  # summary upper bounds
            sc = jnp.take(arrays["sum_comps"], jnp.maximum(cand, 0), axis=0)
            sv = jnp.take(arrays["sum_vals"], jnp.maximum(cand, 0), axis=0)
            est = (jnp.take(q, sc, axis=0) * sv).sum(-1)
            est = jnp.where(cand >= 0, est, -jnp.inf)
            _, probe = jax.lax.top_k(est, n_probe)
            probe_blocks = jnp.take(cand, probe)

        with jax.named_scope("seismic.dedupe"):  # gather candidate docs
            docs = jnp.take(arrays["block_docs"], jnp.maximum(probe_blocks, 0), axis=0)
            docs = jnp.where((probe_blocks >= 0)[:, None], docs, n_docs).reshape(-1)
            docs = jnp.sort(docs)
            dup = jnp.concatenate([jnp.zeros(1, bool), docs[1:] == docs[:-1]])
            docs = jnp.where(dup, n_docs, docs)

        with jax.named_scope("seismic.phase2"):  # exact re-score
            scores = score_candidate_rows(
                cfg.codec, arrays, docs, q, value_scale, backend=cfg.backend
            )
            scores = jnp.where(docs < n_docs, scores, -jnp.inf)
        with jax.named_scope("seismic.topk"):
            top_s, idx = jax.lax.top_k(scores, cfg.k)
            return jnp.take(docs, idx), top_s

    def array_specs(
        self,
        cfg: RetrieverConfig,
        *,
        dim: int,
        n_docs: int,
        n_blocks: int,
        s_max: int,
        bs_max: int,
        l_max: int,
        d_max: int,
        value_dtype=jnp.float16,
    ):
        sds = jax.ShapeDtypeStruct
        arrays = {
            "cbs": sds((dim,), jnp.int32),
            "cbl": sds((dim,), jnp.int32),
            "sum_comps": sds((n_blocks, s_max), jnp.int32),
            "sum_vals": sds((n_blocks, s_max), jnp.float32),
            "block_docs": sds((n_blocks, bs_max), jnp.int32),
        }
        arrays.update(
            row_array_specs(
                cfg.codec, n_docs=n_docs, l_max=l_max, d_max=d_max,
                value_dtype=value_dtype, vq=cfg.vq,
            )
        )
        return arrays

    # -- sharded build --------------------------------------------------
    def shard_build(self, fwd, cfg: RetrieverConfig, n_shards: int):
        return self.shard_from_index(self.host_index(fwd, cfg), cfg, n_shards)

    def shard_from_index(self, index: SeismicIndex, cfg: RetrieverConfig, n_shards: int):
        """Partition a SeismicIndex into ``n_shards`` self-contained
        sub-indexes: blocks round-robin, documents by ownership (a doc
        goes to every shard holding one of its blocks — hence
        ``dedupe_merge``)."""
        A = self.arrays_from_index(index, cfg)
        n_docs = index.fwd.n_docs
        n_blocks = int(A["block_docs"].shape[0])

        shard_docs: list[np.ndarray] = []
        docs_local_max = 0
        for s in range(n_shards):
            blocks = np.arange(s, n_blocks, n_shards)
            docs = np.unique(A["block_docs"][blocks])
            docs = docs[docs < n_docs]
            shard_docs.append(docs)
            docs_local_max = max(docs_local_max, len(docs))

        dicts, idmaps = [], []
        row_keys = [k for k in A if k.endswith("_rows")]
        # shared (non-per-row) value-codec payload — the PQ codebook —
        # is copied verbatim into every shard (DESIGN.md §12)
        shared_vq = {
            k: A[k] for k in A
            if k.startswith("vq_") and not k.endswith("_rows")
        }
        for s in range(n_shards):
            blocks = np.arange(s, n_blocks, n_shards)
            docs = shard_docs[s]
            g2l = np.full(n_docs + 1, docs_local_max, dtype=np.int32)
            g2l[docs] = np.arange(len(docs), dtype=np.int32)
            # comp → local block ranges: blocks of comp c in this shard
            # are contiguous in the round-robin order
            cbs, cbl = A["cbs"], A["cbl"]
            lcbs = (cbs - s + n_shards - 1) // n_shards
            lcbl = (cbs + cbl - s + n_shards - 1) // n_shards - lcbs
            sub = {
                "cbs": lcbs.astype(np.int32),
                "cbl": np.maximum(lcbl, 0).astype(np.int32),
                "sum_comps": A["sum_comps"][blocks],
                "sum_vals": A["sum_vals"][blocks],
                "block_docs": g2l[A["block_docs"][blocks]],
            }
            pad_rows = np.concatenate(
                [docs, np.full(docs_local_max - len(docs) + 1, n_docs)]
            )
            for k in row_keys:
                sub[k] = A[k][pad_rows]
            sub.update(shared_vq)
            dicts.append(sub)
            idmap = np.full(docs_local_max + 1, n_docs, dtype=np.int32)
            idmap[: len(docs)] = docs
            idmaps.append(idmap)
        return dicts, idmaps, docs_local_max, {"block_docs": docs_local_max}
