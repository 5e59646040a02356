"""Kernel-backend registry (DESIGN.md §3) — the fused-Pallas mirror of
the layout registry in ``core/layout.py``.

A codec registered in ``core/layout.py`` tells the system how its gap
streams look; a codec registered HERE tells the system how to *serve*
them fused. Each entry is a ``KernelSet``:

* ``block_scores`` / ``block_scores_batch`` — the full-scan path: one
  fused decode→gather→FMA→reduce kernel over the packed block form
  (``(q_dense, PackedBlocks) → [n_docs]`` and the decode-once/
  score-many query-batched variant ``(Q, PackedBlocks) → [nq,
  n_docs]``);
* ``rows_scores`` — the candidate-rescoring path every serve engine's
  phase 2 runs through (``(arrays, docs, q, scale) → [C]``): the
  scalar-prefetch gather kernel in ``rows_dot.py``. This is the entry
  ``scoring.score_candidate_rows`` dispatches to when
  ``RetrieverConfig(backend="pallas")`` routes a Retriever through the
  fused path;
* ``rows_scores_batch`` — same, for a query batch sharing one
  candidate set (``(arrays, docs, Q, scale) → [nq, C]``);
* ``rows_scan_batch`` — every row of the table against a query batch
  (``(arrays, Q, scale) → [nq, N+1]``): the range form of the rows
  kernel, which reads contiguous row blocks and needs no candidate ids
  (``flat``'s full scan; ``scoring.score_all_rows_batch``).

Registering a ``KernelSet`` under a layout codec's name makes EVERY
engine serve that codec fused with zero engine edits — the exact
contract the layout registry established for the jnp path. Asking a
codec without an entry (or without the relevant field) for a kernel
backend raises (``scoring.score_candidate_rows``).

Every entry's last parameter is the kernel execution ``mode``
(``repro.kernels.modes``): a mode string, ``None`` (auto → compiled),
or the pre-mode-axis booleans (``True`` ↦ pallas_interpret, ``False`` ↦
pallas_compiled) — so the same registry serves the jnp reference, the
CPU semantics-check (interpret) and the compiled lowering (Mosaic on
TPU, XLA elsewhere).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax.numpy as jnp

from . import rows_dot
from .modes import resolve_lowering
from .ops import (
    default_interpret,
    pad_query_lanes,
    score_bitpack,
    score_bitpack_batch,
    score_dotvbyte,
    score_dotvbyte_batch,
    score_streamvbyte,
    score_streamvbyte_batch,
)

__all__ = [
    "KernelSet",
    "register_kernels",
    "get_kernels",
    "available_kernels",
    "rows_scorer",
    "rows_batch_scorer",
    "rows_scan_scorer",
]


@dataclasses.dataclass(frozen=True)
class KernelSet:
    """Fused kernel entry points for one codec (None = not fused)."""

    codec: str
    #: (q_dense, PackedBlocks, mode=None) → [n_docs] f32
    block_scores: Optional[Callable] = None
    #: (Q [nq, dim], PackedBlocks, mode=None) → [nq, n_docs] f32
    block_scores_batch: Optional[Callable] = None
    #: (arrays, docs [C], q [dim], scale, mode=None) → [C] f32
    rows_scores: Optional[Callable] = None
    #: (arrays, docs [C], Q [nq, dim], scale, mode=None) → [nq, C]
    rows_scores_batch: Optional[Callable] = None
    #: (arrays, Q [nq, dim], scale, mode=None) → [nq, N+1], every row
    rows_scan_batch: Optional[Callable] = None


_KERNELS: Dict[str, Callable[[], KernelSet]] = {}


def register_kernels(name: str):
    """Decorator: register a ``KernelSet`` factory under a codec name."""

    def deco(factory: Callable[[], KernelSet]):
        _KERNELS[name] = factory
        return factory

    return deco


def get_kernels(name: str) -> KernelSet:
    try:
        return _KERNELS[name]()
    except KeyError:
        raise ValueError(
            f"no fused kernels for codec {name!r}; have {sorted(_KERNELS)}"
        ) from None


def available_kernels() -> list[str]:
    return sorted(_KERNELS)


def rows_scorer(codec: str) -> Optional[Callable]:
    """The fused rows-rescoring entry for ``codec``, or None when the
    codec has no registered rows kernel (``scoring.score_candidate_
    rows`` then raises)."""
    factory = _KERNELS.get(codec)
    if factory is None:
        return None
    return factory().rows_scores


def rows_batch_scorer(codec: str) -> Optional[Callable]:
    """The fused decode-once/score-many rows entry for ``codec`` —
    one shared candidate set, a resident query batch — or None when
    unregistered (``scoring.score_candidate_rows_batch`` then raises)."""
    factory = _KERNELS.get(codec)
    if factory is None:
        return None
    return factory().rows_scores_batch


def rows_scan_scorer(codec: str) -> Optional[Callable]:
    """The fused every-row entry for ``codec`` (the range form), or
    None when unregistered (``scoring.score_all_rows_batch`` then
    raises)."""
    factory = _KERNELS.get(codec)
    if factory is None:
        return None
    return factory().rows_scan_batch


# ---------------------------------------------------------------------------
# built-in entries
# ---------------------------------------------------------------------------


def _rows_arrays(arrays) -> dict:
    """The row-form fields of an engine array dict (drop engine extras
    so the jit'd XLA rows graph keys on a stable pytree).  Value-codec
    payload (``vq_*``, DESIGN.md §12) rides along — it includes the
    non-``_rows`` ``vq_codebook``."""
    keep = ("vals_rows", "nnz_rows")
    return {
        k: arrays[k]
        for k in arrays
        if k in keep or k.endswith("_rows") or k.startswith("vq_")
    }


def _make_rows(codec: str):
    def rows(arrays, docs, q, scale, mode=None):
        from repro.core import values as value_codecs

        low = resolve_lowering(mode)
        vq = value_codecs.infer_rows_vq(arrays)
        qp = pad_query_lanes(jnp.asarray(q, jnp.float32))
        if low == "jnp":
            from repro.core.scoring import _gather_decode_rows, score_doc_rows

            comps, vals, nnz = _gather_decode_rows(codec, arrays, docs)
            return score_doc_rows(qp, comps, vals, nnz, float(scale))
        if low == "xla":
            return rows_dot.rows_scores_xla(
                codec, qp, docs, _rows_arrays(arrays), float(scale)
            )
        return rows_dot.rows_scores(
            codec,
            qp,
            docs,
            _rows_arrays(arrays),
            scale=float(scale),
            vq=vq,
            interpret=low == "interpret",
        )

    return rows


def _make_rows_batch(codec: str):
    def rows_batch(arrays, docs, Q, scale, mode=None):
        from repro.core import values as value_codecs

        low = resolve_lowering(mode)
        vq = value_codecs.infer_rows_vq(arrays)
        Qp = pad_query_lanes(jnp.asarray(Q, jnp.float32))
        if low == "jnp":
            import jax

            from repro.core.scoring import _gather_decode_rows, score_doc_rows

            comps, vals, nnz = _gather_decode_rows(codec, arrays, docs)
            return jax.vmap(
                lambda q: score_doc_rows(q, comps, vals, nnz, float(scale))
            )(Qp)
        if low == "xla":
            return rows_dot.rows_scores_xla_batch(
                codec, Qp, docs, _rows_arrays(arrays), float(scale)
            )
        return rows_dot.rows_scores_batch(
            codec,
            Qp,
            docs,
            _rows_arrays(arrays),
            scale=float(scale),
            vq=vq,
            interpret=low == "interpret",
        )

    return rows_batch


def _make_rows_scan(codec: str):
    rows_batch = _make_rows_batch(codec)

    def rows_scan(arrays, Q, scale, mode=None):
        from repro.core import values as value_codecs

        low = resolve_lowering(mode)
        if low in ("jnp", "xla"):  # the gather chains over every row
            docs = jnp.arange(arrays["nnz_rows"].shape[0], dtype=jnp.int32)
            return rows_batch(arrays, docs, Q, scale, mode)
        return rows_dot.rows_scan_batch(
            codec,
            pad_query_lanes(jnp.asarray(Q, jnp.float32)),
            _rows_arrays(arrays),
            scale=float(scale),
            vq=value_codecs.infer_rows_vq(arrays),
            interpret=low == "interpret",
        )

    return rows_scan


@register_kernels("dotvbyte")
def _dotvbyte_kernels() -> KernelSet:
    return KernelSet(
        codec="dotvbyte",
        block_scores=score_dotvbyte,
        block_scores_batch=score_dotvbyte_batch,
        rows_scores=_make_rows("dotvbyte"),
        rows_scores_batch=_make_rows_batch("dotvbyte"),
        rows_scan_batch=_make_rows_scan("dotvbyte"),
    )


@register_kernels("streamvbyte")
def _streamvbyte_kernels() -> KernelSet:
    return KernelSet(
        codec="streamvbyte",
        block_scores=score_streamvbyte,
        block_scores_batch=score_streamvbyte_batch,
        rows_scores=_make_rows("streamvbyte"),
        rows_scores_batch=_make_rows_batch("streamvbyte"),
        rows_scan_batch=_make_rows_scan("streamvbyte"),
    )


@register_kernels("bitpack")
def _bitpack_kernels() -> KernelSet:
    return KernelSet(
        codec="bitpack",
        block_scores=score_bitpack,
        block_scores_batch=score_bitpack_batch,
        rows_scores=_make_rows("bitpack"),
        rows_scores_batch=_make_rows_batch("bitpack"),
        rows_scan_batch=_make_rows_scan("bitpack"),
    )


@register_kernels("uncompressed")
def _uncompressed_kernels() -> KernelSet:
    # decode-free: the block scan has nothing to fuse beyond what the
    # jnp path already is (gather + FMA); only the rescoring gather is
    # worth a kernel (HBM→VMEM row DMA via scalar prefetch).
    return KernelSet(
        codec="uncompressed",
        rows_scores=_make_rows("uncompressed"),
        rows_scores_batch=_make_rows_batch("uncompressed"),
        rows_scan_batch=_make_rows_scan("uncompressed"),
    )
