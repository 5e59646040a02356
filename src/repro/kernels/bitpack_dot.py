"""Pallas TPU kernel: fixed-width (bitpack) decode + fused inner product.

The beyond-paper TPU-native codec (DESIGN.md §3): each block of T gaps is
packed at one bit-width, so the decode is a pure shift+mask with *no*
data-dependent offsets — every lane knows statically which word and bit
it reads. Two variants:

* ``bitpack_block_scores``      — runtime per-block width (one kernel for
  the whole index; widths ride along as a [B, 1] i32 stream).
* ``bitpack_block_scores_w``    — compile-time width (one kernel per
  width bucket; tight word arrays, no over-read — the §Perf layout).

Kernels are TILED like ``dotvbyte_dot`` (PR 6, ``tiles.py``): the
single-query scan runs the double-buffered HBM→VMEM DMA pipeline
(:func:`tiles.dma_block_scan`), the batched variant a queries×tiles
grid (:func:`tiles.grid_batch_scores`).  The word stream is lane-padded
at pack time; the decode masks off padding words via the T bound, and
the fused epilogue (q gather → FMA → contiguous-fragment prefix-sum
slot reduce) is the shared tile program in ``tiles``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.scoring import decode_gaps_bitpack

from . import tiles

__all__ = [
    "bitpack_block_scores",
    "bitpack_block_scores_batch",
    "bitpack_block_scores_w",
    "bitpack_block_scores_xla",
    "bitpack_block_scores_xla_batch",
    "bitpack_block_scores_w_xla",
]


def tile_gaps(words: jnp.ndarray, widths: jnp.ndarray, T: int) -> jnp.ndarray:
    """[R, W] words + [R] widths → gaps i32 [R, T]."""
    return decode_gaps_bitpack(words, widths, T)


def _tile_fn(q, words, widths2, seg, sp, sa, vals, *, scale: float):
    gaps = tile_gaps(words, widths2[:, 0], seg.shape[-1])
    return tiles.tile_scores(q, gaps, seg, sp, sa, vals, scale)


def _tile_fn_batch(Q, words, widths2, seg, sp, sa, vals, *, scale: float):
    gaps = tile_gaps(words, widths2[:, 0], seg.shape[-1])
    return tiles.tile_scores_batch(Q, gaps, seg, sp, sa, vals, scale)


def _pad_block_streams(words, widths2, seg, start_pos, start_abs, vals):
    pad = functools.partial(tiles.pad_axis, multiple=tiles.R_TILE, axis=0)
    return (
        pad(words), pad(widths2, fill=1), pad(seg, fill=-1),
        pad(start_pos), pad(start_abs), pad(vals),
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def bitpack_block_scores(
    q, words, widths, seg, start_pos, start_abs, vals, *, scale=1.0, interpret=True
):
    """Runtime-width variant. widths i32 [B]. Returns [B, D] f32 via the
    double-buffered DMA scan."""
    B = words.shape[0]
    D = start_pos.shape[1]
    streams = _pad_block_streams(
        words, widths.astype(jnp.int32)[:, None], seg, start_pos, start_abs, vals
    )
    out = tiles.dma_block_scan(
        functools.partial(_tile_fn, scale=scale), q, streams, D, interpret
    )
    return out[:B]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def bitpack_block_scores_batch(
    Q, words, widths, seg, start_pos, start_abs, vals, *, scale=1.0, interpret=True
):
    """[nq, B, D] batched runtime-width scores via the queries×tiles grid."""
    nq = Q.shape[0]
    B = words.shape[0]
    D = start_pos.shape[1]
    Qp = tiles.pad_axis(Q, tiles.Q_TILE, axis=0)
    streams = _pad_block_streams(
        words, widths.astype(jnp.int32)[:, None], seg, start_pos, start_abs, vals
    )
    out = tiles.grid_batch_scores(
        functools.partial(_tile_fn_batch, scale=scale), Qp, streams, D, interpret
    )
    return out[:nq, :B]


@functools.partial(jax.jit, static_argnames=("scale", "width", "interpret"))
def bitpack_block_scores_w(
    q, words, seg, start_pos, start_abs, vals, *, width: int, scale=1.0, interpret=True
):
    """Compile-time-width variant for width-bucketed indexes. [B, D] f32."""
    B = words.shape[0]
    D = start_pos.shape[1]

    def tile_fn(q_, words_, seg_, sp_, sa_, vals_):
        gaps = tile_gaps(words_, jnp.full((words_.shape[0],), width, jnp.int32), seg_.shape[-1])
        return tiles.tile_scores(q_, gaps, seg_, sp_, sa_, vals_, scale)

    pad = functools.partial(tiles.pad_axis, multiple=tiles.R_TILE, axis=0)
    streams = (pad(words), pad(seg, fill=-1), pad(start_pos), pad(start_abs), pad(vals))
    out = tiles.dma_block_scan(tile_fn, q, streams, D, interpret)
    return out[:B]


@functools.partial(jax.jit, static_argnames=("scale",))
def bitpack_block_scores_xla(
    q, words, widths, seg, start_pos, start_abs, vals, *, scale=1.0
):
    """The same runtime-width tile program lowered through XLA."""
    B = words.shape[0]
    D = start_pos.shape[1]
    streams = _pad_block_streams(
        words, widths.astype(jnp.int32)[:, None], seg, start_pos, start_abs, vals
    )
    return tiles.xla_block_scores(
        functools.partial(_tile_fn, scale=scale), q, streams, D
    )[:B]


@functools.partial(jax.jit, static_argnames=("scale",))
def bitpack_block_scores_xla_batch(
    Q, words, widths, seg, start_pos, start_abs, vals, *, scale=1.0
):
    """XLA lowering of the batched runtime-width tile program → [nq, B, D]."""
    B = words.shape[0]
    D = start_pos.shape[1]
    streams = _pad_block_streams(
        words, widths.astype(jnp.int32)[:, None], seg, start_pos, start_abs, vals
    )
    return tiles.xla_block_scores_batch(
        functools.partial(_tile_fn_batch, scale=scale), Q, streams, D
    )[:, :B]


@functools.partial(jax.jit, static_argnames=("scale", "width"))
def bitpack_block_scores_w_xla(
    q, words, seg, start_pos, start_abs, vals, *, width: int, scale=1.0
):
    """XLA lowering of the compile-time-width tile program. [B, D] f32."""
    B = words.shape[0]
    D = start_pos.shape[1]

    def tile_fn(q_, words_, seg_, sp_, sa_, vals_):
        gaps = tile_gaps(words_, jnp.full((words_.shape[0],), width, jnp.int32), seg_.shape[-1])
        return tiles.tile_scores(q_, gaps, seg_, sp_, sa_, vals_, scale)

    pad = functools.partial(tiles.pad_axis, multiple=tiles.R_TILE, axis=0)
    streams = (pad(words), pad(seg, fill=-1), pad(start_pos), pad(start_abs), pad(vals))
    return tiles.xla_block_scores(tile_fn, q, streams, D)[:B]
