"""Pallas TPU kernel: fused StreamVByte decode + gather + inner product.

StreamVByte (Lemire et al.) is the paper's headline general-purpose
codec: 2-bit controls, four gaps per control byte, 1–4 data bytes per
gap — full 32-bit gap range with byte-aligned decode. The TPU
adaptation keeps the same fusion discipline as ``dotvbyte_dot``:

  2-bit codes ──unpack──► per-value byte counts ──prefix-sum──► offsets
  offsets ──up-to-4 byte-gathers (masked by code)──► gaps
  gaps ──segmented cumsum──► components ──gather q──► qv ──FMA──► prod
  prod ──contiguous-fragment prefix-sum diff──► per-slot scores

Kernels are TILED (PR 6, ``tiles.py``): every step consumes ``R_TILE``
lane-aligned blocks.  The single-query scan runs the explicit
double-buffered HBM→VMEM DMA pipeline (:func:`tiles.dma_block_scan`);
the batched variant maps a queries×tiles grid
(:func:`tiles.grid_batch_scores`) so each decoded tile scores a
resident query tile (decode-once/score-many).  The ctrl stream is
lane-padded at pack time (``layout.LANE_MULTIPLE``); tile functions
slice it tight (``T // 4`` bytes) before decoding, and the data stream
keeps its 3-byte over-read pad so the 4-byte gather never reads out of
bounds.

``interpret=True`` validates the pipeline on any host; the XLA-compiled
lowering of the same tile program lives in ``ops.py``
(mode="pallas_compiled" off-TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.scoring import decode_gaps_streamvbyte

from . import tiles

__all__ = [
    "streamvbyte_block_scores",
    "streamvbyte_block_scores_batch",
    "streamvbyte_block_scores_xla",
    "streamvbyte_block_scores_xla_batch",
]


def tile_gaps(ctrl: jnp.ndarray, data: jnp.ndarray, T: int) -> jnp.ndarray:
    """[R, ≥T/4] ctrl + [R, DP] data → gaps i32 [R, T] (lane padding
    sliced tight before the decode)."""
    return decode_gaps_streamvbyte(ctrl[:, : T // 4], data)


def _tile_fn(q, ctrl, data, seg, sp, sa, vals, *, scale: float):
    return tiles.tile_scores(q, tile_gaps(ctrl, data, seg.shape[-1]), seg, sp, sa, vals, scale)


def _tile_fn_batch(Q, ctrl, data, seg, sp, sa, vals, *, scale: float):
    return tiles.tile_scores_batch(Q, tile_gaps(ctrl, data, seg.shape[-1]), seg, sp, sa, vals, scale)


def _pad_block_streams(ctrl, data, seg, start_pos, start_abs, vals):
    pad = functools.partial(tiles.pad_axis, multiple=tiles.R_TILE, axis=0)
    return (
        pad(ctrl), pad(data), pad(seg, fill=-1), pad(start_pos), pad(start_abs), pad(vals),
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def streamvbyte_block_scores(
    q: jnp.ndarray,  # [vocab_pad] f32, vocab_pad % 128 == 0
    ctrl: jnp.ndarray,  # [B, ≥T/4] u8, lane-padded
    data: jnp.ndarray,  # [B, DP] u8, DP % 128 == 0, ≥ 3 over-read bytes
    seg: jnp.ndarray,  # [B, T] i32 (or i8, slim layout)
    start_pos: jnp.ndarray,  # [B, D] i32
    start_abs: jnp.ndarray,  # [B, D] i32
    vals: jnp.ndarray,  # [B, T] storage dtype
    *,
    scale: float = 1.0,
    interpret: bool = True,
) -> jnp.ndarray:
    """Per-block document scores [B, D] via the double-buffered DMA
    scan (combine with ``scatter_block_scores``)."""
    B = ctrl.shape[0]
    D = start_pos.shape[1]
    streams = _pad_block_streams(ctrl, data, seg, start_pos, start_abs, vals)
    out = tiles.dma_block_scan(
        functools.partial(_tile_fn, scale=scale), q, streams, D, interpret
    )
    return out[:B]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def streamvbyte_block_scores_batch(
    Q: jnp.ndarray,  # [nq, vocab_pad] f32
    ctrl: jnp.ndarray,
    data: jnp.ndarray,
    seg: jnp.ndarray,
    start_pos: jnp.ndarray,
    start_abs: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    scale: float = 1.0,
    interpret: bool = True,
) -> jnp.ndarray:
    """[nq, B, D] per-block scores for a query batch: a queries×tiles
    grid, each block tile decoded once per query tile."""
    nq = Q.shape[0]
    B = ctrl.shape[0]
    D = start_pos.shape[1]
    Qp = tiles.pad_axis(Q, tiles.Q_TILE, axis=0)
    streams = _pad_block_streams(ctrl, data, seg, start_pos, start_abs, vals)
    out = tiles.grid_batch_scores(
        functools.partial(_tile_fn_batch, scale=scale), Qp, streams, D, interpret
    )
    return out[:nq, :B]


@functools.partial(jax.jit, static_argnames=("scale",))
def streamvbyte_block_scores_xla(
    q, ctrl, data, seg, start_pos, start_abs, vals, *, scale: float = 1.0
):
    """The same tile program lowered through XLA — mode="pallas_compiled"
    off-TPU."""
    B = ctrl.shape[0]
    D = start_pos.shape[1]
    streams = _pad_block_streams(ctrl, data, seg, start_pos, start_abs, vals)
    return tiles.xla_block_scores(
        functools.partial(_tile_fn, scale=scale), q, streams, D
    )[:B]


@functools.partial(jax.jit, static_argnames=("scale",))
def streamvbyte_block_scores_xla_batch(
    Q, ctrl, data, seg, start_pos, start_abs, vals, *, scale: float = 1.0
):
    """XLA lowering of the batched tile program → [nq, B, D]."""
    B = ctrl.shape[0]
    D = start_pos.shape[1]
    streams = _pad_block_streams(ctrl, data, seg, start_pos, start_abs, vals)
    return tiles.xla_block_scores_batch(
        functools.partial(_tile_fn_batch, scale=scale), Q, streams, D
    )[:, :B]
