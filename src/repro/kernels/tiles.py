"""Tiled block-scan machinery shared by every codec kernel (DESIGN.md §3).

The PR-6 restructuring: instead of one grid step per packed block (a
``(1, X)`` row at a time — sublane-starved on real Mosaic), every kernel
now processes *tiles* of ``R_TILE`` blocks whose streams were lane-
aligned at pack time (``layout.LANE_MULTIPLE``).  One per-codec **tile
function** — decode a tile's gaps, rebase, gather the query, FMA, and
reduce per-slot via the contiguous-fragment prefix-sum difference
(``scoring.block_slot_scores``) — is shared verbatim by all three
executions of the same program:

* :func:`dma_block_scan` — the Pallas kernel: inputs stay in HBM
  (``memory_space=ANY``); an explicit **double-buffered DMA pipeline**
  copies tile *i+1* HBM→VMEM while tile *i* decodes and scores
  (``pltpu.make_async_copy`` + a 2-slot scratch per stream + DMA
  semaphores).  ``interpret=True`` validates the exact pipeline on any
  host; ``interpret=False`` is the real Mosaic lowering.
* :func:`grid_batch_scores` — the batched Pallas kernel: a 2-D
  **queries×tiles grid** (``Q_TILE`` query rows × ``R_TILE`` blocks per
  step), so each decoded tile scores a whole query tile while Mosaic's
  grid pipeline prefetches the next (decode-once/score-many).
* :func:`xla_block_scores` / :func:`xla_block_scores_batch` — the same
  tile program lowered through XLA: a jit'd ``lax.scan`` over the
  identical tiles.  This is what ``mode="pallas_compiled"`` runs on
  hosts without a Mosaic backend — compiled machine code whose per-tile
  working set stays cache-resident exactly where the TPU pipeline keeps
  it VMEM-resident.

Why the slot reduction wins: the jnp reference reduces B·T products
with one global segment-sum; the tile program reduces each tile to
``[R_TILE, D]`` slot scores first (a prefix-sum difference over the
contiguous fragments) and scatters only B·D values — ~T/D ≈ 8× fewer
elements through the serial scatter, which profiling shows dominates
the jnp scan wall-clock.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scoring import block_slot_scores, components_from_gaps

__all__ = [
    "R_TILE",
    "Q_TILE",
    "tile_scores",
    "tile_scores_batch",
    "pad_axis",
    "dma_block_scan",
    "grid_batch_scores",
    "xla_block_scores",
    "xla_block_scores_batch",
]

#: packed blocks per scan/grid step — 8 f32 sublanes' worth of tiles
R_TILE = 8

#: query rows per grid step in the batched queries×tiles grids
Q_TILE = 8


def pad_axis(x: jnp.ndarray, multiple: int, axis: int = 0, fill=0) -> jnp.ndarray:
    """Trace-time pad of ``axis`` to a multiple (tile-grid alignment).
    ``fill=-1`` builds neutral blocks: seg=-1 elements carry no product
    and doc_ids=-1 slots land in the scatter's overflow bucket."""
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


# ---------------------------------------------------------------------------
# the shared tile program (gaps already decoded by the codec)
# ---------------------------------------------------------------------------


def _tile_values(vals, scale: float, vq: str, vq_lo, vq_scale, vq_cb):
    """The tile program's dequant stage (DESIGN.md §12): the value tile
    — raw storage dtype under ``vq="f16"``, u8 codes otherwise — →
    scaled f32, through the shared ``values.decode_codes`` helpers, so
    quantized value bytes are what the tile DMA'd and f32 rows exist
    only in the tile working set."""
    if vq == "f16":
        return vals.astype(jnp.float32) * jnp.float32(scale)
    from repro.core import values as value_codecs

    cb = vq_cb.reshape(-1) if vq == "pq" else None
    return value_codecs.decode_codes(
        vq, vals, vq_lo, vq_scale, cb
    ) * jnp.float32(scale)


def tile_scores(
    q, gaps, seg, sp, sa, vals, scale: float,
    vq: str = "f16", vq_lo=None, vq_scale=None, vq_cb=None,
) -> jnp.ndarray:
    """One tile, one query: [R, T] streams → [R, D] slot scores."""
    comps = components_from_gaps(gaps, seg, sp, sa)
    qv = jnp.take(q, comps, axis=0)
    prod = qv * _tile_values(vals, scale, vq, vq_lo, vq_scale, vq_cb)
    prod = prod * (seg >= 0).astype(jnp.float32)
    return block_slot_scores(prod, sp)


def tile_scores_batch(
    Q, gaps, seg, sp, sa, vals, scale: float,
    vq: str = "f16", vq_lo=None, vq_scale=None, vq_cb=None,
) -> jnp.ndarray:
    """One tile, a query tile: decode once, score [nq, R, D]."""
    comps = components_from_gaps(gaps, seg, sp, sa)
    w = _tile_values(vals, scale, vq, vq_lo, vq_scale, vq_cb)
    w = w * (seg >= 0).astype(jnp.float32)
    qv = jnp.take(Q, comps, axis=1)  # [nq, R, T]
    return block_slot_scores(qv * w[None], sp)


# ---------------------------------------------------------------------------
# Pallas: double-buffered HBM→VMEM DMA block scan (single query)
# ---------------------------------------------------------------------------


def dma_block_scan(
    tile_fn: Callable,
    q: jnp.ndarray,  # [V] f32, V % 128 == 0 (VMEM-resident)
    streams: Sequence[jnp.ndarray],  # each [Bp, W_s], Bp % R_TILE == 0
    out_dim: int,  # D
    interpret: bool,
) -> jnp.ndarray:
    """Run ``tile_fn(q, *stream_tiles) → [R_TILE, D]`` over all tiles
    with an explicit two-slot DMA pipeline: tile i+1's streams are
    in flight HBM→VMEM while tile i decodes and scores.  Streams stay
    in HBM (``memory_space=ANY``); only the 2-slot scratch and the
    [Bp, D] output live in VMEM.  Returns [Bp, D] slot scores."""
    n_s = len(streams)
    Bp = streams[0].shape[0]
    nt = Bp // R_TILE
    V = q.shape[0]

    def kernel(q_ref, *refs):
        stream_refs, out_ref = refs[:n_s], refs[n_s]

        def scoped(*args):
            scratches, sem = args[:-1], args[-1]

            def copies(slot, i):
                return [
                    pltpu.make_async_copy(
                        stream_refs[s].at[pl.ds(i * R_TILE, R_TILE)],
                        scratches[s].at[slot],
                        sem.at[slot, s],
                    )
                    for s in range(n_s)
                ]

            for c in copies(0, 0):  # warm-up: tile 0 in flight
                c.start()

            def body(i, carry):
                slot = jax.lax.rem(i, 2)

                @pl.when(i + 1 < nt)
                def _():  # prefetch tile i+1 into the other slot
                    for c in copies(jax.lax.rem(i + 1, 2), i + 1):
                        c.start()

                for c in copies(slot, i):  # wait for tile i
                    c.wait()
                tiles = [scratches[s][slot] for s in range(n_s)]
                out_ref[pl.ds(i * R_TILE, R_TILE), :] = tile_fn(q_ref[0], *tiles)
                return carry

            jax.lax.fori_loop(0, nt, body, 0)

        pl.run_scoped(
            scoped,
            *[pltpu.VMEM((2, R_TILE, s.shape[1]), s.dtype) for s in streams],
            pltpu.SemaphoreType.DMA((2, n_s)),
        )

    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((1, V), lambda: (0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_s,
        out_specs=pl.BlockSpec((Bp, out_dim), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, out_dim), jnp.float32),
        interpret=interpret,
    )(q[None, :], *streams)


# ---------------------------------------------------------------------------
# Pallas: queries×tiles batched grid (decode once, score a query tile)
# ---------------------------------------------------------------------------


def grid_batch_scores(
    tile_fn_batch: Callable,
    Q: jnp.ndarray,  # [nqp, V] f32, nqp % Q_TILE == 0
    streams: Sequence[jnp.ndarray],  # each [Bp, W_s], Bp % R_TILE == 0
    out_dim: int,
    interpret: bool,
) -> jnp.ndarray:
    """2-D grid (query tiles × block tiles); each step decodes one
    block tile and scores one resident query tile against it
    (``tile_fn_batch(Q_tile, *stream_tiles) → [Q_TILE, R_TILE, D]``).
    Mosaic's grid pipeline double-buffers the tile streams between
    steps.  Returns [nqp, Bp, D]."""
    nqp, V = Q.shape
    Bp = streams[0].shape[0]
    grid = (nqp // Q_TILE, Bp // R_TILE)

    def kernel(q_ref, *refs):
        stream_refs, out_ref = refs[:-1], refs[-1]
        out_ref[...] = tile_fn_batch(q_ref[...], *[r[...] for r in stream_refs])

    in_specs = [pl.BlockSpec((Q_TILE, V), lambda qi, bi: (qi, 0))] + [
        pl.BlockSpec((R_TILE, s.shape[1]), lambda qi, bi: (bi, 0)) for s in streams
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((Q_TILE, R_TILE, out_dim), lambda qi, bi: (qi, bi, 0)),
        out_shape=jax.ShapeDtypeStruct((nqp, Bp, out_dim), jnp.float32),
        interpret=interpret,
    )(Q, *streams)


# ---------------------------------------------------------------------------
# XLA lowering: the same tile program as a jit'd lax.scan
# ---------------------------------------------------------------------------


def xla_block_scores(
    tile_fn: Callable, q: jnp.ndarray, streams: Sequence[jnp.ndarray], out_dim: int
) -> jnp.ndarray:
    """``lax.scan`` of the tile program over [nt, R_TILE, W] views —
    the compiled fallback of :func:`dma_block_scan`. [Bp, D]."""
    Bp = streams[0].shape[0]
    nt = Bp // R_TILE
    tiles = tuple(s.reshape(nt, R_TILE, s.shape[1]) for s in streams)

    def step(carry, ts):
        return carry, tile_fn(q, *ts)

    _, out = jax.lax.scan(step, 0, tiles)
    return out.reshape(Bp, out_dim)


def xla_block_scores_batch(
    tile_fn_batch: Callable,
    Q: jnp.ndarray,
    streams: Sequence[jnp.ndarray],
    out_dim: int,
) -> jnp.ndarray:
    """Batched form of :func:`xla_block_scores`: decode each tile once,
    score the whole query batch. [nq, Bp, D]."""
    Bp = streams[0].shape[0]
    nt = Bp // R_TILE
    tiles = tuple(s.reshape(nt, R_TILE, s.shape[1]) for s in streams)

    def step(carry, ts):
        return carry, tile_fn_batch(Q, *ts)

    _, out = jax.lax.scan(step, 0, tiles)  # [nt, nq, R, D]
    return out.transpose(1, 0, 2, 3).reshape(Q.shape[0], Bp, out_dim)
