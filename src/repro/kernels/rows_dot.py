"""Pallas TPU kernel: fused candidate-row gather + decode + rescore.

The serve engines' phase-2 hot path (DESIGN.md §7) re-scores a static
set of candidate documents against the packed row form ``[N+1, L]``
(``layout.pack_rows``). The pure-jnp path (``scoring.score_candidate_
rows``) is a take→decode→dot chain whose intermediates — the gathered
codec payload AND the decoded i32 components — materialise in HBM.

This kernel keeps the whole chain fused (DESIGN.md §3): the candidate
doc ids arrive as a *scalar-prefetch* operand, so the grid ``index_map``
itself performs the HBM→VMEM row gather — grid step ``i`` DMAs the
HBM tile that holds the row of document ``docs[i]`` (8 rows of a 32-bit
stream, 16 of a 16-bit, 32 of an 8-bit one: the unit Mosaic can DMA),
picks the row out in VMEM, decodes it and dots it against the
VMEM-resident query group in one step. Decoded components never touch
HBM. Consecutive candidates in one tile reuse the fetched tile, so a
full scan (``flat``) reads each tile once.

  docs (scalar prefetch) ──index_map──► row-tile DMA HBM→VMEM
  row payload ──codec decode──► gaps ──prefix sum──► absolute components
  components ──match query-id union──► qv ──FMA vals·mask──► Σ ──► scores

Every step is written in forms Mosaic lowers (DESIGN.md §3):

* byte gathers (control bytes, data bytes, bit-packed words split into
  bytes) are one-hot matmuls: a byte fits bf16 exactly and the one-hot
  operand is 0/1, so the f32-accumulated product is the byte itself;
* prefix sums are log-step ``pltpu.roll`` adds (exact i32);
* the query lookup matches the decoded ids against the union of the
  query group's nonzero ids, built outside the kernel and walked in
  128-id chunks (the chunk count is a scalar-prefetch operand, so the
  cost follows the queries' nonzeros, and any query is served exactly);
  the weights of the matched ids come out of an f32 matmul at
  ``Precision.HIGHEST`` against the 0/1 match matrix, which carries
  them unrounded.

Row-gap convention: the first gap IS the absolute component
(per-document alignment), so a plain prefix sum rebuilds the ids; the
sentinel row N is all-zero and scores exactly 0 (callers mask it).

All four registered codecs have a rows kernel, at every value codec
(``values.VALUE_CODECS``). Single-query calls compose with ``jax.vmap``:
the candidate ids differ per query, so the batching rule runs one
kernel call per query (``seismic``/``hnsw`` serve this way).

``rows_scores_xla{,_batch}`` lower the SAME chain through XLA — one
jit'd gather→decode→dot graph, candidate-tiled — which is what
``mode="pallas_compiled"`` runs on hosts without Mosaic
(``repro.kernels.modes``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tiles

__all__ = [
    "rows_scores",
    "rows_scores_batch",
    "rows_scores_xla",
    "rows_scores_xla_batch",
]

#: queries per query group: one group's id union and weights stay
#: VMEM-resident for the whole candidate sweep
Q_GROUP = 32

#: candidate rows per kernel call (bounds the scalar-prefetch arrays,
#: which live in SMEM)
C_CALL = 8192

_LANES = 128
_HIGHEST = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# in-kernel building blocks
# ---------------------------------------------------------------------------


def _lanes(shape) -> jnp.ndarray:
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _row(ref, r) -> jnp.ndarray:
    """Row ``r`` of a gathered row tile → [1, W] (i32, or f32 for f32
    streams). Narrow dtypes widen the whole tile first: Mosaic slices
    packed 8/16-bit tiles only at tile granularity."""
    if ref.dtype == jnp.uint32:
        return lax.bitcast_convert_type(ref[pl.ds(r, 1), :], jnp.int32)
    if jnp.dtype(ref.dtype).itemsize == 4:
        return ref[pl.ds(r, 1), :]
    x = ref[...].astype(jnp.int32)
    hit = lax.broadcasted_iota(jnp.int32, x.shape, 0) == r
    return jnp.sum(jnp.where(hit, x, 0), axis=0, keepdims=True)


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the lanes of a [1, L] i32 row:
    log2(L) roll-and-add steps, exact."""
    lane = _lanes(x.shape)
    s = 1
    while s < x.shape[-1]:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    return x


def _planes(rows) -> jnp.ndarray:
    """Stack up to 8 [1, S] i32 rows into one [8, S] matmul operand
    (missing rows are zero)."""
    S = rows[0].shape[-1]
    sub = lax.broadcasted_iota(jnp.int32, (8, S), 0)
    out = jnp.zeros((8, S), jnp.int32)
    for k, r in enumerate(rows):
        out = jnp.where(sub == k, r, out)
    return out


def _shifted(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """x[p + k] along the lanes of a [1, S] row, zero past the end."""
    if k == 0:
        return x
    S = x.shape[-1]
    return jnp.where(_lanes(x.shape) < S - k, pltpu.roll(x, S - k, 1), 0)


def _take(src: jnp.ndarray, idx: jnp.ndarray, exact_f32: bool = False):
    """out[k, j] = src[k, idx[0, j]] for an [8, S] source: a one-hot
    matmul. Bytes are exact in bf16; ``exact_f32`` carries f32 values
    unrounded (``Precision.HIGHEST``). Returns f32 [8, L]."""
    S = src.shape[-1]
    hit = lax.broadcasted_iota(jnp.int32, (S, idx.shape[-1]), 0) == idx
    dt = jnp.float32 if exact_f32 else jnp.bfloat16
    return jnp.dot(
        src.astype(jnp.float32).astype(dt), hit.astype(dt),
        precision=_HIGHEST if exact_f32 else None,
        preferred_element_type=jnp.float32,
    )


def _take_bytes(rows, idx) -> jnp.ndarray:
    """Byte gather: rows (≤ 8 [1, S] byte rows) at ``idx`` → i32 [8, L]."""
    return _take(_planes(rows), idx).astype(jnp.int32)


def _f16_to_f32(h: jnp.ndarray) -> jnp.ndarray:
    """IEEE half bit patterns (i32 holding 16 bits) → f32, exactly."""
    sign = (h >> 15) << 31
    e = (h >> 10) & 31
    m = h & 1023
    normal = lax.bitcast_convert_type(sign | ((e + 112) << 23) | (m << 13), jnp.float32)
    special = lax.bitcast_convert_type(sign | (255 << 23) | (m << 13), jnp.float32)
    sub = m.astype(jnp.float32) * jnp.float32(2.0**-24)
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(e == 0, sub, jnp.where(e == 31, special, normal))


# ---------------------------------------------------------------------------
# per-codec row decoders: (payload tiles, row, scalars) → comps i32 [1, L]
# ---------------------------------------------------------------------------


def _comps_uncompressed(refs, r, sc, L):
    (comps_ref,) = refs
    return _row(comps_ref, r)[:, :L]


def _comps_dotvbyte(refs, r, sc, L):
    ctrl_ref, data_ref = refs
    j = _lanes((1, L))
    bit = (_take_bytes([_row(ctrl_ref, r)], j >> 3)[0:1] >> (j & 7)) & 1
    lens = bit + 1
    starts = _prefix_sum(lens) - lens
    data = _row(data_ref, r)
    b = _take_bytes([data, _shifted(data, 1)], starts)
    return _prefix_sum(b[0:1] + ((b[1:2] * bit) << 8))


def _comps_streamvbyte(refs, r, sc, L):
    ctrl_ref, data_ref = refs
    j = _lanes((1, L))
    code = (_take_bytes([_row(ctrl_ref, r)], j >> 2)[0:1] >> ((j & 3) * 2)) & 3
    lens = code + 1
    starts = _prefix_sum(lens) - lens
    data = _row(data_ref, r)
    b = _take_bytes([_shifted(data, k) for k in range(4)], starts)
    gaps = b[0:1]
    for k in range(1, 4):
        gaps = gaps | ((b[k : k + 1] * (code >= k)) << (8 * k))
    return _prefix_sum(gaps)


def _comps_bitpack(refs, r, sc, L):
    (words_ref,) = refs
    width = sc["width"]
    words = _row(words_ref, r)  # u32 words as i32 bits
    j = _lanes((1, L))
    bitpos = j * width
    wi, off = bitpos >> 5, bitpos & 31
    nxt = _shifted(words, 1)
    rows = [lax.shift_right_logical(w, 8 * k) & 255 for w in (words, nxt) for k in range(4)]
    g = _take_bytes(rows, wi)
    lo = g[0:1] | (g[1:2] << 8) | (g[2:3] << 16) | (g[3:4] << 24)
    hi = g[4:5] | (g[5:6] << 8) | (g[6:7] << 16) | (g[7:8] << 24)
    v = lax.shift_right_logical(lo, off) | jnp.where(off > 0, hi << (32 - off), 0)
    mask = jnp.where(width >= 32, -1, (1 << width) - 1)
    return _prefix_sum(v & mask)


_DECODERS = {
    "uncompressed": _comps_uncompressed,
    "dotvbyte": _comps_dotvbyte,
    "streamvbyte": _comps_streamvbyte,
    "bitpack": _comps_bitpack,
}


def _values(vq: str, vals_ref, r, sc, vq_refs, L) -> jnp.ndarray:
    """In-kernel dequant stage (DESIGN.md §12): the row's stored value
    bytes → f32 storage-unit values [1, L], the same arithmetic as
    ``values.decode_codes``."""
    from repro.core import values as value_codecs

    x = _row(vals_ref, r)
    if vq == "f16":
        if vals_ref.dtype == jnp.uint16:  # f16 values, passed as their bits
            return _f16_to_f32(x)
        return x.astype(jnp.float32)
    j = _lanes((1, L))
    if vq == "u8_sq":
        codes = x
    elif vq == "u4_sq":
        codes = (_take_bytes([x], j >> 1)[0:1] >> ((j & 1) * 4)) & 15
    else:  # pq: code → flat codebook slot, gathered exactly
        M = value_codecs.PQ_M
        slot = _take_bytes([x], j // M)[0:1] * M + j % M
        (cb_ref,) = vq_refs
        cb = jnp.broadcast_to(cb_ref[...], (8, cb_ref.shape[-1]))
        return _take(cb, slot, exact_f32=True)[0:1]
    return value_codecs.dequant_sq(codes, sc["lo"], sc["step"])


def _query_values(uid_ref, wu_ref, n_chunks, comps) -> jnp.ndarray:
    """q[g, comps] for the query group → f32 [QG, L]: match the ids
    against the group's nonzero-id union, 128 ids per chunk. Each id
    appears once in the union, so every entry is one exact weight."""
    QG, L = wu_ref.shape[1], comps.shape[-1]

    def chunk(c, acc):
        ids = jnp.broadcast_to(uid_ref[c], (8, _LANES)).T[:, :1]  # [128, 1]
        hit = (ids == comps).astype(jnp.float32)  # [128, L]
        return acc + jnp.dot(
            wu_ref[c], hit, precision=_HIGHEST, preferred_element_type=jnp.float32
        )

    return lax.fori_loop(0, n_chunks, chunk, jnp.zeros((QG, L), jnp.float32))


def _kernel(docs_ref, nnz_ref, nch_ref, *refs, codec, vq, scale, L, scalar_names,
            n_vq, n_payload):
    n_s = len(scalar_names)
    scalar_refs = refs[:n_s]
    uid_ref, wu_ref, vals_ref = refs[n_s : n_s + 3]
    vq_refs = refs[n_s + 3 : n_s + 3 + n_vq]
    payload_refs = refs[n_s + 3 + n_vq : n_s + 3 + n_vq + n_payload]
    out_ref = refs[-1]
    g, i = pl.program_id(0), pl.program_id(1)
    d = docs_ref[i]
    sc = {name: ref[i] for name, ref in zip(scalar_names, scalar_refs)}

    vals = _values(vq, vals_ref, d % vals_ref.shape[0], sc, vq_refs, L)
    w = jnp.where(_lanes((1, L)) < nnz_ref[i], vals * jnp.float32(scale), 0.0)
    comps = _DECODERS[codec](payload_refs, d % payload_refs[0].shape[0], sc, L)
    qv = _query_values(uid_ref, wu_ref, nch_ref[g], comps)
    s = jnp.sum(qv * w, axis=1, keepdims=True)  # [QG, 1]
    lane = _lanes(out_ref.shape)
    out_ref[...] = jnp.where(lane == i % _LANES, s, out_ref[...])


# ---------------------------------------------------------------------------
# host-side (XLA) operand preparation
# ---------------------------------------------------------------------------


def _query_union(Q: jnp.ndarray):
    """One query group [QG, Vp] → (uid i32 [NCH, 1, 128], weights f32
    [NCH, QG, 128], live chunk count): the union of the group's nonzero
    ids, compacted to the front (-1 past the end, which no component
    matches), and each query's weight at those ids."""
    QG, Vp = Q.shape
    live = jnp.any(Q != 0, axis=0)
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    slot = jnp.where(live, pos, Vp)
    uid = jnp.full((Vp,), -1, jnp.int32).at[slot].set(
        jnp.arange(Vp, dtype=jnp.int32), mode="drop"
    )
    wu = jnp.where(uid >= 0, jnp.take(Q, jnp.maximum(uid, 0), axis=1), 0.0)
    nch = Vp // _LANES
    n_chunks = (pos[-1] + 1 + _LANES - 1) // _LANES
    return (
        uid.reshape(nch, 1, _LANES),
        wu.reshape(QG, nch, _LANES).transpose(1, 0, 2),
        n_chunks.astype(jnp.int32),
    )


def _row_streams(codec: str, vq: str, arrays):
    """(value stream, resident vq operands, payload row streams) in the
    dtypes the kernel loads: f16 values travel as their bits (u16), a
    bitcast XLA fuses into the kernel's input (no copy)."""
    vals = arrays["vals_rows"]
    if vals.dtype == jnp.float16:
        vals = lax.bitcast_convert_type(vals, jnp.uint16)
    resident = []
    if vq == "pq":  # the flat codebook [1, K·M], resident for the whole grid
        resident = [jnp.asarray(arrays["vq_codebook"], jnp.float32).reshape(1, -1)]
    if codec == "uncompressed":
        payload = [arrays["comps_rows"]]
    elif codec == "bitpack":
        payload = [arrays["words_rows"]]
    else:
        payload = [arrays["ctrl_rows"], arrays["data_rows"]]
    return vals, resident, payload


def _candidate_scalars(codec: str, vq: str, arrays, docs):
    """Per-candidate scalars gathered once in XLA and scalar-prefetched:
    the bitpack width, the scalar-quant clip range."""
    from repro.core import values as value_codecs

    out = {}
    if codec == "bitpack":
        out["width"] = jnp.take(arrays["widths_rows"], docs).astype(jnp.int32)
    if vq in ("u8_sq", "u4_sq"):
        for name, key in zip(("lo", "step"), value_codecs.sq_keys(vq)):
            out[name] = jnp.take(arrays[key].reshape(-1), docs).astype(jnp.float32)
    return out


def _tile_spec(a: jnp.ndarray):
    """Gather spec of one row stream: the HBM row tile holding
    ``docs[i]`` (the whole array when it is shorter than a tile)."""
    tb = min(32 // jnp.dtype(a.dtype).itemsize, a.shape[0])
    return pl.BlockSpec((tb, a.shape[1]), lambda g, i, docs, *_: (docs[i] // tb, 0))


def _resident_spec(a: jnp.ndarray):
    return pl.BlockSpec(a.shape, lambda g, i, *_: (0,) * a.ndim)


def _rows_call(codec, vq, scale, interpret, L, docs, nnz, scalars, uid, wu, nch,
               vals, resident, payload):
    """One kernel call over ``Cc`` candidates (a multiple of 128) and
    every query group → f32 [G, QG, Cc]."""
    G, NCH, QG, _ = wu.shape
    Cc = docs.shape[0]
    names = tuple(scalars)
    group = lambda a: pl.BlockSpec(
        (None, *a.shape[1:]), lambda g, i, *_: (g,) + (0,) * (a.ndim - 1)
    )
    in_specs = (
        [group(uid), group(wu), _tile_spec(vals)]
        + [_resident_spec(a) for a in resident]
        + [_tile_spec(p) for p in payload]
    )
    n_operands = 6 + len(names) + len(resident) + len(payload)
    kernel = functools.partial(
        _kernel, codec=codec, vq=vq, scale=scale, L=L, scalar_names=names,
        n_vq=len(resident), n_payload=len(payload),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(names),
            grid=(G, Cc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, QG, _LANES), lambda g, i, *_: (g, 0, i // _LANES)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((G, QG, Cc), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            allow_input_fusion=[k == 5 + len(names) for k in range(n_operands)],
        ),
        interpret=interpret,
        name=f"rows_{codec}_{vq}",
    )(docs, nnz, nch, *[scalars[n] for n in names], uid, wu, vals, *resident, *payload)


@functools.partial(
    jax.jit, static_argnames=("codec", "scale", "vq", "interpret")
)
def rows_scores_batch(
    codec: str,
    Q: jnp.ndarray,  # [nq, vocab_pad] f32, vocab_pad % 128 == 0
    docs: jnp.ndarray,  # i32 [C] candidate doc ids (sentinel = row N)
    arrays,  # row-form dict: vals_rows/nnz_rows + vq + codec payload
    scale: float = 1.0,
    vq: str = "f16",
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused rescoring of C candidate rows against a query batch.

    Returns scores f32 [nq, C]. ``docs`` is consumed as scalar prefetch:
    the grid index_map gathers the row tile of ``docs[i]`` HBM→VMEM at
    step ``i``. Queries run in groups of ``Q_GROUP``; each group's
    nonzero-id union is built here, in XLA, and stays VMEM-resident.

    Under a quantized ``vq`` the value stream carries u8 codes (the
    only value bytes that cross HBM); the scalar-quant clip range is
    scalar-prefetched per candidate, the PQ codebook is grid-resident,
    and the in-kernel dequant stage rebuilds f32 values in VMEM before
    the dot (DESIGN.md §12)."""
    from repro.core import values as value_codecs

    nq = Q.shape[0]
    C = docs.shape[0]
    L = arrays["vals_rows"].shape[1] * value_codecs.code_factor(vq)
    QG = min(Q_GROUP, -(-nq // 8) * 8)
    Qp = tiles.pad_axis(Q.astype(jnp.float32), QG, axis=0)
    G = Qp.shape[0] // QG
    uid, wu, nch = jax.vmap(_query_union)(Qp.reshape(G, QG, -1))

    sentinel = arrays["vals_rows"].shape[0] - 1
    docs = jnp.clip(docs.astype(jnp.int32), 0, sentinel)
    Cc = min(C_CALL, -(-C // _LANES) * _LANES)
    docs = tiles.pad_axis(docs, Cc, fill=sentinel).reshape(-1, Cc)
    vals, resident, payload = _row_streams(codec, vq, arrays)

    def call(d):
        nnz = jnp.take(arrays["nnz_rows"], d).astype(jnp.int32)
        scalars = _candidate_scalars(codec, vq, arrays, d)
        return _rows_call(codec, vq, scale, interpret, L, d, nnz, scalars,
                          uid, wu, nch, vals, resident, payload)

    out = lax.map(call, docs)  # [n_calls, G, QG, Cc]
    out = out.transpose(1, 2, 0, 3).reshape(G * QG, -1)
    return out[:nq, :C]


def rows_scores(
    codec: str,
    q: jnp.ndarray,  # [vocab_pad] f32
    docs: jnp.ndarray,
    arrays,
    scale: float = 1.0,
    vq: str = "f16",
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-query fused rescoring → scores f32 [C]."""
    return rows_scores_batch(
        codec, q[None, :], docs, arrays, scale=scale, vq=vq, interpret=interpret,
    )[0]


# ---------------------------------------------------------------------------
# XLA lowering: the same fused chain as one jit'd candidate-tiled graph
# ---------------------------------------------------------------------------

#: candidate rows per XLA tile — bounds the decoded working set the way
#: the scalar-prefetch grid bounds it to one row per step
C_TILE = 128


@functools.partial(jax.jit, static_argnames=("codec", "scale"))
def rows_scores_xla_batch(
    codec: str,
    Q: jnp.ndarray,  # [nq, dim] f32 (lane padding not required)
    docs: jnp.ndarray,  # i32 [C]
    arrays,  # dict with vals_rows/nnz_rows + codec payload
    scale: float = 1.0,
) -> jnp.ndarray:
    """One compiled gather→decode→dot graph → scores f32 [nq, C].

    The whole chain fuses under jit (no eager HBM materialisation of
    the gathered payload or decoded components between dispatches);
    candidate sets larger than ``C_TILE`` stream through a ``lax.scan``
    so the per-step working set stays cache-resident."""
    from repro.core.scoring import _gather_decode_rows, score_doc_rows

    C = docs.shape[0]
    if C <= C_TILE:
        comps, vals, nnz = _gather_decode_rows(codec, arrays, docs)
        return jax.vmap(lambda q: score_doc_rows(q, comps, vals, nnz, scale))(Q)
    sentinel = arrays["vals_rows"].shape[0] - 1  # all-zero row, scores 0
    dt = tiles.pad_axis(docs, C_TILE, fill=sentinel).reshape(-1, C_TILE)

    def step(carry, d):
        comps, vals, nnz = _gather_decode_rows(codec, arrays, d)
        return carry, jax.vmap(lambda q: score_doc_rows(q, comps, vals, nnz, scale))(Q)

    _, out = jax.lax.scan(step, 0, dt)  # [nt, nq, C_TILE]
    return out.transpose(1, 0, 2).reshape(Q.shape[0], -1)[:, :C]


def rows_scores_xla(codec, q, docs, arrays, scale=1.0):
    """Single-query form of :func:`rows_scores_xla_batch` → [C] f32."""
    return rows_scores_xla_batch(codec, q[None, :], docs, arrays, scale)[0]
