"""Pallas TPU kernel: fused row decode + score, in two grid forms.

The serve engines score documents against the packed row form
``[N+1, L]`` (``layout.pack_rows``; DESIGN.md §7). The pure-jnp path
(``scoring.score_candidate_rows``) is a take→decode→dot chain whose
intermediates — the gathered codec payload AND the decoded i32
components — materialise in HBM. This kernel keeps the whole chain
fused (DESIGN.md §3): decoded components never touch HBM.

Two grids share the decoders, the query lookup and the scoring step;
they differ only in how many rows a step holds and how it finds them.

* **Gather form** (``rows_scores{,_batch}``, kernel ``rows_<codec>_<vq>``),
  for arbitrary candidate ids (``seismic``, ``hnsw``): grid
  ``(G, C)``, one candidate per step. The ids arrive as a
  *scalar-prefetch* operand, so the grid ``index_map`` itself performs
  the HBM→VMEM row gather — step ``i`` DMAs the HBM tile that holds the
  row of ``docs[i]`` (8 rows of a 32-bit stream, 16 of a 16-bit, 32 of
  an 8-bit one: the unit Mosaic can DMA) and picks the row out in VMEM.
  Per-candidate scalars (nnz, codec scalars) are scalar-prefetched too,
  so a call covers at most ``C_CALL`` candidates.
* **Range form** (``rows_scan_batch``, kernel ``rows_scan_<codec>_<vq>``),
  for every row (``flat``'s full scan): grid ``(G, ceil((N+1)/R))``,
  ``R = R_SCAN`` rows per step. Step ``i`` loads rows ``[i·R, i·R + R)``
  of every stream with a plain block ``index_map``, decodes them as one
  ``[R, L]`` tile and scores all R. The per-row scalars come as VMEM
  blocks; one call covers the whole table; the ragged last block is
  masked by row index.

  docs (gather) | block i (range) ──► row tiles HBM→VMEM
  row payload ──codec decode──► gaps ──prefix sum──► absolute components
  components ──match query-id union──► qv ──FMA vals·mask──► Σ ──► scores

Every step is written in forms Mosaic lowers (DESIGN.md §3):

* byte gathers (control bytes, data bytes, bit-packed words split into
  bytes) are one-hot matmuls: a byte fits bf16 exactly and the one-hot
  operand is 0/1, so the f32-accumulated product is the byte itself.
  A gather at an index every row shares (control bytes) is one matmul
  for the whole tile; one at per-row offsets (data bytes) is one per
  row, and each one-hot covers only the source lanes its outputs can
  index;
* prefix sums are log-step ``pltpu.roll`` adds (exact i32);
* the query lookup matches the decoded ids against the union of the
  query group's nonzero ids, built outside the kernel and walked in
  128-id chunks (the chunk count is a scalar-prefetch operand, so the
  cost follows the queries' nonzeros, and any query is served
  exactly); each chunk's ids are transposed once for all R rows, and
  the weights of the matched ids come out of bf16 matmuls of the f32
  weights' three exact bf16 parts against the 0/1 match matrix, which
  carry them unrounded.

Row-gap convention: the first gap IS the absolute component
(per-document alignment), so a plain prefix sum rebuilds the ids; the
sentinel row N is all-zero and scores exactly 0 (callers mask it).

All four registered codecs have both forms, at every value codec
(``values.VALUE_CODECS``). Single-query calls compose with ``jax.vmap``:
the batching rule runs one kernel call per query (``seismic``/``hnsw``
serve the gather form this way).

``rows_scores_xla{,_batch}`` lower the SAME chain through XLA — one
jit'd gather→decode→dot graph, candidate-tiled — which is what
``mode="pallas_compiled"`` runs on hosts without Mosaic
(``repro.kernels.modes``), for both forms.
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
    "rows_scan_batch",
    "rows_scores_xla",
    "rows_scores_xla_batch",
]

#: queries per query group: one group's id union and weights stay
#: VMEM-resident for the whole candidate sweep
Q_GROUP = 32

#: candidate rows per kernel call of the gather form (bounds the
#: scalar-prefetch arrays, which live in SMEM)
C_CALL = 8192

#: rows per grid step of the range form: a multiple of the row tile of
#: an 8-bit stream (32), so every stream's block is whole tiles and no
#: row is picked out, and a divisor of 128 (a step's scores fill lanes
#: of one [QG, 128] output block). 64 from a v5e timing of 32/64/128
#: (PERF.md §6).
R_SCAN = 64

_LANES = 128


# ---------------------------------------------------------------------------
# in-kernel building blocks
# ---------------------------------------------------------------------------


def _lanes(shape) -> jnp.ndarray:
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _widen(x: jnp.ndarray) -> jnp.ndarray:
    """A loaded stream tile → i32 (u32 words as their bits; narrow
    integers widened), or f32 as it is."""
    if x.dtype == jnp.uint32:
        return lax.bitcast_convert_type(x, jnp.int32)
    if jnp.dtype(x.dtype).itemsize == 4:
        return x
    return x.astype(jnp.int32)


def _row(ref, r) -> jnp.ndarray:
    """Row ``r`` of a gathered row tile → [1, W] (i32, or f32 for f32
    streams). Narrow dtypes widen the whole tile first: Mosaic slices
    packed 8/16-bit tiles only at tile granularity."""
    if jnp.dtype(ref.dtype).itemsize == 4:
        return _widen(ref[pl.ds(r, 1), :])
    x = _widen(ref[...])
    hit = lax.broadcasted_iota(jnp.int32, x.shape, 0) == r
    return jnp.sum(jnp.where(hit, x, 0), axis=0, keepdims=True)


def _column(ref, i) -> jnp.ndarray:
    """Row ``i % 8`` of an [8, R] block of per-row scalars → an [R, 1]
    column (a lane-to-sublane move as a masked lane sum: one nonzero)."""
    x = ref[pl.ds(i % 8, 1), :]
    R = x.shape[-1]
    eye = lax.broadcasted_iota(jnp.int32, (R, R), 0) == _lanes((R, R))
    return jnp.sum(jnp.where(eye, x, jnp.zeros_like(x)), axis=1, keepdims=True)


def _per_row(R: int, fn) -> jnp.ndarray:
    """Stack ``fn(r)`` ([1, L] each) into [R, L]: the form of a gather
    whose index differs per row."""
    if R == 1:
        return fn(0)
    out = None
    for r in range(R):
        x = fn(r)
        if out is None:
            out = jnp.zeros((R, x.shape[-1]), x.dtype)
            sub = lax.broadcasted_iota(jnp.int32, out.shape, 0)
        out = jnp.where(sub == r, x, out)
    return out


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the lanes of an [R, L] i32 tile:
    log2(L) roll-and-add steps, exact."""
    lane = _lanes(x.shape)
    s = 1
    while s < x.shape[-1]:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    return x


def _planes(rows) -> jnp.ndarray:
    """Stack up to 8 [1, S] rows into one [8, S] matmul operand
    (missing rows are zero)."""
    S = rows[0].shape[-1]
    sub = lax.broadcasted_iota(jnp.int32, (8, S), 0)
    out = jnp.zeros((8, S), rows[0].dtype)
    for k, r in enumerate(rows):
        out = jnp.where(sub == k, r, out)
    return out


def _shifted(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """x[:, p + k] along the lanes of an [R, S] tile, zero past the end."""
    if k == 0:
        return x
    S = x.shape[-1]
    return jnp.where(_lanes(x.shape) < S - k, pltpu.roll(x, S - k, 1), 0)


def _take(src: jnp.ndarray, idx: jnp.ndarray, span=None):
    """out[k, j] = src[k, idx[0, j]] for an [M, S] source of values
    exact in bf16 (bytes; bf16 parts of f32) → f32 [M, L]: one-hot
    matmuls, one per 128 output lanes. Each output sums one source value
    with zeros, so the gather is exact. ``span(a, b)`` bounds the source
    lanes that output lanes [a, b) can index, so each one-hot covers
    only those source tiles."""
    M, S = src.shape
    if M < 8:
        src = _planes([src[k : k + 1] for k in range(M)])
    a = src.astype(jnp.float32).astype(jnp.bfloat16)
    outs = []
    for j0 in range(0, idx.shape[-1], _LANES):
        lo, hi = span(j0, j0 + _LANES) if span else (0, S)
        lo = max(0, lo) // _LANES * _LANES
        hi = min(S, -(-hi // _LANES) * _LANES)
        src_lane = lax.broadcasted_iota(jnp.int32, (hi - lo, _LANES), 0) + lo
        hit = src_lane == idx[:, j0 : j0 + _LANES]
        outs.append(jnp.dot(
            a[:, lo:hi], hit.astype(jnp.bfloat16), preferred_element_type=jnp.float32
        ))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out[:M]


def _take_bytes(rows, idx, span=None) -> jnp.ndarray:
    """Byte gather: rows (≤ 8 [1, S] byte rows) at ``idx`` → i32 [8, L]."""
    return _take(_planes(rows), idx, span).astype(jnp.int32)


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
# per-codec row decoders: (payload tiles [R, W], scalars, L) → comps i32 [R, L]
# ---------------------------------------------------------------------------
# ``R`` is 1 in the gather form and ``R_SCAN`` in the range form; the
# per-row scalars are [R, 1] columns in both.
# A gather at an index shared by every row (control bytes) is one matmul
# for the whole tile; one at per-row offsets (data bytes) is one per row.


def _comps_uncompressed(streams, sc, L):
    (comps,) = streams
    return comps[:, :L]


def _comps_dotvbyte(streams, sc, L):
    ctrl, data = streams
    R = ctrl.shape[0]
    j = _lanes((R, L))
    code = _take(ctrl, _lanes((1, L)) >> 3, lambda a, b: (a // 8, b // 8 + 1))
    bit = (code.astype(jnp.int32) >> (j & 7)) & 1
    lens = bit + 1
    starts = _prefix_sum(lens) - lens  # in [j, 2j]
    nxt = _shifted(data, 1)

    def gap(r):
        b = _take_bytes([data[r : r + 1], nxt[r : r + 1]], starts[r : r + 1],
                        lambda a, b: (a, 2 * b))
        return b[0:1] + ((b[1:2] * bit[r : r + 1]) << 8)

    return _prefix_sum(_per_row(R, gap))


def _comps_streamvbyte(streams, sc, L):
    ctrl, data = streams
    R = ctrl.shape[0]
    j = _lanes((R, L))
    code = _take(ctrl, _lanes((1, L)) >> 2, lambda a, b: (a // 4, b // 4 + 1))
    code = (code.astype(jnp.int32) >> ((j & 3) * 2)) & 3
    lens = code + 1
    starts = _prefix_sum(lens) - lens  # in [j, 4j]
    shifted = [_shifted(data, k) for k in range(4)]

    def gap(r):
        b = _take_bytes([s[r : r + 1] for s in shifted], starts[r : r + 1],
                        lambda a, b: (a, 4 * b))
        c = code[r : r + 1]
        g = b[0:1]
        for k in range(1, 4):
            g = g | ((b[k : k + 1] * (c >= k)) << (8 * k))
        return g

    return _prefix_sum(_per_row(R, gap))


def _comps_bitpack(streams, sc, L):
    (words,) = streams  # u32 words as i32 bits
    R = words.shape[0]
    width = sc["width"]
    bitpos = _lanes((R, L)) * width
    wi, off = bitpos >> 5, bitpos & 31  # wi <= j: widths are <= 32 bits
    mask = jnp.where(width >= 32, -1, (1 << width) - 1)
    nxt = _shifted(words, 1)

    def gap(r):
        rows = [lax.shift_right_logical(w[r : r + 1], 8 * k) & 255
                for w in (words, nxt) for k in range(4)]
        g = _take_bytes(rows, wi[r : r + 1], lambda a, b: (0, b))
        lo = g[0:1] | (g[1:2] << 8) | (g[2:3] << 16) | (g[3:4] << 24)
        hi = g[4:5] | (g[5:6] << 8) | (g[6:7] << 16) | (g[7:8] << 24)
        o = off[r : r + 1]
        v = lax.shift_right_logical(lo, o) | jnp.where(o > 0, hi << (32 - o), 0)
        return v & mask[r : r + 1]

    return _prefix_sum(_per_row(R, gap))


_DECODERS = {
    "uncompressed": _comps_uncompressed,
    "dotvbyte": _comps_dotvbyte,
    "streamvbyte": _comps_streamvbyte,
    "bitpack": _comps_bitpack,
}


def _values(vq: str, x, f16_bits: bool, sc, cb, L) -> jnp.ndarray:
    """In-kernel dequant stage (DESIGN.md §12): the rows' stored value
    tile ``x`` [R, W] → f32 storage-unit values [R, L], the same
    arithmetic as ``values.decode_codes``. ``f16_bits``: ``x`` holds
    f16 bit patterns; ``cb`` is the flat PQ codebook as three bf16
    parts [3, K·M] (``_bf16_parts``)."""
    from repro.core import values as value_codecs

    if vq == "f16":
        return _f16_to_f32(x) if f16_bits else x.astype(jnp.float32)
    R = x.shape[0]
    j, j1 = _lanes((R, L)), _lanes((1, L))
    if vq == "u8_sq":
        codes = x
    elif vq == "u4_sq":
        codes = _take(x, j1 >> 1, lambda a, b: (a // 2, b // 2 + 1)).astype(jnp.int32)
        codes = (codes >> ((j & 1) * 4)) & 15
    else:  # pq: code → flat codebook slot, gathered exactly
        M = value_codecs.PQ_M
        slot = _take(x, j1 // M, lambda a, b: (a // M, b // M + 1)).astype(jnp.int32)
        slot = slot * M + j % M
        def row(r):
            parts = _take(cb, slot[r : r + 1])  # exact, so is their sum
            return parts[0:1] + parts[1:2] + parts[2:3]

        return _per_row(R, row)
    return value_codecs.dequant_sq(codes, sc["lo"], sc["step"])


def _query_values(uid_ref, wu_ref, n_chunks, comps_ref, acc_ref) -> None:
    """acc_ref[r] = q[g, comps_ref[r]] for the query group, f32 [R, QG,
    L]: match the ids against the group's nonzero-id union, 128 ids per
    chunk. Each chunk's ids are transposed once and matched against all
    R rows. The weights come as three bf16 parts stacked [3·QG, 128]
    (``_bf16_parts``); each union id appears once, so every entry of
    each part's 0/1 product is one part or zero, and the parts' sum is
    the exact weight."""
    R, QG = acc_ref.shape[0], acc_ref.shape[1]
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def chunk(c, carry):
        ids = jnp.broadcast_to(uid_ref[c], (8, _LANES)).T[:, :1]  # [128, 1]
        wc = wu_ref[c]
        for r in range(R):
            hit = (ids == comps_ref[r : r + 1, :]).astype(jnp.bfloat16)  # [128, L]
            m = jnp.dot(wc, hit, preferred_element_type=jnp.float32)
            acc_ref[r] += m[:QG] + m[QG : 2 * QG] + m[2 * QG :]
        return carry

    lax.fori_loop(0, n_chunks, chunk, 0)


def _score(streams, vals, live, sc, n_chunks, lane0, refs, *, codec, vq, scale, L):
    """Decode and score R rows against query group g, and write row r's
    scores to lane ``lane0 + r`` of the resident [QG, 128] output block.
    ``streams``: the rows' payload tiles [R, W]; ``vals``: their value
    tile; ``live`` [R, L]: the slots that hold a component."""
    uid_ref, wu_ref, vals_ref, cb_ref, out_ref, comps_ref, acc_ref = refs
    comps_ref[...] = _DECODERS[codec](streams, sc, L)
    _query_values(uid_ref, wu_ref, n_chunks, comps_ref, acc_ref)
    cb = None if cb_ref is None else cb_ref[...]
    v = _values(vq, vals, vals_ref.dtype == jnp.uint16, sc, cb, L)
    w = jnp.where(live, v * jnp.float32(scale), 0.0)
    lane = _lanes(out_ref.shape)
    s = out_ref[...]
    for r in range(acc_ref.shape[0]):
        s_r = jnp.sum(acc_ref[r] * w[r : r + 1], axis=1, keepdims=True)  # [QG, 1]
        s = jnp.where(lane == lane0 + r, s_r, s)
    out_ref[...] = s


def _split_refs(refs, n_s, n_vq, n_payload):
    """(scalar refs, payload refs, the refs ``_score`` takes)."""
    uid_ref, wu_ref, vals_ref = refs[n_s : n_s + 3]
    cb_ref = refs[n_s + 3] if n_vq else None
    payload = refs[n_s + 3 + n_vq : n_s + 3 + n_vq + n_payload]
    return refs[:n_s], payload, (uid_ref, wu_ref, vals_ref, cb_ref, *refs[-3:])


def _kernel(docs_ref, nch_ref, *refs, scalar_names, n_vq, n_payload, **kw):
    """One step of the gather form: the row of candidate ``docs[i]``."""
    scalar_refs, payload_refs, score_refs = _split_refs(
        refs, len(scalar_names), n_vq, n_payload
    )
    g, i = pl.program_id(0), pl.program_id(1)
    d = docs_ref[i]
    sc = {n: jnp.broadcast_to(ref[i], (1, 1)) for n, ref in zip(scalar_names, scalar_refs)}
    vals_ref = score_refs[2]
    vals = _row(vals_ref, d % vals_ref.shape[0])
    streams = [_row(p, d % p.shape[0]) for p in payload_refs]
    live = _lanes((1, kw["L"])) < sc["nnz"]
    _score(streams, vals, live, sc, nch_ref[g], i % _LANES, score_refs, **kw)


def _scan_kernel(nch_ref, *refs, n_rows, scalar_names, n_vq, n_payload, **kw):
    """One step of the range form: rows [i·R, i·R + R) of every stream,
    decoded as one [R, L] tile and scored against query group g."""
    scalar_refs, payload_refs, score_refs = _split_refs(
        refs, len(scalar_names), n_vq, n_payload
    )
    R = score_refs[-1].shape[0]
    g, i = pl.program_id(0), pl.program_id(1)
    sc = {name: _column(ref, i) for name, ref in zip(scalar_names, scalar_refs)}
    vals = _widen(score_refs[2][...])
    streams = [_widen(p[...]) for p in payload_refs]
    row = i * R + lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    # the ragged last block reads past the arrays: mask by row index
    live = (row < n_rows) & (_lanes((R, kw["L"])) < sc["nnz"])
    _score(streams, vals, live, sc, nch_ref[g], (i * R) % _LANES, score_refs, **kw)


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
    if vq == "pq":  # the flat codebook [3, K·M] in bf16 parts, resident
        cb = jnp.asarray(arrays["vq_codebook"], jnp.float32).reshape(1, -1)
        resident = [_bf16_parts(cb, axis=0).astype(jnp.float32)]
    if codec == "uncompressed":
        payload = [arrays["comps_rows"]]
    elif codec == "bitpack":
        payload = [arrays["words_rows"]]
    else:
        payload = [arrays["ctrl_rows"], arrays["data_rows"]]
    return vals, resident, payload


def _row_scalars(codec: str, vq: str, arrays):
    """Per-row scalars of every row [N+1]: the live count, the bitpack
    width, the scalar-quant clip range. The gather form takes them at
    its candidates and scalar-prefetches them; the range form reads
    them per block."""
    from repro.core import values as value_codecs

    out = {"nnz": arrays["nnz_rows"].astype(jnp.int32)}
    if codec == "bitpack":
        out["width"] = arrays["widths_rows"].astype(jnp.int32)
    if vq in ("u8_sq", "u4_sq"):
        for name, key in zip(("lo", "step"), value_codecs.sq_keys(vq)):
            out[name] = arrays[key].reshape(-1).astype(jnp.float32)
    return out


def _query_groups(Q: jnp.ndarray):
    """A query batch [nq, Vp] → per group of ``Q_GROUP`` (or fewer,
    a multiple of 8): the id union ``uid`` [G, NCH, 1, 128], the
    weights ``wu`` [G, NCH, QG, 128] and the live chunk counts [G]."""
    nq = Q.shape[0]
    QG = min(Q_GROUP, -(-nq // 8) * 8)
    Qp = tiles.pad_axis(Q.astype(jnp.float32), QG, axis=0)
    G = Qp.shape[0] // QG
    return jax.vmap(_query_union)(Qp.reshape(G, QG, -1))


def _bf16_parts(w: jnp.ndarray, axis: int) -> jnp.ndarray:
    """f32 → three bf16 parts stacked along ``axis`` whose f32 sum is
    ``w`` exactly (8 + 8 + 8 significant bits): a 0/1 matmul of each
    part then carries the weight unrounded in three bf16 passes. The
    parts are cut by masking bits, not by rounding through bf16, which
    XLA may skip (excess precision)."""

    def top(x):  # the 8 leading significant bits of x, exactly
        bits = lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return lax.bitcast_convert_type(bits, jnp.float32)

    hi = top(w)
    r = w - hi
    mid = top(r)
    parts = [hi, mid, r - mid]
    return jnp.concatenate([p.astype(jnp.bfloat16) for p in parts], axis=axis)


def _tile_spec(a: jnp.ndarray):
    """Gather spec of one row stream: the HBM row tile holding
    ``docs[i]`` (the whole array when it is shorter than a tile)."""
    tb = min(32 // jnp.dtype(a.dtype).itemsize, a.shape[0])
    return pl.BlockSpec((tb, a.shape[1]), lambda g, i, docs, *_: (docs[i] // tb, 0))


def _resident_spec(a: jnp.ndarray):
    return pl.BlockSpec(a.shape, lambda g, i, *_: (0,) * a.ndim)


def _rows_call(codec, vq, scale, interpret, L, docs, scalars, uid, wu3, nch,
               vals, resident, payload):
    """One kernel call over ``Cc`` candidates (a multiple of 128) and
    every query group → f32 [G, QG, Cc]."""
    G, QG = wu3.shape[0], wu3.shape[2] // 3
    Cc = docs.shape[0]
    names = tuple(scalars)
    in_specs = (
        [_group_spec(uid), _group_spec(wu3), _tile_spec(vals)]
        + [_resident_spec(a) for a in resident]
        + [_tile_spec(p) for p in payload]
    )
    n_operands = 5 + len(names) + len(resident) + len(payload)
    kernel = functools.partial(
        _kernel, codec=codec, vq=vq, scale=scale, L=L, scalar_names=names,
        n_vq=len(resident), n_payload=len(payload),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(names),
            grid=(G, Cc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, QG, _LANES), lambda g, i, *_: (g, 0, i // _LANES)
            ),
            scratch_shapes=_scratch(1, QG, L),
        ),
        out_shape=jax.ShapeDtypeStruct((G, QG, Cc), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            allow_input_fusion=[k == 4 + len(names) for k in range(n_operands)],
        ),
        interpret=interpret,
        name=f"rows_{codec}_{vq}",
    )(docs, nch, *[scalars[n] for n in names], uid, wu3, vals, *resident, *payload)


def _group_spec(a: jnp.ndarray):
    """Query group ``g``'s slice of a [G, ...] operand, resident while
    the grid sweeps the rows."""
    return pl.BlockSpec((None, *a.shape[1:]), lambda g, i, *_: (g,) + (0,) * (a.ndim - 1))


def _scratch(R: int, QG: int, L: int):
    return [
        pltpu.VMEM((R, L), jnp.int32),  # the rows' decoded ids
        pltpu.VMEM((R, QG, L), jnp.float32),  # the group's weights at them
    ]


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
    uid, wu, nch = _query_groups(Q)
    G, QG = wu.shape[0], wu.shape[2]
    wu3 = _bf16_parts(wu, axis=2)

    sentinel = arrays["vals_rows"].shape[0] - 1
    docs = jnp.clip(docs.astype(jnp.int32), 0, sentinel)
    Cc = min(C_CALL, -(-C // _LANES) * _LANES)
    docs = tiles.pad_axis(docs, Cc, fill=sentinel).reshape(-1, Cc)
    vals, resident, payload = _row_streams(codec, vq, arrays)
    row_scalars = _row_scalars(codec, vq, arrays)

    def call(d):
        scalars = {k: jnp.take(v, d) for k, v in row_scalars.items()}
        return _rows_call(codec, vq, scale, interpret, L, d, scalars,
                          uid, wu3, nch, vals, resident, payload)

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


@functools.partial(
    jax.jit, static_argnames=("codec", "scale", "vq", "interpret")
)
def rows_scan_batch(
    codec: str,
    Q: jnp.ndarray,  # [nq, vocab_pad] f32, vocab_pad % 128 == 0
    arrays,  # row-form dict: vals_rows/nnz_rows + vq + codec payload
    scale: float = 1.0,
    vq: str = "f16",
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused scoring of EVERY row (the N+1 rows, sentinel included)
    against a query batch → scores f32 [nq, N+1]: the range form.

    One call, grid ``(G, ceil((N+1)/R_SCAN))``: step ``i`` loads rows
    ``[i·R, i·R + R)`` of every stream with a plain block ``index_map``
    (no candidate ids, no scalar prefetch but the chunk counts), decodes
    them as one [R, L] tile and scores all R against the resident query
    group. The nnz and the per-row codec scalars arrive as [8, R] VMEM
    blocks. The ragged last block reads past the arrays; its rows are
    masked by row index and score exactly 0."""
    from repro.core import values as value_codecs

    nq = Q.shape[0]
    L = arrays["vals_rows"].shape[1] * value_codecs.code_factor(vq)
    uid, wu, nch = _query_groups(Q)
    G, QG = wu.shape[0], wu.shape[2]
    wu3 = _bf16_parts(wu, axis=2)  # [G, NCH, 3·QG, 128]
    vals, resident, payload = _row_streams(codec, vq, arrays)
    n_rows = vals.shape[0]
    R = R_SCAN
    if n_rows < R:  # a block may not be taller than its array
        vals, *payload = (tiles.pad_axis(a, R, axis=0) for a in (vals, *payload))
    nb = -(-n_rows // R)
    # [8·ceil(nb/8), R]: the scalars of block i are row i of it
    scalars = {k: tiles.pad_axis(v, 8 * R).reshape(-1, R)
               for k, v in _row_scalars(codec, vq, arrays).items()}
    names = tuple(scalars)
    rows = lambda a: pl.BlockSpec((R, a.shape[1]), lambda g, i, *_: (i, 0))
    in_specs = (
        [pl.BlockSpec((8, R), lambda g, i, *_: (i // 8, 0)) for _ in names]
        + [_group_spec(uid), _group_spec(wu3), rows(vals)]
        + [_resident_spec(a) for a in resident]
        + [rows(p) for p in payload]
    )
    n_operands = 1 + len(names) + 3 + len(resident) + len(payload)
    kernel = functools.partial(
        _scan_kernel, codec=codec, vq=vq, scale=scale, L=L, n_rows=n_rows,
        scalar_names=names, n_vq=len(resident), n_payload=len(payload),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, QG, _LANES), lambda g, i, *_: (g, 0, i * R // _LANES)
            ),
            scratch_shapes=_scratch(R, QG, L),
        ),
        out_shape=jax.ShapeDtypeStruct((G, QG, -(-nb * R // _LANES) * _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
            allow_input_fusion=[k == 3 + len(names) for k in range(n_operands)],
        ),
        interpret=interpret,
        name=f"rows_scan_{codec}_{vq}",
    )(nch, *[scalars[n] for n in names], uid, wu3, vals, *resident, *payload)
    return out.reshape(G * QG, -1)[:nq, :n_rows]


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
