"""Batched inner-product scoring over packed forward-index blocks.

Pure-jnp reference implementations of the decode+dot paths. These are
(a) the scorers used by the Seismic query processor on CPU, and (b) the
oracles the Pallas kernels in ``repro/kernels`` are validated against.

All functions are jit-friendly: they take plain arrays (from
``PackedBlocks``) plus static ints. The decode semantics mirror
DESIGN.md §3: gaps → prefix sum → per-fragment rebase via out-of-band
absolutes → gather query → FMA → segment reduction.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .forward_index import PackedBlocks

__all__ = [
    "dequantise_values",
    "decode_gaps_dotvbyte",
    "decode_gaps_streamvbyte",
    "decode_gaps_bitpack",
    "decode_block_gaps",
    "components_from_gaps",
    "block_products",
    "combine_block_scores",
    "block_slot_scores",
    "score_packed",
    "score_packed_batch",
    "decode_doc_rows",
    "score_candidate_rows",
    "score_candidate_rows_batch",
]


def dequantise_values(vals: jnp.ndarray, scale: float) -> jnp.ndarray:
    return vals.astype(jnp.float32) * jnp.float32(scale)


def decode_gaps_dotvbyte(ctrl: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """DotVByte decode, vectorised (DESIGN.md §3).

    ctrl u8 [B, T/8], data u8 [B, DP] (DP ≥ T + popcount + 1).
    Returns gaps i32 [B, T].

    The x86 byte-scroll is replaced by an exclusive prefix sum of the
    control bits; the ``_mm_shuffle_epi8`` by two byte gathers.
    """
    B, nc = ctrl.shape
    bits = (ctrl[:, :, None].astype(jnp.int32) >> jnp.arange(8, dtype=jnp.int32)) & 1
    bits = bits.reshape(B, nc * 8)  # LSB-first within each control byte
    lens = bits + 1
    ends = jnp.cumsum(lens, axis=1)
    starts = ends - lens
    d = data.astype(jnp.int32)
    lo = jnp.take_along_axis(d, starts, axis=1)
    hi = jnp.take_along_axis(d, starts + 1, axis=1) * bits
    return lo + (hi << 8)


def decode_gaps_streamvbyte(ctrl: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """StreamVByte decode, vectorised — same shape contract as the
    DotVByte decoder (DESIGN.md §3).

    ctrl u8 [B, T/4] (2-bit codes, value i of a quad in bits 2i..2i+1),
    data u8 [B, DP] (DP ≥ total data bytes + 3 over-read).
    Returns gaps i32 [B, T].

    The x86 ``_mm_shuffle_epi8`` table decode becomes: 2-bit controls →
    prefix-sum byte offsets → up-to-4-byte gathers masked by the code.
    """
    B, nc = ctrl.shape
    codes = (ctrl[:, :, None].astype(jnp.int32) >> (2 * jnp.arange(4, dtype=jnp.int32))) & 0x3
    codes = codes.reshape(B, nc * 4)  # quad-local value i ↔ bits 2i..2i+1
    lens = codes + 1
    ends = jnp.cumsum(lens, axis=1)
    starts = ends - lens
    d = data.astype(jnp.int32)
    out = jnp.take_along_axis(d, starts, axis=1)
    out = out | (jnp.take_along_axis(d, starts + 1, axis=1) * (codes >= 1)) << 8
    out = out | (jnp.take_along_axis(d, starts + 2, axis=1) * (codes >= 2)) << 16
    out = out | (jnp.take_along_axis(d, starts + 3, axis=1) * (codes >= 3)) << 24
    return out


def decode_gaps_bitpack(
    words: jnp.ndarray, widths: jnp.ndarray, block_size: int
) -> jnp.ndarray:
    """Fixed-width unpack: pure shift+mask, no data-dependent offsets.

    words u32 [B, W], widths i32 [B] → gaps i32 [B, T].
    """
    B = words.shape[0]
    T = block_size
    w32 = jnp.concatenate(
        [words.astype(jnp.uint32), jnp.zeros((B, 1), dtype=jnp.uint32)], axis=1
    )
    width = widths[:, None].astype(jnp.uint32)  # [B,1]
    bitpos = jnp.arange(T, dtype=jnp.uint32)[None, :] * width  # [B,T]
    wi = (bitpos // 32).astype(jnp.int32)
    off = bitpos % 32
    lo = jnp.take_along_axis(w32, wi, axis=1) >> off
    hi_shift = jnp.where(off > 0, jnp.uint32(32) - off, jnp.uint32(0))
    hi_raw = jnp.take_along_axis(w32, wi + 1, axis=1)
    hi = jnp.where(off > 0, hi_raw << hi_shift, jnp.uint32(0))
    mask = (jnp.uint32(1) << width) - jnp.uint32(1)
    return ((lo | hi) & mask).astype(jnp.int32)


def decode_block_gaps(codec: str, arrays, block_size: int) -> jnp.ndarray:
    """Codec-dispatching gap decode over a dict of layout arrays.

    ``codec`` must be static under jit (it selects the traced graph).
    The arrays carry the fields the layout codec produced — ctrl/data
    (dotvbyte, streamvbyte) or words/widths (bitpack)."""
    if codec == "dotvbyte":
        # ctrl streams are lane-padded at pack time (layout.LANE_MULTIPLE);
        # slice tight so alignment costs bytes, never decode work
        return decode_gaps_dotvbyte(arrays["ctrl"][:, : block_size // 8], arrays["data"])
    if codec == "streamvbyte":
        return decode_gaps_streamvbyte(arrays["ctrl"][:, : block_size // 4], arrays["data"])
    if codec == "bitpack":
        return decode_gaps_bitpack(arrays["words"], arrays["widths"], block_size)
    raise ValueError(f"no device decoder for codec {codec!r}")


def components_from_gaps(
    gaps: jnp.ndarray,
    seg: jnp.ndarray,
    start_pos: jnp.ndarray,
    start_abs: jnp.ndarray,
) -> jnp.ndarray:
    """Segmented prefix-sum rebase: gaps → absolute component ids.

    comp[i] = start_abs[seg[i]] + t[i] - t[start_pos[seg[i]]] with
    t = inclusive cumsum of gaps; padding (seg = -1) maps to component 0
    (value 0 ⇒ contribution 0, the DotVByte alignment trick).
    """
    seg = seg.astype(jnp.int32)  # i8 in the slim metadata layout
    D = start_pos.shape[1]
    t = jnp.cumsum(gaps, axis=1)
    tp = jnp.take_along_axis(t, start_pos, axis=1)  # [B,D]
    segc = jnp.clip(seg, 0, D - 1)
    base = jnp.take_along_axis(start_abs, segc, axis=1)
    tseg = jnp.take_along_axis(tp, segc, axis=1)
    return jnp.where(seg >= 0, base + t - tseg, 0)


def block_products(
    q: jnp.ndarray, comps: jnp.ndarray, vals_f: jnp.ndarray, seg: jnp.ndarray
) -> jnp.ndarray:
    """q-gather · values, zeroed on padding. [B,T] f32."""
    qv = jnp.take(q, comps, axis=0)
    return qv * vals_f * (seg >= 0)


def combine_block_scores(
    prod_or_scores: jnp.ndarray,
    seg: jnp.ndarray,
    doc_ids: jnp.ndarray,
    n_docs: int,
) -> jnp.ndarray:
    """Reduce per-element products to per-document scores.

    prod [B,T] + seg [B,T] + doc_ids [B,D] → scores [n_docs] via a
    single global segment-sum (the Pallas kernels instead do a per-block
    one-hot MXU matmul; results identical).
    """
    seg = seg.astype(jnp.int32)
    D = doc_ids.shape[1]
    segc = jnp.clip(seg, 0, D - 1)
    gdoc = jnp.take_along_axis(doc_ids, segc, axis=1)  # [B,T]
    gdoc = jnp.where(seg >= 0, gdoc, n_docs)  # padding → overflow bucket
    flat = jax.ops.segment_sum(
        prod_or_scores.reshape(-1), gdoc.reshape(-1), num_segments=n_docs + 1
    )
    return flat[:n_docs]


def scatter_block_scores(
    block_scores: jnp.ndarray, doc_ids: jnp.ndarray, n_docs: int
) -> jnp.ndarray:
    """[B,D] per-block scores + [B,D] doc ids → [n_docs] global scores."""
    ids = jnp.where(doc_ids >= 0, doc_ids, n_docs)
    out = jax.ops.segment_sum(
        block_scores.reshape(-1), ids.reshape(-1), num_segments=n_docs + 1
    )
    return out[:n_docs]


def block_slot_scores(prod: jnp.ndarray, start_pos: jnp.ndarray) -> jnp.ndarray:
    """Per-element products → per-slot (fragment) scores, [.., B, D].

    The tiled kernels' reduction (DESIGN.md §3): inside a block the pack
    loop assigns slots in position order, so each slot's fragment is one
    CONTIGUOUS run ``[start_pos[d], start_pos[d+1])`` (the last used slot
    runs to T, where only zero padding follows).  A slot's score is then
    a difference of the exclusive prefix sum of the products — B·D
    reduced values instead of the B·T-element global segment sum, the
    ~8× smaller scatter the compiled scan wins on.  Slot usage is
    derivable from start_pos alone (slot 0 always starts at 0; every
    later used slot starts strictly after it), so unused slots are
    zeroed without needing doc_ids.  Works on [B,T] and [nq,B,T]
    product arrays (start_pos broadcasts)."""
    T = prod.shape[-1]
    lead = prod.shape[:-2]
    cz = jnp.concatenate(
        [jnp.zeros((*lead, prod.shape[-2], 1), prod.dtype), jnp.cumsum(prod, axis=-1)],
        axis=-1,
    )
    nxt = jnp.concatenate(
        [start_pos[..., 1:], jnp.zeros((*start_pos.shape[:-1], 1), start_pos.dtype)],
        axis=-1,
    )
    ends = jnp.where(nxt > start_pos, nxt, T)
    used = jnp.concatenate(
        [jnp.ones_like(start_pos[..., :1], jnp.bool_), start_pos[..., 1:] > 0],
        axis=-1,
    )
    ends = jnp.broadcast_to(ends, (*lead, *ends.shape[-2:]))
    starts = jnp.broadcast_to(start_pos, ends.shape)
    scores = jnp.take_along_axis(cz, ends, axis=-1) - jnp.take_along_axis(
        cz, starts, axis=-1
    )
    return scores * used.astype(scores.dtype)


@partial(
    jax.jit, static_argnames=("codec", "block_size", "n_docs", "scale", "vq")
)
def _score_packed(
    q,
    seg,
    start_pos,
    start_abs,
    vals,
    doc_ids,
    ctrl,
    data,
    words,
    widths,
    comps,
    vq_lo,
    vq_scale,
    vq_codebook,
    *,
    codec: str,
    block_size: int,
    n_docs: int,
    scale: float,
    vq: str = "f16",
):
    if codec == "uncompressed":  # decode-free layout
        c = comps
    else:
        gaps = decode_block_gaps(
            codec, {"ctrl": ctrl, "data": data, "words": words, "widths": widths},
            block_size,
        )
        c = components_from_gaps(gaps, seg, start_pos, start_abs)
    if vq == "f16":
        vals_f = dequantise_values(vals, scale)
    else:  # quantized values: codes → storage-unit f32 → value scale
        from . import values as value_codecs

        cb = vq_codebook.reshape(-1) if vq == "pq" else None
        vals_f = value_codecs.decode_codes(
            vq, vals, vq_lo, vq_scale, cb
        ) * jnp.float32(scale)
    prod = block_products(q, c, vals_f, seg)
    return combine_block_scores(prod, seg, doc_ids, n_docs)


def _packed_device_args(packed: PackedBlocks):
    """The (arrays, static-kwargs) pair ``_score_packed`` consumes."""
    zero_u8 = np.zeros((packed.n_blocks, 1), dtype=np.uint8)
    zero_u32 = np.zeros((packed.n_blocks, 1), dtype=np.uint32)
    zero_i32 = np.zeros((packed.n_blocks,), dtype=np.int32)
    zero_f32 = np.zeros((packed.n_blocks, 1), dtype=np.float32)
    arrays = (
        jnp.asarray(packed.seg),
        jnp.asarray(packed.start_pos),
        jnp.asarray(packed.start_abs),
        jnp.asarray(packed.vals),
        jnp.asarray(packed.doc_ids),
        jnp.asarray(packed.ctrl if packed.ctrl is not None else zero_u8),
        jnp.asarray(packed.data if packed.data is not None else zero_u8),
        jnp.asarray(packed.words if packed.words is not None else zero_u32),
        jnp.asarray(packed.widths if packed.widths is not None else zero_i32),
        jnp.asarray(
            packed.comps
            if packed.comps is not None
            else np.zeros(packed.seg.shape, dtype=np.int32)
        ),
        jnp.asarray(packed.vq_lo if packed.vq_lo is not None else zero_f32),
        jnp.asarray(
            packed.vq_scale if packed.vq_scale is not None else zero_f32
        ),
        jnp.asarray(
            packed.vq_codebook
            if packed.vq_codebook is not None
            else np.zeros((1,), dtype=np.float32)
        ),
    )
    static = dict(
        codec=packed.codec,
        block_size=packed.block_size,
        n_docs=packed.n_docs,
        scale=float(packed.value_format.scale),
        vq=getattr(packed, "vq", "f16"),
    )
    return arrays, static


def score_packed(q_dense, packed: PackedBlocks) -> jnp.ndarray:
    """Scores of every document for one dense query. [n_docs] f32."""
    arrays, static = _packed_device_args(packed)
    return _score_packed(jnp.asarray(q_dense, dtype=jnp.float32), *arrays, **static)


def score_packed_batch(Q, packed: PackedBlocks) -> jnp.ndarray:
    """Scores for a batch of dense queries. [n_queries, n_docs].

    One ``vmap`` over the jit'd scorer — a single dispatch per batch
    (the decode is still re-traced per query inside the batched graph;
    the *fused* decode-once path is the batched kernel in
    ``repro.kernels``)."""
    arrays, static = _packed_device_args(packed)
    return jax.vmap(lambda q: _score_packed(q, *arrays, **static))(
        jnp.asarray(Q, dtype=jnp.float32)
    )


def make_doc_aligned_scan(
    mesh, axes: tuple[str, ...], docs_local: int, scale: float,
    codec: str = "dotvbyte",
):
    """§Perf opt1: doc-aligned sharded scan (EXPERIMENTS.md).

    Each device owns a contiguous range of ``docs_local`` documents AND
    exactly the packed blocks containing them (arrays carry an explicit
    leading shard dim sharded over ``axes``; doc_ids are range-LOCAL),
    so the score scatter is device-local and the scan path carries ZERO
    collectives. Queries replicate. Any layout codec works — the arrays
    come from ``layout.pack_blocks_sharded(codec=…)``.
    fn(arrays, Q [nq, dim_pad]) → [nq, n_shards·docs_local]."""
    from jax.sharding import PartitionSpec as P

    def local_scan(arrays, Q):
        arrays = jax.tree.map(lambda a: a[0], arrays)  # drop shard dim
        if codec == "uncompressed":
            comps = arrays["comps"]
        else:
            gaps = decode_block_gaps(codec, arrays, arrays["seg"].shape[-1])
            comps = components_from_gaps(
                gaps, arrays["seg"], arrays["start_pos"], arrays["start_abs"]
            )
        vals_f = dequantise_values(arrays["vals"], scale)

        def one(q):
            prod = block_products(q, comps, vals_f, arrays["seg"])
            return combine_block_scores(prod, arrays["seg"], arrays["doc_ids"], docs_local)

        return jax.vmap(one)(Q)

    return jax.shard_map(
        local_scan,
        mesh=mesh,
        in_specs=(P(axes), P(None, None)),
        out_specs=P(None, axes),
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# per-document row layout (serve-engine rescoring path)
# ---------------------------------------------------------------------------
# Candidate re-scoring in the batched serve engines gathers a fixed-
# capacity row per candidate document (built by ``layout.pack_rows``).
# Rows are either raw components (uncompressed) or a codec stream —
# (ctrl, data) for DotVByte/StreamVByte, (words, widths) for bitpack —
# decoded on the fly; the decode is identical to the block path but row
# gaps carry their absolute first component, so a plain cumsum rebuilds
# the ids.

#: row-form fields every codec shares (vals/nnz); everything else in a
#: ``pack_rows`` output is codec payload (``<stream>_rows``)
_ROW_COMMON_KEYS = ("vals_rows", "nnz_rows", "comps_rows")


def decode_doc_rows(codec: str, payload, l_max: int | None = None) -> jnp.ndarray:
    """Row-payload streams → absolute comps i32 [N, L].

    Dispatches through the layout registry (``layout.get_layout``), so
    ANY codec registered in core/layout.py decodes rows with zero edits
    here: ``payload`` maps the codec's ``<stream>_rows`` fields (as
    emitted by ``layout.pack_rows`` — ctrl/data for the byte codecs,
    words/widths for bitpack) to the gathered arrays; ``l_max`` is the
    row capacity (needed by fixed-width codecs). Row gaps are encoded
    with the first gap absolute (per-doc alignment), so a plain cumsum
    rebuilds the ids; padding gaps are 0 with value 0, the usual
    neutral trick.

    Back-compat: the PR-2 positional form ``decode_doc_rows(codec,
    ctrl_rows, data_rows)`` still works (DeprecationWarning)."""
    if not hasattr(payload, "items"):  # legacy (codec, ctrl, data) form
        import warnings

        warnings.warn(
            "decode_doc_rows(codec, ctrl_rows, data_rows) is deprecated; "
            "pass a payload mapping of <stream>_rows arrays",
            DeprecationWarning,
            stacklevel=2,
        )
        payload, l_max = {"ctrl_rows": payload, "data_rows": l_max}, None
    from .layout import get_layout

    lc = get_layout(codec)
    if lc.decode_free:
        raise ValueError(
            f"codec {codec!r} is decode-free; rows store absolute components"
        )
    streams = {
        (k[: -len("_rows")] if k.endswith("_rows") else k): v
        for k, v in payload.items()
    }
    gaps = lc.decode(streams, 0 if l_max is None else int(l_max))
    return jnp.cumsum(gaps, axis=1)


def decode_doc_rows_dotvbyte(ctrl_rows: jnp.ndarray, data_rows: jnp.ndarray) -> jnp.ndarray:
    return decode_doc_rows("dotvbyte", {"ctrl_rows": ctrl_rows, "data_rows": data_rows})


def _check_rows_backend(backend: str) -> None:
    from repro.kernels.modes import SCORING_BACKENDS

    if backend not in SCORING_BACKENDS:
        raise ValueError(
            f"unknown scoring backend {backend!r}; have {list(SCORING_BACKENDS)}"
        )


def _no_rows_kernel(codec: str, backend: str) -> ValueError:
    return ValueError(
        f"backend={backend!r} asks for a fused rows kernel, but codec "
        f"{codec!r} has none registered; serve it with backend='jnp'"
    )


def _gather_decode_rows(codec: str, arrays, docs: jnp.ndarray):
    """Gather + decode the packed rows of ``docs`` → (comps, vals,
    nnz) — the ONE row-materialisation both the single-query and the
    batched jnp rescoring paths share (so a codec/layout change lands
    in exactly one place).

    The VALUE codec is inferred from the payload keys
    (``values.infer_rows_vq``, DESIGN.md §12): quantized rows gather
    their u8 codes + per-row clip columns (or the shared codebook) and
    dequantize through the same ``values.decode_codes`` helpers the
    fused kernels run, so every execution mode computes identical
    value bits.  Decoded values are storage-unit f32; the downstream
    ``value_scale`` FMA applies unchanged."""
    from . import values as value_codecs
    from .layout import get_layout

    vq = value_codecs.infer_rows_vq(arrays)
    vals = jnp.take(arrays["vals_rows"], docs, axis=0)
    nnz = jnp.take(arrays["nnz_rows"], docs, axis=0)
    if vq != "f16":
        lo = step = cb = None
        if vq == "pq":
            cb = jnp.asarray(arrays["vq_codebook"], jnp.float32).reshape(-1)
        else:
            lo_key, sc_key = value_codecs.sq_keys(vq)
            lo = jnp.take(arrays[lo_key], docs, axis=0)
            step = jnp.take(arrays[sc_key], docs, axis=0)
        vals = value_codecs.decode_codes(vq, vals, lo, step, cb)
    if get_layout(codec).decode_free:  # absolute components stored raw
        comps = jnp.take(arrays["comps_rows"], docs, axis=0)
    else:
        payload = {
            k: jnp.take(arrays[k], docs, axis=0)
            for k in arrays
            if k.endswith("_rows")
            and k not in _ROW_COMMON_KEYS
            and not k.startswith("vq_")
        }
        comps = decode_doc_rows(codec, payload, l_max=vals.shape[-1])
    return comps, vals, nnz


def score_candidate_rows(
    codec: str,
    arrays,
    docs: jnp.ndarray,
    q: jnp.ndarray,
    scale: float,
    backend: str = "jnp",
) -> jnp.ndarray:
    """Gather the packed rows of ``docs`` and score them exactly.

    The ONE candidate-rescoring path shared by every serve engine
    (DESIGN.md §7): ``arrays`` holds the row form produced by
    ``layout.pack_rows`` under any registered codec — possibly
    alongside engine-specific fields, which are ignored. Sentinel doc
    ids gather the all-zero row and score 0; mask them afterwards.

    ``backend`` selects the execution path (DESIGN.md §3, §7): ``"jnp"``
    is the take→decode→dot reference below; ``"pallas"`` dispatches to
    the codec's fused rows kernel from ``repro.kernels.registry``
    (scalar-prefetch HBM→VMEM row gather, decode and dot in VMEM —
    decoded components never touch HBM) in its default — compiled —
    mode, while ``"pallas_interpret"`` / ``"pallas_compiled"`` pin the
    kernel ``mode`` explicitly (``repro.kernels.modes``).  A kernel
    backend on a codec with no registered rows kernel raises.  All
    paths return identical scores (asserted by the parity suite and
    ``make kernel-parity``)."""
    _check_rows_backend(backend)
    if backend != "jnp":
        from repro.kernels.modes import backend_mode
        from repro.kernels.registry import rows_scorer

        fn = rows_scorer(codec)
        if fn is None:
            raise _no_rows_kernel(codec, backend)
        return fn(arrays, docs, q, scale, backend_mode(backend))
    comps, vals, nnz = _gather_decode_rows(codec, arrays, docs)
    return score_doc_rows(q, comps, vals, nnz, scale)


def score_candidate_rows_batch(
    codec: str,
    arrays,
    docs: jnp.ndarray,
    Q: jnp.ndarray,
    scale: float,
    backend: str = "jnp",
) -> jnp.ndarray:
    """Rescore ONE candidate set against a whole query batch → [nq, C].

    The decode-once/score-many form of ``score_candidate_rows``
    (DESIGN.md §8): when every query in a batch shares the candidate
    set (the flat engine's full scan; shard-replicated rescoring), the
    candidate rows are gathered and decoded once and dotted against
    every resident query. ``backend="pallas"`` dispatches to the codec's
    ``rows_scores_batch`` kernel registry entry, which keeps each
    decoded row in VMEM across the whole query batch; the jnp path
    hoists the decode out of a ``lax.map`` over ``score_doc_rows``, so
    per-query scores are bitwise those of the single-query path."""
    _check_rows_backend(backend)
    if backend != "jnp":
        from repro.kernels.modes import backend_mode
        from repro.kernels.registry import rows_batch_scorer

        fn = rows_batch_scorer(codec)
        if fn is None:
            raise _no_rows_kernel(codec, backend)
        return fn(arrays, docs, Q, scale, backend_mode(backend))
    comps, vals, nnz = _gather_decode_rows(codec, arrays, docs)
    # decoded once; the q-gather + FMA then run one query at a time: a
    # query axis on the [C, L] gather would sit in the minor dimension
    # of a TPU layout, padded to 128 lanes (26 GB at 200k rows)
    return jax.lax.map(lambda q: score_doc_rows(q, comps, vals, nnz, scale), Q)


def score_doc_rows(
    q: jnp.ndarray,
    comps_rows: jnp.ndarray,  # i32 [N, L]
    vals_rows: jnp.ndarray,  # [N, L] storage dtype
    nnz: jnp.ndarray,  # i32 [N]
    scale: float,
) -> jnp.ndarray:
    """Exact ⟨q, doc⟩ for N gathered candidate rows → [N] f32."""
    L = comps_rows.shape[1]
    mask = jnp.arange(L)[None, :] < nnz[:, None]
    qv = jnp.take(q, comps_rows, axis=0)
    vals = dequantise_values(vals_rows, scale)
    return (qv * vals * mask).sum(axis=1)
