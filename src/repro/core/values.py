"""Value codecs (DESIGN.md §12) — the quantization axis ORTHOGONAL to
the id codec in ``core/layout.py``.

Every layout codec compresses the doc-id gap stream; the value stream
rode as raw storage dtype (f16/u8) until now.  A value codec ``vq``
replaces the value stream with quantized codes *in the same arrays*:

======== ===================== =======================================
vq       codes per stored byte decode
======== ===================== =======================================
f16      —                     pass-through (today's layout, bit-exact)
u8_sq    1                     per-row clip range: lo + code·step
u4_sq    2 (nibble-packed)     per-row clip range, 4-bit codes
pq       ``PQ_M``              codebook gather: sub-vectors of PQ_M
                               consecutive values → one u8 code
======== ===================== =======================================

The codes ride **inside** ``vals_rows`` / ``PackedBlocks.vals`` itself
(dtype u8, width divided by the pack factor), and the per-row clip
ranges / the codebook ride as ordinary payload arrays —
``vq_lo_rows``/``vq_scale_rows`` (u8), ``vq_lo4_rows``/
``vq_scale4_rows`` (u4) f32 ``[N+1, 1]`` columns, ``vq_codebook`` f32
``[PQ_K, PQ_M]`` — so ``pad_stack``, shard stacking, ``mmap_npz`` and
the artifact manifest carry them with zero edits.  The vq of a row
array dict is INFERRED from which of these keys are present
(:func:`infer_rows_vq`), which is what lets every engine and the
sharded/segment/mutable wrappers serve quantized values with zero
per-engine edits.

Parity contract: the scalar quantizers fit each row's clip range on
that row's OWN live values, so a document's code bytes depend only on
its own values — a row packed inside a shard, a delta segment or a
monolithic build is byte-identical (the same invariant the per-doc gap
alignment gives the id streams).  PQ codebooks are fit per *build*
(deterministic seeded k-means), so PQ bytes are reproducible for a
given build input but NOT byte-stable across different shardings —
documented in DESIGN.md §12.

The decode helpers below are pure elementwise jnp (FMA / nibble
unpack / flat codebook gather) shared VERBATIM by the jnp reference
path, the XLA lowering and the in-kernel Pallas dequant stage — one
implementation, so the three execution modes stay byte-identical to
each other at every vq.  Decoded values are in STORAGE units: the
downstream ``value_scale`` FMA applies unchanged.
"""

from __future__ import annotations

from typing import Mapping

import numpy as np

__all__ = [
    "VALUE_CODECS",
    "PQ_K",
    "PQ_M",
    "code_factor",
    "check_vq",
    "encode_rows_values",
    "encode_block_values",
    "fit_pq_codebook",
    "unpack_nibbles",
    "dequant_sq",
    "dequant_pq",
    "decode_codes",
    "infer_rows_vq",
    "value_payload_bytes",
]

#: registered value codecs (RetrieverConfig.vq / pack-time knob)
VALUE_CODECS = ("f16", "u8_sq", "u4_sq", "pq")

#: PQ codebook entries (codes are u8) and sub-vector width
PQ_K = 256
PQ_M = 2

#: per-row clip-range payload keys by vq (f32 [N+1, 1] columns)
_SQ_KEYS = {
    "u8_sq": ("vq_lo_rows", "vq_scale_rows"),
    "u4_sq": ("vq_lo4_rows", "vq_scale4_rows"),
}

_MAXCODE = {"u8_sq": 255, "u4_sq": 15}


def sq_keys(vq: str) -> tuple[str, str]:
    """The (lo, scale) payload key names of a scalar-quant vq."""
    return _SQ_KEYS[vq]


def check_vq(vq: str) -> str:
    if vq not in VALUE_CODECS:
        raise ValueError(f"unknown value codec {vq!r}; have {list(VALUE_CODECS)}")
    return vq


def code_factor(vq: str) -> int:
    """Logical values per stored byte column: the value array's stored
    width is ``logical_width // code_factor(vq)``."""
    check_vq(vq)
    if vq == "u4_sq":
        return 2
    if vq == "pq":
        return PQ_M
    return 1


# ---------------------------------------------------------------------------
# pack-time encoders (numpy, host side)
# ---------------------------------------------------------------------------


def _fit_clip(
    vals: np.ndarray, live: np.ndarray, maxcode: int,
    clip: tuple[float, float] | None,
):
    """Per-row clip range on each row's OWN live values → (lo, step),
    f32 [R, 1].  ``clip=(lo, hi)`` overrides with one global range
    (the QAT export path) — still STORED per row, so the per-document
    byte-parity invariant is unchanged."""
    v = vals.astype(np.float32)
    if clip is not None:
        lo = np.full((v.shape[0], 1), np.float32(clip[0]))
        hi = np.full((v.shape[0], 1), np.float32(clip[1]))
    else:
        big, small = np.float32(np.finfo(np.float32).max), np.float32(
            np.finfo(np.float32).min
        )
        lo = np.where(live, v, big).min(axis=1, keepdims=True)
        hi = np.where(live, v, small).max(axis=1, keepdims=True)
        none_live = ~live.any(axis=1, keepdims=True)
        lo = np.where(none_live, 0.0, lo).astype(np.float32)
        hi = np.where(none_live, 0.0, hi).astype(np.float32)
    step = np.where(hi > lo, (hi - lo) / np.float32(maxcode), 1.0).astype(
        np.float32
    )
    return lo.astype(np.float32), step


def _sq_codes(
    vals: np.ndarray, live: np.ndarray, maxcode: int,
    clip: tuple[float, float] | None,
):
    lo, step = _fit_clip(vals, live, maxcode, clip)
    v = vals.astype(np.float32)
    codes = np.clip(np.rint((v - lo) / step), 0, maxcode).astype(np.uint8)
    return np.where(live, codes, 0).astype(np.uint8), lo, step


def pack_nibbles(codes: np.ndarray) -> np.ndarray:
    """4-bit codes [..., 2W] → packed bytes [..., W]: element ``2i`` in
    the low nibble, ``2i+1`` in the high nibble of byte ``i``."""
    if codes.shape[-1] % 2:
        raise ValueError("nibble packing needs an even trailing dim")
    pairs = codes.reshape(*codes.shape[:-1], -1, 2)
    return (pairs[..., 0] | (pairs[..., 1] << 4)).astype(np.uint8)


def fit_pq_codebook(
    subvecs: np.ndarray, seed: int = 0, iters: int = 8, sample: int = 4096
) -> np.ndarray:
    """Deterministic seeded Lloyd k-means over [S, PQ_M] sub-vectors →
    f32 codebook [PQ_K, PQ_M].  Fixed iteration count, deterministic
    subsample, argmin ties to the lowest index — the same inputs always
    produce the same codebook bytes."""
    sv = np.asarray(subvecs, np.float32).reshape(-1, PQ_M)
    if len(sv) == 0:
        return np.zeros((PQ_K, PQ_M), np.float32)
    rng = np.random.default_rng(seed)
    if len(sv) > sample:
        sv = sv[rng.choice(len(sv), size=sample, replace=False)]
    # init: evenly spaced points of the norm-sorted sample (deterministic
    # spread; duplicates are fine — empty clusters keep their centroid)
    order = np.argsort(np.einsum("ij,ij->i", sv, sv), kind="stable")
    idx = np.linspace(0, len(sv) - 1, PQ_K).astype(np.int64)
    cb = sv[order[idx]].copy()
    for _ in range(iters):
        d2 = ((sv[:, None, :] - cb[None, :, :]) ** 2).sum(-1)  # [S, K]
        assign = np.argmin(d2, axis=1)
        for k in range(PQ_K):
            members = sv[assign == k]
            if len(members):
                cb[k] = members.mean(axis=0)
    return cb.astype(np.float32)


def _pq_codes(vals: np.ndarray, codebook: np.ndarray) -> np.ndarray:
    """Nearest-centroid assignment of every PQ_M sub-vector → u8 codes
    [..., W/PQ_M] (ties to the lowest index, matching the fit)."""
    v = vals.astype(np.float32)
    sv = v.reshape(*v.shape[:-1], -1, PQ_M)
    d2 = ((sv[..., None, :] - codebook[None, :, :]) ** 2).sum(-1)
    return np.argmin(d2, axis=-1).astype(np.uint8)


def encode_rows_values(
    vals_rows: np.ndarray,  # [N+1, cap] storage dtype (row N = sentinel)
    nnz_rows: np.ndarray,  # i32 [N+1]
    vq: str,
    clip: tuple[float, float] | None = None,
    pq_seed: int = 0,
):
    """Quantize a packed row value matrix → (codes u8 [N+1, cap/factor],
    payload extras dict).  ``cap`` must be a multiple of
    ``LANE_MULTIPLE * code_factor(vq)`` (``layout.pack_rows`` rounds it)
    so stored code widths stay lane-aligned."""
    check_vq(vq)
    if vq == "f16":
        return vals_rows, {}
    cap = vals_rows.shape[1]
    if cap % code_factor(vq):
        raise ValueError(
            f"row capacity {cap} not a multiple of the {vq} pack factor "
            f"{code_factor(vq)}"
        )
    live = np.arange(cap)[None, :] < np.asarray(nnz_rows)[:, None]
    if vq in _SQ_KEYS:
        codes, lo, step = _sq_codes(vals_rows, live, _MAXCODE[vq], clip)
        if vq == "u4_sq":
            codes = pack_nibbles(codes)
        lo_key, sc_key = _SQ_KEYS[vq]
        return codes, {lo_key: lo, sc_key: step}
    # pq: fit on live sub-vectors only (a sub-vector is live when its
    # first element is — trailing dead halves carry the padded zero the
    # row matrix already holds, masked by nnz at score time anyway)
    v = np.where(live, vals_rows.astype(np.float32), 0.0)
    sub_live = live[:, ::PQ_M]
    cb = fit_pq_codebook(
        v.reshape(-1, PQ_M)[sub_live.reshape(-1)], seed=pq_seed
    )
    codes = _pq_codes(v, cb)
    return np.where(sub_live, codes, 0).astype(np.uint8), {"vq_codebook": cb}


def encode_block_values(
    vals: np.ndarray,  # [B, T] storage dtype
    seg: np.ndarray,  # [B, T], -1 = padding
    vq: str,
    clip: tuple[float, float] | None = None,
    pq_seed: int = 0,
):
    """Block-form mirror of :func:`encode_rows_values`: per-BLOCK clip
    ranges (``vq_lo``/``vq_scale`` f32 [B, 1]) or a shared codebook.
    Live mask is ``seg >= 0``."""
    check_vq(vq)
    if vq == "f16":
        return vals, {}
    live = np.asarray(seg) >= 0
    if vq in _SQ_KEYS:
        codes, lo, step = _sq_codes(vals, live, _MAXCODE[vq], clip)
        if vq == "u4_sq":
            codes = pack_nibbles(codes)
        return codes, {"vq_lo": lo, "vq_scale": step}
    v = np.where(live, vals.astype(np.float32), 0.0)
    sub_live = live[:, ::PQ_M]
    cb = fit_pq_codebook(
        v.reshape(-1, PQ_M)[sub_live.reshape(-1)], seed=pq_seed
    )
    codes = _pq_codes(v, cb)
    return np.where(sub_live, codes, 0).astype(np.uint8), {"vq_codebook": cb}


# ---------------------------------------------------------------------------
# decode (jnp, shared by jnp reference / XLA lowering / Pallas kernels)
# ---------------------------------------------------------------------------


def unpack_nibbles(codes):
    """Packed bytes [..., W] → interleaved 4-bit codes i32 [..., 2W]
    (low nibble first — the inverse of :func:`pack_nibbles`)."""
    import jax.numpy as jnp

    c = codes.astype(jnp.int32)
    return jnp.stack([c & 0xF, (c >> 4) & 0xF], axis=-1).reshape(
        *codes.shape[:-1], -1
    )


def dequant_sq(codes, lo, step):
    """code → clip-range FMA: ``lo + code·step`` in f32.  ``lo``/``step``
    broadcast ([R, 1] columns on the batched path, scalars in-kernel) —
    pure elementwise, so every execution mode computes identical bits."""
    import jax.numpy as jnp

    return lo + codes.astype(jnp.float32) * step


def dequant_pq(codes, codebook_flat):
    """u8 codes [..., W] + flat codebook f32 [PQ_K·PQ_M] → values
    f32 [..., W·PQ_M] via a flat gather (code·M + lane offset)."""
    import jax.numpy as jnp

    c = codes.astype(jnp.int32)
    idx = c[..., None] * PQ_M + jnp.arange(PQ_M, dtype=jnp.int32)
    flat = jnp.take(codebook_flat, idx.reshape(*c.shape[:-1], -1), axis=0)
    return flat


def decode_codes(vq: str, codes, lo=None, step=None, codebook_flat=None):
    """One dequant dispatch for all three execution modes: quantized
    codes [..., W] → f32 storage-unit values [..., W·factor]."""
    if vq == "f16":
        import jax.numpy as jnp

        return codes.astype(jnp.float32)
    if vq == "u8_sq":
        return dequant_sq(codes, lo, step)
    if vq == "u4_sq":
        return dequant_sq(unpack_nibbles(codes), lo, step)
    if vq == "pq":
        return dequant_pq(codes, codebook_flat)
    raise ValueError(f"unknown value codec {vq!r}; have {list(VALUE_CODECS)}")


# ---------------------------------------------------------------------------
# rows-array plumbing (vq inference)
# ---------------------------------------------------------------------------

#: every payload key a value codec can add to a rows dict
VQ_ROW_KEYS = ("vq_lo_rows", "vq_scale_rows", "vq_lo4_rows",
               "vq_scale4_rows", "vq_codebook")


def infer_rows_vq(arrays: Mapping) -> str:
    """Which value codec a packed rows dict carries — inferred from the
    payload keys, so serving needs no side-channel: ``vq_codebook`` →
    pq, ``vq_lo4_rows`` → u4_sq, ``vq_lo_rows`` → u8_sq, else f16."""
    if "vq_codebook" in arrays:
        return "pq"
    if "vq_lo4_rows" in arrays:
        return "u4_sq"
    if "vq_lo_rows" in arrays:
        return "u8_sq"
    return "f16"


def value_payload_bytes(arrays: Mapping) -> int:
    """Per-candidate value bytes of a rows dict: code bytes per row +
    clip-range columns, with the (read-once) codebook amortised by the
    caller.  Used by the bench bits/posting accounting."""
    per_row = int(np.asarray(arrays["vals_rows"]).dtype.itemsize) * int(
        np.asarray(arrays["vals_rows"]).shape[-1]
    )
    for k in ("vq_lo_rows", "vq_scale_rows", "vq_lo4_rows", "vq_scale4_rows"):
        if k in arrays:
            per_row += int(np.asarray(arrays[k]).dtype.itemsize)
    return per_row
