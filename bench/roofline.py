"""Work counts of the rows kernel and the chip's peaks.

A kernel's roofline share is the least time the chip could take for
the work, over the kernel's traced device time. The least time is the
larger of bytes / peak bandwidth and operations / peak rate; which of
the two is larger says what bounds the kernel.

The work is counted from the uncompressed corpus and the codecs'
definitions, never from the padded arrays that serve it, so the same
work reads the same roofline whatever layout or kernel serves it:

* every document's live components, read once per batched call: their
  encoded id bytes under the id codec (row layout: the first gap of a
  row is its absolute component id) plus their value bytes under the
  value codec;
* the dense f32 query batch, read once per call;
* two operations (multiply, add) per live component per query.
"""

from __future__ import annotations

import numpy as np

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16
        "bytes_per_s": 819e9,  # HBM
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; have {sorted(PEAKS)}"
        ) from None


def row_gaps(components: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Per-component gaps of a CSR corpus; each row's first is absolute."""
    c = components.astype(np.int64)
    gaps = np.empty_like(c)
    if len(c):
        gaps[0] = c[0]
        gaps[1:] = c[1:] - c[:-1]
    starts = offsets[:-1][np.diff(offsets) > 0]
    gaps[starts] = c[starts]
    return gaps


def id_bytes(codec: str, components: np.ndarray, offsets: np.ndarray) -> int:
    """Encoded id bytes of every document's live components."""
    nnz = np.diff(offsets).astype(np.int64)
    if codec == "uncompressed":
        return int(4 * nnz.sum())  # i32 ids
    gaps = row_gaps(components, offsets)
    if codec == "dotvbyte":  # 1 control bit per gap, 1-2 data bytes
        return int(-(-nnz // 8).sum() + len(gaps) + (gaps > 0xFF).sum())
    if codec == "streamvbyte":  # 2 control bits per gap, 1-4 data bytes
        data = 1 + (gaps > 0xFF).astype(np.int64) + (gaps > 0xFFFF) + (gaps > 0xFFFFFF)
        return int(-(-nnz // 4).sum() + data.sum())
    raise ValueError(f"no byte count for id codec {codec!r}")


def value_bytes(vq: str, nnz_per_doc: np.ndarray) -> int:
    """Value bytes of every document's live components."""
    nnz = np.asarray(nnz_per_doc, np.int64)
    if vq == "f16":
        return int(2 * nnz.sum())
    if vq == "u8_sq":  # one code per value + f32 lo and step per row
        return int(nnz.sum() + 8 * len(nnz))
    if vq == "u4_sq":
        return int((-(-nnz // 2)).sum() + 8 * len(nnz))
    raise ValueError(f"no byte count for value codec {vq!r}")


def batch_call_work(corpus, codec: str, vq: str, n_queries: int) -> dict:
    """Bytes and operations of one batched call that scores every
    document of ``corpus`` against ``n_queries`` dense queries."""
    nnz = np.diff(corpus.offsets)
    return {
        "bytes": id_bytes(codec, corpus.components, corpus.offsets)
        + value_bytes(vq, nnz)
        + 4 * n_queries * corpus.dim,
        "flops": 2 * int(nnz.sum()) * n_queries,
    }


def share(work: dict, calls: int, kernel_s: float, device_kind: str):
    """(roofline share in %, what bounds it) for ``calls`` calls of
    ``work`` that took ``kernel_s`` seconds of kernel time; None where
    there is no kernel time to read."""
    if not calls or kernel_s <= 0:
        return None
    pk = peaks(device_kind)
    t_mem = calls * work["bytes"] / pk["bytes_per_s"]
    t_ops = calls * work["flops"] / pk["flops_per_s"]
    return 100.0 * max(t_mem, t_ops) / kernel_s, ("memory" if t_mem >= t_ops else "compute")
