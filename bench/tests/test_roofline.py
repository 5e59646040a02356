"""Work counts of the rows kernel against hand counts, and the peaks table."""

import numpy as np
import pytest

from bench import roofline
from bench.corpus import Corpus

# two documents: ids [3, 300, 301] and [1000, 1001, 1002, 1003, 1004, 1005, 1006, 1007, 1300]
COMPS = np.array([3, 300, 301, 1000, 1001, 1002, 1003, 1004, 1005, 1006, 1007, 1300], np.uint32)
OFFS = np.array([0, 3, 12], np.int64)
TINY = Corpus(dim=2048, components=COMPS, values=np.ones(12, np.float16), offsets=OFFS,
              query_comps=[], query_vals=[])


def test_row_gaps_restart_at_each_document():
    np.testing.assert_array_equal(
        roofline.row_gaps(COMPS, OFFS), [3, 297, 1, 1000, 1, 1, 1, 1, 1, 1, 1, 293])


def test_dotvbyte_bytes_by_hand():
    # doc 0: 1 control byte; gaps 3, 297, 1 -> 1 + 2 + 1 data bytes
    # doc 1: 2 control bytes; gaps 1000 and 293 take 2 bytes, seven take 1
    assert roofline.id_bytes("dotvbyte", COMPS, OFFS) == (1 + 4) + (2 + 11)


def test_streamvbyte_and_uncompressed_bytes_by_hand():
    # 2-bit controls: 1 and 3 control bytes; data bytes as for dotvbyte
    assert roofline.id_bytes("streamvbyte", COMPS, OFFS) == (1 + 4) + (3 + 11)
    assert roofline.id_bytes("uncompressed", COMPS, OFFS) == 4 * 12


def test_value_bytes_by_hand():
    nnz = np.diff(OFFS)
    assert roofline.value_bytes("f16", nnz) == 24
    assert roofline.value_bytes("u8_sq", nnz) == 12 + 2 * 8
    assert roofline.value_bytes("u4_sq", nnz) == (2 + 5) + 2 * 8


def test_batch_call_work_by_hand():
    w = roofline.batch_call_work(TINY, "dotvbyte", "f16", n_queries=4)
    assert w["bytes"] == 18 + 24 + 4 * 4 * 2048
    assert w["flops"] == 2 * 12 * 4


def test_share_and_bound():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["bytes_per_s"] == 819e9
    work = {"bytes": 819e6, "flops": 1e9}  # 1 ms of HBM, ~5 us of compute
    share, bound = roofline.share(work, calls=2, kernel_s=0.004, device_kind="TPU v5 lite")
    assert share == pytest.approx(50.0) and bound == "memory"
    share, bound = roofline.share({"bytes": 1.0, "flops": 197e9}, 1, 0.002, "TPU v5 lite")
    assert share == pytest.approx(50.0) and bound == "compute"
    assert roofline.share(work, calls=0, kernel_s=1.0, device_kind="TPU v5 lite") is None


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        roofline.share({"bytes": 1, "flops": 1}, 1, 1.0, "cpu")


def test_unknown_codec_raises():
    with pytest.raises(ValueError):
        roofline.id_bytes("elias", COMPS, OFFS)
    with pytest.raises(ValueError):
        roofline.value_bytes("pq", np.diff(OFFS))
