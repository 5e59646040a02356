"""Compile-only checks of the fused rows kernel for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler installed with JAX
compiles for a ``v5e:2x2`` topology that is described, not attached,
and refuses what the chip's compiler would refuse (block shapes,
unsupported primitives, VMEM overruns). Shapes are ShapeDtypeStructs
at serving widths — a 30522-term vocabulary, a million-document row
table, the msmarco-splade row capacity — so nothing is allocated.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import values as value_codecs
from repro.kernels import rows_dot

N_ROWS = 1_000_001  # a million documents + the sentinel row
L_MAX = 384  # msmarco-splade row capacity (configs/msmarco_splade.py)
VOCAB = 30592  # 30522 terms, lane-padded
N_CAND = 512
NQ = 8
CODECS = ("uncompressed", "dotvbyte", "streamvbyte", "bitpack")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _row_arrays(codec: str, vq: str, sharding, n_rows: int = N_ROWS, lead=()):
    """The packed row form ``layout.pack_rows`` lays out for rows of
    ``L_MAX`` components of a 30522-term vocabulary: lane-padded
    control/word streams, data streams sized for two bytes per gap."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct((*lead, *shape), dt, sharding=sharding)
    factor = value_codecs.code_factor(vq)
    cap = -(-L_MAX // (128 * factor)) * 128 * factor
    lane = lambda n: -(-n // 128) * 128
    arrays = {
        "vals_rows": sds((n_rows, cap // factor), jnp.float16 if vq == "f16" else jnp.uint8),
        "nnz_rows": sds((n_rows,), jnp.int32),
    }
    if vq == "pq":
        arrays["vq_codebook"] = sds((value_codecs.PQ_K, value_codecs.PQ_M), jnp.float32)
    elif vq != "f16":
        for key in value_codecs.sq_keys(vq):
            arrays[key] = sds((n_rows, 1), jnp.float32)
    if codec == "uncompressed":
        arrays["comps_rows"] = sds((n_rows, cap), jnp.int32)
    elif codec == "bitpack":  # 15-bit gaps at most below 30522
        arrays["words_rows"] = sds((n_rows, lane(cap * 15 // 32)), jnp.uint32)
        arrays["widths_rows"] = sds((n_rows,), jnp.int32)
    else:
        group = 8 if codec == "dotvbyte" else 4
        arrays["ctrl_rows"] = sds((n_rows, lane(cap // group)), jnp.uint8)
        arrays["data_rows"] = sds((n_rows, lane(2 * cap + 3)), jnp.uint8)
    return arrays


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("codec", CODECS)
def test_rows_kernel_compiles_batched(codec, one_chip):
    """The decode-once/score-many form ``flat`` serves through."""
    compiled = rows_dot.rows_scores_batch.lower(
        codec,
        jax.ShapeDtypeStruct((NQ, VOCAB), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((N_CAND,), jnp.int32, sharding=one_chip),
        _row_arrays(codec, "f16", one_chip),
        scale=1.0, vq="f16", interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("codec", CODECS)
def test_rows_kernel_compiles_vmapped(codec, one_chip):
    """The single-query form under ``vmap``, with per-query candidate
    sets — how ``seismic`` and ``hnsw`` serve."""

    def search(Q, docs, arrays):
        one = lambda q, d: rows_dot.rows_scores(codec, q, d, arrays, interpret=False)
        return jax.vmap(one)(Q, docs)

    compiled = jax.jit(search).lower(
        jax.ShapeDtypeStruct((NQ, VOCAB), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((NQ, N_CAND), jnp.int32, sharding=one_chip),
        _row_arrays(codec, "f16", one_chip),
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("vq", ("u8_sq", "u4_sq", "pq"))
def test_rows_kernel_compiles_quantized_values(vq, one_chip):
    """Every value codec's in-kernel dequant stage lowers too."""
    compiled = rows_dot.rows_scores_batch.lower(
        "dotvbyte",
        jax.ShapeDtypeStruct((NQ, VOCAB), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((N_CAND,), jnp.int32, sharding=one_chip),
        _row_arrays("dotvbyte", vq, one_chip),
        scale=1.0, vq=vq, interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("codec", CODECS)
def test_rows_scan_compiles(codec, one_chip):
    """The range form ``flat`` serves through: every row of a
    million-row table in contiguous blocks, a full query group."""
    compiled = rows_dot.rows_scan_batch.lower(
        codec,
        jax.ShapeDtypeStruct((rows_dot.Q_GROUP, VOCAB), jnp.float32, sharding=one_chip),
        _row_arrays(codec, "f16", one_chip),
        scale=1.0, vq="f16", interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("vq", ("u8_sq", "u4_sq", "pq"))
def test_rows_scan_compiles_quantized_values(vq, one_chip):
    """The range form's dequant stage, per-block clip ranges included."""
    compiled = rows_dot.rows_scan_batch.lower(
        "dotvbyte",
        jax.ShapeDtypeStruct((rows_dot.Q_GROUP, VOCAB), jnp.float32, sharding=one_chip),
        _row_arrays("dotvbyte", vq, one_chip),
        scale=1.0, vq=vq, interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_mesh_search_compiles_for_four_chips(topo, monkeypatch):
    """The sharded ``flat`` search over a 4-chip mesh: one shard per
    device, the fused kernel inside, the top-k merged by all-gather."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.kernels import modes
    from repro.serve.api import RetrieverConfig, make_sharded_search

    # the registry picks the kernel lowering from the attached backend;
    # here the chip is only described, so say it is there
    monkeypatch.setattr(modes, "mosaic_available", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    n_local = N_ROWS // 4
    cfg = RetrieverConfig(engine="flat", codec="dotvbyte", backend="pallas", k=10)
    fn = make_sharded_search(
        mesh, cfg, n_local, N_ROWS - 1, 1.0, index_axis="model", query_axes=(),
    )
    shards = NamedSharding(mesh, P("model"))
    arrays = _row_arrays("dotvbyte", "f16", shards, n_rows=n_local + 1, lead=(4,))
    idmap = jax.ShapeDtypeStruct((4, n_local + 1), jnp.int32, sharding=shards)
    Q = jax.ShapeDtypeStruct((NQ, VOCAB), jnp.float32, sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(fn).lower(arrays, idmap, Q).compile()
    _assert_kernel(compiled)
    assert "all-gather" in compiled.as_text()
