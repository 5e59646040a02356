"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret=True)
vs the pure-jnp oracle in repro.kernels.ref, end-to-end vs the CSR
numpy ground truth, and the fused rows-rescoring kernels vs the jnp
``score_candidate_rows`` chain (every registry codec, empty-row and
sentinel-doc-id edge cases included)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import layout
from repro.core.forward_index import ForwardIndex, pack_forward_index
from repro.core.scoring import score_candidate_rows, score_packed, score_packed_batch
from repro.kernels.bitpack_dot import bitpack_block_scores, bitpack_block_scores_w
from repro.kernels.dotvbyte_dot import dotvbyte_block_scores
from repro.kernels.ops import (
    pad_to,
    score_bitpack,
    score_bitpack_bucketed,
    score_dotvbyte,
    score_dotvbyte_batch,
    score_streamvbyte,
    score_streamvbyte_batch,
)
from repro.kernels.ref import (
    bitpack_block_scores_ref,
    dotvbyte_block_scores_ref,
    streamvbyte_block_scores_ref,
)
from repro.kernels.registry import available_kernels, get_kernels
from repro.kernels.streamvbyte_dot import streamvbyte_block_scores


def _collection(rng, n_docs, dim, max_nnz, value_format):
    docs = []
    for _ in range(n_docs):
        n = int(rng.integers(1, max_nnz))
        c = np.sort(rng.choice(dim, size=min(n, dim // 2), replace=False))
        v = rng.gamma(2.0, 0.5, size=len(c)).astype(np.float32) + 0.05
        docs.append((c, v))
    return ForwardIndex.from_docs(docs, dim, value_format=value_format)


def _query(rng, dim, nnz=40):
    q = np.zeros(dim, dtype=np.float32)
    qc = rng.choice(dim, nnz, replace=False)
    q[qc] = rng.gamma(2.0, 0.5, size=nnz)
    return q


SWEEP = [
    # (dim, block_size, n_docs, max_nnz, value_format)
    (2048, 128, 40, 60, "f32"),
    (8192, 256, 60, 200, "f16"),
    (30522, 512, 80, 300, "fixedu8"),
    (512, 128, 10, 500, "f16"),  # docs spanning many blocks
]


@pytest.mark.parametrize("dim,bs,n_docs,max_nnz,vf", SWEEP)
def test_dotvbyte_kernel_vs_ref(dim, bs, n_docs, max_nnz, vf):
    rng = np.random.default_rng(dim + bs)
    fwd = _collection(rng, n_docs, dim, max_nnz, vf)
    packed = pack_forward_index(fwd, codec="dotvbyte", block_size=bs)
    q = _query(rng, dim)
    qpad = np.zeros(((dim + 127) // 128) * 128, np.float32)
    qpad[:dim] = q
    args = (
        jnp.asarray(qpad),
        jnp.asarray(packed.ctrl),
        jnp.asarray(pad_to(packed.data, 128, axis=1)),
        jnp.asarray(packed.seg),
        jnp.asarray(packed.start_pos),
        jnp.asarray(packed.start_abs),
        jnp.asarray(packed.vals),
    )
    scale = float(packed.value_format.scale)
    kern = dotvbyte_block_scores(*args, scale=scale, interpret=True)
    ref = dotvbyte_block_scores_ref(*args, scale=scale)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dim,bs,n_docs,max_nnz,vf", SWEEP)
def test_bitpack_kernel_vs_ref(dim, bs, n_docs, max_nnz, vf):
    rng = np.random.default_rng(dim * 3 + bs)
    fwd = _collection(rng, n_docs, dim, max_nnz, vf)
    packed = pack_forward_index(fwd, codec="bitpack", block_size=bs)
    q = _query(rng, dim)
    qpad = np.zeros(((dim + 127) // 128) * 128, np.float32)
    qpad[:dim] = q
    words = pad_to(packed.words, 128, axis=1)
    scale = float(packed.value_format.scale)
    kern = bitpack_block_scores(
        jnp.asarray(qpad), jnp.asarray(words), jnp.asarray(packed.widths),
        jnp.asarray(packed.seg), jnp.asarray(packed.start_pos),
        jnp.asarray(packed.start_abs), jnp.asarray(packed.vals),
        scale=scale, interpret=True,
    )
    ref = bitpack_block_scores_ref(
        jnp.asarray(qpad), jnp.asarray(words), jnp.asarray(packed.widths),
        jnp.asarray(packed.seg), jnp.asarray(packed.start_pos),
        jnp.asarray(packed.start_abs), jnp.asarray(packed.vals), scale=scale,
    )
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dim,bs,n_docs,max_nnz,vf", SWEEP)
def test_streamvbyte_kernel_vs_ref(dim, bs, n_docs, max_nnz, vf):
    rng = np.random.default_rng(dim * 7 + bs)
    fwd = _collection(rng, n_docs, dim, max_nnz, vf)
    packed = pack_forward_index(fwd, codec="streamvbyte", block_size=bs)
    q = _query(rng, dim)
    qpad = np.zeros(((dim + 127) // 128) * 128, np.float32)
    qpad[:dim] = q
    args = (
        jnp.asarray(qpad),
        jnp.asarray(packed.ctrl),
        jnp.asarray(pad_to(packed.data, 128, axis=1)),
        jnp.asarray(packed.seg),
        jnp.asarray(packed.start_pos),
        jnp.asarray(packed.start_abs),
        jnp.asarray(packed.vals),
    )
    scale = float(packed.value_format.scale)
    kern = streamvbyte_block_scores(*args, scale=scale, interpret=True)
    ref = streamvbyte_block_scores_ref(*args, scale=scale)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("vf", ["f32", "f16", "fixedu8"])
def test_kernel_paths_end_to_end(vf):
    """Kernel wrappers vs numpy CSR ground truth, all value formats."""
    rng = np.random.default_rng(99)
    dim = 30522
    fwd = _collection(rng, 120, dim, 250, vf)
    q = _query(rng, dim)
    want = fwd.exact_scores(q)
    pd = pack_forward_index(fwd, codec="dotvbyte")
    ps = pack_forward_index(fwd, codec="streamvbyte")
    pb = pack_forward_index(fwd, codec="bitpack")
    for name, got in [
        ("dotvbyte", score_dotvbyte(q, pd, interpret=True)),
        ("streamvbyte", score_streamvbyte(q, ps, interpret=True)),
        ("bitpack", score_bitpack(q, pb, interpret=True)),
        ("bitpack_bucketed", score_bitpack_bucketed(q, pb, interpret=True)),
    ]:
        np.testing.assert_allclose(
            np.asarray(got), want, atol=5e-3, rtol=2e-3, err_msg=name
        )


def test_batched_scan_kernels_match_single():
    """Decode-once/score-many variants == per-query single kernel, and
    the vmapped ``score_packed_batch`` == stacked ``score_packed``."""
    rng = np.random.default_rng(17)
    dim = 4096
    fwd = _collection(rng, 60, dim, 120, "f16")
    Q = np.stack([_query(rng, dim) for _ in range(3)])
    pd = pack_forward_index(fwd, codec="dotvbyte", block_size=128)
    ps = pack_forward_index(fwd, codec="streamvbyte", block_size=128)
    for packed, single, batch in [
        (pd, score_dotvbyte, score_dotvbyte_batch),
        (ps, score_streamvbyte, score_streamvbyte_batch),
    ]:
        got = np.asarray(batch(Q, packed, interpret=True))
        want = np.stack([np.asarray(single(q, packed, interpret=True)) for q in Q])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    got = np.asarray(score_packed_batch(Q, ps))
    want = np.stack([np.asarray(score_packed(q, ps)) for q in Q])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# fused candidate-row rescoring kernels (registry + rows_dot)
# ---------------------------------------------------------------------------


def _rows_fixture(rng, dim=2048, n_docs=50):
    """Collection with an empty document; candidate set with the
    sentinel id, duplicates, and the empty doc — the edge cases the
    serve engines rely on being neutral."""
    docs = []
    for _ in range(n_docs):
        n = int(rng.integers(1, 90))
        c = np.sort(rng.choice(dim, size=min(n, dim // 2), replace=False))
        v = rng.gamma(2.0, 0.5, size=len(c)).astype(np.float32) + 0.05
        docs.append((c, v))
    empty_id = len(docs)
    docs.append((np.zeros(0, np.uint32), np.zeros(0, np.float32)))
    fwd = ForwardIndex.from_docs(docs, dim, value_format="f16")
    n = fwd.n_docs
    cand = np.concatenate(
        [rng.choice(n, min(24, n), replace=False), [n, empty_id, 3, 3, n]]
    ).astype(np.int32)
    return fwd, cand


@pytest.mark.parametrize("codec", available_kernels())
def test_rows_kernel_matches_jnp_chain(codec):
    rng = np.random.default_rng(sum(codec.encode()))
    fwd, cand = _rows_fixture(rng)
    arrays = {k: jnp.asarray(v) for k, v in layout.pack_rows(fwd, codec=codec).arrays().items()}
    q = _query(rng, fwd.dim)
    scale = float(fwd.value_format.scale)
    want = score_candidate_rows(
        codec, arrays, jnp.asarray(cand), jnp.asarray(q), scale, backend="jnp"
    )
    got = get_kernels(codec).rows_scores(
        arrays, jnp.asarray(cand), jnp.asarray(q), scale, True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    # sentinel and empty rows score exactly 0 on both paths
    sent = np.asarray(got)[np.asarray(cand) >= fwd.n_docs]
    np.testing.assert_array_equal(sent, np.zeros_like(sent))


@pytest.mark.parametrize("codec", ["streamvbyte", "bitpack"])
def test_rows_kernel_batch_matches_vmapped_single(codec):
    """The explicit query-batched rows kernel == vmap of the single-
    query entry (the form the jit'd Retriever search path uses)."""
    rng = np.random.default_rng(23)
    fwd, cand = _rows_fixture(rng)
    arrays = {k: jnp.asarray(v) for k, v in layout.pack_rows(fwd, codec=codec).arrays().items()}
    Q = jnp.asarray(np.stack([_query(rng, fwd.dim) for _ in range(4)]))
    scale = float(fwd.value_format.scale)
    ks = get_kernels(codec)
    got = ks.rows_scores_batch(arrays, jnp.asarray(cand), Q, scale, True)
    want = jax.vmap(
        lambda q: ks.rows_scores(arrays, jnp.asarray(cand), q, scale, True)
    )(Q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("codec", available_kernels())
def test_flat_batch_matches_vmapped_single_pallas(codec):
    """flat's batched full scan and ``vmap(search_one)`` both take the
    range form under ``backend="pallas"`` (interpret mode here): each
    query's ids and scores are bitwise equal."""
    from repro.serve.api import RetrieverConfig, get_engine

    rng = np.random.default_rng(31 + sum(codec.encode()))
    fwd, Q = _scan_fixture(rng, dim=512, full=128)
    arrays = _scan_arrays(fwd, codec)
    cfg = RetrieverConfig(engine="flat", codec=codec, k=5, backend="pallas_interpret")
    impl, scale = get_engine("flat"), float(fwd.value_format.scale)
    ids_b, sc_b = impl.search_batch(cfg, fwd.n_docs, scale, arrays, jnp.asarray(Q))
    ids_v, sc_v = jax.vmap(
        lambda q: impl.search_one(cfg, fwd.n_docs, scale, arrays, q)
    )(jnp.asarray(Q))
    np.testing.assert_array_equal(np.asarray(ids_b), np.asarray(ids_v))
    np.testing.assert_array_equal(np.asarray(sc_b), np.asarray(sc_v))


# ---------------------------------------------------------------------------
# the range form of the rows kernel (every row, contiguous row blocks)
# ---------------------------------------------------------------------------

from repro.core.scoring import score_candidate_rows_batch  # noqa: E402
from repro.core import values as value_codecs  # noqa: E402
from repro.kernels import rows_dot  # noqa: E402


def _scan_fixture(rng, dim=1024, full=256):
    """A row table whose N+1 rows end in a ragged block of the range
    form, with empty rows and rows at full capacity (nnz = ``full``),
    and three queries (below one query group) whose id union spans
    several 128-id chunks."""
    docs = []
    for d in range(rows_dot.R_SCAN + 12):  # N+1 = R_SCAN + 13
        n = 0 if d % 9 == 4 else (full if d % 9 == 7 else int(rng.integers(1, full)))
        c = np.sort(rng.choice(dim, size=n, replace=False))
        docs.append((c, rng.gamma(2.0, 0.5, size=n).astype(np.float32) + 0.05))
    fwd = ForwardIndex.from_docs(docs, dim, value_format="f16")
    Q = np.stack([_query(rng, dim, nnz=200) for _ in range(3)])
    return fwd, Q


def _scan_arrays(fwd, codec, vq="f16"):
    packed = layout.pack_rows(fwd, codec=codec, vq=vq)
    assert packed.l_max == np.diff(fwd.offsets).max()  # full rows: nnz = l_max
    assert packed.vals_rows.shape[0] % rows_dot.R_SCAN != 0
    return {k: jnp.asarray(v) for k, v in packed.arrays().items()}


@pytest.mark.parametrize("vq", value_codecs.VALUE_CODECS)
@pytest.mark.parametrize("codec", available_kernels())
def test_rows_scan_matches_jnp_chain(codec, vq):
    """The range form scores every row as the jnp chain does, for every
    codec × value codec; the sentinel and empty rows score exactly 0."""
    rng = np.random.default_rng(101 + sum(codec.encode()))
    fwd, Q = _scan_fixture(rng, full=128 * value_codecs.code_factor(vq))
    arrays = _scan_arrays(fwd, codec, vq)
    scale = float(fwd.value_format.scale)
    docs = jnp.arange(fwd.n_docs + 1, dtype=jnp.int32)
    want = np.asarray(score_candidate_rows_batch(
        codec, arrays, docs, jnp.asarray(Q), scale, backend="jnp"
    ))
    got = np.asarray(get_kernels(codec).rows_scan_batch(
        arrays, jnp.asarray(Q), scale, "pallas_interpret"
    ))
    assert got.shape == (len(Q), fwd.n_docs + 1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    empty = np.append(np.flatnonzero(np.diff(fwd.offsets) == 0), fwd.n_docs)
    np.testing.assert_array_equal(got[:, empty], np.zeros((len(Q), len(empty))))


@pytest.mark.parametrize("codec", available_kernels())
def test_rows_scan_matches_gather_form(codec):
    """The range form == the gather form over every row id."""
    rng = np.random.default_rng(7)
    fwd, Q = _scan_fixture(rng, full=128)
    arrays = _scan_arrays(fwd, codec)
    scale = float(fwd.value_format.scale)
    ks = get_kernels(codec)
    docs = jnp.arange(fwd.n_docs + 1, dtype=jnp.int32)
    got = ks.rows_scan_batch(arrays, jnp.asarray(Q), scale, "pallas_interpret")
    want = ks.rows_scores_batch(arrays, docs, jnp.asarray(Q), scale, "pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def _pallas_calls(jaxpr, out):
    """Every pallas_call in a jaxpr and its sub-jaxprs → [(name, grid,
    scalar-prefetch operand count)]."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            gm = e.params["grid_mapping"]
            out.append((e.params["name"], gm.grid, gm.num_index_operands))
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else [v]:
                if isinstance(sub, ClosedJaxpr):
                    _pallas_calls(sub.jaxpr, out)
                elif isinstance(sub, Jaxpr):
                    _pallas_calls(sub, out)
    return out


@pytest.mark.parametrize("engine", ["seismic", "hnsw", "flat"])
def test_engine_rows_kernel_form(engine):
    """Arbitrary candidate ids (seismic, hnsw) take the gather form, one
    candidate row per grid step with the ids scalar-prefetched; flat's
    full scan takes the range form, one block of R_SCAN rows per step."""
    from repro.data.synthetic import SyntheticConfig, generate_collection
    from repro.serve.api import Retriever, RetrieverConfig

    col = generate_collection(SyntheticConfig(
        name="t", dim=1024, n_docs=300, n_queries=2, doc_nnz_mean=40.0,
        query_nnz_mean=12.0, seed=0,
    ), value_format="f16")
    params = {
        "seismic": dict(cut=8, block_budget=256, n_probe=48, n_postings=300, block_size=16),
        "hnsw": dict(beam=48, iters=48, n_seeds=4, m=8, ef_construction=32),
        "flat": {},
    }[engine]
    r = Retriever.build(col.fwd, RetrieverConfig(
        engine=engine, codec="dotvbyte", backend="pallas_interpret", params=params,
    ))
    Q = jnp.asarray(np.stack([col.query_dense(i) for i in range(2)]))
    search = lambda Q: r.impl.search_batch(r.cfg, r.n_docs, r.value_scale, r.arrays, Q)
    calls = _pallas_calls(jax.make_jaxpr(search)(Q).jaxpr, [])
    assert calls
    for name, grid, n_prefetch in calls:
        if engine == "flat":
            assert name == "rows_scan_dotvbyte_f16"
            assert grid[-1] == -(-(col.fwd.n_docs + 1) // rows_dot.R_SCAN)
            assert n_prefetch == 1  # the chunk counts alone
        else:
            assert name == "rows_dotvbyte_f16"
            assert grid[-1] % 128 == 0  # one padded candidate per step
            assert n_prefetch >= 2  # the candidate ids among them


def test_kernel_registry_surface():
    """Registry mirrors the layout registry: every layout codec is
    fused, unknown names raise listing the known ones, and a kernel
    backend on a codec with no rows kernel raises (single and batch
    forms) instead of serving through jnp."""
    assert set(available_kernels()) == set(layout.available_layouts())
    with pytest.raises(ValueError, match=r"bitpack.*streamvbyte"):
        get_kernels("zstd")
    from repro.core.scoring import score_candidate_rows_batch
    from repro.kernels import registry

    rng = np.random.default_rng(3)
    fwd, cand = _rows_fixture(rng, n_docs=10)
    arrays = {k: jnp.asarray(v) for k, v in layout.pack_rows(fwd, codec="dotvbyte").arrays().items()}
    q = jnp.asarray(_query(rng, fwd.dim))
    scale = float(fwd.value_format.scale)
    saved_kernels = registry._KERNELS.pop("dotvbyte")
    try:
        with pytest.raises(ValueError, match="has none registered"):
            score_candidate_rows(
                "dotvbyte", arrays, jnp.asarray(cand), q, scale, backend="pallas"
            )
        with pytest.raises(ValueError, match="has none registered"):
            score_candidate_rows_batch(
                "dotvbyte", arrays, jnp.asarray(cand), q[None], scale,
                backend="pallas_interpret",
            )
        # the jnp backend needs no kernel
        got = score_candidate_rows(
            "dotvbyte", arrays, jnp.asarray(cand), q, scale, backend="jnp"
        )
    finally:
        registry._KERNELS["dotvbyte"] = saved_kernels
    want = get_kernels("dotvbyte").rows_scores(
        arrays, jnp.asarray(cand), q, scale, "pallas_interpret"
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="unknown scoring backend"):
        score_candidate_rows("dotvbyte", arrays, jnp.asarray(cand), q, scale,
                             backend="mosaic")


def test_bucketed_width_kernel_tight_words():
    """Static-width kernel must accept tight (per-width) word arrays."""
    rng = np.random.default_rng(5)
    dim, T = 4096, 128
    fwd = _collection(rng, 60, dim, 100, "f16")
    packed = pack_forward_index(fwd, codec="bitpack", block_size=T)
    q = _query(rng, dim)
    got = score_bitpack_bucketed(q, packed, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), fwd.exact_scores(q), atol=2e-3, rtol=1e-3
    )
    assert len(set(int(w) for w in packed.widths)) >= 2  # multiple buckets hit


def test_kernel_single_block_degenerate():
    dim = 256
    docs = [(np.array([0, 255], dtype=np.uint32), np.array([1.0, 2.0], np.float32))]
    fwd = ForwardIndex.from_docs(docs, dim)
    packed = pack_forward_index(fwd, codec="dotvbyte", block_size=128)
    q = np.zeros(dim, np.float32)
    q[0], q[255] = 3.0, 4.0
    got = np.asarray(score_dotvbyte(q, packed, interpret=True))
    np.testing.assert_allclose(got, [3.0 + 8.0], rtol=1e-6)


# ---------------------------------------------------------------------------
# execution-mode axis (repro.kernels.modes) + tiled edge shapes
# ---------------------------------------------------------------------------

from repro.kernels import modes as kernel_modes  # noqa: E402
from repro.kernels.tiles import Q_TILE, R_TILE  # noqa: E402

_SCAN_WRAPPER = {
    "dotvbyte": score_dotvbyte,
    "streamvbyte": score_streamvbyte,
    "bitpack": score_bitpack_bucketed,
}


def test_mode_resolution():
    """Mode normalisation: None → compiled, legacy booleans map onto
    the two pallas modes, bad spellings raise with the valid list."""
    assert kernel_modes.resolve_mode(None) == "pallas_compiled"
    assert kernel_modes.resolve_mode(True) == "pallas_interpret"
    assert kernel_modes.resolve_mode(False) == "pallas_compiled"
    for m in kernel_modes.MODES:
        assert kernel_modes.resolve_mode(m) == m
    with pytest.raises(ValueError, match="unknown kernel mode"):
        kernel_modes.resolve_mode("fast")
    assert kernel_modes.backend_mode("jnp") == "jnp"
    assert kernel_modes.backend_mode("pallas") is None  # auto
    assert kernel_modes.backend_mode("pallas_interpret") == "pallas_interpret"
    assert kernel_modes.backend_mode("pallas_compiled") == "pallas_compiled"
    with pytest.raises(ValueError, match="unknown scoring backend"):
        kernel_modes.backend_mode("cuda")
    assert kernel_modes.resolve_lowering("jnp") == "jnp"
    assert kernel_modes.resolve_lowering("pallas_interpret") == "interpret"
    assert kernel_modes.resolve_lowering("pallas_compiled") in ("mosaic", "xla")


def test_xla_fallback_warns_once():
    """Without Mosaic, pallas_compiled lowers through XLA with exactly
    one RuntimeWarning for the whole process."""
    if kernel_modes.mosaic_available():
        pytest.skip("Mosaic backend attached: no fallback on this host")
    saved = set(kernel_modes._XLA_FALLBACK_WARNED)
    kernel_modes._XLA_FALLBACK_WARNED.clear()
    try:
        with pytest.warns(RuntimeWarning, match="through XLA"):
            assert kernel_modes.resolve_lowering("pallas_compiled") == "xla"
        import warnings as _w

        with _w.catch_warnings():  # second resolve: already warned
            _w.simplefilter("error", RuntimeWarning)
            assert kernel_modes.resolve_lowering("pallas_compiled") == "xla"
    finally:
        kernel_modes._XLA_FALLBACK_WARNED.clear()
        kernel_modes._XLA_FALLBACK_WARNED.update(saved)


@pytest.mark.parametrize("codec", ["dotvbyte", "streamvbyte", "bitpack"])
def test_scan_modes_parity_edge_shapes(codec):
    """Block counts that are NOT a multiple of the tile height (the
    DMA scan pads with neutral tiles) and a single-doc corpus: all
    three execution modes reproduce the jnp scores."""
    rng = np.random.default_rng(41)
    scorer = _SCAN_WRAPPER[codec]
    for n_docs in (11, 1):
        fwd = _collection(rng, n_docs, 512, 60, "f16")
        packed = pack_forward_index(fwd, codec=codec, block_size=128)
        assert packed.seg.shape[0] % R_TILE != 0  # the shape under test
        q = _query(rng, 512, nnz=20)
        want = np.asarray(scorer(q, packed, mode="jnp"))
        for mode in ("pallas_interpret", "pallas_compiled"):
            got = np.asarray(scorer(q, packed, mode=mode))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{codec} [{mode}]")


@pytest.mark.parametrize("codec", available_kernels())
def test_rows_kernel_modes_parity(codec):
    """Candidate sets with duplicate ids, the sentinel, an empty row,
    and a length far from the rescoring tile width: interpret and
    compiled both reproduce the jnp chain."""
    rng = np.random.default_rng(7 + sum(codec.encode()))
    fwd, cand = _rows_fixture(rng, dim=1024, n_docs=21)
    arrays = {k: jnp.asarray(v) for k, v in layout.pack_rows(fwd, codec=codec).arrays().items()}
    q = _query(rng, fwd.dim)
    scale = float(fwd.value_format.scale)
    ks = get_kernels(codec)
    want = np.asarray(score_candidate_rows(
        codec, arrays, jnp.asarray(cand), jnp.asarray(q), scale, backend="jnp"
    ))
    for mode in ("pallas_interpret", "pallas_compiled"):
        got = np.asarray(ks.rows_scores(
            arrays, jnp.asarray(cand), jnp.asarray(q), scale, mode
        ))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{codec} [{mode}]")


def test_batched_kernels_compiled_mode_parity():
    """Compiled batched grids at nq not a multiple of the query tile:
    scan == vmapped score_packed, rows == the jnp chain per query."""
    rng = np.random.default_rng(67)
    fwd = _collection(rng, 30, 1024, 80, "f16")
    nq = Q_TILE - 3  # forces query-axis padding in the batched grid
    Q = np.stack([_query(rng, 1024, nnz=24) for _ in range(nq)])
    for codec, batch_fn in [("dotvbyte", score_dotvbyte_batch),
                            ("streamvbyte", score_streamvbyte_batch)]:
        packed = pack_forward_index(fwd, codec=codec, block_size=128)
        got = np.asarray(batch_fn(Q, packed, mode="pallas_compiled"))
        want = np.asarray(score_packed_batch(Q, packed))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5, err_msg=codec)

    from repro.core.scoring import score_candidate_rows_batch

    cand = np.array([5, 5, 0, 30, 29, 7, 1], np.int32)  # dups + sentinel
    for codec in ("streamvbyte", "bitpack"):
        arrays = {k: jnp.asarray(v)
                  for k, v in layout.pack_rows(fwd, codec=codec).arrays().items()}
        scale = float(fwd.value_format.scale)
        got = np.asarray(get_kernels(codec).rows_scores_batch(
            arrays, jnp.asarray(cand), jnp.asarray(Q), scale, "pallas_compiled"
        ))
        want = np.asarray(score_candidate_rows_batch(
            codec, arrays, jnp.asarray(cand), jnp.asarray(Q), scale, backend="jnp"
        ))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6, err_msg=codec)


def test_rows_single_doc_corpus_modes():
    """One-document corpus (row table is just the doc + sentinel):
    every mode scores the duplicate/sentinel candidate list alike."""
    docs = [(np.array([1, 200], np.uint32), np.array([1.5, 2.0], np.float32))]
    fwd = ForwardIndex.from_docs(docs, 256, value_format="f32")
    cand = np.array([0, 0, 1], np.int32)  # dup + sentinel row
    q = np.zeros(256, np.float32)
    q[1], q[200] = 2.0, 1.0
    for codec in available_kernels():
        arrays = {k: jnp.asarray(v)
                  for k, v in layout.pack_rows(fwd, codec=codec).arrays().items()}
        scale = float(fwd.value_format.scale)
        for mode in ("jnp", "pallas_interpret", "pallas_compiled"):
            got = np.asarray(score_candidate_rows(
                codec, arrays, jnp.asarray(cand), jnp.asarray(q), scale,
                backend=mode if mode != "jnp" else "jnp",
            ))
            np.testing.assert_allclose(got, [5.0, 5.0, 0.0], rtol=1e-5,
                                       err_msg=f"{codec} [{mode}]")
