"""One ``Retriever`` API (DESIGN.md §7): engine registry, build/serve
split, and on-disk index artifacts.

The paper's thesis is that forward-index compression is common to all
algorithmic flavors of sparse ANNS; this module is where that becomes
an API contract. Every serving engine is a registry entry —

    @register_engine("seismic")
    class SeismicEngine(EngineImpl): ...

— implementing the ``EngineImpl`` protocol (host-side array build,
pure static-shape ``search_one``, dry-run array specs, shard build)
over one shared ``RetrieverConfig`` (engine, codec, k, shard count,
engine params). The engine-agnostic surface is then:

* ``Retriever.build(fwd, cfg)`` — host-side index construction
  (collection → engine arrays under any codec registered in
  ``core/layout.py``);
* ``retriever.search(Q, k)`` — the jit'd static-shape batched search;
* ``retriever.save(path)`` / ``open_retriever(path)`` — the artifact
  lifecycle: a manifest (engine/codec/params/format version) plus an
  npz payload of the packed arrays, so a serving process loads
  pre-packed arrays without re-encoding anything;
* ``build_shard_arrays`` / ``make_sharded_search`` — ONE generic
  sharded-search driver (DESIGN.md §4): per-shard ``search_one``,
  local→global id map, O(k) all-gather merge; engines only declare
  whether the merge must dedupe doc ids.

Three engines ship registered: ``seismic`` (two-phase block probe),
``hnsw`` (static beam search) and ``flat`` (exact full scan — proof
the registry is open, and the recall oracle).

Execution goes through the online serving pipeline
(``repro.serve.pipeline``, DESIGN.md §8): ``Retriever`` holds a
``PlanCache`` — one compiled executable per ``(engine, codec,
backend, k, bucket)`` — and ``search`` pads any query batch up to its
smallest covering bucket so arbitrary batch sizes hit a warm plan;
``search_batch`` reroutes through the micro-batching scheduler
(deadline coalescing + quantized-query result cache + ServeStats).
The per-engine wrapper shims of PR-1/PR-2 (``repro.serve.engine``,
``repro.serve.graph_engine``) were removed after one deprecation
release.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from functools import partial
from typing import Any, Callable, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout
from repro.core import values as value_codecs
from repro.core.forward_index import VALUE_FORMATS, ForwardIndex
from repro.kernels import modes as kernel_modes
from repro.serve import pipeline as serve_pipeline

__all__ = [
    "RetrieverConfig",
    "EngineImpl",
    "register_engine",
    "get_engine",
    "available_engines",
    "Retriever",
    "open_retriever",
    "ArtifactError",
    "MANIFEST_VERSION",
    "build_shard_arrays",
    "make_sharded_search",
    "map_local_ids",
    "merge_topk",
    "row_array_specs",
]

#: bumped whenever the artifact layout changes incompatibly; loading a
#: mismatching artifact fails loudly rather than mis-decoding arrays
MANIFEST_VERSION = 1
_MANIFEST_FORMAT = "repro.serve.retriever"
#: top-level manifest magic of a sharded artifact tree (DESIGN.md §9)
_SHARDED_FORMAT = "repro.serve.retriever-sharded"
_MANIFEST_FILE = "manifest.json"
_ARRAYS_FILE = "arrays.npz"


class ArtifactError(ValueError):
    """A saved index artifact is missing, corrupt, or incompatible."""


# ---------------------------------------------------------------------------
# config + engine registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RetrieverConfig:
    """Engine-agnostic serving configuration.

    ``params`` carries the engine-specific knobs (build AND search
    time); unknown keys are rejected against the engine's declared
    defaults, so typos fail at construction rather than silently
    serving defaults.

    ``backend`` selects the candidate-rescoring execution path
    (DESIGN.md §3, §7): ``"jnp"`` (reference), ``"pallas"`` (fused
    kernels from ``repro.kernels.registry`` in their default —
    compiled — mode), or an explicit kernel mode
    ``"pallas_interpret"`` / ``"pallas_compiled"``
    (``repro.kernels.modes``). Top-k ids are identical across all
    backends, asserted by the parity suite and ``make kernel-parity``.

    ``batch_size`` is the expected steady-state query-batch size: it
    joins the pipeline's padding-bucket set (DESIGN.md §8) so that
    batch shape gets an exact-fit compiled plan instead of rounding up
    to the next power-of-two bucket.

    ``vq`` is the VALUE codec (DESIGN.md §12), orthogonal to the id
    ``codec``: ``"f16"`` stores raw storage-dtype values; ``"u8_sq"``
    / ``"u4_sq"`` store per-row scalar-quant codes with learned clip
    ranges; ``"pq"`` stores product-quantizer codes plus a shared
    codebook. Quantized values are decoded in-kernel on the rescoring
    path; top-k ids stay identical across backends at every ``vq``
    (asserted by ``make value-parity``)."""

    engine: str = "seismic"
    codec: str = "uncompressed"
    backend: str = "jnp"  # a kernel_modes.SCORING_BACKENDS value
    k: int = 10
    batch_size: int | None = None  # steady-state batch hint → bucket set
    n_shards: int = 1  # index shards for the sharded path
    params: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    vq: str = "f16"  # value codec (core.values.VALUE_CODECS)

    def replace(self, **kw) -> "RetrieverConfig":
        return dataclasses.replace(self, **kw)


class EngineImpl:
    """Protocol every registered engine implements.

    An engine is a *pure-function view* of an index: host-side numpy
    array construction (``build_arrays`` / ``arrays_from_index`` /
    ``shard_build``) plus one static-shape ``search_one`` that serves
    the jit'd batched path, the dry-run (ShapeDtypeStruct arrays, via
    ``array_specs``) and the generic sharded driver unmodified."""

    name: str = "abstract"
    #: engine knob defaults; ``RetrieverConfig.params`` overrides
    defaults: Dict[str, Any] = {}
    #: True when one document can be reported by several index shards
    #: (the generic sharded merge then dedupes by doc id)
    dedupe_merge: bool = False

    # -- config plumbing ------------------------------------------------
    def params(self, cfg: RetrieverConfig) -> Dict[str, Any]:
        unknown = set(cfg.params) - set(self.defaults)
        if unknown:
            raise ValueError(
                f"unknown {self.name!r} engine params {sorted(unknown)}; "
                f"known: {sorted(self.defaults)}"
            )
        return {**self.defaults, **cfg.params}

    # -- host-side build ------------------------------------------------
    def build_arrays(self, fwd: ForwardIndex, cfg: RetrieverConfig) -> Dict[str, np.ndarray]:
        """Collection → engine arrays (numpy), via the host index."""
        raise NotImplementedError

    # -- serving --------------------------------------------------------
    def search_one(self, cfg: RetrieverConfig, n_docs: int, value_scale: float, arrays, q):
        """One dense query → (ids [k], scores [k]). Pure, static-shape."""
        raise NotImplementedError

    def search_batch(self, cfg: RetrieverConfig, n_docs: int, value_scale: float, arrays, Q):
        """A query batch → (ids [nq, k], scores [nq, k]) — the unit the
        pipeline's plan cache compiles (DESIGN.md §8).

        The default is ``vmap(search_one)``: per-query results are
        independent of batch-mates, which is what makes bucket padding
        sound. Engines whose candidate sets are query-independent
        override this with a genuinely batched decode-once/score-many
        dispatch (``FlatEngine`` → ``scoring.score_all_rows_batch`` →
        the kernel registry's ``rows_scan_batch``)."""
        return jax.vmap(
            partial(self.search_one, cfg, n_docs, value_scale, arrays)
        )(Q)

    def array_specs(self, cfg: RetrieverConfig, **dims) -> Dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStruct stand-ins for the engine arrays (dry-run)."""
        raise NotImplementedError

    # -- sharded build --------------------------------------------------
    def shard_build(self, fwd: ForwardIndex, cfg: RetrieverConfig, n_shards: int):
        """→ (per-shard array dicts, idmaps, n_docs_local, pad_values).

        ``idmaps[s]`` is i32 [n_docs_local + 1] mapping shard-local doc
        ids to global ones (sentinel → global n_docs); ``pad_values``
        feeds ``layout.pad_stack``."""
        raise NotImplementedError

    def build_shard(
        self, fwd: ForwardIndex, cfg: RetrieverConfig, lo: int, hi: int
    ) -> Dict[str, np.ndarray]:
        """Arrays of ONE self-contained shard over docs ``[lo, hi)``
        with shard-LOCAL ids — the unit the sharded artifact layer
        (DESIGN.md §9) writes per shard directory. The default builds
        the engine's normal arrays over the CSR slice; engines with a
        cheaper range path override (``FlatEngine`` packs rows straight
        from the per-shard pack offsets, no sub-index build)."""
        return self.build_arrays(fwd.slice(lo, hi), cfg)


_ENGINES: Dict[str, Callable[[], EngineImpl]] = {}


def register_engine(name: str):
    """Class decorator: make an ``EngineImpl`` servable by name."""

    def deco(factory: Callable[[], EngineImpl]):
        _ENGINES[name] = factory
        return factory

    return deco


def _ensure_builtin_engines() -> None:
    from . import engines  # noqa: F401  (registers seismic/hnsw/flat)


def get_engine(name: str) -> EngineImpl:
    _ensure_builtin_engines()
    try:
        return _ENGINES[name]()
    except KeyError:
        raise ValueError(
            f"no registered engine {name!r}; have {sorted(_ENGINES)}"
        ) from None


def available_engines() -> list[str]:
    _ensure_builtin_engines()
    return sorted(_ENGINES)


def row_array_specs(
    codec: str,
    *,
    n_docs: int,
    l_max: int,
    d_max: int,
    value_dtype=jnp.float16,
    bitpack_bits: int = 16,
    vq: str = "f16",
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs of the packed row form under ``codec`` — the
    candidate-rescoring arrays every engine shares (dry-run sizing).
    Under a quantized ``vq`` the value stream is u8 codes at
    ``l_max // code_factor`` width plus the clip columns / codebook
    (DESIGN.md §12); ``l_max`` must already be factor-aligned the way
    ``layout.pack_rows`` rounds it."""
    sds = jax.ShapeDtypeStruct
    value_codecs.check_vq(vq)
    factor = value_codecs.code_factor(vq)
    arrays = {
        "vals_rows": (
            sds((n_docs + 1, l_max), value_dtype)
            if vq == "f16"
            else sds((n_docs + 1, l_max // factor), jnp.uint8)
        ),
        "nnz_rows": sds((n_docs + 1,), jnp.int32),
    }
    if vq == "pq":
        arrays["vq_codebook"] = sds(
            (value_codecs.PQ_K, value_codecs.PQ_M), jnp.float32
        )
    elif vq != "f16":
        lo_key, sc_key = value_codecs.sq_keys(vq)
        arrays[lo_key] = sds((n_docs + 1, 1), jnp.float32)
        arrays[sc_key] = sds((n_docs + 1, 1), jnp.float32)
    if codec == "uncompressed":
        arrays["comps_rows"] = sds((n_docs + 1, l_max), jnp.int32)
    elif codec == "bitpack":
        arrays["words_rows"] = sds(
            (n_docs + 1, (l_max * bitpack_bits + 31) // 32), jnp.uint32
        )
        arrays["widths_rows"] = sds((n_docs + 1,), jnp.int32)
    else:  # (ctrl, data) byte-stream codecs
        group = layout.get_layout(codec).block_multiple
        arrays["ctrl_rows"] = sds((n_docs + 1, l_max // group), jnp.uint8)
        arrays["data_rows"] = sds((n_docs + 1, d_max), jnp.uint8)
    return arrays


# ---------------------------------------------------------------------------
# the Retriever surface
# ---------------------------------------------------------------------------


class Retriever:
    """Engine- and codec-agnostic serving handle.

    Holds the static device arrays of ONE engine×codec index plus the
    jit'd batched search. Construct with ``Retriever.build`` (host-side
    build from a ForwardIndex), ``Retriever.from_host_index`` (reuse an
    already-built ``SeismicIndex``/``HNSWIndex`` across codecs), or
    ``open_retriever`` (load a saved artifact, no re-encoding)."""

    def __init__(
        self,
        cfg: RetrieverConfig,
        arrays: Mapping[str, np.ndarray],
        *,
        n_docs: int,
        dim: int,
        value_scale: float,
        value_format: str,
        shard: str = "",
    ):
        self.impl = get_engine(cfg.engine)
        layout.get_layout(cfg.codec)  # raises listing the known codecs
        value_codecs.check_vq(cfg.vq)  # raises listing VALUE_CODECS
        if cfg.backend not in kernel_modes.SCORING_BACKENDS:
            raise ValueError(
                f"unknown backend {cfg.backend!r}; have "
                f"{list(kernel_modes.SCORING_BACKENDS)}"
            )
        if cfg.batch_size is not None and (
            not isinstance(cfg.batch_size, int)
            or isinstance(cfg.batch_size, bool)
            or cfg.batch_size < 1
        ):
            raise ValueError(
                f"batch_size must be a positive int or None, got "
                f"{cfg.batch_size!r}"
            )
        self.impl.params(cfg)  # rejects unknown engine knobs early
        self.cfg = cfg
        self.n_docs = int(n_docs)
        self.dim = int(dim)
        self.value_scale = float(value_scale)
        self.value_format = value_format
        #: shard-topology component of the plan key (DESIGN.md §9):
        #: "" for a monolithic index, "<shard>/<n_shards>" inside a
        #: ShardedRetriever — per-shard executables never collide
        self.shard = shard
        self.arrays = {k: jnp.asarray(v) for k, v in arrays.items()}
        # the compile layer (DESIGN.md §8): one executable per
        # (engine, codec, backend, k, bucket, shard); cfg.batch_size
        # joins the bucket set so the expected batch shape gets an
        # exact fit
        self.plans = serve_pipeline.PlanCache(self)
        self._pipeline: serve_pipeline.Pipeline | None = None

    def make_plans(self, buckets) -> "serve_pipeline.PlanCache":
        """A fresh plan cache with an explicit bucket set (the pipeline
        asks the retriever so sharded handles can answer too)."""
        return serve_pipeline.PlanCache(self, buckets)

    # -- construction ---------------------------------------------------
    @classmethod
    def build(cls, fwd: ForwardIndex, cfg: RetrieverConfig):
        """Host-side index construction: collection → servable arrays.

        With ``cfg.n_shards > 1`` the build routes to the sharded
        artifact layer (DESIGN.md §9): per-shard self-contained
        sub-indexes over contiguous doc ranges, returned as a
        ``ShardedRetriever`` whose ``save``/``open_retriever`` artifact
        tree is one directory per shard."""
        if cfg.n_shards > 1:
            from .sharded import ShardedRetriever

            return ShardedRetriever.build(fwd, cfg)
        impl = get_engine(cfg.engine)
        layout.get_layout(cfg.codec)
        return cls(
            cfg,
            impl.build_arrays(fwd, cfg),
            n_docs=fwd.n_docs,
            dim=fwd.dim,
            value_scale=float(fwd.value_format.scale),
            value_format=fwd.value_format.name,
        )

    @classmethod
    def from_host_index(cls, index, cfg: RetrieverConfig) -> "Retriever":
        """Wrap an already-built host index (``SeismicIndex`` /
        ``HNSWIndex``) — sweeping codecs (or backends) over one build.
        ``cfg``'s build-time params are ignored."""
        impl = get_engine(cfg.engine)
        if not hasattr(impl, "arrays_from_index"):
            raise ValueError(
                f"engine {cfg.engine!r} has no host-index form; use Retriever.build"
            )
        fwd = index.fwd
        return cls(
            cfg,
            impl.arrays_from_index(index, cfg),
            n_docs=fwd.n_docs,
            dim=fwd.dim,
            value_scale=float(fwd.value_format.scale),
            value_format=fwd.value_format.name,
        )

    # -- serving ----------------------------------------------------------
    def search(self, Q, k: int | None = None):
        """[nq, dim] dense queries → (ids [nq, k], scores [nq, k]).

        Dispatches through the plan cache: ``Q`` pads up to its
        smallest covering bucket and runs the warm compiled plan for
        that ``(engine, codec, backend, k, bucket)`` key — padded
        slots carry the zero query and are sliced off, so results are
        byte-identical to an exact-shape dispatch (DESIGN.md §8).

        ``k`` defaults to ``cfg.k`` (the static top-k the search graph
        was traced with); any smaller k is a free slice."""
        ids, scores = self.plans.search(Q)
        if k is None or k == self.cfg.k:
            return ids, scores
        if k > self.cfg.k:
            raise ValueError(
                f"k={k} exceeds the static cfg.k={self.cfg.k}; rebuild the "
                f"Retriever with a larger cfg.k"
            )
        return ids[:, :k], scores[:, :k]

    def pipeline(self, **kw) -> "serve_pipeline.Pipeline":
        """The micro-batching scheduler over this retriever
        (DESIGN.md §8). With no arguments, one default instance is
        created lazily and reused (it shares this retriever's plan
        cache); keyword arguments (``buckets``, ``deadline_us``,
        ``cache_size``, ``clock``) construct a fresh pipeline."""
        if kw:
            return serve_pipeline.Pipeline(self, **kw)
        if self._pipeline is None:
            self._pipeline = serve_pipeline.Pipeline(self)
        return self._pipeline

    def search_batch(self, Q):
        """Serve a query batch through the micro-batching pipeline:
        admission (result-cache lookup) → bucket coalescing → plan
        dispatch → per-query de-multiplex, results in submission
        order. Byte-identical to ``search`` (the parity suite); the
        result cache keys at the index's own value-quantization
        tolerance (``pipeline.quantized_query_key``), so on an
        f16-valued index two queries within one f16 ulp per component
        share a cache entry — pass ``cache_size=0`` or
        ``key_dtype=np.float32`` to ``pipeline(...)`` for strict
        exactness."""
        return self.pipeline().search_batch(Q)

    # -- artifact lifecycle ----------------------------------------------
    def save(self, path, *, compress: bool = True) -> pathlib.Path:
        """Write the index artifact: ``manifest.json`` + ``arrays.npz``.

        The npz payload holds the packed codec arrays exactly as served,
        so ``open_retriever`` performs zero re-encoding.
        ``compress=False`` stores npz members raw (ZIP_STORED) — the
        form the sharded artifact layer memory-maps (DESIGN.md §9)."""
        host = {k: np.asarray(v) for k, v in self.arrays.items()}
        return write_artifact(
            path, manifest_dict(self.cfg, host, n_docs=self.n_docs,
                                dim=self.dim, value_scale=self.value_scale,
                                value_format=self.value_format),
            host, compress=compress,
        )


def manifest_dict(
    cfg: RetrieverConfig,
    host_arrays: Mapping[str, np.ndarray],
    *,
    n_docs: int,
    dim: int,
    value_scale: float,
    value_format: str,
    extra: Mapping[str, Any] | None = None,
) -> dict:
    """The monolithic-artifact manifest payload (serving config, corpus
    stats, per-array dtype/shape specs). ``extra`` merges in shard
    bookkeeping (``shard``, ``doc_lo``/``doc_hi``) for per-shard
    directories of a sharded tree (DESIGN.md §9)."""
    manifest = {
        "format": _MANIFEST_FORMAT,
        "version": MANIFEST_VERSION,
        "engine": cfg.engine,
        "codec": cfg.codec,
        "backend": cfg.backend,
        "k": cfg.k,
        "batch_size": cfg.batch_size,
        "n_shards": cfg.n_shards,
        "params": dict(cfg.params),
        "vq": cfg.vq,
        "n_docs": int(n_docs),
        "dim": int(dim),
        "value_scale": float(value_scale),
        "value_format": value_format,
        "arrays": {
            k: {"dtype": str(v.dtype), "shape": list(v.shape)}
            for k, v in host_arrays.items()
        },
    }
    if extra:
        manifest.update(extra)
    return manifest


def write_artifact(
    path,
    manifest: Mapping[str, Any],
    host_arrays: Mapping[str, np.ndarray],
    *,
    compress: bool = True,
) -> pathlib.Path:
    """Write one artifact directory: ``manifest.json`` + ``arrays.npz``.

    ``compress=False`` writes the npz members ZIP_STORED (raw npy bytes
    at a fixed offset inside the zip) — the property ``mmap_npz`` in
    ``repro.serve.sharded`` relies on to memory-map members in place."""
    path = pathlib.Path(path)
    path.mkdir(parents=True, exist_ok=True)
    with open(path / _MANIFEST_FILE, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    saver = np.savez_compressed if compress else np.savez
    saver(path / _ARRAYS_FILE, **dict(host_arrays))
    return path


def load_manifest(path) -> dict:
    """Read + parse ``manifest.json`` under ``path`` (ArtifactError on
    a missing or unparseable file); no semantic validation."""
    path = pathlib.Path(path)
    mf = path / _MANIFEST_FILE
    if not mf.is_file():
        raise ArtifactError(f"no {_MANIFEST_FILE} under {path}")
    try:
        return json.loads(mf.read_text(encoding="utf-8"))
    except json.JSONDecodeError as e:
        raise ArtifactError(f"corrupt manifest at {mf}: {e}") from None


def check_manifest_names(manifest: Mapping[str, Any], where) -> None:
    """Version / engine / codec / value-format validation shared by the
    monolithic and sharded openers. ``where`` names the offending file
    in the error."""
    version = manifest.get("version")
    if version != MANIFEST_VERSION:
        raise ArtifactError(
            f"artifact version {version!r} at {where} incompatible with "
            f"this build (expected {MANIFEST_VERSION}); rebuild the index"
        )
    engine, codec = manifest["engine"], manifest["codec"]
    if engine not in available_engines():
        raise ArtifactError(
            f"artifact engine {engine!r} is not registered; have "
            f"{available_engines()}"
        )
    if codec not in layout.available_layouts():
        raise ArtifactError(
            f"artifact codec {codec!r} is not registered; have "
            f"{layout.available_layouts()}"
        )
    if manifest["value_format"] not in VALUE_FORMATS:
        raise ArtifactError(
            f"unknown value_format {manifest['value_format']!r}; have "
            f"{sorted(VALUE_FORMATS)}"
        )
    vq = manifest.get("vq", "f16")  # pre-value-codec artifacts are f16
    if vq not in value_codecs.VALUE_CODECS:
        raise ArtifactError(
            f"unknown value codec {vq!r} at {where}; have "
            f"{list(value_codecs.VALUE_CODECS)}"
        )


def check_array_spec(
    spec: Mapping[str, Any], arrays: Mapping[str, np.ndarray], where
) -> None:
    """Manifest array specs vs the actual npz payload — names, dtypes
    and shapes must all agree or the artifact is rejected."""
    if set(spec) != set(arrays):
        raise ArtifactError(
            f"array payload mismatch at {where}: manifest lists "
            f"{sorted(spec)}, npz holds {sorted(arrays)}"
        )
    for k, meta in spec.items():
        got = arrays[k]
        if str(got.dtype) != meta["dtype"] or list(got.shape) != meta["shape"]:
            raise ArtifactError(
                f"array {k!r} at {where} is {got.dtype}{list(got.shape)}, "
                f"manifest says {meta['dtype']}{meta['shape']}"
            )


def cfg_from_manifest(manifest: Mapping[str, Any]) -> RetrieverConfig:
    return RetrieverConfig(
        engine=manifest["engine"],
        codec=manifest["codec"],
        backend=manifest.get("backend", "jnp"),  # pre-backend artifacts
        k=int(manifest["k"]),
        batch_size=manifest.get("batch_size"),  # pre-pipeline artifacts
        n_shards=int(manifest.get("n_shards", 1)),
        params=manifest.get("params", {}),
        vq=manifest.get("vq", "f16"),  # pre-value-codec artifacts
    )


def open_retriever(path):
    """Load a saved index artifact into a servable handle.

    Validates the manifest (format magic, version, engine/codec names,
    per-array dtype/shape) before touching the payload — an
    incompatible or tampered artifact raises ``ArtifactError`` instead
    of mis-decoding. A top-level *sharded* manifest
    (``format="repro.serve.retriever-sharded"``, written by
    ``Retriever.build(..., n_shards=S)``) dispatches to
    ``ShardedRetriever.open``, which memory-maps every shard's arrays —
    O(metadata) open regardless of corpus size (DESIGN.md §9).

    A *mutable* root — a directory holding a ``CURRENT`` pointer file
    written by ``MutableRetriever`` (DESIGN.md §10) — dispatches to
    ``segments.open_mutable``, which follows ``CURRENT`` to the live
    generation directory and reopens base + delta segments +
    tombstones exactly as last committed."""
    path = pathlib.Path(path)
    if (path / "CURRENT").is_file():
        from . import segments

        return segments.open_mutable(path)
    manifest = load_manifest(path)
    fmt = manifest.get("format")
    if fmt == _SHARDED_FORMAT:
        from .sharded import ShardedRetriever

        return ShardedRetriever.open(path, manifest)
    if fmt != _MANIFEST_FORMAT:
        raise ArtifactError(
            f"{path / _MANIFEST_FILE} is not a {_MANIFEST_FORMAT} artifact "
            f"(format={fmt!r})"
        )
    check_manifest_names(manifest, path / _MANIFEST_FILE)
    with np.load(path / _ARRAYS_FILE) as npz:
        arrays = {k: npz[k] for k in npz.files}
    check_array_spec(manifest["arrays"], arrays, path / _ARRAYS_FILE)
    cfg = cfg_from_manifest(manifest)
    return Retriever(
        cfg.replace(n_shards=1),  # one directory == one sub-index
        arrays,
        n_docs=manifest["n_docs"],
        dim=manifest["dim"],
        value_scale=manifest["value_scale"],
        value_format=manifest["value_format"],
    )


# ---------------------------------------------------------------------------
# generic sharded driver (DESIGN.md §4 / §7)
# ---------------------------------------------------------------------------


def build_shard_arrays(
    fwd: ForwardIndex,
    cfg: RetrieverConfig,
    n_shards: int | None = None,
    *,
    host_index=None,
):
    """Partition a collection into self-contained per-shard sub-indexes
    and stack their engine arrays with a leading shard dim.

    Returns (stacked jnp arrays, idmap [n_shards, n_docs_local+1],
    n_docs_local). How the split happens is the engine's business
    (Seismic: blocks round-robin + doc ownership; graph/flat:
    contiguous doc ranges); the stacking is shared ``pad_stack``.

    Pass ``host_index`` to reuse an already-built host index instead
    of rebuilding it inside the shard split (engines that partition by
    doc range rebuild per-range structures regardless and ignore it)."""
    impl = get_engine(cfg.engine)
    n_shards = n_shards or cfg.n_shards
    if host_index is not None and hasattr(impl, "shard_from_index"):
        dicts, idmaps, n_docs_local, pad_values = impl.shard_from_index(
            host_index, cfg, n_shards
        )
    else:
        dicts, idmaps, n_docs_local, pad_values = impl.shard_build(fwd, cfg, n_shards)
    stacked = {
        k: jnp.asarray(v) for k, v in layout.pad_stack(dicts, pad_values).items()
    }
    return stacked, jnp.asarray(np.stack(idmaps)), n_docs_local


def map_local_ids(idmap, ids, n_docs_global: int):
    """Shard-local candidate ids → global doc ids, sentinel-safe.

    ``idmap`` is i32 [n_docs_local + 1]: slot ``i < n_docs_local`` holds
    the global id of local doc ``i``, the last slot holds the
    out-of-corpus sentinel ``n_docs_global``. A bare ``jnp.take``
    CLIPS out-of-range indices (jax's default gather mode), so a -1
    padding id or a local id ≥ the shard's true size would silently
    alias doc 0 / the last doc — the global-id bug class the sharded
    regression suite pins down. Every local id outside
    ``[0, n_docs_local]`` maps to ``n_docs_global`` instead, which
    ``merge_topk`` masks to -inf."""
    n_local = idmap.shape[-1] - 1
    valid = (ids >= 0) & (ids <= n_local)
    mapped = jnp.take(idmap, jnp.clip(ids, 0, n_local))
    return jnp.where(valid, mapped, jnp.int32(n_docs_global))


def merge_topk(flat_ids, flat_scores, k: int, *, dedupe: bool, n_docs_global: int):
    """[nq, S·k] gathered per-shard candidates → global (ids, scores).

    The merge contract (DESIGN.md §9): every out-of-corpus id — negative
    padding sentinels *and* ids ≥ n_docs_global — is masked to -inf so it
    can never displace a real document; with ``dedupe`` (engines whose
    shards may report the same doc, e.g. Seismic block round-robin) the
    candidates are sorted by id and repeats masked before the final
    ``top_k``. ``jax.lax.top_k`` breaks score ties toward the lower
    index, so without dedupe the merge is byte-stable in shard order."""
    nq = flat_scores.shape[0]
    invalid = (flat_ids < 0) | (flat_ids >= n_docs_global)
    flat_scores = jnp.where(invalid, -jnp.inf, flat_scores)
    if dedupe:
        order = jnp.argsort(flat_ids, axis=1)
        si = jnp.take_along_axis(flat_ids, order, axis=1)
        ss = jnp.take_along_axis(flat_scores, order, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((nq, 1), bool), si[:, 1:] == si[:, :-1]], axis=1
        )
        flat_ids = si
        flat_scores = jnp.where(dup, -jnp.inf, ss)
    top_s, pos = jax.lax.top_k(flat_scores, k)
    return jnp.take_along_axis(flat_ids, pos, axis=1), top_s


def make_sharded_search(
    mesh,
    cfg: RetrieverConfig,
    n_docs_local: int,
    n_docs_global: int,
    value_scale: float,
    *,
    index_axis: str = "model",
    query_axes: tuple[str, ...] = ("data",),
    k_local: int | None = None,
):
    """ONE distributed search driver for every registered engine.

    The index is pre-partitioned into ``mesh.shape[index_axis]``
    self-contained sub-indexes (arrays carry a leading shard dim,
    sharded over ``index_axis``; ``idmap`` maps local → global doc
    ids, sentinel → n_docs_global). Queries shard over ``query_axes``
    and replicate across index shards; each device runs the engine's
    ``search_one`` on its shard, then an O(k) all-gather + top-k merge
    produces the global result — deduping by doc id first iff the
    engine declares ``dedupe_merge`` (a Seismic document's blocks
    scatter across shards; graph/flat doc ranges are disjoint).
    Collective bytes per query: 8·k·n_shards.

    ``k_local`` caps the per-shard candidate count below the merge's
    ``cfg.k`` — shards smaller than k serve their whole doc range and
    engines whose score vector is shard-sized (flat) cannot top-k past
    it; the merge sentinel-pads back up to ``cfg.k`` when needed."""
    from jax.sharding import PartitionSpec as P

    impl = get_engine(cfg.engine)
    local_cfg = (
        cfg if k_local is None or k_local == cfg.k else cfg.replace(k=k_local)
    )

    def local(arrays, idmap, Q):
        arrays = jax.tree.map(lambda a: a[0], arrays)  # drop shard dim
        idmap = idmap[0]
        ids, scores = jax.vmap(
            partial(impl.search_one, local_cfg, n_docs_local, value_scale, arrays)
        )(Q)
        gids = map_local_ids(idmap, ids, n_docs_global)  # sentinel-safe
        ag_s = jax.lax.all_gather(scores, index_axis)  # [S, nq, k]
        ag_i = jax.lax.all_gather(gids, index_axis)
        S, nq, k = ag_s.shape
        flat_s = ag_s.transpose(1, 0, 2).reshape(nq, S * k)
        flat_i = ag_i.transpose(1, 0, 2).reshape(nq, S * k)
        if S * k < cfg.k:  # k > corpus: sentinel-pad the merge width
            pad = cfg.k - S * k
            flat_i = jnp.pad(flat_i, ((0, 0), (0, pad)),
                             constant_values=n_docs_global)
            flat_s = jnp.pad(flat_s, ((0, 0), (0, pad)),
                             constant_values=-jnp.inf)
        return merge_topk(
            flat_i, flat_s, cfg.k,
            dedupe=impl.dedupe_merge, n_docs_global=n_docs_global,
        )

    qa = query_axes or None
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(index_axis), P(index_axis), P(qa, None)),
        out_specs=(P(qa, None), P(qa, None)),
        check_vma=False,
    )
