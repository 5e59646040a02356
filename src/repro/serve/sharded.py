"""Sharded artifact tree + out-of-core serving (DESIGN.md §9).

The paper's point is that the forward index dominates index size;
compression buys nothing once the corpus outgrows one host's memory.
This module lifts sharding into the Retriever/artifact layer proper:

* ``Retriever.build(fwd, cfg)`` with ``cfg.n_shards > 1`` partitions
  ``[0, n_docs)`` into contiguous doc ranges (balanced, ragged last
  shard) and builds one SELF-CONTAINED sub-index per range with
  shard-local ids — every engine's ``build_shard`` — returning a
  ``ShardedRetriever``;
* ``save`` writes one directory per shard (an ordinary artifact:
  ``manifest.json`` + ``arrays.npz``, stored UNCOMPRESSED) plus a
  top-level shard manifest carrying per-shard doc ranges, codec, and
  array specs;
* ``open_retriever`` on the tree memory-maps every shard's arrays
  (``mmap_npz``) — O(metadata) open, no array bytes are read until a
  shard is admitted to residency — so a corpus 10–100× larger than
  device memory still opens instantly;
* serving fans a query batch over the shards: ``shard_map`` on a
  ``repro.dist.sharding.index_mesh`` when the host has ≥ n_shards
  devices, otherwise a sequential out-of-core round-robin with a
  bounded resident-shard LRU (``max_resident``); either way the
  per-shard top-k merge is the O(k) ``api.merge_topk`` contract
  (sentinel-safe global ids, dedupe iff the engine asks).

Residency policy: a shard is *resident* when its arrays have been
materialized onto the device as a per-shard ``Retriever`` (with its
own plan cache, keyed by the ``"<shard>/<n_shards>"`` plan-key shard
component). At most ``max_resident`` shards are resident at once;
admission beyond that evicts the least-recently-used shard, dropping
its device arrays AND its compiled plans — re-admission recompiles,
which ``plans.compiles`` keeps counting: recompiles are the honest
cost of running out-of-core. ``resident_bytes()`` /
``peak_resident_bytes`` expose the quantity the LRU bounds
(gated by ``benchmarks/table5_scale.py``).

Prefetch (DESIGN.md §11): the sequential out-of-core loop stages the
NEXT shard on a bounded worker pool while the device scores the
current one — the staging slot is ONE explicit buffer on top of the
``max_resident`` LRU (a classic double buffer: page-in + host→device
transfer + an AOT plan warm happen off the hot path, and the shard
that opens the next rotation is already resident-in-waiting).
``prefetch_hits`` / ``prefetch_misses`` count rotations served from
the staged buffer vs. rotations that paid admission on the critical
path; staged-but-discarded work folds its compiles into the evicted
counter, so recompile accounting stays honest either way.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import struct
import threading
import zipfile
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout
from repro.core.forward_index import ForwardIndex

from . import api
from . import pipeline as serve_pipeline
from .api import ArtifactError, Retriever, RetrieverConfig

__all__ = [
    "SHARD_DIR_FMT",
    "shard_ranges",
    "mmap_npz",
    "Shard",
    "ShardedPlanCache",
    "ShardedRetriever",
]

#: on-disk name of shard ``s`` inside a sharded artifact tree
SHARD_DIR_FMT = "shard_{:04d}"

# one bounded staging worker shared by every ShardedRetriever in the
# process: staging tasks are independent and short, and a shared
# daemon pool avoids spawning (and leaking) a thread per retriever —
# tests build hundreds of them
_PREFETCH_POOL: Optional[ThreadPoolExecutor] = None
_PREFETCH_POOL_LOCK = threading.Lock()


def _prefetch_pool() -> ThreadPoolExecutor:
    global _PREFETCH_POOL
    with _PREFETCH_POOL_LOCK:
        if _PREFETCH_POOL is None:
            _PREFETCH_POOL = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="shard-prefetch"
            )
        return _PREFETCH_POOL


def shard_ranges(n_docs: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous doc ranges tiling ``[0, n_docs)`` — balanced sizes
    (``n_docs % n_shards`` leading shards get one extra doc, so the
    last shard is the ragged one). Every shard must own ≥ 1 document:
    an empty shard serves nothing and breaks the static search shapes,
    so it is rejected at build time rather than discovered at query
    time."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be ≥ 1, got {n_shards}")
    if n_shards > n_docs:
        raise ValueError(
            f"n_shards={n_shards} exceeds n_docs={n_docs}: every shard "
            f"must own at least one document — lower n_shards or grow "
            f"the collection"
        )
    base, rem = divmod(n_docs, n_shards)
    bounds = np.cumsum([0] + [base + (1 if s < rem else 0) for s in range(n_shards)])
    return [(int(bounds[s]), int(bounds[s + 1])) for s in range(n_shards)]


def mmap_npz(path) -> Dict[str, np.ndarray]:
    """Memory-map every member of an *uncompressed* ``.npz`` in place.

    ``np.load(..., mmap_mode="r")`` silently ignores ``mmap_mode`` for
    ``.npz`` archives (it only applies to bare ``.npy`` files), so this
    parses the zip structure itself: ``np.savez`` members are
    ZIP_STORED, i.e. the raw ``.npy`` bytes sit verbatim at a fixed
    offset inside the archive — local file header (30 bytes + filename
    + extra field), then the npy magic/header, then the array data.
    Each member becomes an ``np.memmap`` view at that offset: opening
    costs O(metadata) and pages fault in on first touch.

    Zero-length members fall back to ordinary arrays (an empty range
    cannot be mapped). Compressed members, truncated archives and
    malformed npy headers raise ``ArtifactError``."""
    path = pathlib.Path(path)
    try:
        zf = zipfile.ZipFile(path)
    except FileNotFoundError:
        raise ArtifactError(f"missing shard payload {path}") from None
    except (zipfile.BadZipFile, OSError) as e:
        raise ArtifactError(
            f"corrupt npz at {path} ({e}): the payload is unreadable — "
            f"likely a truncated or partial write; rebuild the shard"
        ) from None
    out: Dict[str, np.ndarray] = {}
    file_size = path.stat().st_size
    with zf, open(path, "rb") as f:
        for info in zf.infolist():
            if info.compress_type != zipfile.ZIP_STORED:
                raise ArtifactError(
                    f"npz member {info.filename!r} in {path} is "
                    f"compressed (type {info.compress_type}); sharded "
                    f"artifacts must be written with ``compress=False`` "
                    f"(np.savez, not savez_compressed) to be "
                    f"memory-mappable — re-save the artifact"
                )
            f.seek(info.header_offset)
            hdr = f.read(30)
            if len(hdr) < 30 or hdr[:4] != b"PK\x03\x04":
                raise ArtifactError(
                    f"truncated npz at {path}: local header of member "
                    f"{info.filename!r} is incomplete; rebuild the shard"
                )
            fn_len, extra_len = struct.unpack("<HH", hdr[26:30])
            f.seek(info.header_offset + 30 + fn_len + extra_len)
            try:
                version = np.lib.format.read_magic(f)
                if version == (1, 0):
                    shape, fortran, dtype = np.lib.format.read_array_header_1_0(f)
                elif version == (2, 0):
                    shape, fortran, dtype = np.lib.format.read_array_header_2_0(f)
                else:
                    raise ValueError(f"unsupported npy format version {version}")
            except ArtifactError:
                raise
            except Exception as e:
                raise ArtifactError(
                    f"corrupt npy member {info.filename!r} in {path}: {e}"
                ) from None
            data_off = f.tell()
            nbytes = int(dtype.itemsize * np.prod(shape, dtype=np.int64))
            if data_off + nbytes > file_size:
                raise ArtifactError(
                    f"truncated npz at {path}: member {info.filename!r} "
                    f"needs {nbytes} bytes at offset {data_off} but the "
                    f"file holds {file_size} — partial write or "
                    f"corruption; rebuild the shard"
                )
            name = info.filename
            if name.endswith(".npy"):
                name = name[:-4]
            if nbytes == 0:
                out[name] = np.zeros(shape, dtype=dtype)
            else:
                out[name] = np.memmap(
                    path, dtype=dtype, mode="r", offset=data_off,
                    shape=shape, order="F" if fortran else "C",
                )
    return out


@dataclasses.dataclass
class Shard:
    """One shard of the tree: its global doc range plus its arrays —
    host numpy right after ``build``, ``np.memmap`` views after
    ``open`` (nothing resident until admission)."""

    doc_lo: int
    doc_hi: int
    arrays: Mapping[str, np.ndarray]

    @property
    def n_docs(self) -> int:
        return self.doc_hi - self.doc_lo

    def disk_bytes(self) -> int:
        return sum(int(np.asarray(a).nbytes) for a in self.arrays.values())


class ShardedPlanCache:
    """The pipeline-facing plan surface of a ``ShardedRetriever``.

    Same ``buckets``/``bucket_for``/``get``/``search``/``compiles``
    contract as ``pipeline.PlanCache``, so the micro-batching scheduler
    works unmodified over shards: each plan pads its batch to the
    bucket and fans the dispatch over the shards (mesh or sequential),
    where every shard hits its OWN per-shard plan cache — plan keys
    carry the ``"<shard>/<n_shards>"`` topology component, so shards of
    one tree (whose array shapes differ, e.g. the ragged last shard)
    never collide on an executable. ``compiles`` aggregates the
    per-shard counters plus everything evicted shards had compiled:
    out-of-core re-admission recompiles, and the recompile metric
    counts it honestly. ``upload_bytes`` counts the host batches the
    facade's own plans upload, once whatever the shard count: the
    per-shard plans are handed the uploaded batch."""

    def __init__(
        self,
        retriever: "ShardedRetriever",
        buckets: Optional[Sequence[int]] = None,
    ):
        cfg = retriever.cfg
        self.retriever = retriever
        self.buckets = serve_pipeline.plan_buckets(cfg.batch_size, buckets)
        self.k = cfg.k
        self._plans: Dict[int, serve_pipeline.SearchPlan] = {}
        self.upload_bytes = 0  # the facade's plans upload; sub-plans get device batches
        self._lock = threading.Lock()

    # same covering-bucket policy as the monolithic cache
    bucket_for = serve_pipeline.PlanCache.bucket_for

    @property
    def compiles(self) -> int:
        r = self.retriever
        with r._admit_lock:
            return r._evicted_compiles + sum(
                sr.plans.compiles for sr in r._resident.values()
            )

    def get(self, bucket: int) -> serve_pipeline.SearchPlan:
        with self._lock:
            plan = self._plans.get(bucket)
            if plan is None:
                from repro.kernels.modes import backend_mode, resolve_mode

                cfg = self.retriever.cfg
                key = serve_pipeline.PlanKey(
                    cfg.engine, cfg.codec, cfg.backend,
                    resolve_mode(backend_mode(cfg.backend)), cfg.k, bucket,
                    shard=f"*/{cfg.n_shards}", vq=cfg.vq,
                )
                plan = serve_pipeline.SearchPlan(
                    key, self.retriever._dispatch_shards, self
                )
                self._plans[bucket] = plan
            return plan

    search = serve_pipeline.PlanCache.search


class ShardedRetriever:
    """Serving handle over a sharded index: same ``search`` /
    ``pipeline`` / ``search_batch`` / ``save`` surface as ``Retriever``
    (the pipeline and launcher never special-case it), fanning every
    dispatch over per-shard sub-indexes and merging with the
    sentinel-safe O(k) contract (``api.merge_topk``).

    Construct with ``Retriever.build(fwd, cfg)`` at ``n_shards > 1``,
    or ``open_retriever(path)`` on a saved tree (memory-mapped)."""

    def __init__(
        self,
        cfg: RetrieverConfig,
        shards: Sequence[Shard],
        *,
        dim: int,
        value_scale: float,
        value_format: str,
        max_resident: int | None = None,
    ):
        if cfg.n_shards != len(shards):
            raise ValueError(
                f"cfg.n_shards={cfg.n_shards} but {len(shards)} shards given"
            )
        self.impl = api.get_engine(cfg.engine)
        layout.get_layout(cfg.codec)
        self.impl.params(cfg)
        self.cfg = cfg
        self.shards = list(shards)
        self.n_docs = self.shards[-1].doc_hi
        self.dim = int(dim)
        self.value_scale = float(value_scale)
        self.value_format = value_format
        #: bound on simultaneously-resident shards (sequential path);
        #: None/n_shards keeps everything warm — set 1 for strict
        #: out-of-core round-robin
        self.max_resident = (
            cfg.n_shards if max_resident is None else max(1, int(max_resident))
        )
        #: None = auto (mesh iff devices ≥ shards); True forces the
        #: mesh path (error when impossible); False forces sequential
        self.use_mesh: bool | None = None
        self._resident: "OrderedDict[int, Retriever]" = OrderedDict()
        self._evicted_compiles = 0
        self.evictions = 0
        self.peak_resident_bytes = 0
        self._mesh_state = None
        self._mesh_static = None  # stacked shard arrays (tombstone-free)
        #: live tombstones (mutable-index integration, DESIGN.md §10):
        #: sorted global doc ids masked to -inf in the shard merge
        self._tombstones = np.zeros(0, np.int64)
        self._tomb_mask = None  # jnp bool [n_docs + 1] when non-empty
        self._shard_tombs = [0] * cfg.n_shards
        # per-shard serving constants, hoisted OUT of the dispatch
        # rotation (admission must cost page-in + compile, not
        # re-derived host-side setup): candidate budget + sub-config
        # per shard, recomputed only when the tombstone set changes
        self._shard_k = [min(sh.n_docs, cfg.k) for sh in self.shards]
        self._shard_cfg = [
            cfg.replace(n_shards=1, k=b) for b in self._shard_k
        ]
        #: overlap the sequential rotation with staging of the next
        #: shard (DESIGN.md §11); flip off for the synchronous baseline
        self.prefetch = True
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self._staged: Optional[Tuple[int, "Future[Retriever]"]] = None
        # guards _resident/_staged/counters: the scheduler thread and
        # direct .search callers race the staging worker's hand-off
        self._admit_lock = threading.RLock()
        self.plans = ShardedPlanCache(self)
        self._pipeline: serve_pipeline.Pipeline | None = None

    # -- construction ---------------------------------------------------
    @classmethod
    def build(cls, fwd: ForwardIndex, cfg: RetrieverConfig) -> "ShardedRetriever":
        """Partition ``[0, n_docs)`` into ``cfg.n_shards`` contiguous
        ranges and build one self-contained sub-index per range
        (shard-local ids) via the engine's ``build_shard``."""
        impl = api.get_engine(cfg.engine)
        layout.get_layout(cfg.codec)
        impl.params(cfg)
        shards = [
            Shard(lo, hi, impl.build_shard(fwd, cfg, lo, hi))
            for lo, hi in shard_ranges(fwd.n_docs, cfg.n_shards)
        ]
        return cls(
            cfg, shards,
            dim=fwd.dim,
            value_scale=float(fwd.value_format.scale),
            value_format=fwd.value_format.name,
        )

    # -- tombstones (mutable-index integration, DESIGN.md §10) ----------
    def set_tombstones(self, ids) -> None:
        """Install the live tombstone set: global doc ids whose
        candidates must be masked to ``-inf`` in the shard merge (a
        ``MutableRetriever`` over a sharded base routes deletes here).

        Every shard's candidate budget grows by the TOTAL tombstone
        count — ``k_local = min(n_docs_s, k + n_tombs)``
        (``dist.sharding.tombstone_budget``) — so each shard still
        surfaces ``k`` *live* candidates even when every tombstoned doc
        outranks them: the parity-preserving extension of the
        shard-smaller-than-k rule. The budget is deliberately UNIFORM
        rather than per-shard-routed: the mesh path's shard_map bakes
        ONE ``k_local`` across devices (SPMD), and dedupe-merging
        engines tie-break by doc id over the gathered candidate strip,
        so byte-parity between the sequential and mesh paths requires
        both to surface identical per-shard candidate sets. Resident
        (or staged) shards whose budget changed are evicted — their
        compiled plans are stale; re-admission recompiles, counted
        honestly."""
        ids = np.unique(np.asarray(ids, dtype=np.int64).reshape(-1))
        if ids.size and (int(ids[0]) < 0 or int(ids[-1]) >= self.n_docs):
            raise ValueError(
                f"tombstone ids outside [0, {self.n_docs}): "
                f"[{ids[0]}, {ids[-1]}]"
            )
        bounds = [sh.doc_lo for sh in self.shards] + [self.n_docs]
        new_tombs = [int(c) for c in np.diff(np.searchsorted(ids, bounds))]
        new_k = [
            min(sh.n_docs, self.cfg.k + int(ids.size)) for sh in self.shards
        ]
        with self._admit_lock:
            for s in list(self._resident):
                if new_k[s] != self._shard_k[s]:
                    old = self._resident.pop(s)
                    self._evicted_compiles += old.plans.compiles
                    self.evictions += 1
            st = self._staged
            if st is not None and new_k[st[0]] != self._shard_k[st[0]]:
                # the staged build carries the old budget — retire it
                # (compiles fold into the evicted counter, as always)
                self._staged = None
                self._evicted_compiles += st[1].result().plans.compiles
            self._shard_tombs = new_tombs
            self._shard_k = new_k
            self._shard_cfg = [
                self.cfg.replace(n_shards=1, k=b) for b in new_k
            ]
            self._tombstones = ids
            if ids.size:
                # one extra slot so the out-of-corpus sentinel id n_docs
                # indexes cleanly (and reads False: already masked)
                mask = np.zeros(self.n_docs + 1, dtype=bool)
                mask[ids] = True
                self._tomb_mask = jnp.asarray(mask)
            else:
                self._tomb_mask = None
            self._mesh_state = None  # the mesh path bakes k_local at trace

    # -- residency (the out-of-core core) -------------------------------
    def _build_shard(self, s: int) -> Retriever:
        """Materialize shard ``s`` as a sub-``Retriever``: pages the
        (possibly memory-mapped) arrays in and puts them on the device.
        Pure build — no LRU mutation, so the staging worker can run it
        off-thread. A shard smaller than its budget serves its ENTIRE
        doc range as the candidate list — the merge needs no more, and
        engines whose score vector is shard-sized (flat) cannot top-k
        past it (budgets hoisted in ``_shard_cfg``, see
        ``set_tombstones``)."""
        sh = self.shards[s]
        return Retriever(
            self._shard_cfg[s],
            sh.arrays,
            n_docs=sh.n_docs,
            dim=self.dim,
            value_scale=self.value_scale,
            value_format=self.value_format,
            shard=f"{s}/{self.cfg.n_shards}",
        )

    def _stage(self, s: int, bucket: int) -> None:
        """Double-buffer: queue shard ``s`` for staging on the shared
        worker pool — page-in + device put (``_build_shard``) + an AOT
        warm of the ``bucket`` plan — while the caller scores the
        current shard. One staged shard at a time (the explicit extra
        buffer the threading model documents); an already-resident or
        already-staged shard is a no-op, and a stale staging for a
        different shard is retired with its compiles counted."""
        with self._admit_lock:
            if s in self._resident:
                return
            st = self._staged
            if st is not None:
                if st[0] == s:
                    return
                self._staged = None
                self._evicted_compiles += st[1].result().plans.compiles
            dim = self.dim

            def task() -> Retriever:
                r = self._build_shard(s)
                plan = r.plans.get(r.plans.bucket_for(bucket))
                plan.warm(dim)
                return r

            self._staged = (s, _prefetch_pool().submit(task))

    def _consume_staged(self, s: int) -> Optional[Retriever]:
        """Take shard ``s`` out of the staging buffer if it's there —
        blocking on an in-flight build (still a win: the build started
        a rotation ago). A staged retriever whose budget went stale
        between staging and admission is discarded, compiles counted.
        Callers hold ``_admit_lock``."""
        st = self._staged
        if st is None or st[0] != s:
            return None
        self._staged = None
        r = st[1].result()
        # the double buffer at its fullest, whatever the thread timing:
        # the staged build has landed, the previous shard is resident
        self.peak_resident_bytes = max(
            self.peak_resident_bytes,
            self.resident_bytes() + sum(int(a.nbytes) for a in r.arrays.values()),
        )
        if r.cfg.k != self._shard_k[s]:
            self._evicted_compiles += r.plans.compiles
            return None
        return r

    def _staged_bytes(self) -> int:
        st = self._staged
        if st is None or not st[1].done() or st[1].exception() is not None:
            return 0
        return sum(int(a.nbytes) for a in st[1].result().arrays.values())

    def _shard_retriever(self, s: int) -> Retriever:
        """The per-shard sub-``Retriever``, admitted to the bounded
        LRU: served from residency, else from the staging buffer
        (``prefetch_hits``), else built on the critical path
        (``prefetch_misses``); admission beyond ``max_resident`` evicts
        the least-recently-used shard — device arrays and compiled
        plans both drop (re-admission recompiles; ``plans.compiles``
        counts it). ``peak_resident_bytes`` includes a completed staged
        build: the double buffer is real memory the bound must own."""
        with self._admit_lock:
            # sample BEFORE consuming the staging buffer: the moment a
            # staged build completes while the previous shard is still
            # resident is exactly the double-buffer transient the peak
            # must own (sampling after _consume_staged would miss it)
            self.peak_resident_bytes = max(
                self.peak_resident_bytes,
                self.resident_bytes() + self._staged_bytes(),
            )
            r = self._resident.get(s)
            if r is not None:
                self._resident.move_to_end(s)
                return r
            r = self._consume_staged(s)
            if r is not None:
                self.prefetch_hits += 1
            else:
                if self.prefetch and self.cfg.n_shards > 1:
                    self.prefetch_misses += 1
                r = self._build_shard(s)
            self._resident[s] = r
            while len(self._resident) > self.max_resident:
                _, old = self._resident.popitem(last=False)
                self._evicted_compiles += old.plans.compiles
                self.evictions += 1
            self.peak_resident_bytes = max(
                self.peak_resident_bytes,
                self.resident_bytes() + self._staged_bytes(),
            )
            return r

    def resident_bytes(self) -> int:
        """Device bytes currently held by resident shard sub-indexes —
        the quantity ``max_resident`` bounds (the scale benchmark's
        peak-memory gate reads ``peak_resident_bytes``)."""
        return sum(
            sum(int(a.nbytes) for a in r.arrays.values())
            for r in self._resident.values()
        )

    def disk_bytes(self) -> int:
        """Total on-disk array payload across shards (bytes gate)."""
        return sum(sh.disk_bytes() for sh in self.shards)

    # -- shard fan-out ----------------------------------------------------
    def _global_ids(self, s: int, ids):
        """Shard-local → global doc ids, sentinel-safe (the merge
        contract): contiguous ranges make the map an offset add, but
        ONLY for ids inside ``[0, n_local)`` — negative padding
        sentinels and out-of-range ids go to the out-of-corpus sentinel
        ``n_docs``, never through arithmetic (the clip-aliasing bug
        class ``api.map_local_ids`` documents)."""
        sh = self.shards[s]
        valid = (ids >= 0) & (ids < sh.n_docs)
        return jnp.where(valid, ids + sh.doc_lo, jnp.int32(self.n_docs))

    def _dispatch_shards(self, Q):
        """One padded ``[bucket, dim]`` batch → merged global top-k.
        The sequential rotation stages shard ``s+1`` (wrapping — the
        wrap primes the NEXT batch's opening shard during the
        inter-batch gap) while shard ``s`` scores."""
        if self._mesh():
            fn, arrays, idmaps = self._mesh_state
            return fn(arrays, idmaps, Q)
        S = self.cfg.n_shards
        do_prefetch = self.prefetch and S > 1
        bucket = int(Q.shape[0])
        flat_i, flat_s = [], []
        for s in range(S):
            r = self._shard_retriever(s)
            if do_prefetch:
                self._stage((s + 1) % S, bucket)
            ids, scores = r.plans.search(Q)
            gids = self._global_ids(s, ids)
            if self._tomb_mask is not None:
                # tombstone filtering in the shard merge: dead global
                # ids go to the out-of-corpus sentinel at -inf, exactly
                # like padding — merge_topk masks both the same way
                dead = jnp.take(self._tomb_mask, gids)
                gids = jnp.where(dead, jnp.int32(self.n_docs), gids)
                scores = jnp.where(dead, -jnp.inf, scores)
            flat_i.append(gids)
            flat_s.append(scores)
        flat_i = jnp.concatenate(flat_i, axis=1)
        flat_s = jnp.concatenate(flat_s, axis=1)
        if flat_i.shape[1] < self.cfg.k:  # k > n_docs: sentinel-pad
            pad = self.cfg.k - flat_i.shape[1]
            flat_i = jnp.pad(flat_i, ((0, 0), (0, pad)),
                             constant_values=self.n_docs)
            flat_s = jnp.pad(flat_s, ((0, 0), (0, pad)),
                             constant_values=-np.inf)
        return api.merge_topk(
            flat_i,
            flat_s,
            self.cfg.k,
            dedupe=self.impl.dedupe_merge,
            n_docs_global=self.n_docs,
        )

    def _mesh(self):
        """Build (once) and report the mesh path: a
        ``dist.sharding.index_mesh`` + ``api.make_sharded_search``
        driver over the stacked shard arrays, taken when the host has
        ≥ n_shards devices (unless ``use_mesh`` overrides).

        Live tombstones ride the mesh (DESIGN.md §11): dead docs are
        baked into the ID-MAP DATA — their local slot maps to the
        out-of-corpus sentinel, which the merge masks to ``-inf`` —
        and every shard's candidate budget is the uniform
        ``tombstone_budget`` (one ``k_local`` across devices: SPMD).
        Idmaps are runtime arguments, so mutating the tombstone SET
        never re-traces; only a changed budget (the tombstone COUNT
        moved) rebuilds the driver, against the cached stacked
        arrays."""
        if self.use_mesh is False or self.cfg.n_shards == 1:
            return None
        if self._mesh_state is not None:
            return self._mesh_state
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.dist.sharding import index_mesh, tombstone_budget

        mesh = index_mesh(self.cfg.n_shards)
        if mesh is None:
            if self.use_mesh:
                raise ValueError(
                    f"use_mesh=True but only {jax.device_count()} "
                    f"device(s) for {self.cfg.n_shards} shards"
                )
            return None
        n_local = max(sh.n_docs for sh in self.shards)
        # shard s lives on the mesh's device s (placed once, not
        # re-sent from one device on every call)
        on_shards = NamedSharding(mesh, P("model"))
        if self._mesh_static is None:
            # zero-padding to common shapes is safe: padding rows are
            # unreachable (in-shard ids never exceed the shard's own
            # sentinel) and zero rows score 0 → idmap sends them to the
            # out-of-corpus sentinel, which the merge masks
            self._mesh_static = {
                k: jax.device_put(v, on_shards)
                for k, v in layout.pad_stack(
                    [dict(sh.arrays) for sh in self.shards]
                ).items()
            }
        stacked = self._mesh_static
        idmaps = np.full(
            (self.cfg.n_shards, n_local + 1), self.n_docs, dtype=np.int32
        )
        for s, sh in enumerate(self.shards):
            idmaps[s, : sh.n_docs] = np.arange(
                sh.doc_lo, sh.doc_hi, dtype=np.int32
            )
            if self._shard_tombs[s]:
                dead = self._tombstones[
                    (self._tombstones >= sh.doc_lo)
                    & (self._tombstones < sh.doc_hi)
                ]
                idmaps[s, dead - sh.doc_lo] = self.n_docs
        fn = api.make_sharded_search(
            mesh, self.cfg, n_local, self.n_docs, self.value_scale,
            index_axis="model", query_axes=(),
            k_local=tombstone_budget(
                self.cfg.k, n_local, int(self._tombstones.size)
            ),
        )
        self._mesh_state = (fn, stacked, jax.device_put(idmaps, on_shards))
        return self._mesh_state

    def shard_devices(self) -> Dict[str, list]:
        """Mesh path: for every stacked shard array (and the id map),
        the id of the device holding each shard's slice, in shard
        order. Empty off the mesh path."""
        state = self._mesh()
        if not state:
            return {}
        _, arrays, idmaps = state
        out = {}
        for name, a in {**arrays, "idmap": idmaps}.items():
            by_shard = sorted(a.addressable_shards, key=lambda s: s.index[0].start or 0)
            out[name] = [s.device.id for s in by_shard]
        return out

    # -- serving (the Retriever surface) --------------------------------
    def make_plans(self, buckets) -> ShardedPlanCache:
        return ShardedPlanCache(self, buckets)

    def search(self, Q, k: int | None = None):
        """[nq, dim] queries → global (ids [nq, k], scores [nq, k]),
        byte-identical to the unsharded oracle's top-k under exhaustive
        engine budgets (the shard-parity gate)."""
        ids, scores = self.plans.search(Q)
        if k is None or k == self.cfg.k:
            return ids, scores
        if k > self.cfg.k:
            raise ValueError(
                f"k={k} exceeds the static cfg.k={self.cfg.k}; rebuild "
                f"with a larger cfg.k"
            )
        return ids[:, :k], scores[:, :k]

    def pipeline(self, **kw) -> serve_pipeline.Pipeline:
        if kw:
            return serve_pipeline.Pipeline(self, **kw)
        if self._pipeline is None:
            self._pipeline = serve_pipeline.Pipeline(self)
        return self._pipeline

    def search_batch(self, Q):
        return self.pipeline().search_batch(Q)

    # -- artifact lifecycle ---------------------------------------------
    def save(self, path, *, compress: bool = False) -> pathlib.Path:
        """Write the sharded artifact tree::

            path/manifest.json           top-level shard manifest
            path/shard_0000/manifest.json  ordinary artifact manifest
            path/shard_0000/arrays.npz     ZIP_STORED → memory-mappable
            path/shard_0001/…

        Per-shard directories are ordinary artifacts (``open_retriever``
        on one serves that shard standalone); the top level carries the
        per-shard doc ranges and array specs. Shard payloads default to
        UNCOMPRESSED npz — the property ``mmap_npz`` needs."""
        path = pathlib.Path(path)
        path.mkdir(parents=True, exist_ok=True)
        entries = []
        for s, sh in enumerate(self.shards):
            host = {k: np.asarray(v) for k, v in sh.arrays.items()}
            sub = api.manifest_dict(
                self.cfg, host,
                n_docs=sh.n_docs, dim=self.dim,
                value_scale=self.value_scale, value_format=self.value_format,
                extra={"shard": s, "doc_lo": sh.doc_lo, "doc_hi": sh.doc_hi},
            )
            sdir = SHARD_DIR_FMT.format(s)
            api.write_artifact(path / sdir, sub, host, compress=compress)
            entries.append(
                {"dir": sdir, "doc_lo": sh.doc_lo, "doc_hi": sh.doc_hi,
                 "arrays": sub["arrays"]}
            )
        top = api.manifest_dict(
            self.cfg, {}, n_docs=self.n_docs, dim=self.dim,
            value_scale=self.value_scale, value_format=self.value_format,
        )
        del top["arrays"]
        top["format"] = api._SHARDED_FORMAT
        top["shards"] = entries
        with open(path / api._MANIFEST_FILE, "w", encoding="utf-8") as f:
            json.dump(top, f, indent=1, sort_keys=True)
        return path

    @classmethod
    def open(cls, path, manifest: Mapping | None = None) -> "ShardedRetriever":
        """Open a sharded artifact tree with every shard's arrays
        MEMORY-MAPPED (``mmap_npz``) — O(metadata): no array bytes are
        read until a shard is admitted to residency.

        Validates before serving, raising ``ArtifactError`` with an
        actionable message on: shard-count mismatch between the
        top-level and per-shard manifests, overlapping/gapped doc
        ranges, per-shard engine/codec/version skew, and truncated or
        compressed shard payloads — never a silent wrong answer."""
        path = pathlib.Path(path)
        if manifest is None:
            manifest = api.load_manifest(path)
        top_mf = path / api._MANIFEST_FILE
        if manifest.get("format") != api._SHARDED_FORMAT:
            raise ArtifactError(
                f"{top_mf} is not a {api._SHARDED_FORMAT} tree "
                f"(format={manifest.get('format')!r})"
            )
        api.check_manifest_names(manifest, top_mf)
        n_shards = int(manifest.get("n_shards", 0))
        entries = manifest.get("shards")
        if not isinstance(entries, list) or not entries:
            raise ArtifactError(f"sharded manifest {top_mf} lists no shards")
        if len(entries) != n_shards:
            raise ArtifactError(
                f"shard-count mismatch at {top_mf}: n_shards={n_shards} "
                f"but {len(entries)} shard entries listed — the tree is "
                f"inconsistent; rebuild it or restore the missing shards"
            )
        n_docs = int(manifest["n_docs"])
        cfg = api.cfg_from_manifest(manifest)
        shards, expect_lo = [], 0
        for s, e in enumerate(entries):
            lo, hi = int(e["doc_lo"]), int(e["doc_hi"])
            if lo != expect_lo or hi <= lo:
                raise ArtifactError(
                    f"shard {s} at {top_mf} covers docs [{lo}, {hi}) but "
                    f"the previous shard ended at {expect_lo}: ranges "
                    f"must tile [0, {n_docs}) contiguously — no gaps, no "
                    f"overlaps; rebuild the tree"
                )
            expect_lo = hi
            sdir = path / e["dir"]
            sub = api.load_manifest(sdir)
            sub_mf = sdir / api._MANIFEST_FILE
            if sub.get("format") != api._MANIFEST_FORMAT:
                raise ArtifactError(
                    f"{sub_mf} is not a shard artifact "
                    f"(format={sub.get('format')!r})"
                )
            api.check_manifest_names(sub, sub_mf)
            for key in ("engine", "codec", "value_format"):
                if sub.get(key) != manifest.get(key):
                    raise ArtifactError(
                        f"shard {s} {key}={sub.get(key)!r} disagrees with "
                        f"the top-level manifest's {manifest.get(key)!r} — "
                        f"mixed-build skew; rebuild the tree consistently"
                    )
            if int(sub.get("n_shards", 1)) != n_shards:
                raise ArtifactError(
                    f"shard-count mismatch: {sub_mf} says "
                    f"n_shards={sub.get('n_shards')}, top-level says "
                    f"{n_shards} — the shard belongs to a different "
                    f"tree; rebuild"
                )
            if (
                int(sub.get("doc_lo", lo)) != lo
                or int(sub.get("doc_hi", hi)) != hi
                or int(sub["n_docs"]) != hi - lo
            ):
                raise ArtifactError(
                    f"shard {s} doc range disagrees between {top_mf} "
                    f"([{lo}, {hi})) and {sub_mf} "
                    f"([{sub.get('doc_lo')}, {sub.get('doc_hi')}), "
                    f"n_docs={sub.get('n_docs')}); rebuild the tree"
                )
            arrays = mmap_npz(sdir / api._ARRAYS_FILE)
            api.check_array_spec(sub["arrays"], arrays, sdir / api._ARRAYS_FILE)
            shards.append(Shard(lo, hi, arrays))
        if expect_lo != n_docs:
            raise ArtifactError(
                f"shard ranges at {top_mf} end at doc {expect_lo} but the "
                f"corpus has {n_docs} docs — a tail shard is missing; "
                f"rebuild the tree"
            )
        return cls(
            cfg, shards,
            dim=int(manifest["dim"]),
            value_scale=float(manifest["value_scale"]),
            value_format=manifest["value_format"],
        )
