"""Live index mutation: delta segments, tombstones, crash-safe merge
(DESIGN.md §10).

Every index in the serving layer used to be write-once: build → save →
open, with a full rebuild the only way to change a document. This
module layers the Lucene-style mutable-index model over the existing
artifact format without touching the engines:

* ``MutableRetriever`` wraps a *base* ``Retriever`` (or
  ``ShardedRetriever``) plus an ordered list of immutable **delta
  segments** — each a self-contained sub-index built through the same
  ``EngineImpl.build_arrays`` path and saved as an ordinary
  ``manifest.json + arrays.npz`` artifact — and per-part **tombstone
  masks** for deletes/updates. Because the paper's compressed forward
  index is the unit of immutability, StreamVByte/DotVByte compression
  carries over to segments unchanged.
* ``search`` fans a query batch over base + segments, maps part-local
  candidate ids through per-part id maps to *stable* doc ids (dead
  rows map to the ``-1`` sentinel at ``-inf``) and merges with the
  sentinel-safe ``api.merge_topk`` contract — top-k stays
  byte-identical to an oracle ``Retriever.build`` over the
  post-mutation corpus (live docs in stable-id order) for every
  engine × codec, enforced by ``make mutation-parity``.
* ``merge()`` — compaction — folds segments + tombstones back into the
  base via a vectorised ``ForwardIndex.concat``/``select`` pass and
  commits with an **atomic generation flip**: write
  ``generation_NNNN/`` completely, then atomically repoint the
  ``CURRENT`` file (``os.replace``). A crash anywhere before the flip
  leaves the previous generation intact and loadable (fault-injection
  tested via ``InjectedCrash`` hooks); orphan directories are ignored
  on open and reclaimed on retry.
* Every mutation and every generation flip bumps ``epoch`` — the
  pipeline's ``ResultCache`` auto-invalidates on the next ``submit``
  (a cached answer can never outlive the index state that produced
  it), and the fan-out plan key carries a ``gen`` component so a flip
  retires stale facade plans instead of silently reusing them.

Per-part candidate budgets extend by the part's own tombstone count
(``k_part = min(n_part, k + dead_part)``) so ``k`` *live* candidates
always survive the mask — the same parity-preserving rule the sharded
driver applies per shard (``ShardedRetriever.set_tombstones``).

On-disk layout under a mutable root (``open_retriever`` dispatches on
the ``CURRENT`` file)::

    root/CURRENT                     ← name of the live generation dir
    root/generation_0000/
        state.json                   ← atomic rewrite per mutation
        store.npz                    ← base CSR rows + stable ids
        base/                        ← ordinary (or sharded) artifact
        segment_0000/                ← ordinary artifact + store.npz
        segment_0001/…
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import threading
import time
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.forward_index import VALUE_FORMATS, ForwardIndex

from . import api
from . import pipeline as serve_pipeline
from .api import ArtifactError, Retriever, RetrieverConfig
from .sharded import ShardedRetriever

__all__ = [
    "InjectedCrash",
    "DeltaSegment",
    "MergeHandle",
    "MutablePlanCache",
    "MutableRetriever",
    "open_mutable",
    "MUTABLE_VERSION",
]

#: bumped whenever the mutable state layout changes incompatibly
MUTABLE_VERSION = 1
_MUTABLE_FORMAT = "repro.serve.mutable"
CURRENT_FILE = "CURRENT"
GEN_DIR_FMT = "generation_{:04d}"
SEGMENT_DIR_FMT = "segment_{:04d}"
STATE_FILE = "state.json"
STORE_FILE = "store.npz"


class InjectedCrash(RuntimeError):
    """Raised by the fault-injection hooks (``_crash_before_commit`` /
    ``crash_before_flip``) to simulate a process death between the
    payload write and the atomic commit — the window the crash-safety
    tests pin down."""


class MergeHandle:
    """Handle on a background compaction (``merge(background=True)``,
    DESIGN.md §11): the generation build runs on a worker thread while
    queries keep serving generation N; ``result()`` joins and returns
    the new base (re-raising anything the merge raised — an injected
    crash surfaces here, not in the serving threads).

    The worker demotes itself to a higher nice value (per-thread on
    Linux), so on a saturated host the compaction soaks up idle cycles
    between query bursts instead of time-slicing evenly against the
    serving path — the standard background-maintenance discipline."""

    #: nice increment for the merge worker (0 disables the demotion)
    NICENESS = 10

    def __init__(self, run):
        self._result = None
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, args=(run,), name="mutable-merge", daemon=True
        )
        self._thread.start()

    def _run(self, run) -> None:
        try:
            if self.NICENESS:
                # Linux scopes setpriority to a single thread when
                # given a thread id; elsewhere this raises and the
                # merge simply runs at normal priority
                os.setpriority(
                    os.PRIO_PROCESS, threading.get_native_id(),
                    os.getpriority(os.PRIO_PROCESS, 0) + self.NICENESS,
                )
        except (AttributeError, OSError):
            pass
        try:
            self._result = run()
        except BaseException as e:  # surfaces via result()
            self._exc = e

    def done(self) -> bool:
        return not self._thread.is_alive()

    def result(self, timeout: Optional[float] = None):
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError(f"merge still running after {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._result


def _atomic_write(path: pathlib.Path, text: str) -> None:
    """Write-then-rename: the commit primitive. ``os.replace`` is
    atomic on POSIX, so readers observe either the old or the new
    content, never a partial file."""
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(text, encoding="utf-8")
    os.replace(tmp, path)


def _store_dict(fwd: ForwardIndex, ids: np.ndarray) -> Dict[str, np.ndarray]:
    return {
        "components": fwd.components,
        "values": fwd.values,
        "offsets": fwd.offsets,
        "ids": np.asarray(ids, np.int64),
    }


def _load_store(path: pathlib.Path, dim: int, value_format: str
                ) -> Tuple[ForwardIndex, np.ndarray]:
    if not path.is_file():
        raise ArtifactError(f"missing row store {path}")
    with np.load(path) as z:
        fwd = ForwardIndex(
            components=z["components"],
            values=z["values"],
            offsets=z["offsets"],
            dim=dim,
            value_format=VALUE_FORMATS[value_format],
        )
        ids = z["ids"]
    if fwd.n_docs != len(ids):
        raise ArtifactError(
            f"row store {path} holds {fwd.n_docs} rows but {len(ids)} ids"
        )
    return fwd, ids


@dataclasses.dataclass
class DeltaSegment:
    """One immutable delta segment: its stable doc ids, CSR row store
    (for merge/compaction), engine arrays (the servable sub-index),
    and the per-row tombstone mask."""

    ids: np.ndarray  # i64 [n] stable doc ids
    fwd: ForwardIndex  # the segment's own rows (merge source)
    arrays: Mapping[str, np.ndarray]  # EngineImpl.build_arrays output
    dead: np.ndarray  # bool [n]

    @property
    def n_docs(self) -> int:
        return len(self.ids)


@dataclasses.dataclass(frozen=True)
class _Part:
    """One fan-out target: a plan surface (``PlanCache`` or the
    sharded facade — same search contract) plus the part-local →
    stable id map (i32 [n_local + 1], dead rows and the sentinel slot
    hold -1)."""

    plans: object
    idmap: jnp.ndarray
    n_local: int


class MutablePlanCache:
    """Pipeline-facing plan surface of a ``MutableRetriever`` — the
    same ``buckets``/``bucket_for``/``get``/``search``/``compiles``
    contract as ``pipeline.PlanCache``, so the micro-batching
    scheduler serves a mutable index unmodified.

    Each plan fans the dispatch over base + segments; its key carries
    ``shard="mut"`` and the generation component ``gen="g<N>"`` — a
    merge/compaction flip changes the component, so the facade plan is
    *retired* (counted in ``retired``) and recreated against the new
    base rather than silently reused. ``compiles`` aggregates every
    part's plan-cache counter plus everything retired parts had
    compiled: mutation-driven recompiles are the honest cost of
    serving a moving corpus."""

    def __init__(
        self,
        retriever: "MutableRetriever",
        buckets: Optional[Sequence[int]] = None,
    ):
        cfg = retriever.cfg
        self.retriever = retriever
        self.buckets = serve_pipeline.plan_buckets(cfg.batch_size, buckets)
        self.k = cfg.k
        self._plans: Dict[int, serve_pipeline.SearchPlan] = {}
        self.upload_bytes = 0  # the facade's plans upload; sub-plans get device batches
        self.retired = 0
        self._lock = threading.Lock()

    bucket_for = serve_pipeline.PlanCache.bucket_for

    @property
    def compiles(self) -> int:
        return self.retriever._part_compiles()

    def get(self, bucket: int) -> serve_pipeline.SearchPlan:
        with self._lock:
            gen = f"g{self.retriever.generation}"
            plan = self._plans.get(bucket)
            if plan is not None and plan.key.gen != gen:
                self.retired += 1
                plan = None
            if plan is None:
                from repro.kernels.modes import backend_mode, resolve_mode

                cfg = self.retriever.cfg
                key = serve_pipeline.PlanKey(
                    cfg.engine, cfg.codec, cfg.backend,
                    resolve_mode(backend_mode(cfg.backend)), cfg.k, bucket,
                    shard="mut", gen=gen, vq=cfg.vq,
                )
                plan = serve_pipeline.SearchPlan(key, self.retriever._dispatch, self)
                self._plans[bucket] = plan
            return plan

    search = serve_pipeline.PlanCache.search


class MutableRetriever:
    """Serving handle over a mutable index: the ``search`` /
    ``pipeline`` / ``search_batch`` / ``make_plans`` surface of
    ``Retriever`` plus ``insert`` / ``delete`` / ``update`` /
    ``merge``. Construct with ``MutableRetriever.create`` (fresh
    corpus, optionally persisted under a root directory) or
    ``open_retriever`` on a mutable root.

    Doc identity is the *stable id*: ``search`` returns stable ids,
    which survive merges (unlike base-local positions). ``next_id`` is
    the id-space high-water mark — the out-of-corpus sentinel for the
    merge contract — and ``epoch`` counts index-state changes (the
    ResultCache invalidation trigger)."""

    def __init__(
        self,
        cfg: RetrieverConfig,
        base,
        *,
        base_fwd: ForwardIndex,
        base_ids: np.ndarray,
        base_dead: Optional[np.ndarray] = None,
        segments: Optional[List[DeltaSegment]] = None,
        next_id: Optional[int] = None,
        generation: int = 0,
        epoch: int = 0,
        root=None,
    ):
        if base_fwd.n_docs != len(base_ids):
            raise ValueError(
                f"base store holds {base_fwd.n_docs} rows but "
                f"{len(base_ids)} ids"
            )
        self.cfg = cfg
        self.impl = api.get_engine(cfg.engine)
        self.base = base
        self.base_fwd = base_fwd
        self.base_ids = np.asarray(base_ids, np.int64)
        self.base_dead = (
            np.zeros(len(self.base_ids), bool)
            if base_dead is None else np.asarray(base_dead, bool).copy()
        )
        self.segments: List[DeltaSegment] = list(segments or [])
        all_ids = [self.base_ids] + [s.ids for s in self.segments]
        top = max((int(a.max()) for a in all_ids if a.size), default=-1)
        self.next_id = int(next_id) if next_id is not None else top + 1
        if self.next_id <= top:
            raise ValueError(f"next_id={next_id} ≤ live id {top}")
        self.generation = int(generation)
        self.epoch = int(epoch)
        self.root = pathlib.Path(root) if root is not None else None
        self.dim = base.dim
        self.value_scale = base.value_scale
        self.value_format = base.value_format
        self._handles: Optional[List[_Part]] = None
        self._wrappers: Dict[object, Retriever] = {}
        self._retired_compiles = 0
        # threading model (DESIGN.md §11): single writer — every
        # mutation (insert/delete/update/merge) holds _write_lock for
        # its whole run, so a background merge freezes the logical
        # corpus without read-side locks; _state_lock guards only the
        # brief in-memory windows readers race (part-list build, the
        # post-flip field swap, tombstone-mask flips)
        self._write_lock = threading.RLock()
        self._state_lock = threading.RLock()
        #: overlap counters (surfaced via ServeStats.sync_overlap):
        #: Σ merge build wall-clock, Σ commit-swap critical-section
        #: wall-clock (the bound on how long any query can block on a
        #: generation flip)
        self.merge_wall_us = 0.0
        self.blocked_swap_us = 0.0
        self.plans = MutablePlanCache(self)
        self._pipeline: serve_pipeline.Pipeline | None = None

    # -- construction ---------------------------------------------------
    @classmethod
    def create(cls, fwd: ForwardIndex, cfg: RetrieverConfig, root=None
               ) -> "MutableRetriever":
        """Build generation 0 from a fresh corpus: base index via the
        ordinary ``Retriever.build`` (sharded iff ``cfg.n_shards>1``),
        stable ids ``0..n_docs-1``. With ``root``, the generation
        directory + ``CURRENT`` pointer are committed immediately."""
        base = Retriever.build(fwd, cfg)
        m = cls(
            cfg, base, base_fwd=fwd,
            base_ids=np.arange(fwd.n_docs, dtype=np.int64), root=root,
        )
        if m.root is not None:
            m._write_generation(base, fwd, m.base_ids, m.generation)
            _atomic_write(
                m.root / CURRENT_FILE, GEN_DIR_FMT.format(m.generation)
            )
        return m

    # -- id bookkeeping --------------------------------------------------
    @property
    def n_docs(self) -> int:
        """Id-space size (the merge sentinel), NOT the live count."""
        return self.next_id

    @property
    def n_live(self) -> int:
        return int((~self.base_dead).sum()) + sum(
            int((~s.dead).sum()) for s in self.segments
        )

    def live_ids(self) -> np.ndarray:
        """Sorted stable ids of every live document."""
        parts = [self.base_ids[~self.base_dead]] + [
            s.ids[~s.dead] for s in self.segments
        ]
        ids = np.concatenate(parts) if parts else np.zeros(0, np.int64)
        return np.sort(ids)

    def live_corpus(self) -> Tuple[ForwardIndex, np.ndarray]:
        """(live rows in stable-id order, their sorted stable ids) —
        exactly the corpus an oracle ``Retriever.build`` sees: oracle
        doc position ``r`` is stable id ``live_ids[r]`` (the parity
        harness' mapping)."""
        big = ForwardIndex.concat(
            [self.base_fwd] + [s.fwd for s in self.segments]
        )
        all_ids = np.concatenate(
            [self.base_ids] + [s.ids for s in self.segments]
        )
        all_dead = np.concatenate(
            [self.base_dead] + [s.dead for s in self.segments]
        )
        live_pos = np.flatnonzero(~all_dead)
        live = all_ids[live_pos]
        order = np.argsort(live, kind="stable")
        return big.select(live_pos[order]), live[order]

    def _find_live(self, doc_id: int):
        """→ ("seg", index, row) | ("base", None, row) | None — where
        the live copy of ``doc_id`` lives (at most one across parts)."""
        for si in range(len(self.segments) - 1, -1, -1):
            s = self.segments[si]
            pos = np.flatnonzero((s.ids == doc_id) & ~s.dead)
            if pos.size:
                return ("seg", si, int(pos[0]))
        pos = np.flatnonzero((self.base_ids == doc_id) & ~self.base_dead)
        if pos.size:
            return ("base", None, int(pos[0]))
        return None

    # -- mutation --------------------------------------------------------
    def insert(self, docs, ids=None, *, _crash_before_commit: bool = False
               ) -> np.ndarray:
        """Insert a batch of documents as ONE new delta segment.

        ``docs`` is a ``ForwardIndex`` or an iterable of
        ``(components, values)`` pairs; ``ids`` assigns explicit stable
        ids (fresh by default) — reusing an id requires its previous
        copy to be deleted first (update-in-place =
        ``update``). Returns the assigned stable ids. Commit protocol:
        the segment artifact is written completely, then ``state.json``
        flips atomically — a crash in between leaves an orphan
        directory that open ignores and a retry reclaims."""
        with self._write_lock:
            return self._insert_locked(docs, ids, _crash_before_commit)

    def _insert_locked(self, docs, ids, _crash_before_commit: bool
                       ) -> np.ndarray:
        seg_fwd = (
            docs if isinstance(docs, ForwardIndex)
            else ForwardIndex.from_docs(docs, self.dim, self.value_format)
        )
        if seg_fwd.dim != self.dim:
            raise ValueError(f"segment dim {seg_fwd.dim} != index {self.dim}")
        if seg_fwd.value_format.name != self.value_format:
            raise ValueError(
                f"segment value_format {seg_fwd.value_format.name!r} != "
                f"index {self.value_format!r}"
            )
        n = seg_fwd.n_docs
        if n == 0:
            raise ValueError("cannot insert an empty segment")
        if ids is None:
            ids = np.arange(self.next_id, self.next_id + n, dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64).reshape(-1)
            if len(ids) != n:
                raise ValueError(f"{n} docs but {len(ids)} ids")
            if len(np.unique(ids)) != n or (ids < 0).any():
                raise ValueError("ids must be unique and ≥ 0")
            for i in ids:
                if self._find_live(int(i)) is not None:
                    raise ValueError(
                        f"doc id {int(i)} is still live; delete it first "
                        f"(or use update)"
                    )
        cfg1 = self.cfg.replace(n_shards=1)
        arrays = self.impl.build_arrays(seg_fwd, cfg1)
        name = SEGMENT_DIR_FMT.format(len(self.segments))
        if self.root is not None:
            sdir = self._gen_dir() / name
            if sdir.exists():  # orphan of a crashed earlier attempt
                shutil.rmtree(sdir)
            host = {k: np.asarray(v) for k, v in arrays.items()}
            api.write_artifact(
                sdir,
                api.manifest_dict(
                    cfg1, host, n_docs=n, dim=self.dim,
                    value_scale=self.value_scale,
                    value_format=self.value_format,
                ),
                host, compress=False,
            )
            np.savez(sdir / STORE_FILE, **_store_dict(seg_fwd, ids))
        if _crash_before_commit:
            raise InjectedCrash(f"crash before committing {name}")
        with self._state_lock:
            self.segments.append(
                DeltaSegment(ids=ids, fwd=seg_fwd, arrays=arrays,
                             dead=np.zeros(n, bool))
            )
            self.next_id = max(self.next_id, int(ids.max()) + 1)
            self._commit_memory()
        self._write_state()
        return ids

    def delete(self, ids) -> None:
        """Tombstone the live copy of every given stable id (KeyError
        if one is not live). Deletes touch only ``state.json`` — the
        segment/base payloads stay immutable."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        with self._write_lock:
            with self._state_lock:
                for i in ids:
                    hit = self._find_live(int(i))
                    if hit is None:
                        raise KeyError(f"doc id {int(i)} is not live")
                    kind, si, row = hit
                    if kind == "seg":
                        self.segments[si].dead[row] = True
                    else:
                        self.base_dead[row] = True
                self._commit_memory()
            self._write_state()

    def update(self, docs, ids) -> np.ndarray:
        """Update-in-place: tombstone the live copies, re-insert the
        new rows as a delta segment under the SAME stable ids."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        with self._write_lock:
            self.delete(ids)
            return self.insert(docs, ids=ids)

    def _commit_memory(self) -> None:
        """In-memory commit of a mutation: epoch bump + part-list
        invalidation, under ``_state_lock`` (callers hold it) so a
        concurrent reader sees either the old or the new index state,
        never a torn one."""
        self.epoch += 1
        self._handles = None

    # -- merge / compaction ---------------------------------------------
    def merge(self, *, crash_before_flip: bool = False,
              background: bool = False):
        """Fold every segment + tombstone into a fresh base index and
        commit via the atomic generation flip: write
        ``generation_{g+1}/`` completely (base artifact, row store,
        ``state.json``), then atomically repoint ``CURRENT``. A crash
        before the flip (``crash_before_flip`` injects one) leaves the
        previous generation untouched and loadable; in-memory state
        mutates only after the flip succeeds. Returns the new base.

        ``background=True`` (DESIGN.md §11) runs the whole build on a
        worker thread and returns a ``MergeHandle`` immediately:
        queries keep serving generation N throughout (compaction does
        not change the live corpus, so their answers stay correct and
        oracle-identical), other writers block on the write lock, and
        the commit swaps fields under ``_state_lock`` — a critical
        section of plain assignments, timed into ``blocked_swap_us``.
        The epoch bump makes the pipeline drop cached results on its
        next admission, exactly as a foreground merge does. A
        background merge also pre-builds the next generation's base
        wrapper and AOT-warms its bucket plans on the worker thread, so
        the first post-flip query pays a dispatch, not a compile."""
        if background:
            return MergeHandle(
                lambda: self._merge_sync(crash_before_flip, prewarm=True)
            )
        return self._merge_sync(crash_before_flip)

    def _merge_sync(self, crash_before_flip: bool, *, prewarm: bool = False):
        with self._write_lock:
            t0 = time.perf_counter()
            merged, new_ids = self.live_corpus()
            if merged.n_docs == 0:
                raise ValueError("merge would produce an empty corpus")
            cfg = self.cfg
            if cfg.n_shards > merged.n_docs:
                # every shard must own ≥ 1 doc; a shrunken corpus falls
                # back to fewer shards rather than failing the merge
                cfg = cfg.replace(n_shards=max(1, merged.n_docs))
            new_base = Retriever.build(merged, cfg)
            next_gen = self.generation + 1
            if self.root is not None:
                gdir = self.root / GEN_DIR_FMT.format(next_gen)
                if gdir.exists():  # orphan of a crashed earlier merge
                    shutil.rmtree(gdir)
                self._write_generation(new_base, merged, new_ids, next_gen)
                if crash_before_flip:
                    raise InjectedCrash(
                        f"crash before flipping CURRENT to generation "
                        f"{next_gen}"
                    )
                _atomic_write(
                    self.root / CURRENT_FILE, GEN_DIR_FMT.format(next_gen)
                )
            elif crash_before_flip:
                raise InjectedCrash(
                    "crash before the in-memory generation flip"
                )
            new_wrapper = None
            if prewarm and not isinstance(new_base, ShardedRetriever):
                # stage generation N+1's serving plans on THIS (worker)
                # thread before the flip (DESIGN.md §11): build the
                # post-merge base wrapper and AOT-compile its bucket
                # plans, so the swap below installs warm executables and
                # no query pays the first-touch compile of a fresh
                # generation
                k_b = min(new_base.n_docs, cfg.k)
                new_wrapper = Retriever(
                    cfg.replace(n_shards=1, k=k_b), new_base.arrays,
                    n_docs=new_base.n_docs, dim=self.dim,
                    value_scale=self.value_scale,
                    value_format=self.value_format, shard="mut:base",
                )
                for b in self.plans.buckets:
                    new_wrapper.plans.get(b).warm(int(self.dim))
            # ---- memory commit (post-flip only): plain assignments
            # under the state lock, so a concurrent reader sees either
            # generation N or N+1 in full, never a mix ----
            new_dead = np.zeros(len(new_ids), bool)
            with self._state_lock:
                # timed INSIDE the lock: this is the only window a
                # reader can be blocked by the commit (waiting for the
                # lock before it is ours measures readers blocking US,
                # which is them making progress, not an outage)
                t_swap = time.perf_counter()
                self._retire_parts()
                if new_wrapper is not None:
                    self._wrappers["base"] = new_wrapper
                self.cfg = cfg
                self.base = new_base
                self.base_fwd = merged
                self.base_ids = new_ids
                self.base_dead = new_dead
                self.segments = []
                self.generation = next_gen
                self.epoch += 1
                self._handles = None
                self.blocked_swap_us += (
                    time.perf_counter() - t_swap) * 1e6
            self.merge_wall_us += (time.perf_counter() - t0) * 1e6
            return new_base

    def _retire_parts(self) -> None:
        """Fold every live part's compile counter into the retired
        total before dropping the part (honest recompile accounting
        across generation flips)."""
        for r in self._wrappers.values():
            self._retired_compiles += r.plans.compiles
        self._wrappers.clear()
        if isinstance(self.base, ShardedRetriever):
            self._retired_compiles += self.base.plans.compiles

    # -- persistence -----------------------------------------------------
    def _gen_dir(self) -> pathlib.Path:
        return self.root / GEN_DIR_FMT.format(self.generation)

    def _write_generation(self, base, fwd: ForwardIndex, ids: np.ndarray,
                          generation: int) -> None:
        gdir = self.root / GEN_DIR_FMT.format(generation)
        gdir.mkdir(parents=True, exist_ok=True)
        base.save(gdir / "base", compress=False)
        np.savez(gdir / STORE_FILE, **_store_dict(fwd, ids))
        self._write_state(gdir=gdir, generation=generation, segments=[],
                          dead={"base": []},
                          epoch=self.epoch + (generation != self.generation))

    def _write_state(self, *, gdir: Optional[pathlib.Path] = None,
                     generation: Optional[int] = None,
                     segments: Optional[list] = None,
                     dead: Optional[dict] = None,
                     epoch: Optional[int] = None) -> None:
        if self.root is None:
            return
        if gdir is None:
            gdir = self._gen_dir()
        if segments is None:
            segments = [
                SEGMENT_DIR_FMT.format(i) for i in range(len(self.segments))
            ]
            dead = {"base": np.flatnonzero(self.base_dead).tolist()}
            for i, s in enumerate(self.segments):
                dead[SEGMENT_DIR_FMT.format(i)] = (
                    np.flatnonzero(s.dead).tolist()
                )
        state = {
            "format": _MUTABLE_FORMAT,
            "version": MUTABLE_VERSION,
            "generation": self.generation if generation is None else generation,
            "epoch": self.epoch if epoch is None else epoch,
            "next_id": self.next_id,
            "segments": segments,
            "dead": dead,
        }
        _atomic_write(gdir / STATE_FILE, json.dumps(state, indent=1,
                                                    sort_keys=True))

    # -- fan-out ---------------------------------------------------------
    def _wrapper(self, key, arrays, n_local: int, k_part: int,
                 label: str) -> Retriever:
        """Per-part serving wrapper at candidate budget ``k_part``
        (re-used while the budget holds; a budget change — the part's
        tombstone count moved — retires the old wrapper's compiles)."""
        cur = self._wrappers.get(key)
        if cur is not None and cur.cfg.k == k_part:
            return cur
        if cur is not None:
            self._retired_compiles += cur.plans.compiles
        r = Retriever(
            self.cfg.replace(n_shards=1, k=k_part), arrays,
            n_docs=n_local, dim=self.dim, value_scale=self.value_scale,
            value_format=self.value_format, shard=f"mut:{label}",
        )
        self._wrappers[key] = r
        return r

    def _idmap(self, ids: np.ndarray, dead: np.ndarray) -> jnp.ndarray:
        m = np.full(len(ids) + 1, -1, np.int32)
        m[:-1] = np.where(dead, -1, ids).astype(np.int32)
        return jnp.asarray(m)

    def _parts(self) -> List[_Part]:
        """The current fan-out part list, built (and memoized) under
        ``_state_lock``: a reader gets a SNAPSHOT — a plain list whose
        parts stay valid even if a merge commits mid-dispatch (the old
        generation's arrays/plans live as long as the list does, and
        compaction does not change the live corpus, so in-flight
        queries against the old parts stay oracle-correct)."""
        with self._state_lock:
            if self._handles is not None:
                return self._handles
            k = self.cfg.k
            parts: List[_Part] = []
            n_base = len(self.base_ids)
            if isinstance(self.base, ShardedRetriever):
                # the sharded base filters its own tombstones in the
                # shard merge (uniform tombstone-extended budgets) and
                # already returns its top-k LIVE candidates — no budget
                # extension needed at this level
                self.base.set_tombstones(np.flatnonzero(self.base_dead))
                parts.append(_Part(
                    self.base.plans,
                    self._idmap(self.base_ids, self.base_dead), n_base,
                ))
            else:
                k_b = min(n_base, k + int(self.base_dead.sum()))
                r = self._wrapper("base", self.base.arrays, n_base, k_b,
                                  "base")
                parts.append(_Part(
                    r.plans, self._idmap(self.base_ids, self.base_dead),
                    n_base,
                ))
            for i, s in enumerate(self.segments):
                k_s = min(s.n_docs, k + int(s.dead.sum()))
                r = self._wrapper(("seg", i), s.arrays, s.n_docs, k_s,
                                  f"seg{i}")
                parts.append(_Part(
                    r.plans, self._idmap(s.ids, s.dead), s.n_docs,
                ))
            self._handles = parts
            return parts

    def _part_compiles(self) -> int:
        with self._state_lock:
            n = self._retired_compiles + sum(
                r.plans.compiles for r in self._wrappers.values()
            )
            if isinstance(self.base, ShardedRetriever):
                n += self.base.plans.compiles
            return n

    def _dispatch(self, Q):
        """One padded ``[bucket, dim]`` batch → merged stable-id top-k
        over base + segments: per-part search, id-map to stable ids
        (dead rows and sentinels → -1 at -inf), sentinel-safe dedupe
        merge keyed on stable id — ties break toward the lower stable
        id, matching the oracle's positional tie-break over its
        stable-id-ordered corpus. Parts and the id-space sentinel are
        snapshotted together, so a merge committing mid-dispatch can't
        mix generations within one batch."""
        with self._state_lock:
            parts = self._parts()
            sentinel = self.next_id
        flat_i, flat_s = [], []
        for p in parts:
            ids, scores = p.plans.search(Q)
            valid = (ids >= 0) & (ids <= p.n_local)
            gids = jnp.take(p.idmap, jnp.clip(ids, 0, p.n_local))
            gids = jnp.where(valid, gids, jnp.int32(-1))
            scores = jnp.where(gids >= 0, scores, -jnp.inf)
            flat_i.append(gids)
            flat_s.append(scores)
        flat_i = jnp.concatenate(flat_i, axis=1)
        flat_s = jnp.concatenate(flat_s, axis=1)
        if flat_i.shape[1] < self.cfg.k:
            pad = self.cfg.k - flat_i.shape[1]
            flat_i = jnp.pad(flat_i, ((0, 0), (0, pad)), constant_values=-1)
            flat_s = jnp.pad(flat_s, ((0, 0), (0, pad)),
                             constant_values=-jnp.inf)
        return api.merge_topk(
            flat_i, flat_s, self.cfg.k,
            dedupe=True, n_docs_global=sentinel,
        )

    # -- serving (the Retriever surface) --------------------------------
    def make_plans(self, buckets) -> MutablePlanCache:
        return MutablePlanCache(self, buckets)

    def search(self, Q, k: int | None = None):
        """[nq, dim] queries → (stable ids [nq, k], scores [nq, k]),
        byte-identical to the post-mutation oracle under exhaustive
        engine budgets (the mutation-parity gate; oracle position
        ``r`` ↔ stable id ``live_ids()[r]``)."""
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


def open_mutable(root) -> MutableRetriever:
    """Open a mutable root at its committed generation: follow
    ``CURRENT`` → ``state.json`` → base artifact + row store + every
    listed segment (+ tombstone masks). Orphan directories from
    crashed commits are ignored; a missing or partially written
    generation raises ``ArtifactError`` rather than serving partial
    state."""
    root = pathlib.Path(root)
    cur = root / CURRENT_FILE
    if not cur.is_file():
        raise ArtifactError(f"no {CURRENT_FILE} under {root}")
    gen_name = cur.read_text(encoding="utf-8").strip()
    gdir = root / gen_name
    sf = gdir / STATE_FILE
    if not sf.is_file():
        raise ArtifactError(
            f"{cur} points at {gen_name!r} but {sf} is missing — the "
            f"committed generation is gone; restore it or rebuild"
        )
    try:
        state = json.loads(sf.read_text(encoding="utf-8"))
    except json.JSONDecodeError as e:
        raise ArtifactError(f"corrupt state at {sf}: {e}") from None
    if state.get("format") != _MUTABLE_FORMAT:
        raise ArtifactError(
            f"{sf} is not a {_MUTABLE_FORMAT} state "
            f"(format={state.get('format')!r})"
        )
    if state.get("version") != MUTABLE_VERSION:
        raise ArtifactError(
            f"mutable state version {state.get('version')!r} at {sf} "
            f"incompatible with this build (expected {MUTABLE_VERSION})"
        )
    base = api.open_retriever(gdir / "base")
    base_fwd, base_ids = _load_store(
        gdir / STORE_FILE, base.dim, base.value_format
    )
    dead_map = state.get("dead", {})

    def _mask(name: str, n: int) -> np.ndarray:
        m = np.zeros(n, bool)
        idx = np.asarray(dead_map.get(name, []), np.int64)
        if idx.size and (idx.min() < 0 or idx.max() >= n):
            raise ArtifactError(
                f"dead row index out of range for {name!r} at {sf}"
            )
        m[idx] = True
        return m

    segments: List[DeltaSegment] = []
    for name in state.get("segments", []):
        seg_r = api.open_retriever(gdir / name)
        seg_fwd, seg_ids = _load_store(
            gdir / name / STORE_FILE, base.dim, base.value_format
        )
        if seg_r.n_docs != len(seg_ids):
            raise ArtifactError(
                f"segment {name!r} artifact holds {seg_r.n_docs} docs "
                f"but its store holds {len(seg_ids)}"
            )
        segments.append(DeltaSegment(
            ids=seg_ids, fwd=seg_fwd, arrays=seg_r.arrays,
            dead=_mask(name, len(seg_ids)),
        ))
    return MutableRetriever(
        base.cfg, base,
        base_fwd=base_fwd, base_ids=base_ids,
        base_dead=_mask("base", len(base_ids)),
        segments=segments,
        next_id=int(state["next_id"]),
        generation=int(state["generation"]),
        epoch=int(state["epoch"]),
        root=root,
    )
