"""Small CPU runs of the harness: the cells of ``BENCHMARK.json`` at a
size a test run holds, with the look for a chip skipped."""

from __future__ import annotations

import time

import numpy as np

from bench import run, spec

#: documents per corpus in the CPU runs
N_DOCS = 2000
SEED = 2**31 + 4099  # larger than 32 signed bits hold


#: ``splade-seismic.open`` as its entries will read once a run on the chip
#: admits it to ``BENCHMARK.json`` (its files are here already)
SEISMIC = {
    "configs": [{"name": "msmarco-splade.seismic-dotvbyte", "source": "see file",
                 "file": "bench/configs/msmarco-splade.seismic-dotvbyte.json",
                 "reduced": ["n_docs"], "why": "see file"}],
    "workloads": [{"name": "splade-seismic.open", "config": "msmarco-splade.seismic-dotvbyte",
                   "traffic": "open-poisson-seismic", "chips": 1, "why": "see PERF.md"}],
    "end_to_end": [{"name": "p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
                    "source": "host_clock", "workloads": ["splade-seismic.open"]},
                   {"name": "recall_at_10", "unit": "fraction", "better": "higher",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["splade-seismic.open"]}],
    "per_layer": [{"name": n, "unit": u, "better": b, "source": "device_trace", "layer": "-",
                   "moves": "p95_ms", "workloads": ["splade-seismic.open"]}
                  for n, u, b in (("rows_kernel_ms_per_q.open", "ms", "lower"),
                                  ("engine_ms_per_q.open", "ms", "lower"),
                                  ("batch_fill.open", "%", "higher"),
                                  ("gen_lag_ms.open", "ms", "lower"),
                                  ("device_idle.open", "%", "lower"))],
}


def benchmark() -> dict:
    """``BENCHMARK.json`` with the seismic cell's entries, where it lacks them."""
    b = spec.load_benchmark()
    if not any(w["name"] == "splade-seismic.open" for w in b["workloads"]):
        for key, entries in SEISMIC.items():
            b[key] = b[key] + entries
    return b


def small_cell(workload: str) -> spec.Cell:
    cell = spec.resolve(benchmark(), workload)
    cell.config["corpus"]["n_docs"] = N_DOCS
    if cell.traffic["loop"] == "closed":
        cell.traffic["query_pool"] = 128
    else:
        cell.traffic["rate_qps"] = 16.0
    return cell


def run_small(workload: str, *, control: bool = False, trace: bool = False) -> dict:
    """One CPU run of ``workload`` at the small size, without JAX's
    persistent compilation cache (a test writes nothing to the checkout)."""
    from repro.launch import compile_cache

    lines = []
    enable = compile_cache.enable
    compile_cache.enable = lambda: None
    try:
        result = run.run_cell(small_cell(workload), SEED, 1.0, trace, require_chip=False,
                              control=control, t_start=time.perf_counter(), out=lines.append)
    finally:
        compile_cache.enable = enable
    assert len(lines) == 1
    return result


def break_plans(monkeypatch, fault):
    """Route every compiled search of the program through ``fault``,
    which takes (dense queries, ids, scores) as numpy and returns the
    (ids, scores) the search then hands on."""
    from repro.serve import pipeline

    orig = pipeline.SearchPlan.__call__

    def broken(self, Q):
        ids, scores = orig(self, Q)
        return fault(np.asarray(Q), np.array(ids), np.array(scores))

    monkeypatch.setattr(pipeline.SearchPlan, "__call__", broken)


def alter_one_answer(Q, ids, scores):
    """One query's best document replaced by another, where it is produced."""
    ids[0, 0] = (ids[0, 0] + 1) % N_DOCS
    return ids, scores


class drop_half_batch:
    """Half of the requests left out: every second query the program
    sees is answered as an empty query would be."""

    def __init__(self):
        self.seen = 0

    def __call__(self, Q, ids, scores):
        blank = (self.seen + np.arange(len(Q))) % 2 == 1
        self.seen += len(Q)
        ids[blank] = np.arange(ids.shape[1])[None, :]
        scores[blank] = 0.0
        return ids, scores


class swap_tickets:
    """Each answer handed to the next query the program sees, in the
    same batch or the next one: the de-multiplexing fault."""

    def __init__(self):
        self.last = None

    def __call__(self, Q, ids, scores):
        prev = self.last
        self.last = ids[-1].copy(), scores[-1].copy()
        ids, scores = np.roll(ids, 1, axis=0), np.roll(scores, 1, axis=0)
        if prev is not None:
            ids[0], scores[0] = prev
        return ids, scores
