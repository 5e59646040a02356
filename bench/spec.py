"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

* a configuration: the JSON file its ``configs`` entry names;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, a module with
  ``read(reduction) -> float | None``.

A later cell, mix or metric is added as files and entries alone; no
file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list  # the BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(path=REPO / "BENCHMARK.json") -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(bench: dict, workload: str, root=REPO) -> Cell:
    """The cell named ``workload`` with its files loaded."""
    root = pathlib.Path(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(by_name)}")
    w = by_name[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text(encoding="utf-8"))
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text(encoding="utf-8")
    )
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(w, config, traffic, e2e, layer)


def metric_reader(name: str, root=REPO):
    """``read`` of ``bench/metrics/<name>.py`` (names may hold dots)."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
