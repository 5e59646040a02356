"""The harness finds every cell's files by name, picks up new ones
without an edit to any file that is there, and refuses to run without
a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

REPO = spec.REPO


def test_every_cell_resolves_to_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("closed", "open")
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e  # a metric's cells report what it moves
            assert callable(spec.metric_reader(m["name"]))


def test_seismic_cell_resolves_to_its_metrics():
    from bench.tests import harness

    bench = harness.benchmark()
    cell = spec.resolve(bench, "splade-seismic.open")
    assert cell.traffic["loop"] == "open" and cell.config["retriever"]["engine"] == "seismic"
    assert {m["name"] for m in cell.end_to_end} == {
        "p95_ms", "recall_at_10", "setup_s", "index_bytes_per_doc"}
    assert {m["name"] for m in cell.per_layer} == {
        "rows_kernel_ms_per_q.open", "engine_ms_per_q.open", "batch_fill.open",
        "gen_lag_ms.open", "device_idle.open"}
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    flat = spec.resolve(bench, "splade-flat.batch32")
    assert {m["name"] for m in flat.end_to_end} == {"setup_s", "qps", "index_bytes_per_doc"}
    assert {m["name"] for m in flat.per_layer} == {
        "rows_kernel_roofline.batch", "device_idle.batch"}


def test_every_metric_reader_finds_nothing_in_an_empty_reduction():
    for m in spec.load_benchmark()["per_layer"]:
        assert spec.metric_reader(m["name"])({}) is None


def test_new_traffic_and_metric_files_are_picked_up(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    (tmp_path / "bench" / "traffic" / "closed-batch8.json").write_text(json.dumps(
        {"loop": "closed", "batch": 8, "query_pool": 64, "check_sample": 64, "who": "test"}))
    (tmp_path / "bench" / "metrics" / "queries.batch8.py").write_text(
        "def read(r):\n    return r.get('completed')\n")
    bench = spec.load_benchmark(tmp_path / "BENCHMARK.json")
    bench["workloads"].append({"name": "splade-flat.batch8",
                               "config": "msmarco-splade.flat-dotvbyte",
                               "traffic": "closed-batch8", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queries.batch8", "unit": "queries", "better": "higher",
                               "source": "program_counter", "layer": "test", "moves": "qps",
                               "workloads": ["splade-flat.batch8"]})
    cell = spec.resolve(bench, "splade-flat.batch8", root=tmp_path)
    assert cell.traffic["batch"] == 8
    assert [m["name"] for m in cell.per_layer] == ["queries.batch8"]
    assert spec.metric_reader("queries.batch8", root=tmp_path)({"completed": 5}) == 5
    assert {p: p.read_bytes() for p in before} == before


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "splade-flat.batch32",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_run_refuses_without_a_tpu():
    proc = _run(REPO)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert _no_result(proc.stdout)
    assert "refused: JAX found no TPU" in proc.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0 and _no_result(proc.stdout)
    assert "No module named 'repro'" in proc.stderr


@pytest.mark.parametrize("name", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_cells_fit_the_contract(name):
    bench = spec.load_benchmark()
    w = {c["name"]: c for c in bench["workloads"]}[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((REPO / cfg["file"]).read_text())
    assert set(cfg["reduced"]) == set(config["reduced"])
    assert w["chips"] == 1 and len(w["why"]) <= 200


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.-]{1,16}$"


def test_benchmark_json_keeps_to_the_contract():
    import re

    raw = (REPO / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert b["command"][1].split("/")[0] in b["paths"]
    one_line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.match(NAME, c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert c["file"].split("/")[0] in b["paths"] and (REPO / c["file"]).is_file()
        assert all(re.match(NAME, k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    names = {c["name"] for c in b["configs"]}
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.match(NAME, w["name"]) and re.match(NAME, w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4) and one_line(w["why"])
        cells.add(w["name"])
    assert {w["config"] for w in b["workloads"]} == names
    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert e2e["setup_s"] == cells
    for m in b["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"]) and one_line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m["workloads"]) <= e2e[m["moves"]]
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    layers = {}
    for m in b["per_layer"]:  # one layer, one spelling
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
