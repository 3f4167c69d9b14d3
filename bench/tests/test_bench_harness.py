"""The harness finds every piece of a cell by name, refuses what it
cannot find, and finds a new piece that only adds files; ``run.py``
refuses to print a result off a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import harness, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_every_cell_resolves():
    bench = harness.benchmark()
    assert bench["command"] == ["python3", "bench/run.py"]
    for w in bench["workloads"]:
        cfg = harness.config(bench, w["config"])
        harness.module("drivers", cfg["driver"])
        harness.module("reference", cfg["reference"])
        traffic.load(w["traffic"])
        e2e = harness.metrics_of(bench, w["name"], "end_to_end")
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        for m in harness.metrics_of(bench, w["name"], "per_layer"):
            assert hasattr(harness.reader(m["name"]), "read")
            assert m["moves"] in [x["name"] for x in e2e]


def test_unknown_names_fail():
    bench = harness.benchmark()
    with pytest.raises(harness.BenchError):
        harness.cell(bench, "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.config(bench, "no-such-config")
    with pytest.raises(harness.BenchError):
        harness.module("drivers", "no_such_driver")
    with pytest.raises(harness.BenchError):
        harness.reader("no_such_metric.decode")


def test_a_new_metric_mix_and_config_are_found_by_adding_files(
        tmp_path, monkeypatch):
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    copy = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", copy / "BENCHMARK.json")
    (copy / "bench" / "metrics" / "queue_depth.py").write_text(
        "def read(run):\n    return float(len(run['counters']['q']))\n")
    (copy / "bench" / "traffic" / "short-burst.json").write_text(
        json.dumps({"kind": "lm", "arrival": {"process": "backlog",
                                              "min_pending": 2}}))
    (copy / "bench" / "configs" / "other.json").write_text(
        json.dumps({"driver": "lm_open_loop", "reference": "decoder"}))
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "other", "source": "x",
                             "file": "bench/configs/other.json",
                             "reduced": [], "why": "x"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "BENCH", copy / "bench")
    monkeypatch.setattr(harness, "ROOT", copy)
    monkeypatch.setattr(traffic, "HERE", copy / "bench")
    assert harness.reader("queue_depth.chat").read(
        {"counters": {"q": [1, 2]}}) == 2.0
    assert traffic.load("short-burst")["arrival"]["min_pending"] == 2
    assert harness.config(harness.benchmark(), "other")["driver"] == \
        "lm_open_loop"
    after = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm3b-decode-backlog", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_off_tpu_exits_nonzero_without_a_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
