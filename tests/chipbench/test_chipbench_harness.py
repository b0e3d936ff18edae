"""The harness finds every piece of a cell by name, refuses what it does
not know, and prints no result without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench_fixtures import bench  # noqa: F401  (fixture; sys.path)
from chipbench import spec

ROOT = spec.ROOT


def test_every_cell_resolves_its_files_by_name(bench):
    names = {m["name"] for m in bench["per_layer"]}
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("closed", "open")
        assert callable(cell.check.expected) and callable(cell.check.compare)
        assert set(cell.readers) <= names
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        # every per-layer metric moves an end-to-end metric of its cells
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]


def test_files_added_in_a_copy_are_found_without_editing_any(
        tmp_path, bench):
    here = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = json.loads((here / "configs" / "tr-day.json").read_text())
    cfg["name"] = "tr-week"
    (here / "configs" / "tr-week.json").write_text(json.dumps(cfg))
    tr = json.loads((here / "traffic" / "sssp-stream.json").read_text())
    tr["sources"] = {"dist": "scrambled_zipfian", "theta": 0.99}
    (here / "traffic" / "sssp-hot.json").write_text(json.dumps(tr))
    (here / "metrics" / "passes_in_window.py").write_text(
        "def read(run):\n    return float(len(run.passes)) or None\n")
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [
        {"name": "tr-week.sssp-hot", "config": "tr-week",
         "traffic": "sssp-hot", "chips": 1, "why": "added"}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "passes_in_window", "unit": "passes", "better": "higher",
         "source": "program_counter", "layer": "engine", "moves": "evps",
         "workloads": ["tr-week.sssp-hot"]}]
    new["end_to_end"] = [dict(m) for m in bench["end_to_end"]]
    for m in new["end_to_end"]:
        if m["name"] == "evps":
            m["workloads"] = m["workloads"] + ["tr-week.sssp-hot"]
    cell = spec.resolve("tr-week.sssp-hot", new, here)
    assert cell.config["name"] == "tr-week"
    assert cell.traffic["sources"]["dist"] == "scrambled_zipfian"
    assert "passes_in_window" in cell.readers
    assert {m["name"] for m in cell.end_to_end} == {"evps", "setup_s"}
    # nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())


def test_an_unknown_cell_or_device_kind_is_refused(bench):
    with pytest.raises(KeyError):
        spec.resolve("no-such.cell", bench)
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v4")


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_without_a_tpu_the_run_fails_and_prints_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"),
         "--workload", "tr-day.sssp-stream", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_a_checkout_of_only_the_benchmark_fails_and_prints_nothing(
        tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = _cpu_env()
    env.pop("PYTHONPATH", None)  # the program is not there to be found
    proc = subprocess.run(
        [*bench["command"], "--workload", "tr-day.sssp-stream", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
