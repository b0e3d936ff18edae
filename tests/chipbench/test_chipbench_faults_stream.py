"""Whole runs of the stream cells at a tiny size on the CPU, past the
look for a chip: a sound run is correct; the control (the reference at
bfloat16 in the program's place) and each fault planted under the timed
path are not."""
import pytest

from chipbench_fixtures import (  # noqa: F401  (fixtures)
    FAULTS, bench, bf16, drive, tiny_here)

CELLS = ["tr-day.sssp-stream", "tr-day.pagerank-stream"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, tiny_here, bench, tmp_path):
    res = drive(cell, tiny_here, bench, tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell, tiny_here, bench, tmp_path):
    res = drive(cell, tiny_here, bench, tmp_path, control=bf16)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_fault_is_not_correct(cell, fault, tiny_here, bench, tmp_path,
                                monkeypatch):
    res = drive(cell, tiny_here, bench, tmp_path, fault=fault,
                mp=monkeypatch)
    assert not res["correct"], (fault, res["checks"])
