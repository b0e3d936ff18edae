"""The four-chip cell: a tiny copy of ``tr-4host.sssp-stream`` on four
forced host devices, with the planner's device query steered to them (in
a subprocess: the device count is fixed when JAX starts), and the exchange
readers' arithmetic on synthetic traces."""
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from chipbench_fixtures import ROOT  # noqa: F401  (sys.path)
from chipbench import collectives, spec
from chipbench import trace as T
from repro.gopher.session import EXCHANGE_BYTES_EVENT

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [str(Path.cwd() / "tests" / "chipbench"), str(Path.cwd()),
                str(Path.cwd() / "src")]
import pytest
from chipbench_fixtures import bf16, drive, tiny_copy
from chipbench import run as run_mod, spec
from repro.launch import mesh as mesh_mod

mesh_mod.planner_devices = lambda: 4
here = tiny_copy(Path(tempfile.mkdtemp()))
bench = spec.benchmark()
cell = "tr-4host.sssp-stream"
out = {}


def brief(res):
    return {"correct": res["correct"], "checks": res["checks"],
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": res["metrics"], "device": res["device"]}


out["sound"] = brief(drive(cell, here, bench, Path(tempfile.mkdtemp())))
out["control"] = brief(drive(cell, here, bench, Path(tempfile.mkdtemp()),
                             control=bf16))
mp = pytest.MonkeyPatch()
out["no_exchange"] = brief(drive(cell, here, bench,
                                 Path(tempfile.mkdtemp()),
                                 fault="no_exchange", mp=mp))
mp.undo()
out["traced"] = brief(run_mod.run_cell(
    cell, 2**31 + 29, 1.0, True, bench=bench, here=here,
    work=Path(tempfile.mkdtemp()), require_chip=False,
    compile_cache=False))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=str(spec.ROOT))
    assert r.returncode == 0, r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_the_tiny_four_chip_cell_is_correct(runs):
    res = runs["sound"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"evps", "setup_s"}
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("case", ["control", "no_exchange"])
def test_the_control_and_a_lost_exchange_are_not_correct(runs, case):
    assert not runs[case]["correct"], runs[case]["checks"]


def test_the_traced_tiny_cell_reads_the_exchange_bytes(runs):
    res = runs["traced"]
    assert res["correct"], res["checks"]
    v = res["metrics"].get("exchange_bytes_per_instance", {}).get("value")
    assert v is not None and v > 0, res["metrics"]
    # the CPU has no device plane: no collective time is read
    assert "collective_s_per_instance" not in res["metrics"]


def test_the_traced_tiny_cell_reads_its_staging_and_engine_layers(runs):
    # the host-side readers it shares with tr-day.sssp-stream; those of
    # the device trace need a device plane, which the CPU has not
    res = runs["traced"]
    for name in ("staged_bytes_per_instance", "sweeps_per_instance",
                 "stage_s_per_instance", "staging_wait_s_per_instance",
                 "h2d_s_per_instance"):
        v = res["metrics"].get(name, {}).get("value")
        assert v is not None and v > 0, (name, res["metrics"])
    assert not {"device_idle.batch", "kernel_roofline.batch",
                "gather_idle_s_per_instance"} & set(res["metrics"])


# ---- the readers on synthetic input ----------------------------------------
def _reader(name):
    return spec.load_module(spec.HERE / "metrics" / f"{name}.py")


def _pass(t0, t1, inst=2):
    return dict(t_start=t0, t_end=t1, ok=True, instances=inst)


AR = "%pmin.2 = f32[20736]{0} all-reduce(f32[20736]{0} %x), to_apply=%min"
KERNEL = ('%fusion.1 = f32[8,2048]{1,0} custom-call(f32[8,2048]{1,0} %p), '
          'custom_call_target="tpu_custom_call"')
# names a collective only as an operand
ADD = "%add.3 = f32[] add(f32[] %all-reduce.7, f32[] %c)"
WHILE = "%while.9 = (f32[8,2048]{1,0}, s32[]) while(%tuple.4), body=%body"


def _trace_run():
    ops = {
        "/device:TPU:0": [(WHILE, 0, 100), (KERNEL, 10, 40), (AR, 30, 50),
                          (AR, 60, 70), (ADD, 80, 90)],
        "/device:TPU:1": [(AR, 0, 20)],
    }
    return SimpleNamespace(trace=T.Trace(window_ns=1e3, device_ops=ops),
                           passes=[_pass(0.0, 2.0)], t0=0.0, seconds=1.5)


def test_collective_time_is_read_by_opcode_and_averaged_over_chips():
    # chip 0: 20 + 10 ns of all-reduce (the add only reads one); chip 1: 20
    assert collectives.collective_seconds(_trace_run()) == \
        pytest.approx(25e-9)
    assert _reader("collective_s_per_instance").read(_trace_run()) == \
        pytest.approx(12.5e-9)


def test_without_a_trace_or_a_collective_the_readers_read_none():
    no_trace = SimpleNamespace(trace=None, passes=[_pass(0.0, 2.0)], t0=0.0,
                               seconds=1.5)
    no_coll = _trace_run()
    no_coll.trace.device_ops = {"/device:TPU:0": [(KERNEL, 0, 10)]}
    reader = _reader("collective_s_per_instance")
    assert reader.read(no_trace) is None
    assert reader.read(no_coll) is None


def test_exchange_bytes_sum_what_the_window_passes_published():
    import jax

    watch = collectives.ExchangeWatch()
    try:
        rec = jax.monitoring.record_scalar
        rec(EXCHANGE_BYTES_EVENT, 5.0)  # the warm-up pass, before the window
        passes = []
        for v in (1000.0, 3000.0):
            t0 = time.perf_counter()
            rec(EXCHANGE_BYTES_EVENT, v)
            rec("/gopher/other", 7.0)
            passes.append(_pass(t0, time.perf_counter()))
        run = SimpleNamespace(passes=passes, t0=passes[0]["t_start"],
                              seconds=0.0)
        run.seconds = passes[-1]["t_end"] - run.t0
        assert collectives.exchange_bytes_per_instance(run, watch) == \
            pytest.approx(1000.0)
        # a program that publishes nothing during the passes
        t = time.perf_counter()
        later = SimpleNamespace(passes=[_pass(t, t + 1.0)], t0=t,
                                seconds=1.0)
        assert collectives.exchange_bytes_per_instance(later, watch) is None
    finally:
        jax.monitoring.unregister_scalar_listener(watch._on)


def test_a_program_that_names_no_exchange_scalar_reads_none(monkeypatch):
    import jax
    from repro.gopher import session

    # a program from before the exchange counter: the scalar has no name
    monkeypatch.delattr(session, "EXCHANGE_BYTES_EVENT")
    watch = collectives.ExchangeWatch()
    try:
        t0 = time.perf_counter()
        jax.monitoring.record_scalar(EXCHANGE_BYTES_EVENT, 1000.0)
        run = SimpleNamespace(passes=[_pass(t0, time.perf_counter())],
                              t0=t0, seconds=0.0)
        run.seconds = run.passes[-1]["t_end"] - t0
        assert watch.event is None
        assert collectives.exchange_bytes_per_instance(run, watch) is None
    finally:
        jax.monitoring.unregister_scalar_listener(watch._on)
