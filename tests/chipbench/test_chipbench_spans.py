"""The span readers: their arithmetic on synthetic traces, and whole tiny
CPU runs in which the names the program opens are the names the readers
look for."""
from types import SimpleNamespace

import pytest

from chipbench_fixtures import bench, tiny_here  # noqa: F401  (fixtures)
from chipbench import run as run_mod, spans, spec
from chipbench import trace as T

DEV = "/device:TPU:0"


def _reader(name):
    return spec.load_module(spec.HERE / "metrics" / f"{name}.py")


def _pass(t0, t1, inst=12):
    return dict(t_start=t0, t_end=t1, ok=True, instances=inst)


def _run(host, device_ops=None, window_ns=10e9, passes=()):
    return SimpleNamespace(
        trace=T.Trace(window_ns=window_ns, device_ops=device_ops or {},
                      host=list(host)),
        passes=list(passes), t0=0.0, seconds=1.5)


def test_span_seconds_sum_the_named_events_inside_the_window():
    run = _run([("python3", "gofs.stage", 0, 2e9),
                ("python3", "gofs.stage", 3e9, 4e9),
                ("python3", "engine.put", 1e9, 1.5e9),
                ("python3", "gofs.stage", 9.5e9, 11e9)])  # cut at 10 s
    assert spans.seconds(run, "gofs.stage") == pytest.approx(3.5)
    assert spans.count(run, "gofs.stage") == 3
    assert spans.seconds(run, "engine.put") == pytest.approx(0.5)
    assert spans.intervals(run, "engine.put") == [(1e9, 1.5e9)]


def test_idle_under_a_span_that_half_overlaps_a_device_op():
    run = _run([("python3", "engine.gather", 0, 4e9)],
               {DEV: [("op", 2e9, 6e9)]})
    assert spans.idle_seconds_under(run, ["engine.gather"]) == \
        pytest.approx(2.0)
    # averaged over the device planes: a second chip busy all through
    run.trace.device_ops["/device:TPU:1"] = [("op", 0, 4e9)]
    assert spans.idle_seconds_under(run, ["engine.gather"]) == \
        pytest.approx(1.0)


def test_idle_counts_overlapping_spans_once():
    run = _run([("python3", "gofs.wait", 0, 3e9),
                ("python3", "engine.put", 2e9, 5e9)],
               {DEV: [("a", 1e9, 2e9), ("b", 1.5e9, 2.5e9)]})
    # union of spans [0, 5], device busy [1, 2.5]: 3.5 s idle
    assert spans.idle_seconds_under(run, ["gofs.wait", "engine.put"]) == \
        pytest.approx(3.5)


def test_per_instance_readers_divide_by_the_window_instances():
    host = [("python3", "gofs.stage", 0, 6e9),
            ("python3", "gofs.wait", 0, 1.2e9),
            ("python3", "engine.put", 1e9, 1.6e9),
            ("python3", "engine.build", 2e9, 4.4e9),
            ("python3", "engine.gather", 5e9, 7e9)]
    # the window holds both passes (the second is the first to end after
    # 1.5 s) and not the third: 24 instances
    run = _run(host, {DEV: [("op", 6e9, 7e9)]},
               passes=[_pass(0, 1), _pass(1, 2), _pass(2, 3)])
    want = {"stage_s_per_instance": 6.0 / 24,
            "staging_wait_s_per_instance": 1.2 / 24,
            "h2d_s_per_instance": 0.6 / 24,
            "program_build_s_per_instance": 2.4 / 24,
            "gather_idle_s_per_instance": 1.0 / 24}
    for name, v in want.items():
        assert _reader(name).read(run) == pytest.approx(v), name


def test_per_batch_readers_divide_by_the_service_execute_spans():
    host = [("gopher-serve", "service.execute", 0, 1e9),
            ("gopher-serve", "service.execute", 2e9, 3e9),
            ("gopher-serve", "service.execute", 4e9, 5e9),
            ("gopher-serve", "engine.build", 0.1e9, 0.7e9)]
    # the device runs the last half of each batch
    ops = {DEV: [("op", 0.5e9, 1e9), ("op", 2.5e9, 3e9),
                 ("op", 4.5e9, 5e9)]}
    run = _run(host, ops)
    assert _reader("program_build_ms_per_batch").read(run) == \
        pytest.approx(600.0 / 3)
    assert _reader("batch_host_ms").read(run) == pytest.approx(500.0)


SPAN_READERS = ["stage_s_per_instance", "staging_wait_s_per_instance",
                "h2d_s_per_instance", "program_build_s_per_instance",
                "program_build_ms_per_batch"]
IDLE_READERS = ["gather_idle_s_per_instance", "batch_host_ms"]


def test_nothing_to_read_gives_none():
    passes = [_pass(0, 2)]
    every = SPAN_READERS + IDLE_READERS
    no_trace = SimpleNamespace(trace=None, passes=passes, t0=0.0,
                               seconds=1.5)
    no_spans = _run([("python3", "PjitFunction(run_dense)", 0, 1e9)],
                    {DEV: [("op", 0, 1e9)]}, passes=passes)
    for name in every:
        assert _reader(name).read(no_trace) is None, name
        assert _reader(name).read(no_spans) is None, name
    host = [(t, n, 0, 1e9) for t, n in (
        ("python3", "gofs.stage"), ("python3", "engine.gather"),
        ("gopher-serve", "service.execute"))]
    no_device = _run(host, passes=passes)
    assert spans.idle_seconds_under(no_device, ["engine.gather"]) is None
    for name in IDLE_READERS:
        assert _reader(name).read(no_device) is None, name
    assert _reader("stage_s_per_instance").read(no_device) == \
        pytest.approx(1.0 / 12)
    # spans but no pass in the window: no instance to divide by
    no_device.passes = []
    assert _reader("stage_s_per_instance").read(no_device) is None


@pytest.mark.parametrize("cell,readers", [
    ("tr-day.sssp-stream", SPAN_READERS[:4]),
    ("tr-live.sssp-steady", SPAN_READERS[4:]),
])
def test_a_traced_tiny_run_reads_every_span(cell, readers, tiny_here,
                                            bench, tmp_path):
    res = run_mod.run_cell(cell, 2**31 + 29, 1.0, True, bench=bench,
                           here=tiny_here, work=tmp_path,
                           require_chip=False, compile_cache=False)
    assert res["correct"], res["checks"]
    for name in readers:
        v = res["metrics"].get(name, {}).get("value")
        assert v is not None and v > 0, (name, res["metrics"])
    # the CPU has no device plane: no idle time under a span is read
    assert not set(IDLE_READERS) & set(res["metrics"])
