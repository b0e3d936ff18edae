"""The trace reduction: busy union, idle gaps and op time on synthetic
events, and the parse of recorded traces."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

import chipbench_fixtures  # noqa: F401  (sets sys.path)
from chipbench import trace as T


def test_union_merges_overlapping_and_touching_intervals():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_counts_overlaps_once_and_clips_to_the_window():
    iv = [(-5, 2), (1, 4), (3, 6), (8, 20)]
    # [0,6] and [8,10] inside the window [0, 10]
    assert T.busy_ns(iv, 0, 10) == 8
    assert T.busy_ns([], 0, 10) == 0
    assert T.busy_ns([(12, 15)], 0, 10) == 0


def test_gaps_cover_the_window_edges():
    iv = [(2, 3), (2.5, 5), (7, 8)]
    assert T.gaps(iv, 0, 10) == [(0, 2), (5, 7), (8, 10)]
    assert T.gaps([(-1, 11)], 0, 10) == []
    assert T.gaps([], 0, 10) == [(0, 10)]


def test_busy_and_gaps_partition_the_window():
    iv = [(0.5, 1.5), (1, 2), (4, 4.5), (9, 12)]
    busy = T.busy_ns(iv, 0, 10)
    idle = sum(e - s for s, e in T.gaps(iv, 0, 10))
    assert busy + idle == pytest.approx(10)


def test_op_time_matches_names_and_clips():
    ops = [("_spmv_kernel.1", 0, 4), ("fusion.2", 4, 6),
           ("_spmv_kernel.3", 8, 12)]
    assert T.op_time(ops, 0, 10, r"_spmv_kernel") == 6
    assert T.op_time(ops, 0, 10) == 8
    assert T.top_ops(ops, 0, 10, n=1) == [("_spmv_kernel.1", 4e-9)]


def test_idle_gap_is_named_by_the_host_event_that_overlaps_it_most():
    host = [("main", "run_pass", 0, 100), ("pool", "fill_tiles", 10, 40),
            ("pool", "ThreadpoolListener::x", 10, 40)]
    assert T.host_activity(host, (12, 38)) == "pool/fill_tiles"
    assert T.host_activity(host, (60, 70)) == "main/run_pass"
    assert T.host_activity(host, (200, 300)) == "no host event"


def _xspace(window_ns):
    """A synthetic trace of one TPU with three ops and a module line,
    and one host thread."""
    text = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "_spmv_kernel" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.7" } }
  event_metadata { key: 3 value { id: 3 name: "jit_run" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 4500000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "TransferToDevice" } }
}
"""
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_load_reads_device_ops_and_host_events(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(10_000))
    tr = T.load(T.find_xplane(str(tmp_path)), 10_000)
    ops = tr.device_ops["/device:TPU:0"]
    # the module line is not an op: busy is the ops' union only
    assert sorted(n for n, _, _ in ops) == ["_spmv_kernel", "_spmv_kernel",
                                            "fusion.7"]
    summ = T.summary(tr)
    assert summ["busy_s"] == pytest.approx(5e-6)  # [1,5] and [8,9] us
    assert summ["window_s"] == pytest.approx(1e-5)
    gaps = dict((round(v * 1e9), k) for k, v in
                summ["breakdown"]["idle_gaps"])
    assert gaps[3000] == "main/TransferToDevice"  # the [5, 8] us gap
    assert summ["breakdown"]["device_ops"][0][0] == "_spmv_kernel"


def test_parse_of_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    tr = T.load(T.find_xplane(str(tmp_path)), 1e9)
    # the CPU has no device plane: nothing is counted as device time
    assert tr.device_ops == {}
    assert T.summary(tr)["busy_s"] == 0.0
    assert any("jit" in name or "dot" in name for _, name, _, _ in tr.host)


def test_short_name_keeps_instruction_opcode_and_target():
    hlo = ('%closed_call.35 = f32[17,1,128]{2,1,0:T(1,128)S(1)} custom-call('
           's32[253]{0:T(256)S(1)} %bitcast.91), custom_call_target='
           '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert T.short_name(hlo) == "%closed_call.35 custom-call tpu_custom_call"
    loop = ('%while.75 = (s32[]{:T(128)}, f32[8,2048]{1,0:T(8,128)}) '
            'while((s32[]{:T(128)}, f32[8,2048]{1,0:T(8,128)}) %tuple.1)')
    assert T.short_name(loop) == "%while.75 while"
    assert T.short_name("fusion.7") == "fusion.7"


def test_top_ops_rank_by_self_time_of_nested_ops():
    # a while loop [0, 10] holds two kernel calls of 3 each
    ops = [("%while.1 = f32[] while(f32[] %a)", 0, 10),
           ("%k.2 = f32[] custom-call(f32[] %b)", 1, 4),
           ("%k.2 = f32[] custom-call(f32[] %b)", 5, 8)]
    self_t = T.self_times(ops, 0, 10)
    assert self_t["%while.1 = f32[] while(f32[] %a)"] == 4
    assert self_t["%k.2 = f32[] custom-call(f32[] %b)"] == 6
    assert T.top_ops(ops, 0, 10) == [("%k.2 custom-call", 6e-9),
                                     ("%while.1 while", 4e-9)]
