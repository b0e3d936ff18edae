"""End-to-end metric arithmetic and the per-layer readers, on records
built by hand."""
import math
from types import SimpleNamespace

import numpy as np
import pytest

import chipbench_fixtures  # noqa: F401  (sets sys.path)
from chipbench import endtoend, layers, roofline
from chipbench import trace as T


def _pass(t0, t1, ok=True, inst=12, **kw):
    return dict(t_start=t0, t_end=t1, ok=ok, instances=inst, **kw)


def test_evps_counts_whole_passes_and_ends_with_the_last():
    passes = [_pass(0, 4), _pass(4, 8), _pass(8, 13), _pass(13, 17)]
    # window of 10 s: the third pass is the first to end after 10 s, so
    # the window is 13 s and holds 3 passes; the fourth is not counted
    assert endtoend.evps(passes, 0.0, 10.0, 1000) == pytest.approx(
        3 * 12 * 1000 / 13)
    assert [p["t_end"] for p in endtoend.pass_window(passes, 0, 10)] == \
        [4, 8, 13]


def test_evps_failed_pass_does_no_work_but_takes_its_time():
    passes = [_pass(0, 5), _pass(5, 11, ok=False)]
    assert endtoend.evps(passes, 0.0, 10.0, 1000) == pytest.approx(
        12 * 1000 / 11)


def test_latency_is_timed_from_the_due_time():
    qs = [dict(due=1.0, t_done=1.5, ok=True),
          dict(due=2.0, t_done=2.2, ok=True)]
    assert endtoend.latencies(qs) == pytest.approx([0.5, 0.2])


def test_percentiles_count_a_failed_query_as_missing():
    lat = [0.1 * i for i in range(1, 20)] + [math.inf]  # 20 queries
    assert endtoend.percentile(lat, 50) == pytest.approx(1.0)
    assert endtoend.percentile(lat, 90) == pytest.approx(1.8)
    # with three of twenty missing, p90 lands on a missing query
    assert endtoend.percentile(lat[:17] + [math.inf] * 3, 90) is None
    assert endtoend.percentile([], 50) is None


def test_queries_per_s_counts_deliveries_inside_the_window():
    qs = [dict(ok=True, t_done=1.0), dict(ok=True, t_done=9.9),
          dict(ok=True, t_done=10.5), dict(ok=False, t_done=None)]
    assert endtoend.delivered_per_s(qs, 0.0, 10.0) == pytest.approx(0.2)


def _hand_graph():
    """4 vertices in 2 partitions {0, 1} and {2, 3}: edges 0->1 and 2->3
    are local, 1->2 and 0->2 cross the cut."""
    from repro.core.blocked import build_blocked
    from repro.core.graph import GraphTemplate

    tmpl = GraphTemplate(num_vertices=4, src=np.array([0, 1, 2, 0]),
                         dst=np.array([1, 2, 3, 2]))
    return tmpl, build_blocked(tmpl, np.array([0, 0, 1, 1]), block_size=2)


def test_useful_bytes_match_a_hand_count():
    from repro.gopher import GopherSession

    tmpl, bg = _hand_graph()
    w = np.ones((3, 4), np.float32)
    sess = GopherSession.from_blocked(bg, weights={"latency": w},
                                      src=tmpl.src, dst=tmpl.dst)
    res = sess.run(sess.plan("sssp", source=0))
    st = res.engine.stats
    sweeps, steps = int(st["local_sweeps"].sum()), int(st["supersteps"].sum())
    # hand count: 2 local edges, 2 boundary edges, 4 vertices
    hand = sweeps * (4 * 2 + 8 * 4) + steps * (4 * 2 + 8 * 4)
    assert roofline.minplus_bytes(sweeps, steps, local_edges=2,
                                  boundary_edges=2, num_vertices=4) == hand
    run = SimpleNamespace(
        queries=[], passes=[_pass(0, 1, inst=3, local_sweeps=sweeps,
                                  supersteps=steps)],
        t0=0.0, seconds=0.5,
        graph={"local_edges": int(len(bg.le_edge_id)),
               "boundary_edges": int(len(bg.re_edge_id)),
               "num_vertices": int(len(bg.part_of))},
        cell=SimpleNamespace(traffic={"analytic": "sssp"}))
    assert layers.useful_bytes(run) == hand
    # PageRank: one local sweep and one superstep per iteration
    run.cell.traffic["analytic"] = "pagerank"
    run.passes[0]["supersteps"] = 3 * 10
    assert layers.useful_bytes(run) == 30 * (4 * 2 + 8 * 4) * 2


def test_kernel_roofline_reads_the_trace_and_stays_under_100():
    run = SimpleNamespace(
        queries=[], t0=0.0, seconds=1.0,
        passes=[_pass(0, 2, inst=1, local_sweeps=10, supersteps=5)],
        graph={"local_edges": 1000, "boundary_edges": 100,
               "num_vertices": 500},
        cell=SimpleNamespace(traffic={"analytic": "sssp"}),
        peaks={"hbm_bytes_per_s": 1e9},
        trace=T.Trace(window_ns=2e9, device_ops={"/device:TPU:0": [
            ("_spmv_kernel", 0, 1e6), ("fusion", 1e6, 5e6)]}))
    ub = 10 * (4000 + 4000) + 5 * (400 + 4000)  # 102,000 B
    got = layers.kernel_roofline(run, r"_spmv_kernel")
    assert got == pytest.approx(100 * (ub / 1e9) / 1e-3)
    assert 0 < got <= 100
    # no kernel event: nothing to read, never a 0
    assert layers.kernel_roofline(run, r"no_such_kernel") is None
    run.trace = None
    assert layers.kernel_roofline(run, r"_spmv_kernel") is None


def test_batch_width_and_device_idle_readers():
    run = SimpleNamespace(service={"start": {"served": 40, "batches": 30},
                                   "end": {"served": 100, "batches": 50}},
                          trace=T.Trace(window_ns=10.0, device_ops={
                              "/device:TPU:0": [("op", 0, 4)]}))
    assert layers.batch_width(run) == pytest.approx(3.0)
    assert layers.device_idle(run) == pytest.approx(60.0)
    run.trace = None
    assert layers.device_idle(run) is None
