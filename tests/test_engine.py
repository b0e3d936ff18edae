"""TemporalEngine parity: the unified blocked runner must agree with the
faithful host iBSP engine (run_ibsp) on every execution pattern (paper
§IV-B) — sequential (SSSP), independent (PageRank, components), eventually
dependent (N-hop Merge) — and report comparable BSPStats."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.algorithms import components, nhop, pagerank, sssp
from repro.core.blocked import build_blocked
from repro.core.engine import (
    TemporalEngine,
    min_plus_program,
    pagerank_program,
    source_init,
)
from repro.core.ibsp import BSPStats, InMemoryProvider
from repro.core.semiring import INF

from tests.conftest import TINY


@pytest.fixture(scope="module")
def env(tiny_collection, tiny_partitioned):
    tmpl, assign, sg_ids, subs = tiny_partitioned
    prov = InMemoryProvider(
        tiny_collection, subs,
        vertex_attrs=("plate", "outdeg_active"),
        edge_attrs=("latency", "active"),
    )
    bg = build_blocked(tmpl, assign, TINY.block_size)
    I = len(tiny_collection)
    weights = np.stack([tiny_collection.edge_values(t, "latency")
                        for t in range(I)])
    active = np.stack([tiny_collection.edge_values(t, "active")
                       for t in range(I)])
    return tmpl, subs, prov, bg, weights, active


def test_sequential_sssp_host_vs_engine(env):
    tmpl, subs, prov, bg, weights, active = env
    res_h, ibsp = sssp.run_host(prov, 0)
    d_h = np.full(tmpl.num_vertices, INF)
    for g, dist in res_h.items():
        d_h[subs[g].vertices] = dist

    eng = TemporalEngine(bg)
    res = eng.run(min_plus_program("sssp", init=source_init(0)), weights,
                  pattern="sequential")
    finite = np.isfinite(d_h)
    assert np.array_equal(np.isfinite(res.final), finite)
    np.testing.assert_allclose(res.final[finite], d_h[finite], rtol=1e-4)
    # stats comparable to the host engine's accounting
    st = res.bsp_stats()
    assert isinstance(st, BSPStats)
    assert st.supersteps > 0 and st.compute_calls >= st.supersteps
    assert st.timestep_messages > 0  # sequential handoff carried state


def test_independent_pagerank_host_vs_engine(env):
    tmpl, subs, prov, bg, weights, active = env
    iters = 10
    prh, _ = pagerank.run_host(prov, tmpl.num_vertices, iters=iters)
    I = active.shape[0]
    w = pagerank.edge_weights_for_instances(tmpl.src, active,
                                            tmpl.num_vertices)
    eng = TemporalEngine(bg)
    res = eng.run(pagerank_program(tmpl.num_vertices, iters=iters), w,
                  pattern="independent")
    for t in range(I):
        pr_h = np.zeros(tmpl.num_vertices)
        for (ts, g), r in prh.items():
            if ts == t:
                pr_h[subs[g].vertices] = r
        np.testing.assert_allclose(res.values[t], pr_h, rtol=1e-4, atol=1e-9)
    assert res.bsp_stats().merge_messages == 0


def test_independent_components_engine_vs_oracle(env):
    tmpl, subs, prov, bg, weights, active = env
    labels = components.run_blocked_temporal(bg, tmpl.src, tmpl.dst, active)
    for t in range(active.shape[0]):
        oracle = components.oracle(tmpl.src, tmpl.dst, active[t],
                                   tmpl.num_vertices)
        assert np.array_equal(labels[t], oracle), t


def test_eventually_nhop_host_vs_engine(env):
    tmpl, subs, prov, bg, weights, active = env
    n_hops = 4
    merged, _ = nhop.run_host(prov, 0, n_hops=n_hops)
    comp_b, per_b = nhop.run_blocked(bg, weights, 0, n_hops=n_hops)
    assert np.array_equal(comp_b, merged["composite"])
    assert per_b.shape[0] == weights.shape[0]


def test_engine_merge_mean_matches_values(env):
    tmpl, subs, prov, bg, weights, active = env
    w = pagerank.edge_weights_for_instances(tmpl.src, active,
                                            tmpl.num_vertices)
    eng = TemporalEngine(bg)
    res = eng.run(pagerank_program(tmpl.num_vertices, iters=6), w,
                  pattern="eventually", merge="mean")
    assert res.merged is not None
    np.testing.assert_allclose(res.merged, res.values.mean(0), atol=1e-6)
    assert res.bsp_stats().merge_messages == w.shape[0]


def test_merge_requires_eventually(env):
    tmpl, subs, prov, bg, weights, active = env
    eng = TemporalEngine(bg)
    with pytest.raises(AssertionError, match="eventually"):
        eng.run(min_plus_program("sssp", init=source_init(0)), weights,
                pattern="independent", merge="mean")


def test_prestaged_tiles_match_weights_path(env):
    """GoFS-style pre-staged tensors and the (I, E) weights path agree."""
    tmpl, subs, prov, bg, weights, active = env
    eng = TemporalEngine(bg)
    prog = min_plus_program("sssp", init=source_init(0))
    tiles, btiles = eng.stage(weights, prog.zero_fill)
    a = eng.run(prog, weights, pattern="sequential")
    b = eng.run(prog, tiles=tiles, btiles=btiles, x0=source_init(0)(bg),
                pattern="sequential")
    fin = np.isfinite(a.final)
    assert np.array_equal(np.isfinite(b.final), fin)
    np.testing.assert_allclose(a.final[fin], b.final[fin])


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.configs.base import GraphConfig
from repro.core.generator import generate_collection
from repro.core.partition import partition_graph
from repro.core.blocked import build_blocked
from repro.core.engine import (TemporalEngine, min_plus_program,
                               pagerank_program, source_init)
from repro.core.algorithms import pagerank

cfg = GraphConfig(name="t", num_vertices=400, avg_degree=3.0,
                  num_instances=4, num_partitions=4, block_size=32, seed=9)
tsg = generate_collection(cfg)
tmpl = tsg.template
assign = partition_graph(tmpl, 4, seed=9)
bg = build_blocked(tmpl, assign, 32)
w = np.stack([tsg.edge_values(t, "latency") for t in range(4)])
active = np.stack([tsg.edge_values(t, "active") for t in range(4)])
mesh = jax.make_mesh((2, 4), ("data", "model"))
eng_m = TemporalEngine(bg, mesh=mesh)
eng_s = TemporalEngine(bg)
prog = min_plus_program("sssp", init=source_init(0))
for pattern in ("sequential", "independent"):
    rm = eng_m.run(prog, w, pattern=pattern)
    rs = eng_s.run(prog, w, pattern=pattern)
    for t in range(4):
        f = np.isfinite(rs.values[t])
        assert np.array_equal(np.isfinite(rm.values[t]), f), (pattern, t)
        assert np.allclose(rm.values[t][f], rs.values[t][f]), (pattern, t)
    # final is the last instance's state, also when instances are sharded
    assert np.array_equal(rm.final, rm.values[-1]), pattern
pw = pagerank.edge_weights_for_instances(tmpl.src, active, tmpl.num_vertices)
pp = pagerank_program(tmpl.num_vertices, iters=10)
rm = eng_m.run(pp, pw, pattern="eventually", merge="mean")
rs = eng_s.run(pp, pw, pattern="eventually", merge="mean")
assert np.abs(rm.values - rs.values).max() < 1e-6
assert np.abs(rm.merged - rs.merged).max() < 1e-6
# async staging under the mesh: per-chunk shard_map dispatch, same results
rm_async = eng_m.run(prog, w, pattern="independent", staging="async")
rm_sync = eng_m.run(prog, w, pattern="independent")
assert np.array_equal(rm_async.values, rm_sync.values)
# single-instance probes (I=1 < data axis) fall back to replicated instances
r1m = eng_m.run(prog, w[:1], pattern="independent")
r1s = eng_s.run(prog, w[:1], pattern="independent")
f1 = np.isfinite(r1s.values[0])
assert np.array_equal(np.isfinite(r1m.values[0]), f1)
assert np.allclose(r1m.values[0][f1], r1s.values[0][f1])
from repro.core.algorithms import nhop
cm, _ = nhop.run_blocked(bg, w, 0, n_hops=4, mesh=mesh)
cs, _ = nhop.run_blocked(bg, w, 0, n_hops=4)
assert np.array_equal(cm, cs)
print("ENGINE MESH OK")
"""


@pytest.mark.slow
def test_engine_mesh_matches_stacked():
    """All three patterns agree between stacked and temporal-parallel mesh
    execution (fixpoint AND iterate programs — not just PageRank)."""
    env_ = dict(os.environ)
    env_.pop("XLA_FLAGS", None)
    env_["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], env=env_, capture_output=True,
        text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "ENGINE MESH OK" in r.stdout
