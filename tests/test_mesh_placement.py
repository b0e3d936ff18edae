"""The planner applies its mesh split: with no mesh given, a TPU backend
of several chips runs each plan on ``propose_mesh_split``'s (data, model)
mesh, built by the session; one chip and the CPU backend stay stacked.

The chips are four forced host devices in a subprocess, and the planner's
device query (``repro.launch.mesh.planner_devices``) is steered to them in
the script.  A store-backed collection of 8 partitions streams in two
4-instance chunks: temporal SSSP on the derived {data 1, model 4} mesh must
equal the stacked session bitwise (values and supersteps) and the host
iBSP reference within the engine-vs-host tolerance; PageRank's derived
split is {data 4, model 1}."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, tempfile
import numpy as np
from repro.configs.base import GraphConfig
from repro.core.algorithms import pagerank, sssp
from repro.core.generator import generate_collection
from repro.core.semiring import INF
from repro.gofs import GoFSStore, deploy_collection
from repro.gopher import GopherSession
from repro.launch import mesh as mesh_mod

cfg = GraphConfig(name="m4", num_vertices=600, avg_degree=3.0,
                  num_instances=8, num_partitions=8, block_size=32,
                  instances_per_slice=4, bins_per_partition=2,
                  cache_slots=4, seed=5)
root = tempfile.mkdtemp()
deploy_collection(generate_collection(cfg), cfg, root)
store = GoFSStore(root, vertex_projection=("plate", "outdeg_active"),
                  edge_projection=("latency", "active"))
V, SRC, ITERS = cfg.num_vertices, 3, 10
out = {}

# (c) the CPU backend, unsteered, and one chip: stacked
stacked = GopherSession(store, block_size=32)
stacked_plans = {"sssp": stacked.plan("sssp", source=SRC),
                 "pagerank": stacked.plan("pagerank", iters=ITERS)}
out["cpu_placement"] = stacked_plans["sssp"].placement.value
mesh_mod.planner_devices = lambda: 1
out["one_chip_placement"] = GopherSession(store, block_size=32).plan(
    "sssp", source=SRC).placement.value

# four chips
mesh_mod.planner_devices = lambda: 4
sess = GopherSession(store, block_size=32)
plans = {"sssp": (sess.plan("sssp", source=SRC), stacked_plans["sssp"]),
         "pagerank": (sess.plan("pagerank", iters=ITERS),
                      stacked_plans["pagerank"])}
bg = sess.bg
for name, (pm, ps) in plans.items():
    rm, rs = sess.run(pm), stacked.run(ps)
    eng = sess._engine(pm)
    out[name] = {
        "placement": [pm.placement.value, pm.placement.source],
        "comm": pm.comm.value, "staging": pm.staging.value,
        "engine_mesh": dict(eng.mesh.shape),
        "chunk_instances": sorted({k[3] for k in eng._runners}),
        "report_mesh": sess.last_run_report["mesh"],
        "exchange_bytes": sess.last_run_report["exchange_bytes"],
        "stacked_mesh": stacked.last_run_report["mesh"],
        "stacked_exchange_bytes": stacked.last_run_report["exchange_bytes"],
        "device_fills": sess.last_run_report["device_fills"],
        "supersteps": int(rs.engine.stats["supersteps"].sum()),
        "same_supersteps": bool(np.array_equal(
            rm.engine.stats["supersteps"], rs.engine.stats["supersteps"])),
        "values_bitwise": bool(np.array_equal(rm.engine.values,
                                              rs.engine.values)),
        "values_gap": float(np.nanmax(np.abs(np.where(
            np.isfinite(rs.engine.values),
            rm.engine.values - rs.engine.values, 0.0)))),
    }
    if name == "sssp":
        res, _ = sssp.run_host(store, SRC)
        d = np.full(V, INF, np.float32)
        for g, dist in res.items():
            d[store.get_topology(g).vertices] = dist
        got = rm.engine.final
        out[name]["host_reach_equal"] = bool(np.array_equal(
            np.isfinite(got), np.isfinite(d)))
        fin = np.isfinite(d)
        out[name]["host_gap"] = float(np.max(
            np.abs(got[fin] - d[fin]) / np.maximum(np.abs(d[fin]), 1.0)))
        out[name]["reached"] = int(fin.sum())
    else:
        res, _ = pagerank.run_host(store, V, iters=ITERS)
        ref = np.zeros((store.num_timesteps(), V))
        for (t, g), r in res.items():
            ref[t, store.get_topology(g).vertices] = r
        out[name]["host_gap"] = float(np.max(np.abs(
            rm.output["ranks"] - ref)))
# a session-staged (sync) batch goes to the mesh through the engine's
# device cache, sharded the same way
sync = sess.run(sess.plan("sssp", source=SRC, staging="sync"))
out["sync_bitwise"] = bool(np.array_equal(
    sync.engine.values, stacked.run(plans["sssp"][1]).engine.values))
out["boundary_nnz"] = int(bg.boundary_nnz)
# the {data 1, model 4} engine's device fill of one chunk: each chip
# holds the host fill's slice of its own partitions, nothing else
from repro.gofs.prefetch import StagedChunk
rows = store.edge_attr_rows("latency", range(4))
tl, tb = sess._engine(plans["sssp"][0])._device_fill(
    StagedChunk(0, 4, edges=rows, zero=INF, bg=bg))
shards = []
for got, ref in ((tl, bg.fill_local_batch(rows)),
                 (tb, bg.fill_boundary_batch(rows))):
    for sh in got.addressable_shards:
        shards.append([sh.data.shape[1],
                       bool(np.array_equal(np.asarray(sh.data),
                                           ref[sh.index]))])
out["fill_shards"] = shards
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sssp_streams_on_the_derived_model_mesh_bitwise(result):
    r = result["sssp"]
    assert r["placement"] == ["mesh{'data': 1, 'model': 4}", "auto"]
    assert r["comm"] == "dense" and r["staging"] == "async"
    assert r["engine_mesh"] == {"data": 1, "model": 4}
    assert r["chunk_instances"] == [4]  # two streamed chunks of 4
    assert r["values_bitwise"] and r["same_supersteps"]
    # the host iBSP engine sums in its own order: the repo's engine-vs-host
    # tolerance (tests/test_engine.py)
    assert r["host_reach_equal"] and r["host_gap"] <= 1e-4
    assert r["reached"] > 1
    assert result["sync_bitwise"]


def test_pagerank_takes_the_data_axis_within_tolerance(result):
    r = result["pagerank"]
    assert r["placement"] == ["mesh{'data': 4, 'model': 1}", "auto"]
    assert r["engine_mesh"] == {"data": 4, "model": 1}
    assert r["values_gap"] <= 1e-6
    assert r["host_gap"] <= 1e-6


def test_one_chip_and_the_cpu_backend_stay_stacked(result):
    assert result["cpu_placement"] == "stacked"
    assert result["one_chip_placement"] == "stacked"
    for name in ("sssp", "pagerank"):
        assert result[name]["stacked_mesh"] is None


def test_exchange_bytes_match_the_hand_count(result):
    sp = result["sssp"]
    # dense all-reduce over 4 model devices: 2 (4 - 1) / 4 x 4 B a
    # published vertex, once a superstep
    per_superstep = 2 * 3 / 4 * 4 * result["boundary_nnz"]
    assert sp["report_mesh"] == {"data": 1, "model": 4}
    assert sp["exchange_bytes"] == round(sp["supersteps"] * per_superstep)
    assert sp["exchange_bytes"] > 0
    # one device per partition group: nothing crosses a device
    pr = result["pagerank"]
    assert pr["report_mesh"] == {"data": 4, "model": 1}
    assert pr["exchange_bytes"] == 0
    for name in ("sssp", "pagerank"):
        assert result[name]["stacked_exchange_bytes"] == 0


def test_streamed_chunks_fill_on_their_shards(result):
    """Each streamed chunk's tiles are filled on the device, per model
    shard: two 4-instance chunks a pass on either derived mesh, and one
    chunk's fill puts on each of the four chips exactly the host fill's
    slice of its two partitions."""
    for name in ("sssp", "pagerank"):
        assert result[name]["device_fills"] == 2
    assert result["fill_shards"] == [[2, True]] * 8
