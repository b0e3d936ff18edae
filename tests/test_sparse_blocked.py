"""Block-sparse layout parity: the packed active-tile format
(``repro.core.blocked.SparseBlocked``, ``TemporalEngine(layout="sparse")``)
must be bitwise-identical to the dense layout for min-plus across all
three iBSP patterns, fixpoint AND iterate programs, sync and async
staging, stacked and mesh (subprocess) — plus the GoFS recorded-tile-map
staging path, the engine's Pallas walk, and the boundary-nnz comm cost
model satellites."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.blocked import build_blocked, pow2_bucket
from repro.core.engine import (
    SemiringProgram,
    TemporalEngine,
    min_plus_program,
    pagerank_program,
    source_init,
)
from repro.core.graph import GraphInstance, GraphTemplate, TimeSeriesGraph
from repro.core.semiring import INF, MIN_PLUS

from tests.conftest import TINY


def _banded(bg, tmpl, w, n_bands=4):
    """Mask weights so instance i only activates one tile-aligned band —
    every tile is fully live or fully absent per instance.  The banding
    itself is the bench's workload generator (one shared implementation)."""
    from benchmarks.bench_temporal import _edge_bands

    band = _edge_bands(bg, tmpl.src, tmpl.dst, n_bands)
    live = band[None, :] == (np.arange(w.shape[0]) % n_bands)[:, None]
    return np.where(live, w, np.inf).astype(np.float32), live


@pytest.fixture(scope="module")
def env(tiny_collection, tiny_partitioned):
    tmpl, assign, _, _ = tiny_partitioned
    bg = build_blocked(tmpl, assign, TINY.block_size)
    I = len(tiny_collection)
    w = np.stack([tiny_collection.edge_values(t, "latency")
                  for t in range(I)])
    wb, live = _banded(bg, tmpl, w)
    return tmpl, bg, wb, live


def bellman_iterate_program(source: int, iters: int = 5) -> SemiringProgram:
    """A min-plus ITERATE program (fixed supersteps, no convergence vote):
    the fixed-count analogue of SSSP, exercising the iterate engine path
    under an idempotent semiring so parity can be asserted bitwise."""
    from repro.core.superstep import _consume, _local_sweep, _publish

    def step(x, dg, comm, use_pallas):
        x1 = _local_sweep(x, dg, MIN_PLUS, use_pallas)
        boundary = _publish(x1, dg, MIN_PLUS, comm)
        return _consume(x1, boundary, dg, MIN_PLUS, use_pallas)

    return SemiringProgram(
        name="bellman_iterate", semiring=MIN_PLUS, zero_fill=INF,
        kind="iterate", iters=iters, step=step, init=source_init(source),
    )


# ---------------------------------------------------------------------------
# Format
# ---------------------------------------------------------------------------

def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (0, 1, 2, 3, 4, 5, 17)] == \
        [1, 1, 2, 4, 4, 8, 32]


def test_sparse_fill_reconstructs_dense(env):
    """Scattering the packed tiles back into template slots must exactly
    rebuild the dense fill; inactive slots hold only the semiring zero."""
    tmpl, bg, wb, live = env
    dense_l = bg.fill_local_batch(wb)
    dense_b = bg.fill_boundary_batch(wb)
    sp = bg.stage_sparse(wb)
    assert 0.0 < sp.occupancy() < 1.0
    for dense, tiles, rows, cols, nnz, rc in (
        (dense_l, sp.tiles, sp.rows, sp.cols, sp.nnz, bg.tiles_rc),
        (dense_b, sp.btiles, sp.brows, sp.bcols, sp.bnnz, bg.btiles_rc),
    ):
        rec = np.full_like(dense, INF)
        for i in range(sp.num_instances):
            for p in range(bg.n_parts):
                n = int(nnz[i, p])
                # padding slots carry -1 index and zero values
                assert np.all(rows[i, p, n:] == -1)
                assert np.all(cols[i, p, n:] == -1)
                assert np.all(tiles[i, p, n:] == np.float32(INF))
                # packed cols stay sorted (the kernel's output-run invariant)
                assert np.all(np.diff(cols[i, p, :n]) >= 0)
                for k in range(n):
                    t = np.nonzero(
                        (rc[p, :, 0] == rows[i, p, k])
                        & (rc[p, :, 1] == cols[i, p, k])
                    )[0]
                    assert len(t) == 1
                    rec[i, p, t[0]] = tiles[i, p, k]
        assert np.array_equal(rec, dense)


def test_bucket_too_small_rejected(env):
    tmpl, bg, wb, live = env
    with pytest.raises(AssertionError, match="bucket"):
        bg.fill_local_batch_sparse(wb, bucket=1)


def test_staged_bytes_shrink_with_occupancy(env):
    tmpl, bg, wb, live = env
    sp = bg.stage_sparse(wb)
    dense_bytes = bg.fill_local_batch(wb).nbytes \
        + bg.fill_boundary_batch(wb).nbytes
    assert sp.staged_bytes() < dense_bytes


# ---------------------------------------------------------------------------
# Engine parity: bitwise for min-plus, every pattern x program kind x staging
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pattern", ["sequential", "independent",
                                     "eventually"])
def test_fixpoint_bitwise_all_patterns(env, pattern):
    tmpl, bg, wb, live = env
    prog = min_plus_program("sssp", init=source_init(0))
    kw = dict(merge="mean") if pattern == "eventually" else {}
    rd = TemporalEngine(bg).run(prog, wb, pattern=pattern, **kw)
    rs = TemporalEngine(bg, layout="sparse").run(prog, wb, pattern=pattern,
                                                 **kw)
    assert np.array_equal(rd.values, rs.values)
    assert np.array_equal(rd.final, rs.final)
    assert np.array_equal(rd.stats["supersteps"], rs.stats["supersteps"])
    if pattern == "eventually":
        assert np.array_equal(rd.merged, rs.merged)
    assert rd.occupancy is None and rs.occupancy is not None


@pytest.mark.parametrize("pattern", ["sequential", "independent"])
def test_iterate_bitwise(env, pattern):
    """Min-plus ITERATE program (fixed supersteps): sparse == dense
    bitwise on the iterate engine path too."""
    tmpl, bg, wb, live = env
    prog = bellman_iterate_program(0, iters=4)
    rd = TemporalEngine(bg).run(prog, wb, pattern=pattern)
    rs = TemporalEngine(bg, layout="sparse").run(prog, wb, pattern=pattern)
    assert np.array_equal(rd.values, rs.values)
    assert np.array_equal(rd.final, rs.final)


def test_prestaged_batches_override_engine_layout(env):
    """Pre-staged batches carry their own layout, symmetrically: sparse=
    on a dense engine runs the sparse runner, tiles=/btiles= on a sparse
    engine runs the dense runner — nothing is silently dropped."""
    tmpl, bg, wb, live = env
    prog = min_plus_program("sssp", init=source_init(0))
    ref = TemporalEngine(bg).run(prog, wb, pattern="sequential")
    eng_sp = TemporalEngine(bg, layout="sparse")
    tiles, btiles = eng_sp.stage(wb, prog.zero_fill)
    r_dense_on_sparse = eng_sp.run(prog, tiles=tiles, btiles=btiles,
                                   pattern="sequential")
    assert np.array_equal(ref.values, r_dense_on_sparse.values)
    assert r_dense_on_sparse.occupancy is None  # the call ran dense
    sp = TemporalEngine(bg).stage_sparse(wb, prog.zero_fill)
    r_sparse_on_dense = TemporalEngine(bg).run(prog, sparse=sp,
                                               pattern="sequential")
    assert np.array_equal(ref.values, r_sparse_on_dense.values)
    assert r_sparse_on_dense.occupancy is not None
    with pytest.raises(AssertionError, match="not both"):
        eng_sp.run(prog, tiles=tiles, btiles=btiles, sparse=sp,
                   pattern="sequential")


def test_async_staging_bitwise(env):
    """Sparse chunks through the prefetcher: async sparse == sync dense."""
    tmpl, bg, wb, live = env
    prog = min_plus_program("sssp", init=source_init(0))
    rd = TemporalEngine(bg).run(prog, wb, pattern="sequential")
    eng = TemporalEngine(bg, layout="sparse", staging="async",
                         chunk_instances=2)
    rs = eng.run(prog, wb, pattern="sequential")
    assert np.array_equal(rd.values, rs.values)
    assert rs.occupancy is not None and 0.0 < rs.occupancy < 1.0


def test_pagerank_sparse_matches_dense(env):
    """Plus-mul: skipped tiles add exact 0.0, so the sparse iterate run
    tracks dense to float-exactness on one device."""
    tmpl, bg, wb, live = env
    from repro.core.algorithms.pagerank import edge_weights_for_instances

    pw = edge_weights_for_instances(tmpl.src, live.astype(np.float32),
                                    tmpl.num_vertices)
    prog = pagerank_program(tmpl.num_vertices, iters=8)
    rd = TemporalEngine(bg).run(prog, pw, pattern="independent")
    rs = TemporalEngine(bg, layout="sparse").run(prog, pw,
                                                 pattern="independent")
    np.testing.assert_allclose(rs.values, rd.values, atol=1e-7)


def test_engine_pallas_walk_bitwise(env):
    """The Pallas kernel (interpret mode) walking packed tiles inside the
    engine: use_pallas x layout, all four combinations agree bitwise."""
    tmpl, bg, wb, live = env
    prog = min_plus_program("sssp", init=source_init(0), max_supersteps=8)
    w2 = wb[:2]
    ref = TemporalEngine(bg).run(prog, w2, pattern="sequential")
    for kw in (dict(use_pallas=True),
               dict(use_pallas=True, layout="sparse")):
        got = TemporalEngine(bg, **kw).run(prog, w2, pattern="sequential")
        assert np.array_equal(ref.values, got.values), kw


# ---------------------------------------------------------------------------
# Fused superstep kernel (kernels/semiring_superstep): the whole local
# stage — tile walk, semiring combine, halt vote — as ONE pallas_call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pattern", ["sequential", "independent",
                                     "eventually"])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_fused_bitwise_all_patterns(env, pattern, layout):
    """min-plus: fused superstep kernel == per-stage SpMV kernel == jnp
    oracle, BITWISE (values, final state, AND superstep counts — the
    in-kernel halt vote must fire on exactly the same superstep) across
    all three iBSP patterns x both layouts, interpret mode."""
    tmpl, bg, wb, live = env
    w2 = wb[:3]
    prog = min_plus_program("sssp", init=source_init(0), max_supersteps=16)
    kw = dict(merge="mean") if pattern == "eventually" else {}
    lay = {} if layout == "dense" else dict(layout="sparse")
    ref = TemporalEngine(bg, **lay).run(prog, w2, pattern=pattern, **kw)
    for up in ("spmv", "fused"):
        got = TemporalEngine(bg, use_pallas=up, **lay).run(
            prog, w2, pattern=pattern, **kw)
        assert np.array_equal(ref.values, got.values), (up, pattern, layout)
        assert np.array_equal(ref.final, got.final), (up, pattern, layout)
        assert np.array_equal(ref.stats["supersteps"],
                              got.stats["supersteps"]), (up, pattern, layout)
        if pattern == "eventually":
            assert np.array_equal(ref.merged, got.merged), (up, layout)


def test_fused_async_staging_bitwise(env):
    """Fused kernel under the async sparse prefetch pipeline."""
    tmpl, bg, wb, live = env
    prog = min_plus_program("sssp", init=source_init(0))
    ref = TemporalEngine(bg).run(prog, wb, pattern="sequential")
    eng = TemporalEngine(bg, use_pallas="fused", layout="sparse",
                         staging="async", chunk_instances=2)
    got = eng.run(prog, wb, pattern="sequential")
    assert np.array_equal(ref.values, got.values)


def test_fused_query_axis_bitwise(env):
    """The query axis vmaps the fused pallas_call over Q sources: batched
    == oracle == per-source runs, bitwise."""
    from repro.core.engine import sources_init

    tmpl, bg, wb, live = env
    w2 = wb[:2]
    sources = [0, 7, 23]
    progs = {s: min_plus_program("sssp", init=source_init(s),
                                 max_supersteps=16) for s in sources}
    batched = min_plus_program("sssp", init=sources_init(sources),
                               max_supersteps=16)
    ref = TemporalEngine(bg).run(batched, w2, pattern="sequential")
    got = TemporalEngine(bg, use_pallas="fused").run(
        batched, w2, pattern="sequential")
    assert np.array_equal(ref.values, got.values)
    for q, s in enumerate(sources):
        one = TemporalEngine(bg, use_pallas="fused").run(
            progs[s], w2, pattern="sequential")
        assert np.array_equal(got.values[q], one.values), s


def test_fused_warm_start_bitwise(env):
    """Warm-started fixpoints re-enter the fused path with a non-trivial
    x0 — still bitwise vs the oracle warm path."""
    tmpl, bg, wb, live = env
    prog = min_plus_program("sssp", init=source_init(0))
    ref = TemporalEngine(bg).run(prog, wb, pattern="independent",
                                 warm_start=True)
    got = TemporalEngine(bg, use_pallas="fused").run(
        prog, wb, pattern="independent", warm_start=True)
    assert np.array_equal(ref.values, got.values)
    assert np.array_equal(ref.stats["supersteps"], got.stats["supersteps"])


def test_fused_pagerank_tolerance(env):
    """plus-mul REASSOCIATES in the fused kernel (the sequential
    dot-product walk vs the oracle's segment sum), so PageRank parity is
    to float tolerance, not bitwise — documented contract."""
    tmpl, bg, wb, live = env
    from repro.core.algorithms.pagerank import edge_weights_for_instances

    pw = edge_weights_for_instances(tmpl.src, live.astype(np.float32),
                                    tmpl.num_vertices)[:2]
    prog = pagerank_program(tmpl.num_vertices, iters=8)
    ref = TemporalEngine(bg).run(prog, pw, pattern="independent")
    got = TemporalEngine(bg, use_pallas="fused").run(prog, pw,
                                                     pattern="independent")
    np.testing.assert_allclose(got.values, ref.values, atol=2e-6)


def test_fused_single_pallas_call_jaxpr(env):
    """The acceptance contract, pinned on the jaxpr: one fused local
    stage lowers to exactly ONE pallas_call — no per-partition launch
    loop (scan/map over partitions), and no state-sized XLA reduction
    for the halt vote outside the kernel (the vote is the kernel's SMEM
    output; only scalar post-processing remains)."""
    import jax
    import jax.numpy as jnp

    from repro.core.semiring import MIN_PLUS
    from repro.core.superstep import (_fused_sweep_vote, _local_sweep,
                                      device_graph)

    tmpl, bg, wb, live = env
    dg = device_graph(bg, bg.fill_local(wb[0]), bg.fill_boundary(wb[0]))
    x = jnp.asarray(np.where(np.asarray(dg.vmask), 1.0, INF), jnp.float32)

    def count(eqns, name, acc=None):
        acc = [] if acc is None else acc
        for e in eqns:
            if e.primitive.name == name:
                acc.append(e)
            for sub in e.params.values():
                if hasattr(sub, "jaxpr"):
                    count(sub.jaxpr.eqns, name, acc)
        return acc

    jx = jax.make_jaxpr(
        lambda xx: _fused_sweep_vote(xx, dg, MIN_PLUS, True))(x)
    assert len(count(jx.jaxpr.eqns, "pallas_call")) == 1
    # no partition-axis launch loop around the kernel
    assert count(jx.jaxpr.eqns, "scan") == []
    # the halt vote never materializes as a state-sized XLA reduce: every
    # reduce left in the jaxpr is over <= P elements (the per-partition
    # changed flags), not over the (P, Vp) state
    state_elems = int(np.prod(x.shape))
    for prim in ("reduce_or", "reduce_max", "reduce_min", "reduce_and"):
        for e in count(jx.jaxpr.eqns, prim):
            n_in = int(np.prod(e.invars[0].aval.shape))
            assert n_in <= dg.n_parts, (prim, e.invars[0].aval.shape)
    # contrast: the per-stage spmv path needs a separate state-sized vote
    jx_spmv = jax.make_jaxpr(
        lambda xx: _local_sweep(xx, dg, MIN_PLUS, ("spmv", True)))(x)
    assert len(count(jx_spmv.jaxpr.eqns, "pallas_call")) >= 1


def test_kernel_mode_resolution():
    """kernel_mode maps every accepted use_pallas spelling to a
    (mode, interpret) pair and rejects unknown modes."""
    from repro.core.superstep import kernel_mode

    assert kernel_mode(None) == ("off", None)
    assert kernel_mode(False) == ("off", None)
    assert kernel_mode(True) == ("spmv", None)
    assert kernel_mode("fused") == ("fused", None)
    assert kernel_mode(("fused", True)) == ("fused", True)
    with pytest.raises(ValueError, match="kernel mode"):
        kernel_mode("warp")


def test_planner_kernel_auto_selection(env):
    """Planner kernel knob: off on non-TPU backends, fused for TPU +
    sparse-regime occupancy at lane-width blocks, spmv for TPU dense or
    narrower blocks; overrides win."""
    from repro.gopher import GopherSession, get_analytic
    from repro.gopher.planner import plan_analytic

    tmpl, bg, wb, live = env
    sess = GopherSession.from_blocked(bg, weights={"latency": wb})
    # this process runs on CPU: auto -> off, recorded on the plan
    p = sess.plan("sssp", source=0)
    assert p.kernel.value == "off" and p.kernel.source == "auto"
    assert "kernel" in p.explain()
    # session-wide use_pallas becomes a per-plan override
    s2 = GopherSession.from_blocked(bg, weights={"latency": wb},
                                    use_pallas="fused")
    p2 = s2.plan("sssp", source=0)
    assert p2.kernel.value == "fused" and p2.kernel.source == "override"
    # and the override actually reaches the engine the plan runs on
    r_auto = sess.run(p)
    r_fused = s2.run(p2)
    assert np.array_equal(r_auto.engine.values, r_fused.engine.values)
    # TPU rules, simulated through plan_analytic's backend input
    a = get_analytic("sssp")
    common = dict(bg=bg, mesh=None, model_axes=("model",),
                  store_backed=False, num_instances=2)
    low = plan_analytic(a, {"source": 0}, occupancy=0.1,
                        sparse_buckets=None, backend="tpu", **common)
    assert low.kernel.value == "spmv"  # B=32: the fused DMA cannot compile
    lane = dict(common, bg=build_blocked(tmpl, bg.part_of, 128))
    low = plan_analytic(a, {"source": 0}, occupancy=0.1,
                        sparse_buckets=None, backend="tpu", **lane)
    assert low.kernel.value == "fused"
    high = plan_analytic(a, {"source": 0}, occupancy=0.9,
                         sparse_buckets=None, backend="tpu", **common)
    assert high.kernel.value == "spmv"
    forced = plan_analytic(a, {"source": 0}, occupancy=0.9,
                           sparse_buckets=None, backend="tpu",
                           kernel="off", **common)
    assert forced.kernel.value == "off"
    assert forced.kernel.source == "override"


# ---------------------------------------------------------------------------
# GoFS: recorded per-pack tile maps -> packed staging
# ---------------------------------------------------------------------------

def _masked_collection(tiny_collection, bg):
    tmpl = tiny_collection.template
    w = np.stack([tiny_collection.edge_values(t, "latency")
                  for t in range(len(tiny_collection))])
    wb, _ = _banded(bg, tmpl, w)
    insts = []
    for t, g in enumerate(tiny_collection.instances):
        ev = dict(g.edge_values)
        ev["latency"] = wb[t]
        insts.append(GraphInstance(timestamp=g.timestamp,
                                   duration=g.duration,
                                   vertex_values=g.vertex_values,
                                   edge_values=ev))
    return TimeSeriesGraph(tmpl, insts), wb


def test_gofs_sparse_roundtrip(tiny_collection, tiny_partitioned, tmp_path):
    """Deploy with recorded tile maps -> sparse load/stream: identical to
    the value-scan staging, bitwise engine parity, buckets pinned from
    the maps without reading value slices."""
    from repro.gofs import GoFSStore, deploy_collection

    tmpl, assign, _, _ = tiny_partitioned
    bg = build_blocked(tmpl, assign, TINY.block_size)
    tsg, wb = _masked_collection(tiny_collection, bg)
    root = str(tmp_path / "gofs_sparse")
    meta = deploy_collection(tsg, TINY, root, assign=assign,
                             sparse_absent={"latency": float("inf")})
    assert meta["sparse_absent"] == {"latency": float("inf")}
    store = GoFSStore(root)
    maps = store.edge_tile_maps("latency")
    assert maps is not None and float(maps["absent"]) == INF

    # recorded maps == value-scan activity, field by field
    sp_rec = store.load_blocked(bg, "latency", layout="sparse")
    sp_scan = bg.stage_sparse(wb)
    for f in ("tiles", "btiles", "rows", "cols", "brows", "bcols",
              "nnz", "bnnz"):
        assert np.array_equal(getattr(sp_rec, f), getattr(sp_scan, f)), f

    # buckets derivable from maps alone (pre-stream, no value reads)
    assert store.sparse_buckets(bg, "latency") == \
        (sp_rec.bucket, sp_rec.bbucket)
    # absent-value mismatch falls back safely (no map, None buckets)
    assert store.sparse_buckets(bg, "latency", zero=0.0) is None

    prog = min_plus_program("sssp", init=source_init(0))
    tiles, btiles = store.load_blocked(bg, "latency")
    rd = TemporalEngine(bg).run(prog, tiles=tiles, btiles=btiles,
                                pattern="sequential")
    rs = TemporalEngine(bg, layout="sparse").run(prog, sparse=sp_rec,
                                                 pattern="sequential")
    stream = store.load_blocked_stream(bg, "latency", layout="sparse")
    rst = TemporalEngine(bg).run(prog, pattern="sequential", stream=stream)
    assert np.array_equal(rd.values, rs.values)
    assert np.array_equal(rd.values, rst.values)
    assert rst.occupancy == pytest.approx(sp_rec.occupancy())


def test_gofs_stale_map_falls_back(tiny_collection, tiny_partitioned,
                                   tmp_path):
    """A recorded map for a DIFFERENT blocked structure must be ignored,
    not trusted: staging falls back to scanning the values."""
    from repro.gofs import GoFSStore, deploy_collection

    tmpl, assign, _, _ = tiny_partitioned
    bg = build_blocked(tmpl, assign, TINY.block_size)
    tsg, wb = _masked_collection(tiny_collection, bg)
    root = str(tmp_path / "gofs_stale")
    deploy_collection(tsg, TINY, root, assign=assign,
                      sparse_absent={"latency": float("inf")})
    store = GoFSStore(root)
    bg2 = build_blocked(tmpl, assign, TINY.block_size * 2)  # other blocking
    assert store.sparse_buckets(bg2, "latency") is None
    sp = store.load_blocked(bg2, "latency", layout="sparse")  # still right
    sp_scan = bg2.stage_sparse(wb)
    assert np.array_equal(sp.tiles, sp_scan.tiles)


# ---------------------------------------------------------------------------
# Boundary-nnz comm costing satellites
# ---------------------------------------------------------------------------

def test_boundary_nnz_cost_model(env):
    from repro.dist.collectives import boundary_exchange_bytes

    tmpl, bg, wb, live = env
    nnz = bg.boundary_nnz
    assert 0 < nnz <= bg.num_boundary
    padded = boundary_exchange_bytes(bg.num_boundary, 4, "dense")
    actual = boundary_exchange_bytes(bg.num_boundary, 4, "dense",
                                     boundary_nnz=nnz)
    assert actual["bytes_per_device"] <= padded["bytes_per_device"]
    assert actual["bytes_per_device"] == \
        boundary_exchange_bytes(nnz, 4, "dense")["bytes_per_device"]


def test_recommended_comm_sparse_cut():
    from repro.launch.mesh import RING_MIN_CUT_BYTES, recommended_comm

    class FakeMesh:  # only truthiness/axis lookup is needed
        axis_names = ("pod", "data", "model")

    mesh = FakeMesh()
    axes = ("pod", "model")
    # unknown cut: conservative ring over DCI (unchanged behavior)
    assert recommended_comm(mesh, axes) == "ring"
    # tiny actual cut: latency-bound, all-reduce wins even across pods
    assert recommended_comm(mesh, axes, boundary_nnz=16) == "dense"
    big = RING_MIN_CUT_BYTES // 4 + 1
    assert recommended_comm(mesh, axes, boundary_nnz=big) == "ring"
    assert recommended_comm(None, boundary_nnz=16) == "host"


# ---------------------------------------------------------------------------
# Bench --check regression gate (pure comparison logic; no bench re-run)
# ---------------------------------------------------------------------------

def test_bench_check_gate(tmp_path):
    import copy
    import json

    from benchmarks.bench_temporal import check_against_baseline

    base = {
        "staging": {"speedup": 2.0},
        "gofs_staging": {"speedup": 1000.0},
        "async_staging": {"speedup": 1.0},
        "async_staging_bound": {"speedup": 2.0},
        "delta_staging": {"staged_bytes_ratio": 3.7, "load_speedup": 2.0},
        "warm_start": {"speedup": 9.0, "supersteps_saved": 682},
        "pagerank_runner": {"speedup": 2.0},
        "sparse": {"step_speedup": 4.0, "staged_bytes_ratio": 4.6,
                   "occupancy": 0.125},
        "plan_overhead": {"frac": 0.001},
        "shared_staging": {"staged_bytes_ratio": 2.0},
        "serving": {"throughput_ratio": 6.0, "restaged_bytes_repeat": 0,
                    "restaging_passes_repeat": 0},
        "streaming_ingest": {"speedup": 12.0, "incremental_steps": 4},
        "fused_superstep": {"fused_pallas_calls": 1, "state_vote_reduces": 0,
                            "eqn_ratio": 1.4},
        "cluster_scaling": {"max_per_host_fraction": 0.5},
    }
    p = str(tmp_path / "base.json")
    with open(p, "w") as f:
        json.dump(base, f)
    assert check_against_baseline(copy.deepcopy(base), p) == []
    # regression below both floor and baseline fraction -> caught
    bad = copy.deepcopy(base)
    bad["sparse"]["step_speedup"] = 1.0
    assert any("step_speedup" in v for v in check_against_baseline(bad, p))
    # occupancy is a deterministic cap
    bad2 = copy.deepcopy(base)
    bad2["sparse"]["occupancy"] = 0.5
    assert any("occupancy" in v for v in check_against_baseline(bad2, p))
    # the fused-kernel structural gates are deterministic too: a second
    # pallas_call or an escaped state-sized reduce is a fusion regression
    bad3 = copy.deepcopy(base)
    bad3["fused_superstep"]["fused_pallas_calls"] = 2
    assert any("fused_pallas_calls" in v
               for v in check_against_baseline(bad3, p))
    bad4 = copy.deepcopy(base)
    bad4["fused_superstep"]["state_vote_reduces"] = 1
    assert any("state_vote_reduces" in v
               for v in check_against_baseline(bad4, p))
    # noise-dominated rows gate on the absolute floor only: a big swing vs
    # baseline passes as long as the optimization clearly still exists
    noisy = copy.deepcopy(base)
    noisy["gofs_staging"]["speedup"] = 60.0
    assert check_against_baseline(noisy, p) == []
    noisy["gofs_staging"]["speedup"] = 3.0  # order(s) of magnitude lost
    assert any("gofs_staging" in v for v in check_against_baseline(noisy, p))
    # cluster staging economy is shard-derived: a host materializing the
    # whole collection again is a sharding regression, not noise
    bad5 = copy.deepcopy(base)
    bad5["cluster_scaling"]["max_per_host_fraction"] = 1.0
    assert any("max_per_host_fraction" in v
               for v in check_against_baseline(bad5, p))
    # missing rows and missing baseline are loud
    assert any("missing" in v
               for v in check_against_baseline({"staging": {}}, p))
    assert any("baseline" in v for v in check_against_baseline(
        base, str(tmp_path / "nope.json")))


# ---------------------------------------------------------------------------
# Mesh (subprocess): sparse == dense on the temporal-parallel lowering
# ---------------------------------------------------------------------------

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
from tests.test_sparse_blocked import _banded, bellman_iterate_program

cfg = GraphConfig(name="sp", num_vertices=400, avg_degree=3.0,
                  num_instances=4, num_partitions=4, block_size=32, seed=9)
tsg = generate_collection(cfg)
tmpl = tsg.template
assign = partition_graph(tmpl, 4, seed=9)
bg = build_blocked(tmpl, assign, 32)
w = np.stack([tsg.edge_values(t, "latency") for t in range(4)])
wb, live = _banded(bg, tmpl, w)
mesh = jax.make_mesh((2, 4), ("data", "model"))
eng_s = TemporalEngine(bg)
eng_m = TemporalEngine(bg, mesh=mesh, layout="sparse")
prog = min_plus_program("sssp", init=source_init(0))
for pattern in ("sequential", "independent"):
    rm = eng_m.run(prog, wb, pattern=pattern)
    rs = eng_s.run(prog, wb, pattern=pattern)
    assert np.array_equal(rm.values, rs.values), pattern
# iterate program on the mesh sparse path
it = bellman_iterate_program(0, iters=4)
assert np.array_equal(eng_m.run(it, wb, pattern="independent").values,
                      eng_s.run(it, wb, pattern="independent").values)
# eventually + merge, sparse mesh vs dense stacked
pm = eng_m.run(prog, wb, pattern="eventually", merge="mean")
ps = eng_s.run(prog, wb, pattern="eventually", merge="mean")
assert np.array_equal(pm.values, ps.values)
assert np.array_equal(pm.merged, ps.merged)
# async sparse staging under the mesh
ra = eng_m.run(prog, wb, pattern="independent", staging="async")
assert np.array_equal(ra.values, rs.values)
# ring comm backend with sparse tiles (comm is layout-agnostic)
eng_r = TemporalEngine(bg, mesh=mesh, layout="sparse", comm="ring")
assert np.array_equal(eng_r.run(prog, wb, pattern="independent").values,
                      rs.values)
# fused superstep kernel (interpret) inside shard_map: both layouts,
# sequential AND independent, still bitwise vs the stacked oracle
for lay in ({}, dict(layout="sparse")):
    eng_f = TemporalEngine(bg, mesh=mesh, use_pallas="fused", **lay)
    for pattern in ("sequential", "independent"):
        rf = eng_f.run(prog, wb, pattern=pattern)
        ro = eng_s.run(prog, wb, pattern=pattern)
        assert np.array_equal(rf.values, ro.values), (lay, pattern)
        assert np.array_equal(rf.stats["supersteps"],
                              ro.stats["supersteps"]), (lay, pattern)
# fused kernel x ring-rs comm: the v2 exchange composes with the fused
# local stage (min-plus stays bitwise end to end)
eng_frs = TemporalEngine(bg, mesh=mesh, layout="sparse",
                         use_pallas="fused", comm="ring-rs")
assert np.array_equal(eng_frs.run(prog, wb, pattern="independent").values,
                      rs.values)
print("SPARSE MESH OK")
"""


@pytest.mark.slow
def test_sparse_mesh_matches_dense_stacked():
    env_ = dict(os.environ)
    env_.pop("XLA_FLAGS", None)
    env_["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], env=env_, capture_output=True,
        text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "SPARSE MESH OK" in r.stdout
