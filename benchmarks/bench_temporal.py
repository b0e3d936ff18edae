"""Temporal engine economy: batched instance staging + unified runner vs
the per-instance Python loop the algorithms used before the engine.

Rows (also written to BENCH_temporal.json; field-by-field reference in
docs/BENCHMARKS.md):

* staging           — fill_local/fill_boundary per instance + np.stack
                      vs one fill_*_batch scatter for the whole collection
* gofs_staging      — per-(timestep, subgraph) instance reads vs the
                      GoFSStore.load_blocked bulk slice path
* async_staging     — end-to-end (GoFS stage + engine run): one-shot sync
                      staging vs the double-buffered SlicePrefetcher stream
                      (slice reads + tile fills overlap device execution).
                      On a single-core box with page-cached files both
                      halves are CPU-bound, so this row records ~1.0x —
                      the staging-bound regime lives in the next row
* async_staging_bound — the same pipeline against a store with emulated
                      per-slice read latency (the paper's remote-disk
                      regime, where GoFS slices arrive from 12 hosts):
                      a deep prefetch window + parallel read workers
                      overlap the I/O waits with execution for a real
                      wall-clock win (sleeps burn no CPU, so the overlap
                      is measurable even single-core)
* delta_staging     — full sparse value loads vs the deploy-time delta
                      chain (deduplicated tile payload pools) on a
                      slowly-varying collection: bytes moved from the
                      store + load time, bitwise parity asserted
* warm_start        — cold fixpoints vs warm-started ones (instance t
                      seeded from t-1's converged state) on a
                      monotone-tightening chain workload: supersteps
                      saved + wall-clock speedup, bitwise parity asserted
* pagerank_runner   — per-instance device_graph + pagerank_run loop vs one
                      engine run scanning the staged (I, ...) tensors
* sparse            — dense vs block-sparse layout on a banded-activity
                      workload (~1/8 tile occupancy): staged bytes +
                      engine-step time, bitwise min-plus parity asserted
* use_pallas        — the semiring SpMV kernel (interpret mode) walking the
                      dense template tile list vs the packed active-tile
                      list with an nnz skip, vs the jnp oracle
* comm_backend      — the same engine run under each boundary-exchange
                      backend (repro.core.comm): dense psum/pmin vs
                      collective-permute ring vs host-side gather, stacked
                      in-process + dense-vs-ring on a forced host mesh
* mesh              — stacked vs temporal-parallel mesh execution on forced
                      host devices (subprocess; tracks scaling regressions)
* plan_overhead     — GopherSession.plan cost (auto-selection + cost
                      models, metadata only) vs executing the planned run
* shared_staging    — run_many over 3 analytics (sssp, nhop, tracking):
                      shared staging passes/bytes vs 3 independent runs,
                      results asserted identical
* serving           — warm GopherService answering Q=8 concurrent SSSP
                      point queries (source-axis batching + resident
                      staging cache) vs one cold session per query:
                      p50/p95 latency, throughput ratio, zero bytes
                      re-staged on repeat queries — results asserted
                      bitwise identical per source

``run(check=True)`` (CLI: ``--check``, also via ``benchmarks.run temporal
--check``) re-measures and compares against the committed
BENCH_temporal.json with per-row regression thresholds instead of
rewriting it; any violation exits nonzero.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import BENCH_GRAPH, deployments, emit, store_for
from repro.core.blocked import build_blocked
from repro.core.engine import (
    TemporalEngine,
    min_plus_program,
    pagerank_program,
    source_init,
)
from repro.core.generator import generate_collection
from repro.core.graph import GraphTemplate, TimeSeriesGraph
from repro.core.partition import partition_graph
from repro.core.semiring import INF
from repro.core.algorithms.pagerank import (
    edge_weights_for_instance,
    edge_weights_for_instances,
)
from repro.gofs import deploy_collection
from repro.gofs.slices import read_array_slice
from repro.gofs.store import GoFSStore

OUT_JSON = "BENCH_temporal.json"


class _SlowStore(GoFSStore):
    """GoFSStore with emulated per-slice read latency.

    The paper's GoFS serves slices from the local disks of 12 hosts; on
    this box every file is page-cached, so reads cost ~0 wall-clock and
    the prefetch pipeline has nothing to hide.  Sleeping inside the cache
    loader (cache misses only) restores the remote-read regime without
    burning CPU — which is also why the overlap shows up even on a
    single-core machine."""

    io_delay_s = 0.05

    def _load(self, pid, slice_name):
        path = os.path.join(self.root, f"part_{pid}", slice_name)

        def loader():
            time.sleep(self.io_delay_s)
            return read_array_slice(path, self.stats)

        return self.cache.get(f"{pid}/{slice_name}", loader)


def _delta_collection(cfg) -> TimeSeriesGraph:
    """Bench-scale slowly-varying collection: localized sparse support,
    ~1/8 of the live edges tightening per step — most tiles are bitwise
    unchanged between consecutive instances (the delta chain's regime)."""
    col = generate_collection(cfg)
    src = np.asarray(col.template.src)
    dst = np.asarray(col.template.dst)
    rng = np.random.default_rng(0)
    live = (src < 512) & (dst < 512)
    w = np.where(live, np.asarray(col.edge_values(0, "latency"), np.float32),
                 np.float32(INF)).astype(np.float32)
    ws = [w]
    idx = np.nonzero(live)[0]
    for _t in range(1, len(col)):
        w = ws[-1].copy()
        band = rng.choice(idx, size=max(1, len(idx) // 8), replace=False)
        w[band] = (w[band] * 0.7).astype(np.float32)
        ws.append(w)
    insts = []
    for t in range(len(col)):
        gi = col.instances[t]
        ev = dict(gi.edge_values)
        ev["latency"] = ws[t]
        insts.append(dataclasses.replace(gi, edge_values=ev))
    return TimeSeriesGraph(template=col.template, instances=insts)


def _time(fn, repeats: int = 3) -> float:
    fn()  # warm (jit/compile/cache)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _edge_bands(bg, src, dst, n_bands: int) -> np.ndarray:
    """Tile-aligned band id per edge: every edge of one tile shares
    (row_block, col_block), so banding on their sum keeps each tile
    single-band — instance *i* activating band ``i % n_bands`` yields
    ~1/n_bands tile occupancy, the GoFS-motivating sparse-activity
    regime."""
    B = bg.block_size
    local = bg.part_of[src] == bg.part_of[dst]
    slot_of = np.full(len(bg.part_of), 0, np.int64)
    pub = bg.bslot_of_src
    valid = pub >= 0
    slot_of[pub[valid]] = np.nonzero(valid)[0]
    row_blk = np.where(local, bg.local_of[src] // B, slot_of[src] // B)
    return (row_blk + bg.local_of[dst] // B) % n_bands


def run(check: bool = False) -> None:
    tsg = generate_collection(BENCH_GRAPH)
    tmpl = tsg.template
    assign = partition_graph(tmpl, BENCH_GRAPH.num_partitions,
                             seed=BENCH_GRAPH.seed)
    bg = build_blocked(tmpl, assign, BENCH_GRAPH.block_size)
    I = len(tsg)
    w = np.stack([tsg.edge_values(t, "latency") for t in range(I)])
    active = np.stack([tsg.edge_values(t, "active") for t in range(I)])
    results = {}

    # ---- staging: per-instance fill loop vs batched scatter ---------------
    def stage_loop():
        lt = np.stack([bg.fill_local(w[i]) for i in range(I)])
        bt = np.stack([bg.fill_boundary(w[i]) for i in range(I)])
        return lt, bt

    def stage_batch():
        return bg.fill_local_batch(w), bg.fill_boundary_batch(w)

    t_loop = _time(stage_loop)
    t_batch = _time(stage_batch)
    a, b = stage_loop(), stage_batch()
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    emit("temporal/staging_loop", t_loop * 1e6, f"instances={I}")
    emit("temporal/staging_batch", t_batch * 1e6,
         f"speedup={t_loop / max(t_batch, 1e-12):.2f}x")
    results["staging"] = {
        "instances": I, "loop_s": t_loop, "batch_s": t_batch,
        "speedup": t_loop / max(t_batch, 1e-12),
    }

    # ---- GoFS staging: per-instance reads vs bulk slice path --------------
    store = store_for("s4-i6", cache_slots=14)

    def gofs_loop():
        out = np.empty((store.num_timesteps(), tmpl.num_edges), np.float32)
        for g in store.subgraph_ids():
            topo = store.get_topology(g)
            for t in range(store.num_timesteps()):
                sgi = store.get_instance(t, g)
                out[t, topo.local_edge_id] = sgi.local_edge_values["latency"]
                out[t, topo.remote_edge_id] = sgi.remote_edge_values["latency"]
        return out

    def gofs_bulk():
        return store.edge_attr_matrix("latency")

    t_gloop = _time(gofs_loop)
    t_gbulk = _time(gofs_bulk)
    assert np.allclose(gofs_loop(), gofs_bulk())
    emit("temporal/gofs_staging_loop", t_gloop * 1e6, "")
    emit("temporal/gofs_staging_bulk", t_gbulk * 1e6,
         f"speedup={t_gloop / max(t_gbulk, 1e-12):.2f}x")
    results["gofs_staging"] = {
        "loop_s": t_gloop, "bulk_s": t_gbulk,
        "speedup": t_gloop / max(t_gbulk, 1e-12),
    }

    # ---- async staging: end-to-end (stage + run), sync vs prefetched ------
    # cache_slots=0 so every repeat pays the real disk reads; sequential
    # SSSP is the paper's flagship temporal workload (carried distances).
    store0 = store_for("s4-i6", cache_slots=0)
    eng_t = TemporalEngine(bg)
    prog = min_plus_program("sssp", init=source_init(0))

    def e2e_sync():
        tiles, btiles = store0.load_blocked(bg, "latency")
        return eng_t.run(prog, tiles=tiles, btiles=btiles,
                         pattern="sequential")

    def e2e_async():
        stream = store0.load_blocked_stream(bg, "latency", prefetch_depth=2)
        return eng_t.run(prog, pattern="sequential", stream=stream)

    t_sync = _time(e2e_sync, repeats=3)
    t_async = _time(e2e_async, repeats=3)
    ra, rb = e2e_sync(), e2e_async()
    assert np.array_equal(ra.values, rb.values)  # staging must be invisible
    emit("temporal/e2e_sync_staging", t_sync * 1e6, f"instances={I}")
    emit("temporal/e2e_async_staging", t_async * 1e6,
         f"speedup={t_sync / max(t_async, 1e-12):.2f}x")
    results["async_staging"] = {
        "instances": I, "prefetch_depth": 2,
        "sync_s": t_sync, "async_s": t_async,
        "speedup": t_sync / max(t_async, 1e-12),
    }

    # ---- async staging, staging-bound: emulated remote-slice latency ------
    # s4-i1 (one instance per pack) maximizes slice count; cache_slots=0
    # forces every read through the delayed loader.  A depth-4 window with
    # 4 read workers keeps 3 chunks' reads in flight concurrently — the
    # sleeps overlap each other AND the engine run, so the pipeline wins
    # ~2x while the sync path pays every wait serially.
    _, root_i1 = deployments()["s4-i1"]
    slow = _SlowStore(root_i1, cache_slots=0)

    def bnd_sync():
        tiles, btiles = slow.load_blocked(bg, "latency")
        return eng_t.run(prog, tiles=tiles, btiles=btiles,
                         pattern="sequential")

    def bnd_async():
        stream = slow.load_blocked_stream(
            bg, "latency", prefetch_depth=4, chunk_instances=2,
            num_workers=4)
        return eng_t.run(prog, pattern="sequential", stream=stream)

    ra, rb = bnd_sync(), bnd_async()
    assert np.array_equal(ra.values, rb.values)  # staging must be invisible
    t_bsync = _time(bnd_sync, repeats=2)
    t_basync = _time(bnd_async, repeats=2)
    emit("temporal/e2e_sync_staging_bound", t_bsync * 1e6,
         f"io_delay_s={_SlowStore.io_delay_s}")
    emit("temporal/e2e_async_staging_bound", t_basync * 1e6,
         f"speedup={t_bsync / max(t_basync, 1e-12):.2f}x")
    results["async_staging_bound"] = {
        "instances": I, "io_delay_s": _SlowStore.io_delay_s,
        "prefetch_depth": 4, "chunk_instances": 2, "num_workers": 4,
        "sync_s": t_bsync, "async_s": t_basync,
        "speedup": t_bsync / max(t_basync, 1e-12),
    }

    # ---- delta staging: full sparse loads vs the deploy-time delta chain --
    # slowly-varying collection deployed once (skip-if-exists, like
    # common.deployments); c0 cache so timings pay real reads.  The byte
    # ratio is deterministic (recorded chain vs staged shapes).
    cfg_d = dataclasses.replace(BENCH_GRAPH, name="tr-bench-delta")
    root_d = "/tmp/gofs_bench_delta"
    if not os.path.exists(os.path.join(root_d, "collection.json")):
        deploy_collection(_delta_collection(cfg_d), cfg_d, root_d,
                          sparse_absent={"latency": INF})
    store_d = GoFSStore(root_d, cache_slots=0)
    full = store_d.load_blocked(bg, "latency", zero=INF, layout="sparse",
                                delta=False)
    dlt = store_d.load_blocked(bg, "latency", zero=INF, layout="sparse",
                               delta=True)
    # reconstruction must be bitwise-invisible before any byte counts
    assert np.array_equal(np.asarray(full.tiles), np.asarray(dlt.tiles))
    assert np.array_equal(np.asarray(full.btiles), np.asarray(dlt.btiles))
    assert full.source_bytes is None and dlt.source_bytes is not None
    t_dfull = _time(lambda: store_d.load_blocked(
        bg, "latency", zero=INF, layout="sparse", delta=False))
    t_ddelta = _time(lambda: store_d.load_blocked(
        bg, "latency", zero=INF, layout="sparse", delta=True))
    dratio, dmono = store_d.delta_stats("latency", zero=INF)
    bytes_full = full.staged_bytes()
    bratio = bytes_full / max(dlt.source_bytes, 1)
    emit("temporal/delta_staging_full", t_dfull * 1e6,
         f"bytes={bytes_full}")
    emit("temporal/delta_staging_delta", t_ddelta * 1e6,
         f"bytes_ratio={bratio:.2f}x;unique_ratio={dratio:.3f}")
    results["delta_staging"] = {
        "instances": I, "occupancy": full.occupancy(),
        "delta_unique_ratio": dratio, "delta_monotone": dmono,
        "staged_bytes_full": bytes_full,
        "source_bytes_delta": dlt.source_bytes,
        "staged_bytes_ratio": bratio,
        "full_load_s": t_dfull, "delta_load_s": t_ddelta,
        "load_speedup": t_dfull / max(t_ddelta, 1e-12),
    }

    # ---- warm start: cold fixpoints vs t-1-seeded ones --------------------
    # chain graph whose every block hop crosses partitions: a cold SSSP
    # fixpoint needs ~V/B supersteps per instance, while the warm seed is
    # already converged up to the slowly-tightening tail — the incremental
    # recompute the delta chain makes worth exploiting.
    Vw, Bw, Pw, Iw = 2048, 32, 4, 12
    tmpl_w = GraphTemplate(num_vertices=Vw, src=np.arange(Vw - 1),
                           dst=np.arange(1, Vw))
    bg_w = build_blocked(tmpl_w, (np.arange(Vw) // Bw) % Pw, Bw)
    w_w = np.ones((Iw, Vw - 1), np.float32)
    for t in range(1, Iw):
        w_w[t] = w_w[t - 1]
        w_w[t, -32:] *= 0.9  # tail tightens: monotone-improving
    prog_w = min_plus_program("sssp", init=source_init(0),
                              max_supersteps=256)
    eng_w = TemporalEngine(bg_w)
    cold = eng_w.run(prog_w, w_w, pattern="independent")
    warm = eng_w.run(prog_w, w_w, pattern="independent", warm_start=True)
    assert np.array_equal(cold.values, warm.values)  # warm is exact here
    saved = warm.supersteps_saved()
    t_cold = _time(lambda: eng_w.run(prog_w, w_w, pattern="independent"))
    t_warm = _time(lambda: eng_w.run(prog_w, w_w, pattern="independent",
                                     warm_start=True))
    emit("temporal/warm_start_cold", t_cold * 1e6,
         f"supersteps={int(cold.stats['supersteps'].sum())}")
    emit("temporal/warm_start_warm", t_warm * 1e6,
         f"speedup={t_cold / max(t_warm, 1e-12):.2f}x;"
         f"saved={int(saved.sum())}")
    results["warm_start"] = {
        "instances": Iw, "num_vertices": Vw,
        "supersteps_cold": int(cold.stats["supersteps"].sum()),
        "supersteps_warm": int(warm.stats["supersteps"].sum()),
        "supersteps_saved": int(saved.sum()),
        "cold_s": t_cold, "warm_s": t_warm,
        "speedup": t_cold / max(t_warm, 1e-12),
    }

    # ---- gopher session: plan overhead ------------------------------------
    # planning is metadata-only (blocked structure + recorded maps + comm
    # cost model); the row gates that it stays a rounding error next to
    # the run it configures.
    from repro.gopher import GopherSession

    t0_sess = time.perf_counter()
    sess_po = GopherSession(store, block_size=BENCH_GRAPH.block_size)
    t_sess_init = time.perf_counter() - t0_sess
    t_plan = _time(lambda: sess_po.plan("sssp", source=0))
    plan_po = sess_po.plan("sssp", source=0)
    t_planned_run = _time(lambda: sess_po.run(plan_po), repeats=2)
    emit("temporal/gopher_plan", t_plan * 1e6,
         f"staging={plan_po.staging.value};layout={plan_po.layout.value}")
    emit("temporal/gopher_planned_run", t_planned_run * 1e6,
         f"plan_frac={t_plan / max(t_planned_run, 1e-12):.4f}")
    results["plan_overhead"] = {
        "session_init_s": t_sess_init,
        "plan_s": t_plan,
        "run_s": t_planned_run,
        "frac": t_plan / max(t_planned_run, 1e-12),
    }

    # ---- gopher session: shared staging (run_many) ------------------------
    # three analytics over one collection: sssp + nhop share the latency
    # batch, nhop's hop probe + tracking share the unit-weight batch, so
    # the shared pass stages each distinct batch once while 3 independent
    # runs stage 2x each.  The byte ratio is shape-derived (deterministic);
    # results are asserted identical before timing counts.
    def _sh_session():
        return GopherSession(store_for("s4-i6", cache_slots=14),
                             block_size=BENCH_GRAPH.block_size)

    def _sh_plans(s):
        return [s.plan("sssp", source=0),
                s.plan("nhop", source=0, n_hops=6),
                s.plan("tracking", plate=3, initial_vertex=0)]

    s_sh = _sh_session()
    t0 = time.perf_counter()
    r_shared = s_sh.run_many(_sh_plans(s_sh))
    t_shared = time.perf_counter() - t0
    rep_sh = dict(s_sh.last_run_report)

    t0 = time.perf_counter()
    bytes_ind = passes_ind = 0
    singles = []
    for p in _sh_plans(_sh_session()):
        s1 = _sh_session()
        singles.append(s1.run(p))
        bytes_ind += s1.last_run_report["staged_bytes"]
        passes_ind += s1.last_run_report["staging_passes"]
    t_indep = time.perf_counter() - t0
    for a, b in zip(r_shared, singles):  # sharing must be invisible
        if a.engine is not None and b.engine is not None:
            assert np.array_equal(a.engine.values, b.engine.values)
        for k in a.output:
            assert np.array_equal(a.output[k], b.output[k]), k
    ratio = bytes_ind / max(rep_sh["staged_bytes"], 1)
    emit("temporal/shared_staging", t_shared * 1e6,
         f"bytes_ratio={ratio:.2f}x;passes={rep_sh['staging_passes']}"
         f"vs{passes_ind}")
    emit("temporal/independent_staging", t_indep * 1e6,
         f"speedup={t_indep / max(t_shared, 1e-12):.2f}x")
    results["shared_staging"] = {
        "analytics": rep_sh["analytics"],
        "staged_bytes_shared": rep_sh["staged_bytes"],
        "staged_bytes_independent": bytes_ind,
        "staged_bytes_ratio": ratio,
        "staging_passes_shared": rep_sh["staging_passes"],
        "staging_passes_independent": passes_ind,
        "shared_s": t_shared,
        "independent_s": t_indep,
        "speedup": t_indep / max(t_shared, 1e-12),
    }

    # ---- gopher service: warm serving vs one-session-per-query ------------
    results["serving"] = serving_row()

    # ---- streaming ingestion: live tail steps vs full re-runs -------------
    results["streaming_ingest"] = streaming_ingest_row()

    # ---- runner: per-instance pagerank loop vs one engine scan ------------
    from repro.core.superstep import Comm, device_graph, pagerank_run

    iters = 10
    V = tmpl.num_vertices

    def pr_loop():
        ranks = []
        for i in range(I):
            wi = edge_weights_for_instance(tmpl.src, active[i], V)
            dg = device_graph(bg, bg.fill_local(wi, zero=0.0),
                              bg.fill_boundary(wi, zero=0.0))
            r, _ = pagerank_run(dg, Comm(), num_vertices=V, iters=iters)
            ranks.append(bg.gather_vertex(np.asarray(r)))
        return np.stack(ranks)

    eng = TemporalEngine(bg)
    prog = pagerank_program(V, iters=iters)
    pw = edge_weights_for_instances(tmpl.src, active, V)

    def pr_engine():
        return eng.run(prog, pw, pattern="independent").values

    t_ploop = _time(pr_loop, repeats=2)
    t_peng = _time(pr_engine, repeats=2)
    assert np.abs(pr_loop() - pr_engine()).max() < 1e-6
    emit("temporal/pagerank_loop", t_ploop / I * 1e6,
         f"instances={I};iters={iters}")
    emit("temporal/pagerank_engine", t_peng / I * 1e6,
         f"speedup={t_ploop / max(t_peng, 1e-12):.2f}x")
    results["pagerank_runner"] = {
        "instances": I, "iters": iters,
        "loop_s": t_ploop, "engine_s": t_peng,
        "speedup": t_ploop / max(t_peng, 1e-12),
    }

    # ---- block-sparse layout: staged bytes + engine-step economy ----------
    # banded temporal activity (~1/8 tile occupancy): per instance only one
    # of n_bands tile-aligned bands is live — the regime the sparse layout
    # targets (most inter-subgraph tiles empty per timestep).
    n_bands = 8
    band = _edge_bands(bg, tmpl.src, tmpl.dst, n_bands)
    live = band[None, :] == (np.arange(I) % n_bands)[:, None]  # (I, E)
    eng_d = TemporalEngine(bg)
    eng_sp = TemporalEngine(bg, layout="sparse")

    # parity first (bitwise for min-plus), on banded SSSP latencies
    wb = np.where(live, w, np.inf).astype(np.float32)
    prog_s = min_plus_program("sssp", init=source_init(0))
    r_dense = eng_d.run(prog_s, wb, pattern="sequential")
    r_sparse = eng_sp.run(prog_s, wb, pattern="sequential")
    assert np.array_equal(r_dense.values, r_sparse.values)  # layout invisible

    # timing on fixed-work PageRank (20 supersteps — no convergence noise)
    sp_iters = 20
    pw_b = edge_weights_for_instances(tmpl.src, live.astype(np.float32), V)
    prog_p = pagerank_program(V, iters=sp_iters)
    tiles_d, btiles_d = eng_d.stage(pw_b, prog_p.zero_fill)
    sp = eng_sp.stage_sparse(pw_b, prog_p.zero_fill)
    rp_d = eng_d.run(prog_p, tiles=tiles_d, btiles=btiles_d,
                     pattern="independent")
    rp_s = eng_sp.run(prog_p, sparse=sp, pattern="independent")
    assert np.abs(rp_d.values - rp_s.values).max() < 1e-6
    t_dstep = _time(lambda: eng_d.run(prog_p, tiles=tiles_d,
                                      btiles=btiles_d,
                                      pattern="independent"))
    t_sstep = _time(lambda: eng_sp.run(prog_p, sparse=sp,
                                       pattern="independent"))
    bytes_d = int(np.asarray(tiles_d).nbytes + np.asarray(btiles_d).nbytes)
    bytes_s = sp.staged_bytes()
    occ = sp.occupancy()
    emit("temporal/sparse_engine_dense", t_dstep * 1e6,
         f"tiles={bg.t_max}+{bg.tb_max}")
    emit("temporal/sparse_engine_sparse", t_sstep * 1e6,
         f"speedup={t_dstep / max(t_sstep, 1e-12):.2f}x;"
         f"occupancy={occ:.3f}")
    emit("temporal/sparse_staged_bytes", float(bytes_s),
         f"dense={bytes_d};ratio={bytes_d / max(bytes_s, 1):.2f}x")
    results["sparse"] = {
        "instances": I, "iters": sp_iters, "n_bands": n_bands,
        "occupancy": occ,
        "bucket": sp.bucket, "bbucket": sp.bbucket,
        "t_max": bg.t_max, "tb_max": bg.tb_max,
        "dense_step_s": t_dstep, "sparse_step_s": t_sstep,
        "step_speedup": t_dstep / max(t_sstep, 1e-12),
        "staged_bytes_dense": bytes_d, "staged_bytes_sparse": bytes_s,
        "staged_bytes_ratio": bytes_d / max(bytes_s, 1),
    }

    # ---- use_pallas: kernel walking dense vs packed active-tile lists -----
    from repro.core.semiring import MIN_PLUS
    from repro.kernels.semiring_spmm.ops import spmv_blocked
    import jax.numpy as jnp

    p0 = 0
    dt = jnp.asarray(bg.fill_local(wb[0])[p0])
    drows = jnp.asarray(bg.tiles_rc[p0, :, 0])
    dcols = jnp.asarray(bg.tiles_rc[p0, :, 1])
    sp_mp = bg.stage_sparse(wb[:1])  # same instance, min-plus zero fill
    st = jnp.asarray(sp_mp.tiles[0, p0])
    srows = jnp.asarray(sp_mp.rows[0, p0])
    scols = jnp.asarray(sp_mp.cols[0, p0])
    snnz = jnp.asarray(int(sp_mp.nnz[0, p0]), jnp.int32)
    x = jnp.asarray(np.random.default_rng(0).random(bg.vp), jnp.float32)

    def k_dense():
        return spmv_blocked(dt, drows, dcols, x, MIN_PLUS,
                            use_pallas=True, interpret=True).block_until_ready()

    def k_sparse():
        return spmv_blocked(st, srows, scols, x, MIN_PLUS, use_pallas=True,
                            interpret=True, nnz=snnz,
                            n_out_blocks=bg.vp // bg.block_size,
                            ).block_until_ready()

    def k_ref():
        return spmv_blocked(st, srows, scols, x, MIN_PLUS, use_pallas=False,
                            n_out_blocks=bg.vp // bg.block_size,
                            ).block_until_ready()

    yk_d, yk_s, yk_r = np.asarray(k_dense()), np.asarray(k_sparse()), \
        np.asarray(k_ref())
    assert np.array_equal(yk_s, yk_r) and np.array_equal(yk_d, yk_s)
    t_kd, t_ks, t_kr = _time(k_dense), _time(k_sparse), _time(k_ref)
    emit("temporal/use_pallas_dense_walk", t_kd * 1e6,
         f"tiles={int(dt.shape[0])};interpret=True")
    emit("temporal/use_pallas_sparse_walk", t_ks * 1e6,
         f"tiles={int(st.shape[0])};nnz={int(snnz)}")
    results["use_pallas"] = {
        "interpret": True, "block_size": bg.block_size,
        "tiles_dense": int(dt.shape[0]), "tiles_packed": int(st.shape[0]),
        "nnz": int(snnz),
        "pallas_dense_s": t_kd, "pallas_sparse_s": t_ks, "jnp_sparse_s": t_kr,
        "dense_vs_sparse": t_kd / max(t_ks, 1e-12),
    }

    # ---- fused superstep kernel: one pallas_call per local stage ----------
    # The gated metrics are jaxpr-derived and DETERMINISTIC: the fused
    # path must lower its whole local stage (tile walk + semiring combine
    # + halt vote) to exactly one pallas_call with no state-sized XLA
    # reduction left outside the kernel, and must need strictly fewer
    # equations than the per-stage spmv sweep + separate vote.  Interpret
    # -mode wall clocks are recorded for the record but NOT gated (on CPU
    # the interpreter dominates; the structural counts are what transfer
    # to the TPU lowering).
    import jax

    from repro.core.superstep import (_fused_sweep_vote, _local_sweep,
                                      device_graph)

    dgf = device_graph(bg, bg.fill_local(wb[0]), bg.fill_boundary(wb[0]))
    x0f = jnp.asarray(np.where(np.asarray(dgf.vmask), 1.0, np.inf),
                      jnp.float32)

    def _all_eqns(jx):
        out, stack = [], list(jx.jaxpr.eqns)
        while stack:
            e = stack.pop()
            out.append(e)
            for sub in e.params.values():
                if hasattr(sub, "jaxpr"):
                    stack.extend(sub.jaxpr.eqns)
        return out

    def fused_sweep(xx):
        return _fused_sweep_vote(xx, dgf, MIN_PLUS, True)

    def spmv_sweep_vote(xx):
        xn = _local_sweep(xx, dgf, MIN_PLUS, ("spmv", True))
        return xn, jnp.any(jnp.where(dgf.vmask, xn != xx, False))

    eq_f = _all_eqns(jax.make_jaxpr(fused_sweep)(x0f))
    eq_s = _all_eqns(jax.make_jaxpr(spmv_sweep_vote)(x0f))
    n_pallas_f = sum(e.primitive.name == "pallas_call" for e in eq_f)
    state_elems_cap = int(dgf.n_parts)  # reduces over flags are fine
    n_state_reduces = sum(
        1 for e in eq_f
        if e.primitive.name in ("reduce_or", "reduce_and",
                                "reduce_max", "reduce_min")
        and int(np.prod(e.invars[0].aval.shape)) > state_elems_cap)

    # parity before timing, then interpret-mode wall clocks (ungated)
    jf = jax.jit(fused_sweep)
    js = jax.jit(spmv_sweep_vote)
    xf, chf = jf(x0f)
    xs_, chs = js(x0f)
    assert np.array_equal(np.asarray(xf), np.asarray(xs_))
    assert bool(np.max(np.asarray(chf)) > 0) == bool(np.asarray(chs))
    t_fsweep = _time(lambda: jax.block_until_ready(jf(x0f)))
    t_ssweep = _time(lambda: jax.block_until_ready(js(x0f)))

    # end-to-end engine runs, banded SSSP, all three kernel modes
    prog_f = min_plus_program("sssp", init=source_init(0))
    eng_fu = TemporalEngine(bg, use_pallas="fused")
    eng_pv = TemporalEngine(bg, use_pallas="spmv")
    r_or = eng_d.run(prog_f, wb, pattern="sequential")
    r_fu = eng_fu.run(prog_f, wb, pattern="sequential")
    r_pv = eng_pv.run(prog_f, wb, pattern="sequential")
    assert np.array_equal(r_or.values, r_fu.values)
    assert np.array_equal(r_or.values, r_pv.values)
    t_eor = _time(lambda: eng_d.run(prog_f, wb, pattern="sequential"),
                  repeats=2)
    t_efu = _time(lambda: eng_fu.run(prog_f, wb, pattern="sequential"),
                  repeats=2)
    t_epv = _time(lambda: eng_pv.run(prog_f, wb, pattern="sequential"),
                  repeats=2)
    emit("temporal/fused_superstep_pallas_calls", float(n_pallas_f),
         f"eqns={len(eq_f)};spmv_eqns={len(eq_s)}")
    emit("temporal/fused_superstep_sweep", t_fsweep * 1e6,
         f"spmv={t_ssweep * 1e6:.0f}us;interpret=True")
    results["fused_superstep"] = {
        "interpret": True,
        "fused_pallas_calls": n_pallas_f,
        "state_vote_reduces": n_state_reduces,
        "sweep_eqns_fused": len(eq_f),
        "sweep_eqns_spmv": len(eq_s),
        "eqn_ratio": len(eq_s) / max(len(eq_f), 1),
        "sweep_fused_s": t_fsweep, "sweep_spmv_s": t_ssweep,
        "engine_oracle_s": t_eor, "engine_spmv_s": t_epv,
        "engine_fused_s": t_efu,
    }

    # ---- comm backends: one workload, three boundary exchanges ------------
    prog_c = min_plus_program("sssp", init=source_init(0))
    comm_engines = {
        b: TemporalEngine(bg, comm=b) for b in ("dense", "ring", "host")
    }

    def comm_run(b):
        return comm_engines[b].run(prog_c, w, pattern="sequential")

    ref_vals = comm_run("dense").values
    stacked = {}
    for b in ("dense", "ring", "host"):
        # backends must be invisible: bitwise parity before timing
        assert np.array_equal(comm_run(b).values, ref_vals), b
        stacked[f"{b}_s"] = _time(lambda b=b: comm_run(b))
        emit(f"temporal/comm_{b}_stacked", stacked[f"{b}_s"] * 1e6,
             f"instances={I}")
    stacked["host_vs_dense"] = stacked["host_s"] / max(stacked["dense_s"],
                                                       1e-12)
    results["comm_backend"] = {"instances": I, "stacked": stacked,
                               "mesh": _comm_mesh_rows()}

    # ---- mesh: stacked vs temporal-parallel shard_map (forced devices) ----
    results["mesh"] = _mesh_rows()

    # ---- cluster: 2-process shard-local staging + inter-process gather ----
    results["cluster_scaling"] = _cluster_scaling_row()

    if check:
        failures = check_against_baseline(results)
        if "error" in results["cluster_scaling"]:
            failures.append("cluster_scaling: 2-process parity run failed — "
                            + results["cluster_scaling"]["error"][-200:])
        for f_ in failures:
            emit("temporal/check_failed", 0.0, f_)
        if failures:
            print(f"[bench_temporal --check] {len(failures)} regression(s):",
                  file=sys.stderr)
            for f_ in failures:
                print(f"  {f_}", file=sys.stderr)
            raise SystemExit(1)
        emit("temporal/check_ok", 0.0, f"rows={len(THRESHOLDS)}")
        return

    with open(OUT_JSON, "w") as f:
        json.dump(results, f, indent=2)
    emit("temporal/json_written", 0.0, OUT_JSON)


def serving_row() -> dict:
    """The serving economy row (standalone so the slow tier-1 test can run
    just this): a warm :class:`~repro.gopher.GopherService` answering Q=8
    concurrent SSSP point queries vs the no-serving-layer alternative —
    one cold :class:`~repro.gopher.GopherSession` per query.  Batched
    results are asserted bitwise identical to the per-query runs before
    any timing counts; the repeat-query staging report must show ZERO
    bytes re-staged (the warm-cache acceptance criterion).

    The collection is interactive-scale (deployed once, like the delta
    row's): the serving layer's regime is many small point queries where
    session spin-up (staging passes + jit compiles, paid per cold
    session) rivals the engine run — the main bench collection's
    multi-second dense runs would bury that economy under raw semiring
    compute on a CPU box."""
    from repro.gopher import GopherService, GopherSession

    cfg_s = dataclasses.replace(
        BENCH_GRAPH, name="tr-bench-serve", num_vertices=1024,
        num_instances=8, block_size=32)
    root_s = "/tmp/gofs_bench_serve"
    if not os.path.exists(os.path.join(root_s, "collection.json")):
        deploy_collection(generate_collection(cfg_s), cfg_s, root_s)

    Q = 8
    sources = list(range(Q))
    reqs = [("sssp", {"source": s}) for s in sources]
    svc = GopherService(GoFSStore(root_s, cache_slots=14),
                        block_size=cfg_s.block_size).start()
    svc.query("sssp", source=sources[0])  # warm: stage + compile
    svc.query("sssp", source=sources[0])  # repeat: must re-stage nothing
    restaged = int(svc.session.last_run_report["staged_bytes"])
    repeat_passes = int(svc.session.last_run_report["staging_passes"])

    def served():
        return svc.query_many(reqs)

    t_warm_batch = _time(served, repeats=3)
    outs = served()
    rep = svc.report()
    svc.stop()

    # baseline: a fresh session per query (cold staging, cold jit)
    def per_query():
        res = []
        for s in sources:
            sess = GopherSession(GoFSStore(root_s, cache_slots=14),
                                 block_size=cfg_s.block_size)
            res.append(sess.run(sess.plan("sssp", source=s)))
        return res

    singles = per_query()
    for a, b in zip(outs, singles):  # batching must be invisible
        assert np.array_equal(a.output["final"], b.output["final"])
    t_per_query = _time(per_query, repeats=2)

    ratio = t_per_query / max(t_warm_batch, 1e-12)
    emit("temporal/serving_per_query", t_per_query * 1e6, f"q={Q}")
    emit("temporal/serving_warm_batched", t_warm_batch * 1e6,
         f"throughput_ratio={ratio:.2f}x;"
         f"p95_ms={rep['p95_ms']:.1f};restaged={restaged}")
    return {
        "q": Q,
        "p50_ms": rep["p50_ms"], "p95_ms": rep["p95_ms"],
        "widest_batch": rep["widest_batch"],
        "warm_batch_s": t_warm_batch, "per_query_s": t_per_query,
        "throughput_ratio": ratio,
        "restaged_bytes_repeat": restaged,
        "restaging_passes_repeat": repeat_passes,
    }


def streaming_ingest_row() -> dict:
    """The streaming-ingestion row (standalone so the slow tier-1 test can
    run just this): a live-tailed session absorbing appended instances vs
    re-running the analytic from scratch after every append.

    A prefix of an interactive-scale collection is deployed, a
    ``GopherSession.tail`` establishes the initial full result, then the
    remaining instances are appended batch-by-batch
    (:func:`~repro.gofs.append_instances`) with one tail step timed per
    append — refresh (manifest poll + tail cache invalidation) plus one
    warm incremental engine pass over just the appended batch.  The tailed
    history is asserted bitwise identical to a cold full run over the
    grown collection BEFORE any timing counts.  The gated ``speedup`` is
    cold-full-re-run wall time over the steady-state tail step (both
    jit-warm: the tail loop repeats one suffix shape, the parity check
    compiles the full-size runner)."""
    import shutil

    from repro.gofs import append_instances
    from repro.gopher import GopherSession

    cfg_t = dataclasses.replace(
        BENCH_GRAPH, name="tr-bench-stream", num_vertices=1024,
        num_instances=12, block_size=32)
    tsg_t = generate_collection(cfg_t)
    prefix, batch = 4, 2
    root_t = "/tmp/gofs_bench_stream"
    # always redeploy the prefix: the row itself grows the collection, so
    # a previous run's grown deployment must not short-circuit the appends
    if os.path.exists(root_t):
        shutil.rmtree(root_t)
    deploy_collection(
        TimeSeriesGraph(template=tsg_t.template,
                        instances=tsg_t.instances[:prefix]),
        cfg_t, root_t)

    sess = GopherSession(GoFSStore(root_t, cache_slots=14),
                         block_size=cfg_t.block_size,
                         staging_cache_bytes=256 << 20)
    u = sess.tail("sssp", source=0)
    assert u.mode == "full", u.mode
    tail_steps = []
    for k in range(prefix, len(tsg_t), batch):
        append_instances(
            TimeSeriesGraph(template=tsg_t.template,
                            instances=tsg_t.instances[k:k + batch]),
            root_t)
        t0 = time.perf_counter()
        u = sess.tail("sssp", source=0)
        tail_steps.append(time.perf_counter() - t0)
        assert u.mode == "incremental", u.mode

    # exactness gates the row: the tailed full history must be bitwise
    # identical to a cold run over the grown collection
    cold = GopherSession(GoFSStore(root_t, cache_slots=14),
                         block_size=cfg_t.block_size)
    ref = cold.run(cold.plan("sssp", source=0))
    assert np.array_equal(u.result.engine.values, ref.engine.values)
    assert np.array_equal(u.result.output["final"], ref.output["final"])

    # baseline: no streaming layer — re-run from scratch over the grown
    # collection (fresh session: staging passes + planning paid again)
    def full_rerun():
        s = GopherSession(GoFSStore(root_t, cache_slots=14),
                          block_size=cfg_t.block_size)
        return s.run(s.plan("sssp", source=0))

    t_full = _time(full_rerun, repeats=2)
    # warm-session full re-run (jit + session warm, staging re-done):
    # the strongest non-streaming alternative, reported for context
    t_full_warm = _time(lambda: cold.run(cold.plan("sssp", source=0)),
                        repeats=2)
    # steady-state step: first append pays the suffix-shape compile
    t_tail = min(tail_steps[1:]) if len(tail_steps) > 1 else tail_steps[0]
    speedup = t_full / max(t_tail, 1e-12)
    emit("temporal/streaming_full_rerun", t_full * 1e6,
         f"instances={len(tsg_t)}")
    emit("temporal/streaming_tail_step", t_tail * 1e6,
         f"speedup={speedup:.2f}x;appends={len(tail_steps)};batch={batch}")
    return {
        "instances_total": len(tsg_t), "prefix": prefix, "batch": batch,
        "incremental_steps": len(tail_steps),
        "tail_step_s": t_tail,
        "tail_step_first_s": tail_steps[0],
        "full_rerun_s": t_full,
        "full_rerun_warm_s": t_full_warm,
        "speedup": speedup,
        "speedup_vs_warm": t_full_warm / max(t_tail, 1e-12),
    }


# Per-row regression gates for ``--check``: (row, field) -> (kind, floor,
# rel_frac).  ``min``: fresh value must be >= max(floor, rel_frac *
# baseline) — the absolute floor catches a lost optimization outright, the
# relative guard (None = disabled) catches slow drift vs the committed
# BENCH_temporal.json on rows stable enough to compare run-to-run.
# ``max``: fresh value must stay <= ceiling (deterministic quantities
# only).  Rows whose ratio is dominated by disk/cache or thread-scheduling
# noise (gofs_staging swings 2x between runs; async_staging shares cores
# between fill threads and the engine on CPU boxes) gate on the absolute
# floor alone.
THRESHOLDS = {
    ("staging", "speedup"): ("min", 1.3, 0.5),
    ("gofs_staging", "speedup"): ("min", 50.0, None),
    ("async_staging", "speedup"): ("min", 0.5, None),
    # staging-bound variant: deterministic sleeps dominate the sync path,
    # so the overlap win is stable run-to-run (~2x measured single-core)
    ("async_staging_bound", "speedup"): ("min", 1.5, 0.6),
    # deterministic (recorded chain vs staged shapes): the acceptance
    # target for the delta dedupe — and the load must not get slower
    ("delta_staging", "staged_bytes_ratio"): ("min", 2.0, 0.9),
    ("delta_staging", "load_speedup"): ("min", 0.8, None),
    # warm-started fixpoints: supersteps saved is deterministic, the
    # wall-clock win tracks it (~9x measured)
    ("warm_start", "speedup"): ("min", 1.5, 0.5),
    ("warm_start", "supersteps_saved"): ("min", 100.0, 0.9),
    ("pagerank_runner", "speedup"): ("min", 1.3, 0.5),
    ("sparse", "step_speedup"): ("min", 1.5, 0.5),
    # deterministic (shape-derived): the acceptance targets themselves
    ("sparse", "staged_bytes_ratio"): ("min", 4.0, 0.9),
    ("sparse", "occupancy"): ("max", 0.25, None),
    # gopher session: planning must stay a rounding error vs the run it
    # configures; shared staging must amortize (byte ratio shape-derived)
    ("plan_overhead", "frac"): ("max", 0.1, None),
    ("shared_staging", "staged_bytes_ratio"): ("min", 1.5, 0.9),
    # warm serving: the acceptance targets — >=2x throughput over one
    # cold session per query at Q=8, and a repeat query on a warm cache
    # re-stages NOTHING (both deterministic enough to gate hard; the
    # ratio also folds in jit-compile amortization, so it sits far above
    # the floor in practice)
    ("serving", "throughput_ratio"): ("min", 2.0, 0.5),
    ("serving", "restaged_bytes_repeat"): ("max", 0.0, None),
    ("serving", "restaging_passes_repeat"): ("max", 0.0, None),
    # fused superstep kernel: jaxpr-derived structural counts, fully
    # deterministic — the whole local stage must stay ONE pallas_call,
    # the halt vote must never fall out of the kernel as a state-sized
    # XLA reduce, and the fused lowering must stay strictly leaner than
    # the per-stage spmv sweep + separate vote (floor kept conservative
    # so a jax upgrade shifting eqn counts by noise does not trip it)
    ("fused_superstep", "fused_pallas_calls"): ("max", 1.0, None),
    ("fused_superstep", "state_vote_reduces"): ("max", 0.0, None),
    ("fused_superstep", "eqn_ratio"): ("min", 1.1, None),
    # streaming ingestion: the acceptance target — a steady-state tail
    # step (warm incremental recompute of one appended batch) must beat a
    # cold full re-run over the grown collection by >=3x; the step count
    # is deterministic (collection size / batch)
    ("streaming_ingest", "speedup"): ("min", 3.0, 0.5),
    ("streaming_ingest", "incremental_steps"): ("min", 4.0, None),
    # 2-process cluster lane: deterministic (shard-derived) — every host
    # must materialize strictly less than the single-process staging cost
    # (exactly 1/2 with 2 procs on an even partition split; cap leaves
    # headroom for odd partition counts where low ranks take the
    # remainder).  Parity itself is asserted inside the subprocess — a
    # failed run surfaces as an explicit --check failure, not a row.
    ("cluster_scaling", "max_per_host_fraction"): ("max", 0.75, None),
}


def check_against_baseline(fresh: dict, path: str = OUT_JSON) -> list:
    """Compare fresh results against the committed baseline.  Returns a
    list of human-readable violations (empty = pass)."""
    if not os.path.exists(path):
        return [f"baseline {path} missing — run `benchmarks.run temporal` "
                f"once to create it"]
    with open(path) as f:
        base = json.load(f)
    failures = []
    for (row, field), (kind, bound, rel) in THRESHOLDS.items():
        got = fresh.get(row, {}).get(field)
        if got is None:
            failures.append(f"{row}.{field}: missing from fresh results")
            continue
        ref = base.get(row, {}).get(field)
        if kind == "min":
            floor = bound
            if rel is not None and ref is not None:
                floor = max(bound, rel * ref)
            if got < floor:
                failures.append(
                    f"{row}.{field}: {got:.3f} < floor {floor:.3f} "
                    f"(baseline {'n/a' if ref is None else f'{ref:.3f}'})"
                )
        else:  # max
            if got > bound:
                failures.append(f"{row}.{field}: {got:.3f} > cap {bound:.3f}")
    return failures


# Runs in a subprocess: XLA_FLAGS must be set before jax imports, and the
# in-process benches above need the single real CPU device.
MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np, jax
from repro.configs.base import GraphConfig
from repro.core.generator import generate_collection
from repro.core.partition import partition_graph
from repro.core.blocked import build_blocked
from repro.core.engine import TemporalEngine, pagerank_program
from repro.core.algorithms.pagerank import edge_weights_for_instances

cfg = GraphConfig(name="mesh-bench", num_vertices=1024, avg_degree=3.0,
                  num_instances=8, num_partitions=4, block_size=32, seed=7)
tsg = generate_collection(cfg)
tmpl = tsg.template
assign = partition_graph(tmpl, cfg.num_partitions, seed=cfg.seed)
bg = build_blocked(tmpl, assign, cfg.block_size)
I = len(tsg)
active = np.stack([tsg.edge_values(t, "active") for t in range(I)])
w = edge_weights_for_instances(tmpl.src, active, tmpl.num_vertices)
prog = pagerank_program(tmpl.num_vertices, iters=20)
mesh = jax.make_mesh((2, 4), ("data", "model"))
eng_s = TemporalEngine(bg)
eng_m = TemporalEngine(bg, mesh=mesh)


def best(fn, repeats=3):
    fn()
    t = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        t = min(t, time.perf_counter() - t0)
    return t


t_stacked = best(lambda: eng_s.run(prog, w, pattern="independent"))
t_mesh = best(lambda: eng_m.run(prog, w, pattern="independent"))
rs = eng_s.run(prog, w, pattern="independent")
rm = eng_m.run(prog, w, pattern="independent")
assert np.abs(rs.values - rm.values).max() < 1e-6
t_mesh_merge = best(
    lambda: eng_m.run(prog, w, pattern="eventually", merge="mean"))
print(json.dumps({
    "instances": I, "iters": 20, "devices": 8,
    "mesh_shape": {"data": 2, "model": 4},
    "stacked_s": t_stacked, "mesh_s": t_mesh,
    "mesh_eventually_merge_s": t_mesh_merge,
    "mesh_vs_stacked": t_stacked / max(t_mesh, 1e-12),
}))
"""


# Dense all-reduce vs collective-permute ring under shard_map; forced host
# devices need a fresh process (XLA_FLAGS before jax imports).
COMM_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np, jax
from repro.configs.base import GraphConfig
from repro.core.generator import generate_collection
from repro.core.partition import partition_graph
from repro.core.blocked import build_blocked
from repro.core.engine import TemporalEngine, pagerank_program
from repro.core.algorithms.pagerank import edge_weights_for_instances

cfg = GraphConfig(name="comm-bench", num_vertices=1024, avg_degree=3.0,
                  num_instances=8, num_partitions=4, block_size=32, seed=7)
tsg = generate_collection(cfg)
tmpl = tsg.template
assign = partition_graph(tmpl, cfg.num_partitions, seed=cfg.seed)
bg = build_blocked(tmpl, assign, cfg.block_size)
I = len(tsg)
active = np.stack([tsg.edge_values(t, "active") for t in range(I)])
w = edge_weights_for_instances(tmpl.src, active, tmpl.num_vertices)
prog = pagerank_program(tmpl.num_vertices, iters=20)
mesh = jax.make_mesh((2, 4), ("data", "model"))
eng_d = TemporalEngine(bg, mesh=mesh)
eng_r = TemporalEngine(bg, mesh=mesh, comm="ring")


def best(fn, repeats=3):
    fn()
    t = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        t = min(t, time.perf_counter() - t0)
    return t


rd = eng_d.run(prog, w, pattern="independent")
rr = eng_r.run(prog, w, pattern="independent")
assert np.abs(rd.values - rr.values).max() < 1e-6  # documented reassociation
t_dense = best(lambda: eng_d.run(prog, w, pattern="independent"))
t_ring = best(lambda: eng_r.run(prog, w, pattern="independent"))
print(json.dumps({
    "instances": I, "iters": 20, "devices": 8,
    "mesh_shape": {"data": 2, "model": 4},
    "dense_s": t_dense, "ring_s": t_ring,
    "ring_vs_dense": t_ring / max(t_dense, 1e-12),
}))
"""


def _cpu_child_env() -> dict:
    """Environment of a CPU-emulation child: forced host devices need the
    CPU backend, and a child that reached for the accelerator would
    contend with this process, which already holds it."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


def _comm_mesh_rows() -> dict:
    env = _cpu_child_env()
    r = subprocess.run(
        [sys.executable, "-c", COMM_MESH_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if r.returncode != 0:
        emit("temporal/comm_mesh_failed", 0.0, r.stderr.strip()[-200:])
        return {"error": r.stderr.strip()[-2000:]}
    rows = json.loads(r.stdout.strip().splitlines()[-1])
    emit("temporal/comm_dense_mesh", rows["dense_s"] * 1e6,
         f"devices={rows['devices']}")
    emit("temporal/comm_ring_mesh", rows["ring_s"] * 1e6,
         f"ring_vs_dense={rows['ring_vs_dense']:.2f}x")
    return rows


def _cluster_scaling_row() -> dict:
    """2-process localhost cluster run (shard-local staging + real
    inter-process gather) through ``repro.launch.cluster_graph --check``:
    the subprocess asserts bitwise parity with the single-process run and
    per-host staged bytes below it, then prints the byte report."""
    import tempfile
    import time as _time_mod

    env = _cpu_child_env()
    with tempfile.TemporaryDirectory() as td:
        t0 = _time_mod.perf_counter()
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.cluster_graph",
             "--num-processes", "2", "--apps", "sssp,pagerank",
             "--size", "tiny", "--deploy", os.path.join(td, "gofs"),
             "--out", os.path.join(td, "out"), "--check"],
            env=env, capture_output=True, text=True, timeout=900,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        wall = _time_mod.perf_counter() - t0
    if r.returncode != 0:
        emit("temporal/cluster_scaling_failed", 0.0, r.stderr.strip()[-200:])
        return {"error": (r.stdout + r.stderr).strip()[-2000:]}
    line = next(l for l in r.stdout.splitlines() if "parity OK:" in l)
    report = json.loads(line.split("parity OK:", 1)[1])
    row = {"num_processes": 2, "apps": sorted(report),
           "parity": "bitwise", "wall_s": wall,
           "max_per_host_fraction": 0.0}
    for app, st in report.items():
        single = st["single_staged_bytes"]
        hosts = st["per_host_staged_bytes"]
        row[app] = {
            "single_staged_bytes": single,
            "per_host_staged_bytes": hosts,
            "per_host_fraction": [b / max(single, 1) for b in hosts],
        }
        frac = max(b / max(single, 1) for b in hosts)
        row["max_per_host_fraction"] = max(
            row["max_per_host_fraction"], frac)
        emit(f"temporal/cluster_{app}_staged_frac", frac * 100.0,
             f"per-host bytes / single-process bytes, 2 procs")
    return row


def _mesh_rows() -> dict:
    env = _cpu_child_env()
    r = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if r.returncode != 0:
        emit("temporal/mesh_failed", 0.0, r.stderr.strip()[-200:])
        return {"error": r.stderr.strip()[-2000:]}
    rows = json.loads(r.stdout.strip().splitlines()[-1])
    emit("temporal/mesh_stacked", rows["stacked_s"] * 1e6,
         f"devices={rows['devices']}")
    emit("temporal/mesh_temporal_parallel", rows["mesh_s"] * 1e6,
         f"mesh_vs_stacked={rows['mesh_vs_stacked']:.2f}x")
    emit("temporal/mesh_eventually_merge",
         rows["mesh_eventually_merge_s"] * 1e6, "")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="compare fresh numbers against the committed "
                         f"{OUT_JSON} (per-row thresholds) and exit "
                         "nonzero on regression instead of rewriting it")
    run(check=ap.parse_args().check)
