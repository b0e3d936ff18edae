#!/usr/bin/env python3
"""Smoke run of the Gopher main path on a TPU.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the mesh path across four chips

One chip: deploys the ``small`` collection from its seed into
``.chip_smoke/`` under the checkout, runs sequential SSSP (min-plus) and
independent PageRank (plus-mul) through ``GopherSession`` with the
planner's own plans and again under the fused superstep kernel, checks the
first instances against the host iBSP reference, then has a
``GopherService`` answer SSSP and N-hop point queries and checks each
answer against a single-source session run.

Four chips: ``TemporalEngine`` in mesh mode (partitions over ``model``,
instances over ``data`` where the pattern allows) under each exchange
backend the planner can pick there, against the stacked one-chip run in
the same process: bitwise for min-plus.

Exits non-zero, printing no result, when JAX finds no TPU.  The last line
of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.algorithms import pagerank, sssp  # noqa: E402
from repro.core.engine import (  # noqa: E402
    TemporalEngine, min_plus_program, pagerank_program, source_init)
from repro.core.semiring import INF  # noqa: E402
from repro.gofs import GoFSStore  # noqa: E402
from repro.gopher import GopherService, GopherSession  # noqa: E402
from repro.gopher.service import DEFAULT_CACHE_BYTES  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.run_graph import ensure_deployment  # noqa: E402

DEPLOY = os.path.join(ROOT, ".chip_smoke")
SIZE = "small"
SOURCE = 0
PAGERANK_ITERS = 10
HOST_CHECK_INSTANCES = 2  # the host iBSP reference covers instances [0, 2)
MESH_INSTANCES = 4  # the four-chip comparison stages instances [0, 4)
TIME_LIMIT_S = 1140
# tolerances of the repo's parity tests: engine vs host iBSP
# (tests/test_engine.py), plus-mul across kernels and exchange backends
# (tests/test_comm_backends.py); min-plus is compared bitwise
HOST_RTOL, HOST_ATOL = 1e-4, 1e-9
PLUS_MUL_ATOL = 1e-6


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds of XLA backend compilation (Mosaic kernels included),
    summed from JAX's own monitoring events on any thread."""

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs


def timed(clock: CompileClock, fn, *args, **kw):
    """Run ``fn`` and return (result, compile seconds, other seconds)."""
    c0, t0 = clock.secs, time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    compile_s = clock.secs - c0
    return out, compile_s, wall - compile_s


def require_tpu() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")


def device_line() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def deploy(size: str):
    shutil.rmtree(DEPLOY, ignore_errors=True)
    t0 = time.perf_counter()
    cfg, store = ensure_deployment(size, DEPLOY, cache_slots=14)
    log(f"deployed {cfg.name}: {store.meta['num_vertices']} vertices, "
        f"{store.meta['num_edges']} edges, {store.num_timesteps()} "
        f"instances, {store.meta['num_partitions']} partitions, "
        f"B={cfg.block_size} in {time.perf_counter() - t0:.1f}s")
    return cfg, store


def window(store: GoFSStore, n: int) -> GoFSStore:
    """The same deployment seen through a temporal filter on its first
    ``n`` instances."""
    ts = store.meta["timestamps"]
    return GoFSStore(store.root, vertex_projection=store.vertex_projection,
                     edge_projection=store.edge_projection,
                     time_range=(ts[0], ts[n]))


def lowered_kernel_calls(sess: GopherSession, plan) -> int:
    """``tpu_custom_call`` sites in every dense single-source step the
    plan's engine compiled."""
    eng = sess._engine(plan)
    bg, B = eng.bg, eng.bg.block_size
    f32 = jnp.float32
    calls = 0
    for key, fn in eng._runners.items():
        n, sparse, multi = key[3], key[4], key[6]
        if sparse or multi:
            continue
        text = fn.lower(
            jax.ShapeDtypeStruct((n, bg.n_parts, bg.t_max, B, B), f32),
            jax.ShapeDtypeStruct((n, bg.n_parts, bg.tb_max, B, B), f32),
            jax.ShapeDtypeStruct((bg.n_parts, bg.global_of.shape[1]), f32),
            *eng._struct).as_text()
        calls += text.count("tpu_custom_call")
    return calls


def host_sssp(win: GoFSStore, source: int) -> np.ndarray:
    res, _ = sssp.run_host(win, source)
    d = np.full(int(win.meta["num_vertices"]), INF, np.float32)
    for g, dist in res.items():
        d[win.get_topology(g).vertices] = dist
    return d


def host_pagerank(win: GoFSStore, iters: int) -> np.ndarray:
    V = int(win.meta["num_vertices"])
    res, _ = pagerank.run_host(win, V, iters=iters)
    out = np.zeros((win.num_timesteps(), V))
    for (t, g), r in res.items():
        out[t, win.get_topology(g).vertices] = r
    return out


def one_chip(size: str = SIZE) -> None:
    clock = CompileClock()
    cfg, store = deploy(size)
    sess = GopherSession(store, block_size=cfg.block_size)
    plans = {
        "sssp/planner": sess.plan("sssp", source=SOURCE),
        "pagerank/planner": sess.plan("pagerank", iters=PAGERANK_ITERS),
        "sssp/fused": sess.plan("sssp", source=SOURCE, kernel="fused"),
        "pagerank/fused": sess.plan("pagerank", iters=PAGERANK_ITERS,
                                    kernel="fused"),
    }
    results = {}
    for name, plan in plans.items():
        kern = plan.kernel.value
        assert kern != "off", f"{name}: the plan's kernel is off"
        res, cs, rs = timed(clock, sess.run, plan)
        calls = lowered_kernel_calls(sess, plan)
        assert calls > 0, f"{name}: no tpu_custom_call in the compiled step"
        results[name] = res
        log(f"{name}: kernel={kern} layout={plan.layout.value} "
            f"staging={plan.staging.value} comm={plan.comm.value} "
            f"compile_s={cs:.2f} run_s={rs:.2f} "
            f"tpu_custom_calls={calls} "
            f"staged_bytes={sess.last_run_report['staged_bytes']}")
    kernels = {p.kernel.value for p in plans.values()}
    assert {"spmv", "fused"} <= kernels, kernels

    # kernels agree: min-plus bitwise, plus-mul within the backend bound
    sp, fu = results["sssp/planner"].engine, results["sssp/fused"].engine
    assert np.array_equal(sp.values, fu.values), "sssp: fused != spmv"
    assert np.array_equal(sp.stats["supersteps"], fu.stats["supersteps"])
    np.testing.assert_allclose(results["pagerank/fused"].output["ranks"],
                               results["pagerank/planner"].output["ranks"],
                               rtol=0, atol=PLUS_MUL_ATOL)
    log("sssp fused == spmv bitwise; pagerank fused ~ spmv "
        f"(atol {PLUS_MUL_ATOL})")

    # host iBSP reference on the first instances
    k = HOST_CHECK_INSTANCES
    win = window(store, k)
    t0 = time.perf_counter()
    d_h = host_sssp(win, SOURCE)
    d_b = sp.values[k - 1]
    assert np.array_equal(np.isfinite(d_b), np.isfinite(d_h))
    fin = np.isfinite(d_h)
    np.testing.assert_allclose(d_b[fin], d_h[fin], rtol=HOST_RTOL)
    pr_h = host_pagerank(win, PAGERANK_ITERS)
    for name in ("pagerank/planner", "pagerank/fused"):
        np.testing.assert_allclose(results[name].output["ranks"][:k], pr_h,
                                   rtol=HOST_RTOL, atol=HOST_ATOL)
    log(f"host iBSP reference agrees on instances [0, {k}) "
        f"(sssp: {int(fin.sum())} reached; {time.perf_counter() - t0:.1f}s)")

    # warm service: batched point queries vs single-source session runs
    rng = np.random.default_rng(cfg.seed)
    s2, s3 = (int(v) for v in rng.integers(1, cfg.num_vertices, 2))
    # the two SSSP queries ride one source-axis batch
    queries = [("sssp", {"source": SOURCE}), ("sssp", {"source": s2}),
               ("nhop", {"source": s3, "n_hops": 4})]
    with GopherService(store, block_size=cfg.block_size) as svc:
        answers, cs, rs = timed(clock, svc.query_many, queries)
        rep = svc.report()
    log(f"service: {rep['served']} queries in {rep['batches']} batch(es), "
        f"p50 {rep['p50_ms']:.0f} ms, compile_s={cs:.2f} run_s={rs:.2f}")
    ref = GopherSession(store, block_size=cfg.block_size,
                        staging_cache_bytes=DEFAULT_CACHE_BYTES)
    for (name, params), got in zip(queries, answers):
        if name == "sssp" and params["source"] == SOURCE:
            want = results["sssp/planner"]
        else:
            want = ref.run(ref.plan(name, **params))
        keys = ("final",) if name == "sssp" else ("composite", "histograms")
        for key in keys:
            assert np.array_equal(got.output[key], want.output[key]), \
                f"service {name}{params}: {key} != single-source run"
    log("service answers == single-source session runs (bitwise)")
    log(f"peak_bytes_in_use={peak_bytes(jax.devices()[0])} "
        f"compile_s_total={clock.secs:.2f}")
    shutil.rmtree(DEPLOY, ignore_errors=True)


def four_chips(size: str = SIZE) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()
    assert len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}"
    clock = CompileClock()
    cfg, store = deploy(size)
    sess = GopherSession(store, block_size=cfg.block_size)
    bg, V = sess.bg, int(store.meta["num_vertices"])
    rows = range(MESH_INSTANCES)
    lat = store.edge_attr_rows("latency", rows)
    prw = pagerank.edge_weights_for_instances(
        sess.src, store.edge_attr_rows("active", rows), V)
    staged = {
        "min_plus": (bg.fill_local_batch(lat, zero=INF),
                     bg.fill_boundary_batch(lat, zero=INF)),
        "plus_mul": (bg.fill_local_batch(prw, zero=0.0),
                     bg.fill_boundary_batch(prw, zero=0.0)),
    }
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    sssp_prog = min_plus_program("sssp", init=source_init(SOURCE))
    pr_prog = pagerank_program(V, iters=PAGERANK_ITERS)
    runs = [("sssp/sequential", sssp_prog, "sequential", "min_plus"),
            ("sssp/independent", sssp_prog, "independent", "min_plus"),
            ("pagerank/independent", pr_prog, "independent", "plus_mul")]

    stacked = TemporalEngine(bg, use_pallas="spmv")
    refs = {}
    for name, prog, pattern, sr in runs:
        tiles, btiles = staged[sr]
        refs[name], cs, rs = timed(clock, stacked.run, prog,
                                   pattern=pattern, tiles=tiles,
                                   btiles=btiles)
        log(f"stacked {name}: compile_s={cs:.2f} run_s={rs:.2f}")

    for comm in ("dense", "ring", "ring-rs"):
        eng = TemporalEngine(bg, mesh=mesh, use_pallas="spmv", comm=comm)
        for name, prog, pattern, sr in runs:
            # instances over data only where the pattern runs them
            # concurrently; partitions over model always
            iaxis = "data" if pattern == "independent" else None
            sh = NamedSharding(mesh, P(iaxis, "model"))
            tiles, btiles = (jax.device_put(a, sh) for a in staged[sr])
            assert len(tiles.sharding.device_set) == 4, tiles.sharding
            got, cs, rs = timed(clock, eng.run, prog, pattern=pattern,
                                tiles=tiles, btiles=btiles)
            want = refs[name]
            if sr == "min_plus":
                assert np.array_equal(got.values, want.values), (comm, name)
                assert np.array_equal(got.final, want.final), (comm, name)
                assert np.array_equal(got.stats["supersteps"],
                                      want.stats["supersteps"]), (comm, name)
                agree = "bitwise"
            else:
                np.testing.assert_allclose(got.values, want.values,
                                           rtol=0, atol=PLUS_MUL_ATOL)
                agree = f"atol {PLUS_MUL_ATOL}"
            log(f"mesh{dict(mesh.shape)} comm={comm} {name}: "
                f"{agree} vs stacked; compile_s={cs:.2f} run_s={rs:.2f}")
    peaks = [peak_bytes(d) for d in devs]
    assert all(p > 0 for p in peaks), peaks
    log(f"peak_bytes_in_use per device={peaks} "
        f"compile_s_total={clock.secs:.2f}")
    shutil.rmtree(DEPLOY, ignore_errors=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path and its "
                         "stacked reference")
    args = ap.parse_args(argv)
    # a run must end inside 20 minutes: past that, print where every
    # thread stands and exit non-zero rather than hold the chip
    faulthandler.dump_traceback_later(TIME_LIMIT_S, exit=True)
    require_tpu()
    cache = use_compile_cache()
    log(f"device: {device_line()}; compile cache: {cache}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": device_line()}), flush=True)


if __name__ == "__main__":
    main()
