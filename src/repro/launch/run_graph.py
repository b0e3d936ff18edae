"""Gopher driver: run a time-series graph analytics application over a GoFS
deployment (the paper's end-to-end path).

  PYTHONPATH=src python -m repro.launch.run_graph --app sssp --size small \
      --deploy /tmp/gofs --source 0

Apps: sssp (sequential), pagerank (independent), nhop (eventually),
tracking (sequential, Alg. 1), cc (independent).

``--engine blocked`` runs the TPU-adapted path through the declarative
Gopher session API (``repro.gopher``): the session reconstructs the
blocked structure straight from the deployed topology slices and
auto-selects layout/comm/staging — pass ``--comm``/``--layout``/
``--staging`` to override any knob, and ``--explain`` to print the chosen
plan with its cost estimates WITHOUT executing anything.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro.configs import get_graph_config
from repro.core.algorithms import nhop, pagerank, sssp, tracking
from repro.core.generator import generate_collection
from repro.gofs import GoFSStore, deploy_collection
from repro.launch.compile_cache import use_compile_cache


def ensure_deployment(size: str, root: str, cache_slots: int):
    cfg = get_graph_config(size)
    if not os.path.exists(os.path.join(root, "collection.json")):
        print(f"[gopher] deploying {cfg.name} to {root} ...")
        tsg = generate_collection(cfg)
        deploy_collection(tsg, cfg, root)
    return cfg, GoFSStore(
        root, cache_slots=cache_slots,
        vertex_projection=("plate", "outdeg_active"),
        edge_projection=("latency", "active"),
    )


def session_plan(store, cfg, args):
    """Build the declarative session + plan for the chosen app."""
    from repro.gopher import GopherSession

    sess = GopherSession(store, block_size=cfg.block_size)
    knobs = dict(comm=args.comm, layout=args.layout, staging=args.staging)
    if args.app == "sssp":
        plan = sess.plan("sssp", source=args.source, **knobs)
    elif args.app == "pagerank":
        plan = sess.plan("pagerank", iters=10, **knobs)
    elif args.app == "nhop":
        plan = sess.plan("nhop", source=args.source, n_hops=6, **knobs)
    elif args.app == "tracking":
        plan = sess.plan("tracking", plate=args.plate,
                         initial_vertex=args.source, **knobs)
    else:  # cc
        plan = sess.plan("components", **knobs)
    return sess, plan


def report_blocked(app: str, res) -> None:
    out = res.output
    if app == "sssp":
        dist = out["final"]
        ss = res.engine.stats["supersteps"].tolist()
        print(f"[gopher] SSSP reached {int(np.isfinite(dist).sum())}; "
              f"supersteps/timestep={ss}")
    elif app == "pagerank":
        print(f"[gopher] PageRank top vertex (t=0): "
              f"{int(out['ranks'][0].argmax())}")
    elif app == "nhop":
        print(f"[gopher] N-hop composite: {out['composite']}")
    elif app == "tracking":
        print(f"[gopher] track: {out['trace']}")
    else:
        counts = [len(np.unique(l)) for l in out["labels"]]
        print(f"[gopher] components per instance: {counts}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="sssp",
                    choices=["sssp", "pagerank", "nhop", "tracking", "cc"])
    ap.add_argument("--size", default="small", choices=["tiny", "small", "full"])
    ap.add_argument("--deploy", default="/tmp/gofs_deploy")
    ap.add_argument("--engine", default="host", choices=["host", "blocked"])
    ap.add_argument("--source", type=int, default=0)
    ap.add_argument("--plate", type=int, default=3)
    ap.add_argument("--cache-slots", type=int, default=14)
    ap.add_argument("--workers", type=int, default=0)
    ap.add_argument("--comm", default=None,
                    choices=["dense", "ring", "host"],
                    help="override the planned boundary-exchange backend "
                         "(repro.core.comm; default: planner-selected)")
    ap.add_argument("--layout", default=None, choices=["dense", "sparse"],
                    help="override the planned tile layout")
    ap.add_argument("--staging", default=None, choices=["sync", "async"],
                    help="override the planned staging mode")
    ap.add_argument("--explain", action="store_true",
                    help="print the execution plan (auto-selected knobs + "
                         "cost estimates) and exit without executing")
    args = ap.parse_args()
    use_compile_cache()

    cfg, store = ensure_deployment(args.size, args.deploy, args.cache_slots)

    if args.explain:
        sess, plan = session_plan(store, cfg, args)
        print(plan.explain())
        return

    t0 = time.time()
    if args.engine == "host":
        if args.app == "sssp":
            dist, res = sssp.run_host(store, args.source, workers=args.workers)
            reached = sum(int(np.isfinite(d).sum()) for d in dist.values())
            print(f"[gopher] SSSP reached {reached} vertices; "
                  f"supersteps={res.stats.supersteps} "
                  f"msgs={res.stats.superstep_messages}")
        elif args.app == "pagerank":
            ranks, res = pagerank.run_host(
                store, store.meta["num_vertices"], iters=10,
                workers=args.workers)
            print(f"[gopher] PageRank over {store.num_timesteps()} instances; "
                  f"supersteps={res.stats.supersteps}")
        elif args.app == "nhop":
            merged, res = nhop.run_host(store, args.source, n_hops=6,
                                        workers=args.workers)
            print(f"[gopher] N-hop composite histogram: {merged['composite']}")
        elif args.app == "tracking":
            trace, res = tracking.run_host(store, args.plate, args.source)
            print(f"[gopher] track: {trace}")
        else:
            raise SystemExit("cc requires --engine blocked")
    else:
        sess, plan = session_plan(store, cfg, args)
        print(plan.explain())
        res = sess.run(plan)
        report_blocked(args.app, res)

    print(f"[gopher] {args.app}/{args.engine} done in {time.time()-t0:.1f}s; "
          f"GoFS stats: {store.snapshot_stats()}")


if __name__ == "__main__":
    main()
