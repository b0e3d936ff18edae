"""Multi-process Gopher driver: N local workers over one GoFS deployment.

The paper's deployment shape (§V) — every worker computes on the shard it
hosts — as a runnable entrypoint:

  PYTHONPATH=src python -m repro.launch.cluster_graph \\
      --num-processes 2 --apps sssp,pagerank --size tiny --check

The parent deploys the collection (once), picks a free coordinator port,
and spawns ``--num-processes`` workers of THIS module (``--worker``).
Each worker boots its :class:`~repro.cluster.runtime.ClusterRuntime`,
opens a :class:`~repro.gopher.session.GopherSession` bound to it — so
staging is shard-local and the boundary exchange is the real
inter-process gather — runs every requested app, and writes its results
(values, finals, superstep counts, per-host staged bytes) to an ``.npz``
in ``--out``.

``--check`` makes the parent ALSO run the identical apps in a plain
single-process session and assert the cluster acceptance:

* every worker's values/finals are **bitwise identical** to the
  single-process run (and to each other);
* every worker's staged bytes are **strictly less** than the
  single-process staging cost (shard-local staging is real).

Exit status is non-zero on any violation — this is the CI multi-process
lane's command.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List

import numpy as np

from repro.launch.compile_cache import use_compile_cache

APP_PARAMS: Dict[str, dict] = {
    "sssp": {"source": 0},            # sequential pattern
    "pagerank": {"iters": 10},        # independent pattern
    "components": {},                 # independent, symmetrized graph
}


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_apps(sess, apps: List[str]) -> Dict[str, Dict[str, np.ndarray]]:
    """Run each app through the session, recording result arrays and the
    staging economy of its pass."""
    out: Dict[str, Dict[str, np.ndarray]] = {}
    for app in apps:
        plan = sess.plan(app, staging="async", **APP_PARAMS[app])
        res = sess.run_many([plan])[0]
        eng = res.engine
        out[app] = {
            "values": np.asarray(eng.values),
            "final": np.asarray(eng.final),
            "supersteps": np.asarray(eng.stats["supersteps"]),
            "staged_bytes": np.asarray(
                int(sess.last_run_report["staged_bytes"])),
        }
    return out


def worker_main(args) -> None:
    from repro.cluster.runtime import init_cluster
    from repro.gopher import GopherSession
    from repro.launch.run_graph import ensure_deployment

    rt = init_cluster(transport=args.transport)  # GOFFISH_* env from parent
    cfg, store = ensure_deployment(args.size, args.deploy, args.cache_slots)
    sess = GopherSession(store, block_size=cfg.block_size, cluster=rt)
    results = run_apps(sess, args.apps.split(","))
    flat = {f"{app}/{k}": v for app, r in results.items()
            for k, v in r.items()}
    os.makedirs(args.out, exist_ok=True)
    np.savez(os.path.join(args.out, f"worker_{rt.process_id}.npz"), **flat)
    rt.barrier("done")
    rt.close()


def refuse_on_accelerator(num_processes: int) -> None:
    """Each localhost worker is a full JAX process: on an accelerator host
    every one of them would claim all of the host's chips, and all but
    the first would fail or hang.  The platform is probed in a
    short-lived child, so this parent holds no device while it spawns."""
    if num_processes <= 1:
        return
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, check=True)
    platform = probe.stdout.split()[-1]
    if platform != "cpu":
        raise SystemExit(
            f"cluster_graph: JAX platform is {platform!r}; "
            f"{num_processes} localhost workers would each claim every "
            f"chip of this host.  Emulate the cluster on CPU "
            f"(JAX_PLATFORMS=cpu) or run one process per host.")


def launch_workers(args, coordinator: str) -> List[subprocess.Popen]:
    from repro.cluster import runtime as cr

    procs = []
    for pid in range(args.num_processes):
        env = dict(
            os.environ,
            **{cr.ENV_COORDINATOR: coordinator,
               cr.ENV_NUM_PROCESSES: str(args.num_processes),
               cr.ENV_PROCESS_ID: str(pid),
               cr.ENV_TRANSPORT: args.transport},
        )
        cmd = [
            sys.executable, "-m", "repro.launch.cluster_graph", "--worker",
            "--apps", args.apps, "--size", args.size,
            "--deploy", args.deploy, "--out", args.out,
            "--transport", args.transport,
            "--cache-slots", str(args.cache_slots),
        ]
        procs.append(subprocess.Popen(
            cmd, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    return procs


def wait_workers(procs: List[subprocess.Popen], timeout: float) -> None:
    deadline = time.monotonic() + timeout
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(
                timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise SystemExit(f"worker {i} timed out")
        if p.returncode != 0:
            sys.stderr.write(out or "")
            raise SystemExit(f"worker {i} exited with {p.returncode}")


def check_parity(args) -> Dict[str, dict]:
    """Single-process reference run + the acceptance assertions."""
    from repro.gopher import GopherSession
    from repro.gopher.planner import staged_bytes_estimate
    from repro.launch.run_graph import ensure_deployment

    apps = args.apps.split(",")
    cfg, store = ensure_deployment(args.size, args.deploy, args.cache_slots)
    sess = GopherSession(store, block_size=cfg.block_size)
    ref = run_apps(sess, apps)
    # one process streams edge rows and fills the tiles on the device; a
    # shard fills its own partitions' tiles on the host, so its saving is
    # against the host tiles of every partition
    full_tiles = staged_bytes_estimate(sess.bg, store.num_timesteps())

    workers = []
    for pid in range(args.num_processes):
        path = os.path.join(args.out, f"worker_{pid}.npz")
        assert os.path.exists(path), f"worker {pid} left no results"
        workers.append(np.load(path))

    report: Dict[str, dict] = {}
    for app in apps:
        single = int(ref[app]["staged_bytes"])
        per_host = []
        for pid, w in enumerate(workers):
            for key in ("values", "final", "supersteps"):
                got, want = w[f"{app}/{key}"], ref[app][key]
                assert np.array_equal(got, want), \
                    f"{app}: worker {pid} {key} diverges from the " \
                    f"single-process run"
            per_host.append(int(w[f"{app}/staged_bytes"]))
        # components stages its symmetrized variant through the
        # materialized path (full-width, engine-sliced); only streamed
        # template apps must show the per-host byte saving
        if single > 0 and app != "components":
            for pid, b in enumerate(per_host):
                assert b < full_tiles, \
                    f"{app}: worker {pid} staged {b} bytes, the host tiles " \
                    f"of every partition take {full_tiles} — shard " \
                    f"staging saved nothing"
        report[app] = {"single_staged_bytes": single,
                       "full_tile_bytes": full_tiles,
                       "per_host_staged_bytes": per_host}
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as one spawned worker process")
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--apps", default="sssp,pagerank",
                    help=f"comma list from {sorted(APP_PARAMS)}")
    ap.add_argument("--size", default="tiny",
                    choices=["tiny", "small", "full"])
    ap.add_argument("--deploy", default="/tmp/gofs_cluster")
    ap.add_argument("--out", default="/tmp/gofs_cluster_out")
    ap.add_argument("--transport", default="tcp",
                    choices=["tcp", "jax"],
                    help="tcp: host-lane exchange only (CI default); "
                         "jax: also initialize jax.distributed")
    ap.add_argument("--cache-slots", type=int, default=14)
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--check", action="store_true",
                    help="run the single-process reference and assert "
                         "bitwise parity + per-host staged-byte savings")
    args = ap.parse_args()
    use_compile_cache()
    for app in args.apps.split(","):
        assert app in APP_PARAMS, f"unknown app {app!r}"

    if args.worker:
        worker_main(args)
        return

    from repro.launch.run_graph import ensure_deployment

    refuse_on_accelerator(args.num_processes)
    ensure_deployment(args.size, args.deploy, args.cache_slots)  # once
    coordinator = f"127.0.0.1:{free_port()}"
    t0 = time.time()
    procs = launch_workers(args, coordinator)
    wait_workers(procs, args.timeout)
    print(f"[cluster] {args.num_processes} workers x {args.apps} done "
          f"in {time.time()-t0:.1f}s")
    if args.check:
        report = check_parity(args)
        print(f"[cluster] parity OK: {json.dumps(report)}")


if __name__ == "__main__":
    main()
