"""Find a live configuration's knee: the highest Poisson rate at which a
warm service keeps up over a window.

  python3 chipbench/knee.py --workload tr-live.sssp-steady --seed 5 \
      --seconds 45 --rates 2 3 4 5

One process deploys the cell's configuration, warms the service up as a
run does, then offers each rate for ``--seconds`` under the cell's own
source distribution, and prints one JSON line per rate: offered and
delivered in the window, the backlog (submitted and not yet delivered) at
the window's middle and end, and latency percentiles from due time.  A
rate is sustained when delivered is within 1% of offered and the backlog
at the end is no longer than at the middle.  Used once to fix the rates
of the open-loop traffic files; the benchmark's runs never call it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import endtoend, run as R, spec  # noqa: E402
from chipbench import traffic as traffic_gen  # noqa: E402


def backlog_at(queries, t: float) -> int:
    return sum(1 for q in queries
               if q["t_submit"] <= t and (q["t_done"] is None
                                          or q["t_done"] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload, spec.benchmark())
    R.require_chips(cell.chips)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    store, _ = R.deploy(cell, args.seed, R.WORK / "deploy")
    svc = R.service(cell, store)
    V = int(len(svc.session.bg.part_of))
    with svc:
        R.warm_service(svc, cell.traffic)
        for k, rate in enumerate(args.rates):
            tr = dict(cell.traffic, rate_qps=rate,
                      schedule_seed=args.seed + k)
            due = traffic_gen.arrivals(tr, args.seconds)
            srcs = traffic_gen.draw_sources(
                R.rng(args.seed, 200 + k), tr["sources"], svc.session.src,
                V, len(due))
            rep0 = svc.report()
            t0 = time.perf_counter()
            qs, tks, late = R.offer(svc, tr, due, srcs, t0)
            end = t0 + args.seconds
            time.sleep(max(0.0, end - time.perf_counter()))
            rep1 = svc.report()
            R.collect(qs, tks, end + 600.0)
            done = sum(1 for q in qs if q["ok"] and q["t_done"] <= end)
            lat = endtoend.latencies(qs)
            print(json.dumps({
                "rate_qps": rate, "offered": len(qs),
                "delivered_in_window": done,
                "delivered_share": done / max(1, len(qs)),
                "backlog_mid": backlog_at(qs, t0 + args.seconds / 2),
                "backlog_end": backlog_at(qs, end),
                "p50_ms": (endtoend.percentile(lat, 50) or 0) * 1e3,
                "p90_ms": (endtoend.percentile(lat, 90) or 0) * 1e3,
                "batches": rep1["batches"] - rep0["batches"],
                "served": rep1["served"] - rep0["served"],
                "generator_late_ms": late * 1e3,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
