"""What the per-layer readers under ``metrics/`` share: counts over the
window's passes and queries, and the device trace's busy time."""
from __future__ import annotations

from typing import Optional

from chipbench import endtoend, roofline, trace as trace_mod

# analytics whose kernels run the plus-mul semiring (one local sweep and
# one superstep per iteration); every other analytic is a min-plus
# fixpoint counted by its own sweeps and supersteps
PLUS_MUL = ("pagerank",)


def window_passes(run):
    return [p for p in endtoend.pass_window(run.passes, run.t0, run.seconds)
            if p["ok"]]


def per_instance(run, key: str) -> Optional[float]:
    """``key`` summed over the window's passes, per instance staged."""
    ps = window_passes(run)
    n = sum(p["instances"] for p in ps)
    return sum(p[key] for p in ps) / n if n else None


def batch_width(run) -> Optional[float]:
    """Queries delivered per executed batch over the window, from the
    service's own ``served`` and ``batches`` counters."""
    if "start" not in run.service or "end" not in run.service:
        return None
    s, e = run.service["start"], run.service["end"]
    batches = e["batches"] - s["batches"]
    return (e["served"] - s["served"]) / batches if batches else None


def p90_ms(run) -> Optional[float]:
    """The 90th percentile of latency from due time over the window's
    queries, in ms; ``None`` when it falls on a missing query."""
    if not run.queries:
        return None
    v = endtoend.percentile(endtoend.latencies(run.queries), 90)
    return None if v is None else v * 1e3


def device_idle(run) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device, averaged over the chips."""
    if run.trace is None or not run.trace.device_ops:
        return None
    summ = trace_mod.summary(run.trace)
    if summ["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - summ["busy_s"] / summ["window_s"])


def useful_bytes(run) -> Optional[int]:
    """The kernels' useful bytes over the window's passes or queries."""
    g = run.graph
    kw = dict(local_edges=g["local_edges"],
              boundary_edges=g["boundary_edges"],
              num_vertices=g["num_vertices"])
    counts = run.query_engine if run.queries else window_passes(run)
    if not counts:
        return None
    if run.cell.traffic["analytic"] in PLUS_MUL:
        return sum(roofline.plusmul_bytes(c["supersteps"], **kw)
                   for c in counts)
    return sum(roofline.minplus_bytes(c["local_sweeps"], c["supersteps"],
                                      **kw) for c in counts)


def kernel_roofline(run, pattern: str) -> Optional[float]:
    """Percent of the kernels' roofline: the least time for their useful
    bytes at the chip's HBM bandwidth, over their summed device time in
    the trace (ops whose name matches ``pattern``)."""
    if run.trace is None or not run.peaks:
        return None
    ops = [op for plane in run.trace.device_ops.values() for op in plane]
    secs = trace_mod.op_time(ops, 0.0, run.trace.window_ns, pattern) / 1e9
    ub = useful_bytes(run)
    if secs <= 0 or not ub:
        return None
    least = roofline.least_seconds(ub, run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
