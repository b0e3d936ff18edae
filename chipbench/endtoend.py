"""End-to-end metrics, from the host's clock, over all the work and all
the time of the window."""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def pass_window(passes: Sequence[Dict], t0: float, seconds: float
                ) -> List[Dict]:
    """The passes of the window: back to back from ``t0`` up to and
    including the first pass that finishes at or after ``t0 + seconds``."""
    out = []
    for p in passes:
        out.append(p)
        if p["t_end"] >= t0 + seconds:
            break
    return out


def evps(passes: Sequence[Dict], t0: float, seconds: float,
         vertices_plus_edges: int) -> Optional[float]:
    """Edges plus vertices per second (LDBC Graphalytics' EVPS): the
    summed (V + E) x instances of the window's completed passes over the
    window, which ends with its last pass."""
    win = pass_window(passes, t0, seconds)
    if not win:
        return None
    work = sum(vertices_plus_edges * p["instances"] for p in win if p["ok"])
    return work / (win[-1]["t_end"] - t0)


def latencies(queries: Sequence[Dict]) -> List[float]:
    """Seconds from each query's due time to its delivery; a query that
    failed or never came counts as infinitely late."""
    return [q["t_done"] - q["due"] if q["ok"] else math.inf
            for q in queries]


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile; ``None`` when it falls on a missing
    (infinite) value or there are no values."""
    if not values:
        return None
    xs = sorted(values)
    v = xs[max(0, math.ceil(p / 100.0 * len(xs)) - 1)]
    return None if math.isinf(v) else v


def delivered_per_s(queries: Sequence[Dict], t0: float,
                    seconds: float) -> float:
    """Queries delivered inside the window, over the window."""
    n = sum(1 for q in queries
            if q["ok"] and t0 <= q["t_done"] <= t0 + seconds)
    return n / seconds
