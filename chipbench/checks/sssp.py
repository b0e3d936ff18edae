"""Plain reference and comparison for temporal SSSP (``"sssp"``).

The analytic's semantics: instances run in order; instance t starts from
instance t-1's distances (the source at 0 before the first) and relaxes
every edge with that instance's ``latency`` until nothing changes.  The
reference is Bellman-Ford over the generated edge list in the stated
precision, float32: each candidate is one float32 addition, so the least
fixpoint is the same set of float32 sums whatever the order of
relaxation.  It shares nothing with the program: no GoFS read, no tiles,
no partitioning.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import numpy as np

WEIGHT_ATTR = "latency"
NUMBERS = ("missing", "reach_mismatch", "dist_gap")


def _by_dst(src, dst):
    order = np.argsort(dst, kind="stable")
    d_sorted = dst[order]
    starts = np.flatnonzero(np.r_[True, d_sorted[1:] != d_sorted[:-1]])
    return order, d_sorted[starts], starts


def answer(result, per_query: bool) -> np.ndarray:
    """What a run delivered: a point query's final distances (V,), or a
    whole pass's distances after every instance (I, V)."""
    return result.output["final"] if per_query else result.engine.values


def expected(data: Dict, params: Dict, instances: Sequence[int],
             rnd: Optional[Callable] = None) -> np.ndarray:
    """Distances from ``params["source"]`` after each of ``instances`` in
    turn, (len(instances), V).  ``rnd`` rounds every stored value and sum
    to a lower precision (the control); ``None`` keeps float32."""
    source = int(params["source"])
    src, dst = data["src"], data["dst"]
    rnd = rnd or (lambda a: a)
    order, heads, starts = _by_dst(src, dst)
    s_src = src[order]
    d = np.full(data["num_vertices"], np.inf, np.float32)
    d[source] = 0.0
    out = np.empty((len(instances), len(d)), np.float32)
    for k, t in enumerate(instances):
        w = rnd(data["edges"][WEIGHT_ATTR][t][order].astype(np.float32))
        while True:
            cand = rnd(d[s_src] + w)
            best = np.minimum.reduceat(cand, starts)
            better = best < d[heads]
            if not better.any():
                break
            d[heads[better]] = best[better]
        out[k] = d
    return out


def compare(got: Sequence[Optional[np.ndarray]],
            ref: Sequence[np.ndarray]) -> Dict[str, float]:
    """The numbers compared over the checked answers: ``missing`` (no
    answer, or one of the wrong shape), ``reach_mismatch`` (vertices
    reached on one side only) and ``dist_gap`` (the widest gap between a
    served and a reference distance, relative to max(reference, 1))."""
    missing = reach = 0
    gap = 0.0
    for g, r in zip(got, ref):
        if g is None or np.shape(g) != np.shape(r):
            missing += 1
            continue
        g = np.asarray(g, np.float32)
        fg, fr = np.isfinite(g), np.isfinite(r)
        reach += int(np.count_nonzero(fg != fr))
        both = fg & fr
        if both.any():
            gap = max(gap, float(np.max(
                np.abs(g[both].astype(np.float64) - r[both])
                / np.maximum(np.abs(r[both].astype(np.float64)), 1.0))))
    return {"missing": missing, "reach_mismatch": reach, "dist_gap": gap}
