"""Plain reference and comparison for per-instance PageRank
(``"pagerank"``).

The analytic's semantics: each instance independently, over its active
edges (``active`` attribute), ``iters`` power iterations of
``r' = (1 - d) / N + d * sum_u r[u] * active(u, v) / outdeg_active(u)``
from ``r = 1 / N``, without dangling-mass redistribution.  The reference
computes it in float64 over the generated edge list; the configuration
states float32 ranks.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import numpy as np

ACTIVE_ATTR = "active"
NUMBERS = ("missing", "rank_gap")


def answer(result, per_query: bool) -> np.ndarray:
    """What a run delivered: the ranks of every instance, (I, V)."""
    assert not per_query, "pagerank has no point-query form"
    return result.output["ranks"]


def expected(data: Dict, params: Dict, instances: Sequence[int],
             rnd: Optional[Callable] = None) -> np.ndarray:
    """Ranks of each of ``instances``, (len(instances), V), under
    ``params["damping"]`` and ``params["iters"]``.  ``rnd`` rounds every
    stored value, product and sum to a lower precision (the control);
    ``None`` keeps float64."""
    damping, iters = float(params["damping"]), int(params["iters"])
    src, dst, V = data["src"], data["dst"], data["num_vertices"]
    rnd = rnd or (lambda a: a)
    out = np.empty((len(instances), V), np.float64)
    for k, t in enumerate(instances):
        act = data["edges"][ACTIVE_ATTR][t].astype(np.float64)
        deg = np.bincount(src, weights=act, minlength=V)
        w = rnd(np.where(deg[src] > 0, act / np.maximum(deg[src], 1e-30),
                         0.0))
        r = rnd(np.full(V, 1.0 / V))
        for _ in range(iters):
            contrib = rnd(np.bincount(dst, weights=rnd(r[src] * w),
                                      minlength=V))
            r = rnd((1.0 - damping) / V + rnd(damping * contrib))
        out[k] = r
    return out


def compare(got: Sequence[Optional[np.ndarray]],
            ref: Sequence[np.ndarray]) -> Dict[str, float]:
    """``missing`` (no answer, or one of the wrong shape) and
    ``rank_gap`` (the widest relative gap between a served and a
    reference rank; every reference rank is at least (1 - d) / N > 0)."""
    missing = 0
    gap = 0.0
    for g, r in zip(got, ref):
        if g is None or np.shape(g) != np.shape(r):
            missing += 1
            continue
        gap = max(gap, float(np.max(
            np.abs(np.asarray(g, np.float64) - r) / np.abs(r))))
    return {"missing": missing, "rank_gap": gap}
