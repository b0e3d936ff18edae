"""The one traffic generator: every traffic file under ``traffic/`` is
parameters for it.

A traffic file (``traffic/<name>.json``) holds:

- ``loop``: ``"closed"`` (one client, back-to-back whole-collection passes
  through ``GopherSession.run``) or ``"open"`` (point queries submitted to a
  ``GopherService`` on a fixed arrival schedule, whatever the service is
  doing);
- ``analytic`` and ``params``: the registered analytic and its fixed
  parameters; ``source_param`` names the parameter that takes a vertex;
- ``sources``: how vertices are drawn: ``{"dist": "uniform_out"}`` (uniform
  over vertices with out-edges) or ``{"dist": "scrambled_zipfian",
  "theta": 0.99}`` (YCSB's scrambled Zipfian over the vertex ids);
- open loop only: ``rate_qps``, ``arrivals`` (``"poisson"``) and
  ``schedule_seed``: one realization of Poisson arrivals at that rate,
  the same for every run, so that each run offers the same bursts; the
  run's seed draws which sources arrive in which order;
- ``check``: how many answers of a run are compared with the reference.

Everything else random is drawn from the run's ``--seed``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

# YCSB's 64-bit FNV-1a constants (ZipfianGenerator / ScrambledZipfian)
_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 1099511628211
_MASK64 = (1 << 64) - 1


def fnv64(v: int) -> int:
    """YCSB's ``Utils.fnvhash64`` of one 64-bit value, byte by byte: the
    absolute value of the signed 64-bit hash."""
    h = _FNV_OFFSET
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * _FNV_PRIME) & _MASK64
        v >>= 8
    return (1 << 64) - h if h >> 63 else h


def stratified(rng: np.random.Generator, count: int) -> np.ndarray:
    """``count`` uniform quantiles, one from the middle of each of
    ``count`` equal strata, in an order drawn from ``rng``: every seed
    gets the same set of values, so the same amount of work, in another
    order."""
    return rng.permutation((np.arange(count) + 0.5) / count)


def zipf_ranks(rng: np.random.Generator, n: int, theta: float,
               count: int) -> np.ndarray:
    """``count`` ranks in ``[0, n)`` of a Zipfian of constant ``theta``
    (rank 0 the most popular), by inverse transform of stratified
    quantiles over the exact cumulative weights."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, stratified(rng, count)), n - 1)


def scrambled_zipfian(rng: np.random.Generator, n: int, theta: float,
                      count: int) -> np.ndarray:
    """A scrambled Zipfian in YCSB's manner: a Zipfian rank over the ``n``
    ids, hashed onto the id space with YCSB's FNV-1a, so the popular ids
    are spread over it.  The hot ids are the same for every seed; the seed
    draws the order."""
    ranks = zipf_ranks(rng, n, theta, count)
    return np.array([fnv64(int(r)) % n for r in ranks], np.int64)


def draw_sources(rng: np.random.Generator, spec: Dict, src: np.ndarray,
                 num_vertices: int, count: int) -> np.ndarray:
    """``count`` source vertices under the traffic's ``sources`` spec."""
    dist = spec["dist"]
    if dist == "uniform_out":
        pool = np.unique(src)
        return pool[rng.integers(0, len(pool), size=count)]
    if dist == "scrambled_zipfian":
        return scrambled_zipfian(rng, num_vertices, float(spec["theta"]),
                                 count)
    raise ValueError(f"unknown source distribution {dist!r}")


def arrivals(traffic: Dict, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of the
    ``round(rate * seconds)`` queries of a ``seconds`` window.  Poisson:
    exponential inter-arrival gaps at stratified quantiles, in the order
    the traffic's ``schedule_seed`` draws, scaled so the window holds
    them all.  Every run of the traffic gets the same schedule: at four
    fifths of the knee the tail latency depends on where the bursts fall
    far more than on anything a change to the program does."""
    kind = traffic["arrivals"]
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    n = max(1, int(round(float(traffic["rate_qps"]) * seconds)))
    gaps = -np.log1p(-stratified(rng, n + 1))
    due = np.cumsum(gaps)
    return due[:n] * (seconds / due[n])


def sink_vertices(src: np.ndarray, num_vertices: int, count: int) -> List[int]:
    """``count`` vertices with no out-edge: a query from one converges in
    one superstep, so warming a batch width with them costs little."""
    has_out = np.zeros(num_vertices, bool)
    has_out[src] = True
    sinks = np.nonzero(~has_out)[0]
    if len(sinks) < count:
        raise ValueError(f"{len(sinks)} sink vertices; the warm-up needs "
                         f"{count}")
    return [int(v) for v in sinks[:count]]
