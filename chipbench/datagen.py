"""The benchmark's own collection generator.

A copy of the TR-shaped generator (``repro.core.generator``) kept with the
benchmark, so that a change to the program's generator cannot change the
work a cell measures.  The template (topology and partitioning) comes from
the configuration's ``template_seed``: every run of a cell serves the same
graph, as a deployment does.  The per-instance attribute values come from
the run's ``--seed``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from repro.configs.base import GraphConfig
from repro.core.graph import (AttributeDef, GraphInstance, GraphTemplate,
                              TimeSeriesGraph)

# the TR collection's 7 vertex and 7 edge attributes (paper sec. VI-A)
VERTEX_ATTRS = (
    AttributeDef("plate", "int32", default=-1),
    AttributeDef("obs_count", "int32", default=0),
    AttributeDef("outdeg_active", "float32", default=0.0),
    AttributeDef("ip_class", "int32", constant=3),
    AttributeDef("is_router", "int32", default=0),
    AttributeDef("load", "float32", default=0.0),
    AttributeDef("uptime", "float32", default=1.0),
)
EDGE_ATTRS = (
    AttributeDef("latency", "float32", default=1.0),
    AttributeDef("bandwidth", "float32", default=100.0),
    AttributeDef("active", "float32", default=1.0),
    AttributeDef("loss", "float32", default=0.0),
    AttributeDef("hops_seen", "int32", default=0),
    AttributeDef("mtu", "int32", constant=1500),
    AttributeDef("jitter", "float32", default=0.0),
)
NUM_PLATES = 32


def graph_config(cfg: Dict) -> GraphConfig:
    """The program's deployment settings for configuration ``cfg``; the
    partitioner is seeded from the template seed, so the partitioning is
    the same for every run."""
    return GraphConfig(
        name=cfg["name"], num_vertices=int(cfg["num_vertices"]),
        avg_degree=float(cfg["avg_degree"]),
        num_instances=int(cfg["num_instances"]),
        num_partitions=int(cfg["num_partitions"]),
        block_size=int(cfg["block_size"]),
        instances_per_slice=int(cfg["instances_per_slice"]),
        bins_per_partition=int(cfg["bins_per_partition"]),
        cache_slots=int(cfg["cache_slots"]),
        seed=int(cfg["template_seed"]),
    )


def template(cfg: Dict) -> GraphTemplate:
    """Hub-and-spoke small-world digraph: a preferential-attachment-like
    backbone plus a quarter of reverse links, deduplicated, no self loops."""
    rng = np.random.default_rng(int(cfg["template_seed"]))
    V = int(cfg["num_vertices"])
    E = int(V * float(cfg["avg_degree"]))
    tail = rng.integers(1, V, size=E)
    zipf_like = np.minimum(
        (tail * rng.random(E) ** 2.5).astype(np.int64), tail - 1)
    src = np.concatenate([tail, zipf_like[: E // 4]])
    dst = np.concatenate([zipf_like, tail[: E // 4]])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    _, idx = np.unique(src * V + dst, return_index=True)
    idx = np.sort(idx)
    return GraphTemplate(num_vertices=V, src=src[idx].astype(np.int64),
                         dst=dst[idx].astype(np.int64),
                         vertex_attrs=VERTEX_ATTRS, edge_attrs=EDGE_ATTRS,
                         name=cfg["name"])


def collection(cfg: Dict, seed: int) -> TimeSeriesGraph:
    """The configuration's collection: its fixed template, and instance
    values (a diurnal latency pattern, 80% edge activity, vehicle walks)
    drawn from ``seed``."""
    tmpl = template(cfg)
    rng = np.random.default_rng(int(seed))
    V, E = tmpl.num_vertices, tmpl.num_edges
    n_inst = int(cfg["num_instances"])
    step = float(cfg["instance_seconds"])
    plate_pos = rng.integers(0, V, size=NUM_PLATES)
    indptr, indices = tmpl.undirected_adjacency()
    out = []
    for t in range(n_inst):
        phase = 2 * np.pi * t / n_inst
        lat = (50.0 + 30.0 * np.sin(phase)
               + rng.gamma(2.0, 10.0, size=E)).astype(np.float32)
        active = (rng.random(E) < 0.8).astype(np.float32)
        plates = np.full(V, -1, np.int32)
        for i in range(NUM_PLATES):
            v = int(plate_pos[i])
            plates[v] = i
            deg = indptr[v + 1] - indptr[v]
            if deg > 0:
                plate_pos[i] = int(indices[indptr[v] + rng.integers(0, deg)])
        deg_active = np.zeros(V, np.float32)
        np.add.at(deg_active, tmpl.src, active)
        out.append(GraphInstance(
            timestamp=t * step, duration=step,
            vertex_values={
                "plate": plates,
                "obs_count": rng.poisson(2.0, V).astype(np.int32),
                "outdeg_active": deg_active,
                "is_router": (rng.random(V) < 0.1).astype(np.int32),
                "load": rng.random(V).astype(np.float32),
                "uptime": np.minimum(1.0, rng.random(V) + 0.5)
                .astype(np.float32),
            },
            edge_values={
                "latency": lat,
                "bandwidth": rng.gamma(3.0, 30.0, size=E).astype(np.float32),
                "active": active,
                "loss": (rng.random(E) * 0.05).astype(np.float32),
                "hops_seen": rng.poisson(1.0, E).astype(np.int32),
                "jitter": rng.gamma(1.0, 2.0, size=E).astype(np.float32),
            },
        ))
    return TimeSeriesGraph(tmpl, out)
