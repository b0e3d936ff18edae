"""Useful bytes of the semiring kernels, counted from edges and vertices.

A local sweep reads each local edge's weight once (4 B) and each vertex's
state once in and once out (8 B); a superstep does the same over the
boundary edges.  A plus-mul PageRank iteration is one of each.  Tile
shapes never enter: padding and empty tile cells are not useful work, so
no layout or kernel change can push the share past 100%.  The kernels
are memory-bound (2 operations per 4 B weight), so the least time is the
useful bytes over the chip's HBM bandwidth.
"""
from __future__ import annotations

EDGE_BYTES = 4
VERTEX_BYTES = 8


def minplus_bytes(local_sweeps: int, supersteps: int, *, local_edges: int,
                  boundary_edges: int, num_vertices: int) -> int:
    """Useful bytes of ``local_sweeps`` local sweeps and ``supersteps``
    boundary supersteps (summed over instances and queries)."""
    return (local_sweeps * (EDGE_BYTES * local_edges
                            + VERTEX_BYTES * num_vertices)
            + supersteps * (EDGE_BYTES * boundary_edges
                            + VERTEX_BYTES * num_vertices))


def plusmul_bytes(iterations: int, *, local_edges: int, boundary_edges: int,
                  num_vertices: int) -> int:
    """Useful bytes of ``iterations`` PageRank iterations (summed over
    instances): one local sweep and one superstep each."""
    return minplus_bytes(iterations, iterations, local_edges=local_edges,
                         boundary_edges=boundary_edges,
                         num_vertices=num_vertices)


def least_seconds(useful_bytes: float, hbm_bytes_per_s: float) -> float:
    return useful_bytes / hbm_bytes_per_s
