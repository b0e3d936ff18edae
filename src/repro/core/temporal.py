"""Temporal parallelism on the mesh (paper §IV-B orchestration, DESIGN §2).

The *independent* and *eventually dependent* patterns expose concurrency
ACROSS graph instances; on the production mesh this maps instances onto the
``data`` axis while graph partitions stay on ``model`` — both forms of the
paper's parallelism at once:

    tiles  (I, P, T, B, B)   I sharded over data, P sharded over model
    ranks  (I, P, Vp)        same

Each device holds I/|data| instances x P/|model| partitions; the spatial
boundary exchange runs over ``model`` ONLY (instances never talk), through
whichever ``repro.core.comm`` backend the deployment picks (dense psum
all-reduce by default, a collective-permute ring for multi-pod DCI), and
the eventually-dependent Merge is a final reduction over ``data``.

This module provides the shape-polymorphic ``shard_map`` builder
(``make_temporal_runner``) used by the dry-run to lower temporal cells from
abstract shapes alone.  Concrete executions go through
``repro.core.engine.TemporalEngine``, which generalizes the same lowering
to every semiring program (SSSP, components, N-hop — not just PageRank)
and adds batched instance staging; ``pagerank_temporal`` below is the
engine-backed host wrapper kept for the paper's independent-pattern
workload.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocked import BlockedGraph
from repro.core.comm import make_comm
from repro.core.superstep import DeviceGraph, pagerank_step


def make_temporal_runner(
    mesh,
    run_one: Callable[[jax.Array, jax.Array, Dict[str, jax.Array]], jax.Array],
    *,
    data_axis: str = "data",
    model_axes: Tuple[str, ...] = ("model",),
    merge: bool = True,
):
    """Lower a per-instance local program onto the temporal-parallel mesh.

    ``run_one(tiles_l (P_l, T, B, B), btiles_l, struct)`` computes one
    instance's final vertex state (P_l, Vp) on the local partition shard
    (collectives over ``model_axes`` only — typically a ``repro.core.comm``
    backend bound to those axes, so the same runner lowers to a dense
    all-reduce or a collective-permute ring depending on the closure's
    ``comm`` choice).  The returned jittable fn takes
    the global (I, P, ...) tensors, shards instances over ``data_axis`` and
    partitions over ``model_axes``, vmaps ``run_one`` over the local
    instances, and (when ``merge``) folds the across-instance mean as one
    reduction over the data axis — the eventually-dependent Merge.
    """
    from jax.sharding import PartitionSpec as P_

    maxes = model_axes if len(model_axes) > 1 else model_axes[0]

    def local_fn(tiles_l, btiles_l, rows, cols, brows, bcols,
                 out_slot, out_local, out_mask, vmask):
        struct = {
            "rows": rows, "cols": cols, "brows": brows, "bcols": bcols,
            "out_slot": out_slot, "out_local": out_local,
            "out_mask": out_mask, "vmask": vmask,
        }
        states = jax.vmap(lambda t, b: run_one(t, b, struct))(
            tiles_l, btiles_l
        )  # over local instances
        if not merge:
            return states, jnp.zeros_like(states[0])
        # eventually-dependent Merge: mean over ALL instances (data axis)
        part = jnp.sum(states, axis=0)
        total = jax.lax.psum(part, data_axis)
        n_inst = jax.lax.psum(jnp.asarray(states.shape[0], jnp.float32),
                              data_axis)
        return states, total / n_inst

    def spec(*axes):
        return P_(*axes)

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            spec(data_axis, maxes, None, None, None),  # tiles
            spec(data_axis, maxes, None, None, None),  # btiles
            spec(maxes, None), spec(maxes, None),      # rows, cols
            spec(maxes, None), spec(maxes, None),      # brows, bcols
            spec(maxes, None), spec(maxes, None),      # out_slot, out_local
            spec(maxes, None), spec(maxes, None),      # out_mask, vmask
        ),
        out_specs=(
            spec(data_axis, maxes, None),
            spec(maxes, None),  # merged (P_l, Vp): replicated over data
        ),
        check_vma=False,
    )


def make_temporal_pagerank(
    mesh,
    *,
    block_size: int,
    num_boundary: int,
    num_vertices: int,
    damping: float = 0.85,
    iters: int = 30,
    data_axis: str = "data",
    model_axes: Tuple[str, ...] = ("model",),
    merge: bool = True,
    comm="dense",
):
    """Build the jittable temporal-parallel PageRank (the paper's
    independent-pattern workload) on top of ``make_temporal_runner``.

    Inputs (global shapes): tiles (I, P, T, B, B), btiles (I, P, Tb, B, B),
    struct arrays (P, ...).  Returns ranks (I, P, Vp) and, when ``merge``,
    the across-instance mean rank (P, Vp).  Fixed iteration count keeps
    every instance's loop in lockstep, so the model-axis collectives stay
    congruent under the data-axis sharding.  ``comm`` picks the boundary
    exchange backend (``"dense"`` or ``"ring"``; see ``repro.core.comm``).
    """
    comm = make_comm(comm, mesh=mesh, model_axes=model_axes)

    def run_one(tiles, btiles, struct):
        dg = DeviceGraph(
            block_size=block_size, num_boundary=num_boundary,
            rows=struct["rows"], cols=struct["cols"], tiles=tiles,
            brows=struct["brows"], bcols=struct["bcols"], btiles=btiles,
            out_slot=struct["out_slot"], out_local=struct["out_local"],
            out_mask=struct["out_mask"], vmask=struct["vmask"],
        )
        r0 = jnp.where(dg.vmask, 1.0 / num_vertices, 0.0)

        def body(r, _):
            r = pagerank_step(
                r, dg, comm, damping=damping, num_vertices=num_vertices,
            )
            return r, None

        r, _ = jax.lax.scan(body, r0, None, length=iters)
        return r

    return make_temporal_runner(
        mesh, run_one, data_axis=data_axis, model_axes=model_axes,
        merge=merge,
    )


def pagerank_temporal(
    bg: BlockedGraph,
    src: np.ndarray,
    instance_active: np.ndarray,  # (I, E)
    mesh,
    *,
    num_vertices: int,
    damping: float = 0.85,
    iters: int = 30,
    data_axis: str = "data",
    model_axes: Tuple[str, ...] = ("model",),
    comm="dense",
) -> Tuple[np.ndarray, np.ndarray]:
    """Host wrapper: batched-stage per-instance tiles, run all instances
    concurrently on the mesh through the TemporalEngine.  ``comm`` selects
    the boundary exchange backend.  Returns (ranks (I, V), merged mean
    rank (V,))."""
    from repro.core.algorithms.pagerank import edge_weights_for_instances
    from repro.core.engine import TemporalEngine, pagerank_program

    w = edge_weights_for_instances(src, instance_active, num_vertices)
    eng = TemporalEngine(
        bg, mesh=mesh, data_axis=data_axis, model_axes=model_axes, comm=comm,
    )
    res = eng.run(
        pagerank_program(num_vertices, damping=damping, iters=iters),
        w, pattern="eventually", merge="mean",
    )
    return res.values, res.merged
