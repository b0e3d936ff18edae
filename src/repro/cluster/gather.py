"""Real inter-process boundary exchange behind the ``HostGather`` seam.

``repro.core.comm.HostGather`` folds the stacked (P, NB) publish buffer
on the host with a fixed-association left fold (``_host_fold_*``) — the
module has always documented that fold site as "where the MPI-style
gather slots in on a real multi-host cluster".  :class:`ClusterGather`
is that gather: each process folds ONLY its own partition shard's
(P_local, NB) buffer rows; the callback allgathers the shards over the
:class:`~repro.cluster.runtime.TcpExchange` in process-id order,
concatenates them back into the full (P, NB) buffer, and applies the
IDENTICAL ``_host_fold_*`` left fold on every host.

Because :meth:`ClusterRuntime.partition_shard` assigns contiguous
partition ranges in process-id order, the concatenation reconstructs the
exact single-process buffer — so the distributed combine is
**bitwise-identical** to the single-process fold, for min-plus AND
plus-mul (same 0..P-1 association, same IEEE f32 adds).

The halt vote (``any_changed``) becomes a cross-process OR: every
process's ``while_loop`` then runs the same superstep count — which is
both what makes the reported ``supersteps`` stats match the
single-process run and what keeps the per-superstep exchange
deadlock-free (no process exits the loop while others still expect its
buffers).  ``local_sweeps`` stays a per-process statistic: a shard
holding fewer partitions locally converges in fewer sweeps, and the
extra sweeps the single-process run performs on already-converged
partitions are idempotent no-ops — values are unaffected.

``sum_scalar`` (only the standalone ``pagerank_run`` tolerance driver
uses it; the engine's PageRank is fixed-iteration) sums the per-process
partials in rank order — associated differently than the single-process
``jnp.sum`` over all partitions, so tolerance-triggered halts may differ
in low-order bits there.  The engine paths the parity suite gates never
touch it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import (CommBackend, HostGather, _host_fold_min,
                             _host_fold_sum)
from repro.core.semiring import Semiring
from repro.cluster.runtime import ClusterRuntime


@dataclass(frozen=True)
class ClusterGather(CommBackend):
    """Inter-process boundary combine (see module docstring).

    Degrades exactly to :class:`~repro.core.comm.HostGather` when the
    runtime is single-process — same callback, same fold, zero network.

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core.semiring import MIN_PLUS
    >>> from repro.cluster.runtime import ClusterRuntime
    >>> cg = ClusterGather(runtime=ClusterRuntime(0, 1))
    >>> buf = jnp.asarray([[0., 7., jnp.inf], [jnp.inf, 2., 5.]])
    >>> np.asarray(cg.combine_boundary(buf, MIN_PLUS))
    array([0., 2., 5.], dtype=float32)
    """

    name: str = "cluster"
    runtime: Optional[ClusterRuntime] = None

    def __post_init__(self):
        assert self.runtime is not None, "ClusterGather needs a runtime"
        assert self.axis_name is None, \
            "ClusterGather is mesh-free (stacked per-process shards)"

    def combine_boundary(self, buf: jax.Array, sr: Semiring) -> jax.Array:
        fold = _host_fold_sum if sr.name == "plus_mul" else _host_fold_min
        rt = self.runtime

        def exchange_fold(b) -> np.ndarray:
            full = rt.allgather_concat(
                np.asarray(b), axis=0, tag=f"combine/{sr.name}")
            return fold(full)

        return jax.pure_callback(
            exchange_fold,
            jax.ShapeDtypeStruct(buf.shape[1:], buf.dtype), buf,
            vmap_method="sequential",
        )

    def any_changed(self, flag: jax.Array) -> jax.Array:
        if not self.runtime.is_distributed:
            return flag
        rt = self.runtime

        def vote(f) -> np.ndarray:
            return np.asarray(rt.all_reduce_or(bool(f), tag="vote"))

        return jax.pure_callback(
            vote, jax.ShapeDtypeStruct((), jnp.bool_), flag,
            vmap_method="sequential")

    def sum_scalar(self, x: jax.Array) -> jax.Array:
        if not self.runtime.is_distributed:
            return x
        rt = self.runtime

        def ssum(v) -> np.ndarray:
            parts = rt.allgather("sum", np.asarray(v))
            out = parts[0]
            for p in parts[1:]:
                out = out + p
            return out

        return jax.pure_callback(
            ssum, jax.ShapeDtypeStruct(x.shape, x.dtype), x,
            vmap_method="sequential")


def cluster_comm(runtime: Optional[ClusterRuntime]) -> CommBackend:
    """The comm backend a cluster-placed engine should default to: the
    inter-process gather when distributed, plain ``HostGather`` (same
    fold, no exchange) single-process."""
    if runtime is not None and runtime.is_distributed:
        return ClusterGather(runtime=runtime)
    return HostGather()
