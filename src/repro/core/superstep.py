"""Sub-graph-centric BSP supersteps on blocked graphs (TPU-native Gopher).

The engine realizes the paper's superstep semantics as linear algebra
(DESIGN.md §2):

* one *superstep* = (optional) local convergence inside each partition
  followed by ONE boundary exchange;
* *sub-graph-centric* mode iterates the local semiring SpMV to fixpoint
  before exchanging (the paper's "do much local work per message" trade) —
  valid for idempotent semirings (SSSP, reachability, components);
* *vertex-centric* mode does exactly one local sweep per superstep — the
  Pregel baseline the paper compares against.  Same code path, one knob.

Both a stacked single-process path (partitions on a leading axis, used by
CPU tests/benchmarks) and an SPMD path (partitions sharded over a mesh axis
inside ``shard_map``, used by the dry-run and production launch) share the
kernel-level step functions; only the :class:`repro.core.comm.CommBackend`
reduction differs.

The boundary exchange is a dense (num_boundary,) buffer combined with the
semiring's add — O(cut vertices) collective bytes per superstep, the
blocked analogue of Gopher's message-count win.  HOW those bytes move is
pluggable (``repro.core.comm``): a psum/pmin all-reduce (default), a
``ppermute`` ring for DCI-bound multi-pod topologies, or a host-side
gather for mesh-free CPU clusters — same drivers, same algorithms.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocked import BlockedGraph
from repro.core.comm import (  # noqa: F401  (re-exported: historical home)
    Comm,
    CommBackend,
    DenseAllReduce,
    HostGather,
    RingExchange,
    make_comm,
)
from repro.core.semiring import MIN_PLUS, PLUS_MUL, Semiring
from repro.kernels.semiring_spmm.ops import spmv_blocked
from repro.kernels.semiring_superstep.ops import fused_step

#: Engine kernel modes: ``"off"`` is the pure-jnp oracle, ``"spmv"`` the
#: per-stage blocked SpMV Pallas kernel, ``"fused"`` the single-call
#: superstep kernel (sweep + semiring combine + halt vote in one
#: ``pallas_call``, ``kernels/semiring_superstep``).  Plain bools keep
#: their historical meaning (``False`` -> off, ``True`` -> spmv).
KERNEL_MODES = ("off", "spmv", "fused")


def kernel_mode(use_pallas) -> Tuple[str, Any]:
    """Normalize a ``use_pallas`` value to ``(mode, interpret)``.

    ``use_pallas`` is the historical knob name and still accepts bools;
    it now also accepts a mode string from :data:`KERNEL_MODES` or a
    ``(mode, interpret)`` tuple for callers (tests, the engine) that
    force interpret mode explicitly.  ``interpret=None`` defers to the
    cached backend probe in ``kernels/semiring_spmm/ops.py``.
    """
    interpret = None
    if isinstance(use_pallas, tuple):
        use_pallas, interpret = use_pallas
    if use_pallas is False or use_pallas is None:
        return "off", interpret
    if use_pallas is True:
        return "spmv", interpret
    if use_pallas not in KERNEL_MODES:
        raise ValueError(
            f"unknown kernel mode {use_pallas!r}: pick from {KERNEL_MODES}")
    return use_pallas, interpret


@dataclass
class DeviceGraph:
    """Device-resident blocked structure+values, leading partition axis."""

    block_size: int
    num_boundary: int
    rows: jax.Array  # (P, T) int32
    cols: jax.Array  # (P, T) int32
    tiles: jax.Array  # (P, T, B, B) float32 — per-instance values
    brows: jax.Array  # (P, Tb) int32 (boundary block index)
    bcols: jax.Array  # (P, Tb) int32 (local dst block index)
    btiles: jax.Array  # (P, Tb, B, B) float32 — per-instance values
    out_slot: jax.Array  # (P, O) int32
    out_local: jax.Array  # (P, O) int32
    out_mask: jax.Array  # (P, O) bool
    vmask: jax.Array  # (P, Vp) bool valid-vertex mask

    @property
    def n_parts(self) -> int:
        return self.rows.shape[0]

    @property
    def vp(self) -> int:
        return self.vmask.shape[1]


def device_graph(
    bg: BlockedGraph,
    local_vals: np.ndarray,  # (P, T, B, B) from bg.fill_local
    boundary_vals: np.ndarray,  # (P, Tb, B, B) from bg.fill_boundary
) -> DeviceGraph:
    P, O = bg.out_slot.shape
    out_mask = np.arange(O)[None, :] < bg.n_out[:, None]
    vmask = bg.global_of >= 0
    return DeviceGraph(
        block_size=bg.block_size,
        num_boundary=bg.num_boundary,
        rows=jnp.asarray(bg.tiles_rc[:, :, 0]),
        cols=jnp.asarray(bg.tiles_rc[:, :, 1]),
        tiles=jnp.asarray(local_vals),
        brows=jnp.asarray(bg.btiles_rc[:, :, 0]),
        bcols=jnp.asarray(bg.btiles_rc[:, :, 1]),
        btiles=jnp.asarray(boundary_vals),
        out_slot=jnp.asarray(bg.out_slot),
        out_local=jnp.asarray(bg.out_local),
        out_mask=jnp.asarray(out_mask),
        vmask=jnp.asarray(vmask),
    )


# ---------------------------------------------------------------------------
# Step primitives
# ---------------------------------------------------------------------------

def _blocks(x: jax.Array, dg: DeviceGraph) -> jax.Array:
    """(P, Vp) state -> (P, NVB, B) block view for the fused kernel."""
    return x.reshape(x.shape[0], -1, dg.block_size)


def _fused_sweep_vote(
    x: jax.Array, dg: DeviceGraph, sr: Semiring, interpret,
) -> Tuple[jax.Array, jax.Array]:
    """One fused sweep: x' = add(x, A^T x) plus the per-partition halt
    vote vs the pre-sweep state, all inside one ``pallas_call``."""
    xs = _blocks(x, dg)
    xo, changed = fused_step(dg.tiles, dg.rows, dg.cols, xs, xs, xs,
                             _blocks(dg.vmask, dg), sr, interpret=interpret)
    return xo.reshape(x.shape), changed


def _fused_consume_vote(
    x: jax.Array, boundary: jax.Array, dg: DeviceGraph, sr: Semiring,
    x_ref: jax.Array, interpret, combine: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Fused boundary consume: x' = add(x, R^T boundary), voting against
    ``x_ref`` (the superstep start) in-kernel."""
    xs = _blocks(x, dg)
    comb = xs if combine else sr.full(xs.shape, xs.dtype)
    xo, changed = fused_step(
        dg.btiles, dg.brows, dg.bcols,
        boundary.reshape(1, -1, dg.block_size), comb, _blocks(x_ref, dg),
        _blocks(dg.vmask, dg), sr, interpret=interpret)
    return xo.reshape(x.shape), changed


def _local_sweep(
    x: jax.Array, dg: DeviceGraph, sr: Semiring, use_pallas
) -> jax.Array:
    """One relaxation sweep of every partition: x' = add(x, A^T x)."""
    mode, interpret = kernel_mode(use_pallas)
    if mode == "fused":
        return _fused_sweep_vote(x, dg, sr, interpret)[0]

    def one(tiles, rows, cols, xp):
        y = spmv_blocked(tiles, rows, cols, xp, sr,
                         use_pallas=mode == "spmv", interpret=interpret)
        return sr.add(xp, y)

    return jax.vmap(one)(dg.tiles, dg.rows, dg.cols, x)


def _spmv_only(
    x: jax.Array, dg: DeviceGraph, sr: Semiring, use_pallas
) -> jax.Array:
    """Plain y = A^T x per partition (no combine with x) — PageRank path."""
    mode, interpret = kernel_mode(use_pallas)
    if mode == "fused":
        # add(zero, y) == y and untouched blocks stay sr.zero — the
        # fused kernel degenerates to the plain SpMV (vote ignored)
        xs = _blocks(x, dg)
        xo, _ = fused_step(dg.tiles, dg.rows, dg.cols, xs,
                           sr.full(xs.shape, xs.dtype), xs,
                           _blocks(dg.vmask, dg), sr, interpret=interpret)
        return xo.reshape(x.shape)

    def one(tiles, rows, cols, xp):
        return spmv_blocked(tiles, rows, cols, xp, sr,
                            use_pallas=mode == "spmv", interpret=interpret)

    return jax.vmap(one)(dg.tiles, dg.rows, dg.cols, x)


def _local_converge(
    x: jax.Array, dg: DeviceGraph, sr: Semiring, use_pallas,
    max_sweeps: int,
) -> Tuple[jax.Array, jax.Array]:
    """Sweep to local fixpoint (idempotent sr).  Returns (x, n_sweeps)."""
    mode, interpret = kernel_mode(use_pallas)

    def cond(carry):
        _, changed, it = carry
        return jnp.logical_and(changed, it < max_sweeps)

    def body(carry):
        xc, _, it = carry
        if mode == "fused":
            # the kernel's per-partition vote is ready-made: the loop
            # folds P scalars instead of re-reading two (P, Vp) states
            xn, chv = _fused_sweep_vote(xc, dg, sr, interpret)
            changed = jnp.max(chv) > 0
        else:
            xn = _local_sweep(xc, dg, sr, use_pallas)
            changed = jnp.any(jnp.where(dg.vmask, xn != xc, False))
        return xn, changed, it + 1

    x, _, sweeps = jax.lax.while_loop(
        cond, body, (x, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    )
    return x, sweeps


def _publish(x: jax.Array, dg: DeviceGraph, sr: Semiring,
             comm: CommBackend) -> jax.Array:
    """Scatter owned boundary-vertex values into the global boundary buffer
    and combine across partitions.  Returns (NB,)."""

    def one(xp, slots, locals_, mask):
        vals = jnp.where(mask, xp[locals_], jnp.asarray(sr.zero, xp.dtype))
        buf = sr.full((dg.num_boundary,), xp.dtype)
        return sr.scatter_add(buf, slots, vals)

    buf = jax.vmap(one)(x, dg.out_slot, dg.out_local, dg.out_mask)
    # on a mesh this is the superstep's collective: its ops carry the
    # scope's name in their HLO metadata
    with jax.named_scope("gopher.exchange"):
        return comm.combine_boundary(buf, sr)


def _consume(
    x: jax.Array, boundary: jax.Array, dg: DeviceGraph, sr: Semiring,
    use_pallas, combine: bool = True,
) -> jax.Array:
    """Apply incoming cut edges: y = R^T boundary; x' = add(x, y)."""
    mode, interpret = kernel_mode(use_pallas)
    if mode == "fused":
        return _fused_consume_vote(x, boundary, dg, sr, x, interpret,
                                   combine=combine)[0]
    nob = dg.vp // dg.block_size

    def one(btiles, brows, bcols, xp):
        y = spmv_blocked(
            btiles, brows, bcols, boundary, sr,
            n_out_blocks=nob, use_pallas=mode == "spmv", interpret=interpret,
        )
        return sr.add(xp, y) if combine else y

    return jax.vmap(one, in_axes=(0, 0, 0, 0))(dg.btiles, dg.brows, dg.bcols, x)


def make_spmd_superstep(mesh, sr: Semiring = MIN_PLUS, *,
                        use_pallas=False,
                        comm="dense"):
    """One BSP superstep as an explicit shard_map program: partitions are
    sharded one-per-device over ALL mesh axes; the boundary exchange is one
    combine of the (num_boundary,) buffer through the selected
    ``repro.core.comm`` backend (``"dense"`` pmin/psum all-reduce or
    ``"ring"`` collective-permute ring).

    This is the production lowering — letting XLA auto-shard the stacked
    (P, NB) publish buffer instead materializes an all-gather of P x NB
    bytes per superstep (measured 995 MB/device on the TR-full cell vs
    3.9 MB here; EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    comm = make_comm(comm, mesh=mesh, model_axes=axes)

    def superstep_with_nb(nb: int):
        def run(x, rows, cols, tiles, brows, bcols, btiles,
                out_slot, out_local, out_mask, vmask):
            def local_fn(x_l, rows_l, cols_l, tiles_l, brows_l, bcols_l,
                         btiles_l, out_slot_l, out_local_l, out_mask_l,
                         vmask_l):
                d = DeviceGraph(
                    block_size=tiles_l.shape[-1], num_boundary=nb,
                    rows=rows_l, cols=cols_l, tiles=tiles_l,
                    brows=brows_l, bcols=bcols_l, btiles=btiles_l,
                    out_slot=out_slot_l, out_local=out_local_l,
                    out_mask=out_mask_l, vmask=vmask_l,
                )
                x1 = _local_sweep(x_l, d, sr, use_pallas)
                boundary = _publish(x1, d, sr, comm)
                return _consume(x1, boundary, d, sr, use_pallas)

            def lead(a):
                return P(axes, *([None] * (a.ndim - 1)))

            args = (x, rows, cols, tiles, brows, bcols, btiles,
                    out_slot, out_local, out_mask, vmask)
            fn = jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=tuple(lead(a) for a in args),
                out_specs=lead(x),
                check_vma=False,
            )
            return fn(*args)

        return run

    return superstep_with_nb


# ---------------------------------------------------------------------------
# BSP drivers
# ---------------------------------------------------------------------------

def bsp_fixpoint(
    x0: jax.Array,  # (P, Vp) initial vertex values
    dg: DeviceGraph,
    sr: Semiring = MIN_PLUS,
    *,
    comm: CommBackend = DenseAllReduce(),
    subgraph_centric: bool = True,
    max_supersteps: int = 64,
    max_local_sweeps: int = 1024,
    use_pallas=False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run BSP supersteps until global fixpoint (idempotent semirings).

    Returns (x, stats) with stats = {supersteps, local_sweeps}.
    ``subgraph_centric=False`` gives the vertex-centric (Pregel) baseline:
    exactly one local sweep per superstep.
    """
    assert sr.idempotent, "bsp_fixpoint needs an idempotent semiring"
    sweeps_cap = max_local_sweeps if subgraph_centric else 1
    mode, interpret = kernel_mode(use_pallas)

    def cond(carry):
        _, changed, ss, _ = carry
        return jnp.logical_and(changed, ss < max_supersteps)

    def body(carry):
        x0_step, _, ss, lsw = carry
        x, s = _local_converge(x0_step, dg, sr, use_pallas, sweeps_cap)
        boundary = _publish(x, dg, sr, comm)
        # vote-to-halt compares against the superstep START: in
        # vertex-centric mode the single local sweep can progress even when
        # the boundary exchange is quiet.
        if mode == "fused":
            # the consume kernel emits the vote: the while_loop consumes
            # a (P, 1) scalar fold, never re-reading the full states
            xn, chv = _fused_consume_vote(x, boundary, dg, sr, x0_step,
                                          interpret)
            changed = jnp.max(chv) > 0
        else:
            xn = _consume(x, boundary, dg, sr, use_pallas)
            changed = jnp.any(jnp.where(dg.vmask, xn != x0_step, False))
        changed = comm.any_changed(changed)
        return xn, changed, ss + 1, lsw + s

    x, _, supersteps, local_sweeps = jax.lax.while_loop(
        cond, body,
        (x0, jnp.asarray(True), jnp.asarray(0, jnp.int32),
         jnp.asarray(0, jnp.int32)),
    )
    return x, {"supersteps": supersteps, "local_sweeps": local_sweeps}


def pagerank_step(
    rank: jax.Array,  # (P, Vp)
    dg: DeviceGraph,  # tiles already hold 1/out_degree weights
    comm: CommBackend,
    *,
    damping: float = 0.85,
    num_vertices: int,
    use_pallas=False,
) -> jax.Array:
    """One PageRank superstep: contribution SpMV + boundary exchange."""
    contrib = _spmv_only(rank, dg, PLUS_MUL, use_pallas)
    boundary = _publish(rank, dg, PLUS_MUL, comm)
    contrib = contrib + _consume(
        jnp.zeros_like(rank), boundary, dg, PLUS_MUL, use_pallas, combine=False
    )
    base = (1.0 - damping) / num_vertices
    out = jnp.where(dg.vmask, base + damping * contrib, 0.0)
    return out


def pagerank_run(
    dg: DeviceGraph,
    comm: CommBackend = DenseAllReduce(),
    *,
    damping: float = 0.85,
    num_vertices: int,
    iters: int = 30,
    tol: float = 0.0,
    use_pallas=False,
) -> Tuple[jax.Array, jax.Array]:
    """PageRank to ``iters`` supersteps (or L1 tolerance).  Returns
    (rank (P, Vp), supersteps)."""
    P, Vp = dg.vmask.shape
    r0 = jnp.where(dg.vmask, 1.0 / num_vertices, 0.0)

    def cond(carry):
        _, delta, it = carry
        return jnp.logical_and(delta > tol, it < iters)

    def body(carry):
        r, _, it = carry
        rn = pagerank_step(
            r, dg, comm, damping=damping, num_vertices=num_vertices,
            use_pallas=use_pallas,
        )
        delta = comm.sum_scalar(jnp.sum(jnp.abs(rn - r)))
        return rn, delta, it + 1

    r, _, it = jax.lax.while_loop(
        cond, body, (r0, jnp.asarray(jnp.inf), jnp.asarray(0, jnp.int32))
    )
    return r, it
