"""Fused BSP-superstep stage as ONE Pallas TPU kernel.

Grid ``(P, T)``: partition-major walk over every partition's col-sorted
packed tile list.  Per grid step the kernel

* double-buffers the next tile's HBM->VMEM copy against the current
  tile's compute (manual ``make_async_copy`` ping-pong over a 2-slot
  VMEM scratch, chained across the partition boundary);
* accumulates the blocked SpMV partial for the current output run into a
  VMEM-resident ``y`` accumulator (tiles are col-sorted, so each output
  block is one contiguous run — same invariant as
  ``kernels/semiring_spmm``);
* at the end of a run, combines the run's ``y`` into the VMEM-resident
  output state: ``x_out[c] = sr.add(x_comb[c], y)`` — the semiring
  combine that used to be a separate XLA op;
* at the last tile of a partition, writes the per-partition halt vote
  ``changed[p] = any(vmask & (x_out != x_ref))`` into SMEM — the
  vote-to-halt reduction that used to re-read both full states in XLA.

The x/y vertex state for partition ``p`` (``x_in``/``x_comb``/``x_ref``/
``x_out`` rows plus the run accumulator) stays VMEM-resident across the
whole ``T``-step walk; only tiles stream from HBM.  Padding tiles
(``cols < 0``, always sorted last) skip compute under ``pl.when`` but
keep the DMA chain uniform.

Semantics per partition (min-plus shown):

    y      = A_p^T x_in
    x_out  = min(x_comb, y)          (untouched blocks keep x_comb)
    changed[p] = any(vmask_p & (x_out_p != x_ref_p))

``interpret=True`` runs the same kernel under the Pallas interpreter —
the CI-provable parity tier used by the CPU test suite.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import INF

_ZEROS = {"min_plus": INF, "plus_mul": 0.0, "max_plus": -INF}
# the MXU multiplies f32 in bf16 passes unless asked for full precision
_F32 = jax.lax.Precision.HIGHEST


def _fused_kernel(
    # scalar prefetch (SMEM)
    rows_ref,  # (P, T) int32, pad rows clamped to 0
    cols_ref,  # (P, T) int32, -1 = pad (sorted last)
    # inputs
    tiles_hbm,  # (P, T, B, B) — stays in HBM, manually DMA'd
    x_in_ref,  # (1, NVBin, B) VMEM block (partition row or shared buffer)
    x_comb_ref,  # (1, NVB, B) VMEM block
    x_ref_ref,  # (1, NVB, B) VMEM block
    vmask_ref,  # (1, NVB, B) VMEM block, 0/1 float
    # outputs
    x_out_ref,  # (1, NVB, B) VMEM block — revisited across the t-walk
    changed_ref,  # (P, 1) int32 SMEM (whole array)
    # scratch
    y_ref,  # (1, B) VMEM run accumulator
    tbuf,  # (2, B, B) VMEM tile ping-pong
    sems,  # DMA semaphores, one per slot
    *,
    sr_name: str,
    n_t: int,
    total: int,
):
    zero = _ZEROS[sr_name]
    p = pl.program_id(0)
    t = pl.program_id(1)
    g = p * n_t + t
    slot = jax.lax.rem(g, 2)
    nslot = jax.lax.rem(g + 1, 2)

    # ---- double-buffered tile DMA: warm up, then overlap t+1 with t ----
    @pl.when(g == 0)
    def _():
        pltpu.make_async_copy(
            tiles_hbm.at[0, 0], tbuf.at[0], sems.at[0]).start()

    @pl.when(g + 1 < total)
    def _():
        g1 = g + 1
        pltpu.make_async_copy(
            tiles_hbm.at[g1 // n_t, jax.lax.rem(g1, n_t)],
            tbuf.at[nslot], sems.at[nslot]).start()

    # ---- superstep baseline: untouched blocks must carry x_comb ----
    @pl.when(t == 0)
    def _():
        x_out_ref[...] = x_comb_ref[...]

    c = cols_ref[p, t]
    valid = c >= 0
    cprev = cols_ref[p, jnp.maximum(t - 1, 0)]
    cnext = cols_ref[p, jnp.minimum(t + 1, n_t - 1)]
    first = jnp.logical_and(valid, jnp.logical_or(t == 0, cprev != c))
    last = jnp.logical_and(valid, jnp.logical_or(t == n_t - 1, cnext != c))

    pltpu.make_async_copy(
        tiles_hbm.at[p, t], tbuf.at[slot], sems.at[slot]).wait()

    @pl.when(first)
    def _():
        y_ref[...] = jnp.full_like(y_ref, zero)

    @pl.when(valid)
    def _():
        r = rows_ref[p, t]
        xb = x_in_ref[0, pl.ds(r, 1), :]  # (1, B)
        w = tbuf[slot]
        if sr_name == "plus_mul":
            # 2-D (1, B) @ (B, B): Mosaic lowers no 1-D dot
            y_ref[...] += jnp.dot(xb, w, precision=_F32,
                                  preferred_element_type=jnp.float32)
        else:
            # broadcast-add + min-reduce on the VPU (idempotent: exact)
            y_ref[...] = jnp.minimum(
                y_ref[...],
                jnp.min(xb.reshape(-1, 1) + w, axis=0, keepdims=True))

    @pl.when(last)
    def _():
        base = x_comb_ref[0, pl.ds(c, 1), :]
        if sr_name == "plus_mul":
            x_out_ref[0, pl.ds(c, 1), :] = base + y_ref[...]
        else:
            x_out_ref[0, pl.ds(c, 1), :] = jnp.minimum(base, y_ref[...])

    # ---- halt vote: one VMEM-resident compare per partition ----
    @pl.when(t == n_t - 1)
    def _():
        diff = jnp.logical_and(vmask_ref[...] != 0.0,
                               x_out_ref[...] != x_ref_ref[...])
        changed_ref[p, 0] = jnp.any(diff).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("sr_name", "interpret"))
def fused_step_pallas(
    tiles: jax.Array,  # (P, T, B, B) float32
    rows: jax.Array,  # (P, T) int32, -1 = pad
    cols: jax.Array,  # (P, T) int32, -1 = pad (sorted last)
    x_in: jax.Array,  # (Pin, NVBin, B); Pin in {P, 1}
    x_comb: jax.Array,  # (P, NVB, B)
    x_ref: jax.Array,  # (P, NVB, B)
    vmask: jax.Array,  # (P, NVB, B) float32 0/1
    *,
    sr_name: str = "min_plus",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns ``(x_out (P, NVB, B), changed (P, 1) int32)``."""
    P, T, B, _ = tiles.shape
    if not interpret and B % 128:
        # Mosaic slices the HBM tile array in (1, 128) lane tiles: a
        # narrower tile cannot be DMA'd on its own
        raise ValueError(
            f"fused_step_pallas needs a block size that is a multiple of "
            f"the TPU lane width 128 to compile; got B={B}")
    nvb = x_comb.shape[1]
    nvb_in = x_in.shape[1]
    shared_xin = x_in.shape[0] == 1

    def xin_map(p, t, r, c):
        del t, r, c
        return (0, 0, 0) if shared_xin else (p, 0, 0)

    def part_row(p, t, r, c):
        del t, r, c
        return (p, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P, T),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # tiles stay in HBM
            pl.BlockSpec((1, nvb_in, B), xin_map),
            pl.BlockSpec((1, nvb, B), part_row),
            pl.BlockSpec((1, nvb, B), part_row),
            pl.BlockSpec((1, nvb, B), part_row),
        ],
        out_specs=[
            pl.BlockSpec((1, nvb, B), part_row),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, B), jnp.float32),
            pltpu.VMEM((2, B, B), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _fused_kernel, sr_name=sr_name, n_t=T, total=P * T)
    x_out, changed = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((P, nvb, B), x_comb.dtype),
            jax.ShapeDtypeStruct((P, 1), jnp.int32),
        ],
        # the t-walk accumulates into revisited VMEM blocks and the DMA
        # chain crosses the partition boundary: both grid dims sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.maximum(rows, 0), cols, tiles, x_in, x_comb, x_ref, vmask)
    return x_out, changed
