"""Pallas TPU kernel: blocked semiring SpMV (the paper's compute hot-spot,
TPU-adapted per DESIGN.md §2).

One grid step processes one (B x B) adjacency tile resident in VMEM.  The
tile list may be the dense template list or a block-sparse *packed*
active-tile list (``repro.core.blocked.SparseBlocked``): either way, tiles
are pre-sorted by destination (column) block — ``repro.core.blocked``
guarantees this for the template order, and a packed subset preserves
it — so the sequential TPU grid revisits each output block in a contiguous
run and the kernel can initialize it on first touch and combine in place
afterwards (classic scalar-prefetch block-sparse pattern).

Padding tiles (cols == -1 in the caller) are redirected to a dummy output
block at index ``n_out_blocks`` which is sliced off afterwards; they sort
last, preserving the contiguous-runs invariant.  When the caller passes
the packed list's valid-tile count (``nnz``, a scalar-prefetch value), the
kernel additionally skips the VPU/MXU work of every padding step — the
pow2-bucket padding then costs only its (pipelined) DMAs, so the walk is
effectively over the active-tile list alone.

* plus_mul  — the (1,B)x(B,B) product runs on the MXU.
* min_plus  — broadcast-add + min-reduce on the VPU (no MXU analogue of a
  tropical matmul; B=128 keeps lanes full).

VMEM footprint per step: tile (B*B*4) + x block (B*4) + y block (B*4)
≈ 64 KiB at B=128 — far under the ~16 MiB/core VMEM budget, so the implicit
pipeline can run multi-buffered with room to spare.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the MXU multiplies f32 in bf16 passes unless asked for full precision
_F32 = jax.lax.Precision.HIGHEST


def _spmv_body(t, cols, tile_ref, x_ref, y_ref, *, sr_name: str, zero: float):
    first = jnp.logical_or(t == 0, cols[t] != cols[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _():
        y_ref[...] = jnp.full_like(y_ref, zero)

    xb = x_ref[...]  # (1, B)
    w = tile_ref[...]  # (B, B)
    if sr_name == "plus_mul":
        # 2-D (1, B) @ (B, B): Mosaic lowers no 1-D dot
        y_ref[...] += jnp.dot(xb, w, precision=_F32,
                              preferred_element_type=jnp.float32)
    else:  # min_plus
        part = jnp.min(xb.reshape(-1, 1) + w, axis=0, keepdims=True)
        y_ref[...] = jnp.minimum(y_ref[...], part)


def _spmv_kernel(rows, cols, tile_ref, x_ref, y_ref, *, sr_name: str,
                 zero: float):
    _spmv_body(pl.program_id(0), cols, tile_ref, x_ref, y_ref,
               sr_name=sr_name, zero=zero)


def _spmv_kernel_nnz(rows, cols, nnz, tile_ref, x_ref, y_ref, *,
                     sr_name: str, zero: float):
    t = pl.program_id(0)

    # packed active-tile walk: steps past the valid count are pure padding
    # (pow2 bucket) — skip their compute entirely; their (clamped) DMAs
    # overlap the pipeline and their dummy output block is sliced off.
    @pl.when(t < nnz[0])
    def _():
        _spmv_body(t, cols, tile_ref, x_ref, y_ref, sr_name=sr_name,
                   zero=zero)


@functools.partial(
    jax.jit, static_argnames=("sr_name", "n_out_blocks", "interpret")
)
def spmv_blocked_pallas(
    tiles: jax.Array,  # (T, B, B) float32, padding tiles filled with sr zero
    rows: jax.Array,  # (T,) int32, -1 = padding
    cols: jax.Array,  # (T,) int32, sorted ascending among valid, -1 = padding
    x: jax.Array,  # (nvb * B,) float32
    *,
    sr_name: str,
    n_out_blocks: int,
    interpret: bool = False,
    nnz: jax.Array | None = None,  # () or (1,) int32 valid-tile count
) -> jax.Array:
    T, B, _ = tiles.shape
    nvb = x.shape[0] // B
    zero = 0.0 if sr_name == "plus_mul" else float(jnp.inf)

    rows_c = jnp.maximum(rows, 0)  # padding reads block 0, contributes zero
    cols_c = jnp.where(cols < 0, n_out_blocks, cols)  # padding -> dummy block

    # x and y move as (1, B) rows of a (blocks, 1, B) view: the block's
    # last two dims then equal the array's, which the TPU's (8, 128)
    # block rule accepts for any row index
    if nnz is None:
        n_prefetch = 2
        prefetch = (rows_c, cols_c)
        kernel = functools.partial(_spmv_kernel, sr_name=sr_name, zero=zero)
        tile_spec = pl.BlockSpec((None, B, B), lambda t, r, c: (t, 0, 0))
        x_spec = pl.BlockSpec((None, 1, B), lambda t, r, c: (r[t], 0, 0))
        out_spec = pl.BlockSpec((None, 1, B), lambda t, r, c: (c[t], 0, 0))
    else:
        n_prefetch = 3
        prefetch = (rows_c, cols_c, jnp.asarray(nnz, jnp.int32).reshape(1))
        kernel = functools.partial(_spmv_kernel_nnz, sr_name=sr_name,
                                   zero=zero)
        tile_spec = pl.BlockSpec((None, B, B), lambda t, r, c, n: (t, 0, 0))
        x_spec = pl.BlockSpec((None, 1, B), lambda t, r, c, n: (r[t], 0, 0))
        out_spec = pl.BlockSpec((None, 1, B),
                                lambda t, r, c, n: (c[t], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(T,),
        in_specs=[tile_spec, x_spec],
        out_specs=out_spec,
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out_blocks + 1, 1, B),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential grid: accumulation
        ),
    )(*prefetch, tiles, x.reshape(nvb, 1, B))
    y = y[:n_out_blocks, 0]
    # blocks never touched by a valid tile hold uninitialized memory
    if nnz is None:
        touched = jnp.zeros((n_out_blocks + 1,), jnp.bool_).at[cols_c].set(True)
    else:
        valid = jnp.arange(T) < jnp.asarray(nnz, jnp.int32).reshape(())
        touched = jnp.zeros((n_out_blocks + 1,), jnp.bool_).at[
            jnp.where(valid, cols_c, n_out_blocks)
        ].set(True)
    return jnp.where(touched[:n_out_blocks, None], y, zero).reshape(-1)
