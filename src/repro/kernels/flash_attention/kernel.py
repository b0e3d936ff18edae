"""Pallas TPU flash attention (prefill): tiled online-softmax.

Grid = (batch*heads, q_blocks, kv_blocks); the kv dimension is innermost and
sequential, so the fp32 accumulators (acc, m, l) live in VMEM scratch and
persist across kv steps of one q block.  Causal + sliding-window masking is
applied from absolute positions; fully-masked kv blocks are skipped via
``pl.when`` (upper-triangle blocks cost nothing but the grid step).

Block sizes default to (128, 128): q tile (128, d) + k/v tiles (128, d) +
(128,128) logits in fp32 ≈ 3·128·d·4 + 64 KiB — comfortably inside VMEM for
d ≤ 256, MXU-aligned on both matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, bq: int, bk: int, causal: bool, window: int, q_offset: int, scale: float,
):
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos0 = i * bq + q_offset
    # skip kv blocks entirely above the causal diagonal / below the window
    needed = True
    if causal:
        needed = j * bk <= qpos0 + bq - 1
        if window:
            needed = jnp.logical_and(needed, (j + 1) * bk - 1 > qpos0 - window)

    @pl.when(needed)
    def _():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]  # (bk, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        if causal:
            qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = kpos <= qpos
            if window:
                ok = jnp.logical_and(ok, kpos > qpos - window)
            logits = jnp.where(ok, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (BH, Sq, d)
    k: jax.Array,  # (BH, Skv, d)
    v: jax.Array,  # (BH, Skv, d)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = True,
) -> jax.Array:
    BH, Sq, d = q.shape
    Skv = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    scale = 1.0 / float(d) ** 0.5
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        q_offset=q_offset, scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, Sq // bq, Skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
