"""Pallas TPU decode attention: one query token against a long KV cache.

Decode is memory-bound — the entire cost is streaming the KV cache through
VMEM once.  Grid = (B*K, kv_blocks); the (G, d) query tile for one KV head
group stays resident while (bk, d) K/V tiles stream; online softmax
accumulates in VMEM scratch.  GQA folds the G = H/K queries of a KV head
into the left matmul dimension so each KV byte is used G times (arithmetic
intensity ~G instead of ~1 — the GQA decode win).

For a 32k cache at bk=512 that is 64 sequential steps per (B,K) — long
enough for the implicit DMA pipeline to hide HBM latency.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(
    len_ref,  # scalar prefetch: (B,) lengths
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, bk: int, G: int, n_b: int, window: int, scale: float,
):
    bkh = pl.program_id(0)  # fused (batch, kv-head) index
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    # lengths are pre-expanded to (B*K,) by the wrapper
    length = len_ref[bkh]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    lo = j * bk
    needed = lo < length
    if window:
        needed = jnp.logical_and(needed, (j + 1) * bk - 1 > length - 1 - window)

    @pl.when(needed)
    def _():
        q = q_ref[0]  # (G, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]  # (bk, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (G, bk)
        pos = lo + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        ok = pos < length
        if window:
            ok = jnp.logical_and(ok, pos > length - 1 - window)
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

    @pl.when(j == nj - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "bk", "interpret")
)
def decode_attention_pallas(
    q: jax.Array,  # (BK, G, d)
    k: jax.Array,  # (BK, S, d)
    v: jax.Array,  # (BK, S, d)
    lengths: jax.Array,  # (BK,) int32
    *,
    window: int = 0,
    bk: int = 512,
    interpret: bool = True,
) -> jax.Array:
    BK, G, d = q.shape
    S = k.shape[1]
    bk = min(bk, S)
    assert S % bk == 0
    scale = 1.0 / float(d) ** 0.5
    kernel = functools.partial(
        _decode_kernel, bk=bk, G=G, n_b=BK, window=window, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BK, S // bk),
        in_specs=[
            pl.BlockSpec((1, G, d), lambda b, j, L: (b, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, L: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, L: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, d), lambda b, j, L: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BK, G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
