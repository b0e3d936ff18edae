"""GQA attention: chunked-softmax jnp path (memory-safe at 32k+), KV cache,
sliding-window / prefix-LM / cross-attention masking, RoPE.

The chunked path is mathematically identical to flash attention (online
softmax over KV chunks) and doubles as the large-shape oracle for the Pallas
kernels in ``repro.kernels``; ``repro.kernels.flash_attention.ops`` dispatches
to the Pallas kernel on TPU when ``use_pallas`` is set.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ParamDef, apply_rope

Params = Any

NEG_INF = -1e30


def attn_schema(cfg, cross: bool = False) -> Dict[str, ParamDef]:
    d, h = cfg.d_model, cfg.head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    if cross:
        nkv = nh  # whisper cross-attention is MHA
    return {
        "wq": ParamDef((d, nh * h), ("embed", "heads")),
        "wk": ParamDef((d, nkv * h), ("embed", "kv_heads")),
        "wv": ParamDef((d, nkv * h), ("embed", "kv_heads")),
        "wo": ParamDef((nh * h, d), ("heads", "embed")),
    }


def _split_heads(x: jax.Array, n: int, h: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, h))


def chunked_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, K, hd)
    v: jax.Array,  # (B, Skv, K, hd)
    *,
    q_positions: jax.Array,  # (B, Sq) absolute positions
    kv_positions: jax.Array,  # (B, Skv) absolute positions (invalid -> very negative)
    kv_len: Optional[jax.Array] = None,  # (B,) valid cache length, None = all
    causal: bool = True,
    window: Optional[jax.Array] = None,  # scalar; None/0 = unbounded
    prefix_len: int | jax.Array = 0,  # bidirectional prefix (prefix-LM / meta tokens)
    softcap: float = 0.0,
    chunk: int = 1024,
    return_stats: bool = False,  # return unnormalized (acc, m, l) for
    #                               cross-device softmax combination
) -> jax.Array:
    """Online-softmax attention over KV chunks.  Returns (B, Sq, H, hd),
    or ((B,K,G,Sq,hd) acc, (B,K,G,Sq) m, (B,K,G,Sq) l) when return_stats."""
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    chunk = min(chunk, Skv)
    # pad Skv to a multiple of chunk with masked slots
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)), constant_values=-(2**30))
    n_chunks = (Skv + pad) // chunk

    qg = _split_heads(q.reshape(B, Sq, H * hd), K, G * hd).reshape(B, Sq, K, G, hd)
    qg = qg.transpose(0, 2, 3, 1, 4)  # (B, K, G, Sq, hd)
    kc = k.transpose(0, 2, 1, 3).reshape(B, K, n_chunks, chunk, hd)
    vc = v.transpose(0, 2, 1, 3).reshape(B, K, n_chunks, chunk, hd)
    kpc = kv_positions.reshape(B, n_chunks, chunk)

    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qpos = q_positions[:, None, None, :, None]  # (B,1,1,Sq,1)

    def body(carry, idx):
        acc, m, l = carry
        kb = jax.lax.dynamic_index_in_dim(kc, idx, 2, keepdims=False)  # (B,K,chunk,hd)
        vb = jax.lax.dynamic_index_in_dim(vc, idx, 2, keepdims=False)
        kp = jax.lax.dynamic_index_in_dim(kpc, idx, 1, keepdims=False)  # (B,chunk)
        logits = jnp.einsum(
            "bkgsh,bkch->bkgsc", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        kpb = kp[:, None, None, None, :]  # (B,1,1,1,chunk)
        ok = kpb > -(2**29)  # padded / unwritten slots masked out
        if kv_len is not None:
            slot = idx * chunk + jnp.arange(chunk)
            ok &= slot[None, None, None, None, :] < kv_len[:, None, None, None, None]
        if causal:
            allowed = kpb <= qpos
            pl = prefix_len
            both_prefix = (kpb < pl) & (qpos < pl)
            allowed |= both_prefix
            if window is not None:
                in_window = kpb > qpos - window
                allowed &= in_window | (kpb < pl)  # prefix (meta) always visible
            ok &= allowed
        logits = jnp.where(ok, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgsc,bkch->bkgsh", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, K, G, Sq, hd), jnp.float32)
    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(n_chunks))
    if return_stats:
        return acc, m, l
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def init_kv_cache(cfg, batch: int, max_len: int, n_layers: int, dtype=jnp.bfloat16):
    """Stacked (layers-leading) KV cache for scan-over-layers decode."""
    K, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((n_layers, batch, max_len, K, hd), dtype),
        "v": jnp.zeros((n_layers, batch, max_len, K, hd), dtype),
        # absolute position stored per slot; very-negative = unwritten
        "pos": jnp.full((n_layers, batch, max_len), -(2**30), jnp.int32),
        "len": jnp.zeros((n_layers, batch), jnp.int32),
    }


def cache_update(
    layer_cache: Dict[str, jax.Array],
    k_new: jax.Array,  # (B, S_new, K, hd)
    v_new: jax.Array,
    positions: jax.Array,  # (B, S_new)
    start: jax.Array,  # (B,) write offset (== current length)
) -> Dict[str, jax.Array]:
    """Write S_new entries at ``start`` (sequential layout, no ring)."""

    def upd_one(ck, cv, cp, cl, kn, vn, pos, st):
        ck = jax.lax.dynamic_update_slice(ck, kn.astype(ck.dtype), (st, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, vn.astype(cv.dtype), (st, 0, 0))
        cp = jax.lax.dynamic_update_slice(cp, pos, (st,))
        return ck, cv, cp, cl + kn.shape[0]

    k, v, p, l = jax.vmap(upd_one)(
        layer_cache["k"], layer_cache["v"], layer_cache["pos"], layer_cache["len"],
        k_new, v_new, positions, start,
    )
    return {"k": k, "v": v, "pos": p, "len": l}


def flash_decode_tp(
    q: jax.Array,  # (B, 1, H, hd) — replicated over the TP axis
    cache: Dict[str, jax.Array],  # k/v (B,S,K,hd) seq-sharded, pos (B,S), len (B,)
    k_new: jax.Array,  # (B, 1, K, hd) this step's K (cache write)
    v_new: jax.Array,  # (B, 1, K, hd)
    q_positions: jax.Array,  # (B, 1)
    runtime,
    *,
    window: Optional[jax.Array],
    prefix_len: int | jax.Array,
    softcap: float,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Distributed flash decoding with a FUSED shard-local cache write.

    Each TP peer (a) writes the new token's K/V into its sequence shard iff
    the write position falls inside it, then (b) attends over its LOCAL KV
    shard; the partial online-softmax stats (acc, m, l) are combined with an
    O(B·H·hd) psum.  Neither the cache write nor the read ever all-gathers
    the O(B·S·K·hd) cache (beyond-paper optimization, EXPERIMENTS.md §Perf —
    replaces XLA's auto-sharding gathers on both paths).

    kv_pos carries ABSOLUTE positions, so causal/window/prefix masking is
    local-shard-correct by construction (padding slots are very negative).
    Returns (out (B,1,H,hd), updated cache dict).
    """
    from jax.sharding import PartitionSpec as P

    tp = runtime.tp_axis
    B = q.shape[0]
    ndp = 1
    for a in runtime.dp_axes:
        ndp *= runtime.axis_size(a)
    bspec = runtime.batch_axes if (B % max(ndp, 1) == 0 and B >= ndp) else None

    def local_fn(q_l, k_l, v_l, pos_l, len_l, kn_l, vn_l, qpos_l):
        S_loc = k_l.shape[1]
        start = jax.lax.axis_index(tp) * S_loc
        rel = len_l - start  # (Bl,) local write offset

        def write_one(buf, new, r):
            upd = jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (jnp.clip(r, 0, S_loc - 1), 0, 0)
            )
            return jnp.where(jnp.logical_and(r >= 0, r < S_loc), upd, buf)

        k_l = jax.vmap(write_one)(k_l, kn_l, rel)
        v_l = jax.vmap(write_one)(v_l, vn_l, rel)

        def write_pos(pbuf, r, qp):
            upd = jax.lax.dynamic_update_slice(
                pbuf, qp, (jnp.clip(r, 0, S_loc - 1),)
            )
            return jnp.where(jnp.logical_and(r >= 0, r < S_loc), upd, pbuf)

        pos_l = jax.vmap(write_pos)(pos_l, rel, qpos_l)

        acc, m, l = chunked_attention(
            q_l, k_l.astype(q_l.dtype), v_l.astype(q_l.dtype),
            q_positions=qpos_l, kv_positions=pos_l,
            causal=True, window=window, prefix_len=prefix_len,
            softcap=softcap, return_stats=True,
        )
        m_g = jax.lax.pmax(m, tp)
        corr = jnp.exp(m - m_g)
        num = jax.lax.psum(acc * corr[..., None], tp)
        den = jax.lax.psum(l * corr, tp)
        out = num / jnp.maximum(den, 1e-30)[..., None]
        Bl, K, G, Sq, hd = out.shape
        out = out.transpose(0, 3, 1, 2, 4).reshape(Bl, Sq, K * G, hd)
        return out.astype(q_l.dtype), k_l, v_l, pos_l

    kv_spec = P(bspec, tp, None, None)
    fn = jax.shard_map(
        local_fn,
        mesh=runtime.mesh,
        in_specs=(
            P(bspec, None, None, None),
            kv_spec, kv_spec, P(bspec, tp), P(bspec),
            P(bspec, None, None, None), P(bspec, None, None, None),
            P(bspec, None),
        ),
        out_specs=(P(bspec, None, None, None), kv_spec, kv_spec, P(bspec, tp)),
        check_vma=False,
    )
    out, k_upd, v_upd, pos_upd = fn(
        q, cache["k"], cache["v"], cache["pos"], cache["len"],
        k_new, v_new, q_positions,
    )
    new_cache = {
        "k": k_upd, "v": v_upd, "pos": pos_upd,
        "len": cache["len"] + k_new.shape[1],
    }
    return out, new_cache


def apply_attention(
    p: Params,
    x: jax.Array,  # (B, Sq, d)
    cfg,
    *,
    positions: jax.Array,  # (B, Sq)
    causal: bool = True,
    window: Optional[jax.Array] = None,
    prefix_len: int | jax.Array = 0,
    softcap: float = 0.0,
    layer_cache: Optional[Dict[str, jax.Array]] = None,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # encoder K,V (B,Se,K,hd)
    rope: bool = True,
    runtime=None,  # enables the TP flash-decode path when set
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- or cross-attention with optional KV cache read/write.

    Returns (output (B,Sq,d), updated layer cache or None).
    """
    B, Sq, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    dt = x.dtype

    q = _split_heads(x @ p["wq"].astype(dt), H, hd)
    if rope:
        q = apply_rope(q, positions, cfg)

    new_cache = None
    if cross_kv is not None:
        k, v = cross_kv
        kv_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (B, k.shape[1]))
        out = chunked_attention(
            q, k, v, q_positions=positions, kv_positions=kv_pos,
            causal=False, softcap=softcap,
        )
    else:
        K = cfg.num_kv_heads
        k = _split_heads(x @ p["wk"].astype(dt), K, hd)
        v = _split_heads(x @ p["wv"].astype(dt), K, hd)
        if rope:
            k = apply_rope(k, positions, cfg)
        if layer_cache is not None:
            use_flash_tp = (
                runtime is not None and runtime.mesh is not None
                and getattr(runtime, "flash_decode", False)
                and Sq == 1 and causal
                and layer_cache["k"].shape[1]
                % runtime.axis_size(runtime.tp_axis) == 0
            )
            if use_flash_tp:
                out, new_cache = flash_decode_tp(
                    q, layer_cache, k, v, positions, runtime,
                    window=window, prefix_len=prefix_len, softcap=softcap,
                )
            else:
                new_cache = cache_update(
                    layer_cache, k, v, positions, layer_cache["len"]
                )
                kf, vf = new_cache["k"].astype(dt), new_cache["v"].astype(dt)
                out = chunked_attention(
                    q, kf, vf,
                    q_positions=positions, kv_positions=new_cache["pos"],
                    causal=causal, window=window, prefix_len=prefix_len, softcap=softcap,
                )
        else:
            kv_pos = jnp.broadcast_to(positions[:, :1] + jnp.arange(Sq)[None], (B, Sq))
            kv_pos = positions  # self-attention over the same tokens
            out = chunked_attention(
                q, k, v, q_positions=positions, kv_positions=kv_pos,
                causal=causal, window=window, prefix_len=prefix_len, softcap=softcap,
            )
    y = out.reshape(B, Sq, H * hd) @ p["wo"].astype(dt)
    return y, new_cache


def make_cross_kv(p: Params, enc_out: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Precompute encoder K/V once for all decode steps (whisper)."""
    B, Se, _ = enc_out.shape
    H, hd = cfg.num_heads, cfg.head_dim
    dt = enc_out.dtype
    k = _split_heads(enc_out @ p["wk"].astype(dt), H, hd)
    v = _split_heads(enc_out @ p["wv"].astype(dt), H, hd)
    return k, v
