"""Per-kernel interpret-mode allclose sweeps against the pure-jnp oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.semiring import MIN_PLUS, PLUS_MUL
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.semiring_spmm.ops import spmv_blocked

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# semiring_spmm
# ---------------------------------------------------------------------------

def _random_block_structure(B, nvb, T_valid, T_pad):
    cols = np.sort(RNG.integers(0, nvb, T_valid)).astype(np.int32)
    rows = RNG.integers(0, nvb, T_valid).astype(np.int32)
    rows = np.concatenate([rows, np.full(T_pad, -1, np.int32)])
    cols = np.concatenate([cols, np.full(T_pad, -1, np.int32)])
    return rows, cols


@pytest.mark.parametrize("B", [8, 16, 128])
@pytest.mark.parametrize("sr", [MIN_PLUS, PLUS_MUL], ids=lambda s: s.name)
@pytest.mark.parametrize("density", [0.05, 0.5])
def test_spmv_kernel_vs_ref(B, sr, density):
    nvb = int(RNG.integers(2, 6))
    T_valid = int(RNG.integers(1, 14))
    rows, cols = _random_block_structure(B, nvb, T_valid, int(RNG.integers(0, 4)))
    T = len(rows)
    tiles = np.full((T, B, B), sr.zero, np.float32)
    for t in range(T_valid):
        m = RNG.random((B, B)) < density
        tiles[t][m] = RNG.random(int(m.sum()))
    x = RNG.random(nvb * B).astype(np.float32)
    args = (jnp.asarray(tiles), jnp.asarray(rows), jnp.asarray(cols),
            jnp.asarray(x), sr)
    yk = np.asarray(spmv_blocked(*args, use_pallas=True, interpret=True))
    yr = np.asarray(spmv_blocked(*args, use_pallas=False))
    inf_k, inf_r = ~np.isfinite(yk), ~np.isfinite(yr)
    assert np.array_equal(inf_k, inf_r)
    np.testing.assert_allclose(yk[~inf_k], yr[~inf_r], rtol=2e-5, atol=2e-5)


def test_spmv_empty_structure():
    """All-padding tile list -> all-zero (semiring) output."""
    B, nvb = 8, 3
    rows = np.full(4, -1, np.int32)
    cols = np.full(4, -1, np.int32)
    tiles = np.full((4, B, B), MIN_PLUS.zero, np.float32)
    x = np.ones(nvb * B, np.float32)
    y = spmv_blocked(jnp.asarray(tiles), jnp.asarray(rows), jnp.asarray(cols),
                     jnp.asarray(x), MIN_PLUS, use_pallas=True, interpret=True)
    assert np.all(np.isinf(np.asarray(y)))


@pytest.mark.parametrize("sr", [MIN_PLUS, PLUS_MUL], ids=lambda s: s.name)
@pytest.mark.parametrize("nnz", [0, 3, 7])
def test_spmv_packed_walk_nnz(sr, nnz):
    """Block-sparse packed list (interpret mode): the Pallas walk with the
    ``nnz`` padding-skip == the walk without it == the jnp segment-reduce
    oracle, for every semiring and valid-tile count (0 = fully padded)."""
    B, nvb, T = 8, 4, 7
    cols = np.sort(RNG.integers(0, nvb, nnz)).astype(np.int32)
    rows = RNG.integers(0, nvb, nnz).astype(np.int32)
    rows = np.concatenate([rows, np.full(T - nnz, -1, np.int32)])
    cols = np.concatenate([cols, np.full(T - nnz, -1, np.int32)])
    tiles = np.full((T, B, B), sr.zero, np.float32)
    tiles[:nnz] = RNG.random((nnz, B, B))
    x = RNG.random(nvb * B).astype(np.float32)
    args = (jnp.asarray(tiles), jnp.asarray(rows), jnp.asarray(cols),
            jnp.asarray(x), sr)
    y_ref = np.asarray(spmv_blocked(*args, use_pallas=False))
    y_pal = np.asarray(spmv_blocked(*args, use_pallas=True, interpret=True))
    y_nnz = np.asarray(spmv_blocked(
        *args, use_pallas=True, interpret=True,
        nnz=jnp.asarray(nnz, jnp.int32),
    ))
    assert np.array_equal(y_pal, y_nnz)
    fin = np.isfinite(y_ref)
    assert np.array_equal(np.isfinite(y_nnz), fin)
    np.testing.assert_allclose(y_nnz[fin], y_ref[fin], rtol=2e-5, atol=2e-5)


def test_spmv_packed_subset_matches_dense_walk():
    """Dropping all-zero tiles from the walked list must not change the
    output (the sparse layout's core claim, at kernel level, bitwise)."""
    B, nvb = 8, 4
    T = 10
    cols = np.sort(RNG.integers(0, nvb, T)).astype(np.int32)
    rows = RNG.integers(0, nvb, T).astype(np.int32)
    for sr in (MIN_PLUS, PLUS_MUL):
        tiles = np.full((T, B, B), sr.zero, np.float32)
        live = RNG.random(T) < 0.5
        for t in np.nonzero(live)[0]:
            tiles[t] = RNG.random((B, B))
        x = RNG.random(nvb * B).astype(np.float32)
        k = int(live.sum())
        packed = np.full((T, B, B), sr.zero, np.float32)
        prows = np.full(T, -1, np.int32)
        pcols = np.full(T, -1, np.int32)
        packed[:k] = tiles[live]
        prows[:k] = rows[live]
        pcols[:k] = cols[live]
        for use_pallas in (False, True):
            kw = dict(use_pallas=use_pallas, n_out_blocks=nvb)
            if use_pallas:
                kw["interpret"] = True
            y_dense = np.asarray(spmv_blocked(
                jnp.asarray(tiles), jnp.asarray(rows), jnp.asarray(cols),
                jnp.asarray(x), sr, **kw))
            y_packed = np.asarray(spmv_blocked(
                jnp.asarray(packed), jnp.asarray(prows), jnp.asarray(pcols),
                jnp.asarray(x), sr,
                nnz=jnp.asarray(k, jnp.int32) if use_pallas else None, **kw))
            assert np.array_equal(y_dense, y_packed), (sr.name, use_pallas)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

FLASH_SWEEP = [
    # (B, Sq, Skv, H, K, d, causal, window, q_offset, dtype)
    (2, 64, 64, 4, 2, 32, True, 0, 0, jnp.float32),
    (1, 128, 128, 8, 8, 64, True, 0, 0, jnp.float32),
    (2, 32, 32, 4, 1, 16, False, 0, 0, jnp.float32),
    (1, 64, 64, 2, 2, 32, True, 24, 0, jnp.float32),
    (1, 32, 96, 4, 2, 32, True, 0, 64, jnp.float32),
    (1, 64, 64, 4, 2, 32, True, 0, 0, jnp.bfloat16),
    (1, 128, 128, 2, 2, 128, True, 0, 0, jnp.float32),
]


@pytest.mark.parametrize("case", FLASH_SWEEP,
                         ids=[f"case{i}" for i in range(len(FLASH_SWEEP))])
def test_flash_attention_vs_ref(case):
    B, Sq, Skv, H, K, d, causal, window, qoff, dt = case
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, d)), dt)
    k = jnp.asarray(RNG.normal(size=(B, Skv, K, d)), dt)
    v = jnp.asarray(RNG.normal(size=(B, Skv, K, d)), dt)
    kw = dict(causal=causal, window=window, q_offset=qoff)
    o_ref = flash_attention(q, k, v, use_pallas=False, **kw)
    o_pal = flash_attention(q, k, v, use_pallas=True, interpret=True,
                            bq=32, bk=32, **kw)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_matches_model_chunked_path():
    """The model's chunked-softmax path is the production jnp attention; it
    must agree with the flash oracle."""
    from repro.models.attention import chunked_attention

    B, S, H, K, d = 2, 96, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(B, S, H, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, K, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, K, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    o_chunk = chunked_attention(q, k, v, q_positions=pos, kv_positions=pos,
                                causal=True, chunk=32)
    o_ref = flash_attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(o_chunk), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

DECODE_SWEEP = [
    (2, 128, 4, 2, 32, 0, jnp.float32),
    (1, 256, 8, 1, 64, 0, jnp.float32),
    (3, 128, 4, 4, 32, 48, jnp.float32),
    (2, 128, 8, 2, 64, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DECODE_SWEEP,
                         ids=[f"case{i}" for i in range(len(DECODE_SWEEP))])
def test_decode_attention_vs_ref(case):
    B, S, H, K, d, window, dt = case
    q = jnp.asarray(RNG.normal(size=(B, H, d)), dt)
    k = jnp.asarray(RNG.normal(size=(B, S, K, d)), dt)
    v = jnp.asarray(RNG.normal(size=(B, S, K, d)), dt)
    lens = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    o_ref = decode_attention(q, k, v, lens, window=window, use_pallas=False)
    o_pal = decode_attention(q, k, v, lens, window=window, use_pallas=True,
                             interpret=True, bk=64)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_fused_kernel_refuses_sub_lane_blocks():
    """Compiled (not interpreted), the fused kernel needs lane-width
    blocks: a narrower B raises a clear error instead of a Mosaic one."""
    from repro.kernels.semiring_superstep.kernel import fused_step_pallas

    P_, T_, nvb, B = 2, 3, 2, 64
    tiles = jnp.zeros((P_, T_, B, B), jnp.float32)
    idx = jnp.zeros((P_, T_), jnp.int32)
    x = jnp.zeros((P_, nvb, B), jnp.float32)
    with pytest.raises(ValueError, match="lane width"):
        fused_step_pallas(tiles, idx, idx, x, x, x, x, interpret=False)
