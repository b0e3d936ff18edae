"""The graph kernels compile for a TPU v5e, with no chip attached.

Each case lowers and compiles a Pallas kernel (interpret off) for one chip
of a described ``v5e:2x2`` topology at the chip path's block size: what
the TPU's compiler refuses here — misaligned blocks, a dot it cannot
lower, too much VMEM — it would refuse on the chip.  The topology is
described inside a fixture (never at import: one process at a time may
load the TPU library) and the persistent compilation cache is off around
these compiles (their entries cannot be read back without a chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_graph_config
import repro.core  # noqa: F401  (before the kernels: they import core back)
from repro.kernels.semiring_spmm.kernel import spmv_blocked_pallas
from repro.kernels.semiring_superstep.kernel import fused_step_pallas

B = get_graph_config("small").block_size  # the chip path's block size
T, NVB, P = 64, 16, 4  # tiles per partition, vertex blocks, partitions
SEMIRINGS = ("min_plus", "plus_mul")


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2, with the persistent cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # every staged tile is an argument of the compiled step
    assert mem.argument_size_in_bytes >= T * B * B * 4
    return mem


@pytest.mark.parametrize("packed", [False, True], ids=["template", "nnz"])
@pytest.mark.parametrize("sr", SEMIRINGS)
def test_spmv_compiles(chip, sr, packed):
    def spmv(tiles, rows, cols, x, nnz):
        return spmv_blocked_pallas(tiles, rows, cols, x, sr_name=sr,
                                   n_out_blocks=NVB,
                                   nnz=nnz if packed else None)

    i32 = jnp.int32
    _compile(spmv, _sds(chip, (T, B, B)), _sds(chip, (T,), i32),
             _sds(chip, (T,), i32), _sds(chip, (NVB * B,)),
             _sds(chip, (), i32))


@pytest.mark.parametrize("sr", SEMIRINGS)
def test_fused_compiles(chip, sr):
    def fused(tiles, rows, cols, x):
        return fused_step_pallas(tiles, rows, cols, x, x, x, x, sr_name=sr)

    i32 = jnp.int32
    _compile(fused, _sds(chip, (P, T, B, B)), _sds(chip, (P, T), i32),
             _sds(chip, (P, T), i32), _sds(chip, (P, NVB, B)))


@pytest.mark.parametrize("kernel", ["spmv", "fused"])
def test_source_axis_vmap_compiles(chip, kernel):
    """The query axis GopherService batches on: Q source states over one
    shared tile set, around the per-partition kernel calls the engine
    makes (``core/superstep.py``)."""

    def step(tiles, rows, cols, xq):
        if kernel == "fused":
            def one(x):
                return fused_step_pallas(tiles, rows, cols, x, x, x, x)[0]
        else:
            def one(x):
                return jax.vmap(
                    lambda t, r, c, xp: spmv_blocked_pallas(
                        t, r, c, xp.reshape(-1), sr_name="min_plus",
                        n_out_blocks=NVB))(tiles, rows, cols, x)
        return jax.vmap(one)(xq)

    i32 = jnp.int32
    _compile(step, _sds(chip, (P, T, B, B)), _sds(chip, (P, T), i32),
             _sds(chip, (P, T), i32), _sds(chip, (3, P, NVB, B)))
