"""Forced-parallel CPU lane: the mesh + fused-kernel paths exercised
IN-PROCESS on forced host devices.

The other mesh tests isolate ``XLA_FLAGS=--xla_force_host_platform_
device_count`` in subprocesses so the main pytest process keeps its
single real CPU device.  CI additionally runs this module in a dedicated
lane that sets the flag for the WHOLE process (see
``.github/workflows/ci.yml``) — there the skips below turn into real
runs and shard_map, the ring/ring-rs exchanges, and the fused superstep
kernel execute without a subprocess boundary around every assertion.
Locally the module is skipped unless the flag is already set.
"""
import functools
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

needs_devices = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (set "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)


@pytest.fixture(scope="module")
def mesh_env(tiny_collection, tiny_partitioned):
    from repro.core.blocked import build_blocked

    from tests.conftest import TINY
    from tests.test_sparse_blocked import _banded

    tmpl, assign, _, _ = tiny_partitioned
    # the model axis must match the partition count; repartition the tiny
    # template to 4 so the (1, 4) mesh maps one partition per device
    from repro.core.partition import partition_graph

    assign4 = partition_graph(tmpl, 4, seed=TINY.seed)
    bg = build_blocked(tmpl, assign4, TINY.block_size)
    I = len(tiny_collection)
    w = np.stack([tiny_collection.edge_values(t, "latency")
                  for t in range(I)])
    wb, live = _banded(bg, tmpl, w)
    mesh = jax.make_mesh((1, 4), ("data", "model"))
    return tmpl, bg, wb, mesh


@needs_devices
def test_device_count_lane_contract():
    """The CI lane's contract: when the forced-device flag is set the
    process really does see the devices (guards against the lane
    silently degrading to single-device runs)."""
    if "--xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        assert jax.device_count() >= 4


@needs_devices
def test_mesh_fused_matches_stacked_oracle(mesh_env):
    """shard_map + fused kernel (interpret) in-process: bitwise vs the
    stacked jnp oracle for min-plus, both layouts."""
    from repro.core.engine import (TemporalEngine, min_plus_program,
                                   source_init)

    tmpl, bg, wb, mesh = mesh_env
    prog = min_plus_program("sssp", init=source_init(0), max_supersteps=16)
    w2 = wb[:2]
    ref = TemporalEngine(bg).run(prog, w2, pattern="sequential")
    for lay in ({}, dict(layout="sparse")):
        eng = TemporalEngine(bg, mesh=mesh, use_pallas="fused", **lay)
        got = eng.run(prog, w2, pattern="sequential")
        assert np.array_equal(ref.values, got.values), lay
        assert np.array_equal(ref.stats["supersteps"],
                              got.stats["supersteps"]), lay


@needs_devices
@pytest.mark.parametrize("backend", ["dense", "ring", "ring-rs"])
def test_mesh_comm_backends_in_process(mesh_env, backend):
    """All mesh comm backends agree bitwise on min-plus in-process,
    composed with the fused kernel."""
    from repro.core.engine import (TemporalEngine, min_plus_program,
                                   source_init)

    tmpl, bg, wb, mesh = mesh_env
    prog = min_plus_program("sssp", init=source_init(0), max_supersteps=16)
    w2 = wb[:2]
    ref = TemporalEngine(bg).run(prog, w2, pattern="independent")
    eng = TemporalEngine(bg, mesh=mesh, comm=backend, use_pallas="fused")
    got = eng.run(prog, w2, pattern="independent")
    assert np.array_equal(ref.values, got.values)


@needs_devices
def test_mesh_device_fill_holds_each_shards_partitions(mesh_env):
    """The per-shard ``shard_map`` fill on {data 1, model 4}: every chip
    holds the host fill's slice of its own partition, bitwise for an INF
    zero (within 1e-6 for 0.0, whose shared slots sum in the scatter's
    order); a streamed SSSP on the mesh equals the stacked run bitwise."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.engine import (TemporalEngine, min_plus_program,
                                   source_init)
    from repro.gofs.prefetch import StagedChunk

    tmpl, bg, wb, mesh = mesh_env
    eng = TemporalEngine(bg, mesh=mesh)
    w2 = np.ascontiguousarray(wb[:2], np.float32)
    for zero in (np.inf, 0.0):
        tiles, btiles = eng._device_fill(
            StagedChunk(0, 2, edges=w2, zero=zero, bg=bg))
        for got, ref in ((tiles, bg.fill_local_batch(w2, zero=zero)),
                         (btiles, bg.fill_boundary_batch(w2, zero=zero))):
            assert got.sharding.is_equivalent_to(
                NamedSharding(mesh, P(None, "model")), got.ndim)
            assert len(got.addressable_shards) == 4
            for sh in got.addressable_shards:
                assert sh.data.shape[1] == bg.n_parts // 4
                if zero == np.inf:
                    assert np.array_equal(np.asarray(sh.data), ref[sh.index])
                else:
                    np.testing.assert_allclose(np.asarray(sh.data),
                                               ref[sh.index], atol=1e-6)
    prog = min_plus_program("sssp", init=source_init(0), max_supersteps=16)
    ref = TemporalEngine(bg).run(prog, wb[:4], pattern="sequential")
    streamed = TemporalEngine(bg, mesh=mesh, staging="async",
                              chunk_instances=2)
    got = streamed.run(prog, wb[:4], pattern="sequential")
    assert np.array_equal(ref.values, got.values)
    assert np.array_equal(ref.stats["supersteps"], got.stats["supersteps"])
    assert streamed.device_fills == 2


@needs_devices
def test_ring_rs_combine_parity_in_process():
    """RingExchange rs_ag vs circulate vs dense, raw combine_boundary
    under shard_map: min-plus bitwise, ragged and tiny buffer widths."""
    import jax.numpy as jnp
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.comm import make_comm
    from repro.core.semiring import MIN_PLUS

    mesh = jax.make_mesh((4,), ("model",))
    rng = np.random.default_rng(3)
    for nb in (12, 13, 1, 7):
        buf = rng.normal(size=(8, nb)).astype(np.float32)
        buf[rng.random(buf.shape) < 0.3] = np.inf
        want = functools.reduce(MIN_PLUS.add,
                                [jnp.asarray(buf[i]) for i in range(8)])
        for name in ("dense", "ring", "ring-rs"):
            comm = make_comm(name, mesh=mesh, model_axes=("model",))
            f = shard_map(lambda b, c=comm: c.combine_boundary(b, MIN_PLUS),
                          mesh=mesh, in_specs=P("model", None),
                          out_specs=P(), check_rep=False)
            got = jax.jit(f)(jnp.asarray(buf))
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                (name, nb)
