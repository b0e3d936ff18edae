"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set
XLA_FLAGS before anything initializes devices.

Mesh shapes:
  single pod : (16, 16)      axes (data, model)      = 256 chips (v5e pod)
  multi-pod  : (2, 16, 16)   axes (pod, data, model) = 512 chips

``pod`` composes with ``data`` for data parallelism: the only cross-pod
(DCI) collective in steady state is the gradient all-reduce, optionally
int8-compressed (repro.dist.compression).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(data: int = 1, model: int = 1) -> Mesh:
    """The (data, model) mesh over the default backend's first
    ``data * model`` devices: the mesh a ``GopherSession`` builds for a
    plan whose placement the planner chose (on the CPU backend it needs
    XLA_FLAGS=--xla_force_host_platform_device_count >= data*model)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:data * model])


def planner_devices() -> int:
    """How many chips the planner may spread a plan over when the caller
    gives no mesh: every device of a TPU backend driven by one process,
    else 1.  The CPU backend (forced host devices included) and
    multi-process runs stay stacked."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or jax.process_count() > 1:
        return 1
    return len(devs)


def mesh_dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# Below ~64 KiB of actual cut payload the DCI exchange is latency-bound,
# not bandwidth-bound: the all-reduce tree's 2(P-1) short hops beat the
# ring's P-1 full-buffer circulations even across pods.
RING_MIN_CUT_BYTES = 1 << 16

# Above ~1 MiB of cut payload the circulate ring's own (P-1)×NB traffic
# becomes the bandwidth bottleneck; the reduce-scatter + all-gather ring
# ("ring-rs") halves per-device bytes to 2(P-1)/P×NB while keeping every
# transfer neighbor-to-neighbor, and its doubled hop count is noise at
# this payload size.
RING_RS_MIN_CUT_BYTES = 1 << 20


def recommended_comm(
    mesh: Optional[Mesh], model_axes: Tuple[str, ...] = ("model",),
    *,
    boundary_nnz: Optional[int] = None,
) -> str:
    """Default boundary-exchange backend for a placement
    (``repro.core.comm``; full selection table in docs/ARCHITECTURE.md).

    What matters is whether the EXCHANGE axes (``model_axes`` — the axes
    the boundary combine actually runs over) cross DCI, not whether the
    mesh is multi-pod: on the standard production mesh ``pod`` composes
    with ``data`` and the model axis stays intra-pod on ICI, so the dense
    all-reduce remains the right default there.

    ``boundary_nnz`` — the boundary vertices actually published
    (``BlockedGraph.boundary_nnz``), NOT the block-padded buffer length:
    sparse cuts flip the DCI recommendation back to ``dense`` when the
    real payload (``4·nnz`` bytes) is too small for byte volume to beat
    hop latency (``RING_MIN_CUT_BYTES``).

    * no mesh                      -> ``"host"``  (mesh-free CPU cluster:
      combine per-partition buffers on the host, no shard_map at all)
    * ``pod`` among the exchange axes and the cut huge
      (``>= RING_RS_MIN_CUT_BYTES``) -> ``"ring-rs"`` (the exchange is
      bandwidth-bound even over the ring; the reduce-scatter + all-gather
      schedule halves per-device bytes at double the hop count)
    * ``pod`` among the exchange axes and the cut large (or unknown)
      -> ``"ring"`` (the combine crosses DCI; neighbor-to-neighbor hops
      keep each slow link at one buffer/hop)
    * otherwise                    -> ``"dense"`` (ICI all-reduce is
      latency-optimal for the O(cut) boundary buffer)

    >>> recommended_comm(None)
    'host'
    """
    if mesh is None:
        return "host"
    if "pod" in model_axes:
        if (boundary_nnz is not None
                and boundary_nnz * 4 < RING_MIN_CUT_BYTES):
            return "dense"
        if (boundary_nnz is not None
                and boundary_nnz * 4 >= RING_RS_MIN_CUT_BYTES):
            return "ring-rs"
        return "ring"
    return "dense"
