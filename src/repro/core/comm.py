"""Pluggable communication backends for the boundary exchange.

The paper's iBSP supersteps hinge on ONE collective: merging boundary
vertex state across partitions (§IV-B).  How that merge moves bytes is a
deployment decision, not an algorithm decision — GoFFish itself targets a
commodity Ethernet cluster (§V) while this repro's production lowering
targets a TPU mesh — so the engine treats it as a pluggable
:class:`CommBackend`:

==================  ========================================================
backend             boundary combine
==================  ========================================================
``DenseAllReduce``  one ``lax.psum``/``pmin`` of the (num_boundary,) buffer
                    over the mesh axis — XLA's tree/ring all-reduce, the
                    default on a single pod (lowest latency per superstep)
``RingExchange``    a ``lax.ppermute`` ring over the mesh axis: each device
                    circulates its semiring-partial buffer in P-1
                    neighbor-to-neighbor hops, folding with the semiring
                    add at every hop.  Every transfer is point-to-point, so
                    on multi-pod DCI (or any bandwidth-asymmetric topology)
                    no hop crosses the slow links more than once per
                    superstep — the regime where a ring beats the
                    all-reduce tree.  Two variants: ``circulate`` (v1)
                    moves the FULL (NB,) buffer on every hop —
                    ``(n-1) * NB`` bytes per device; ``rs_ag`` (v2,
                    backend name ``"ring-rs"``) runs a chunked
                    reduce-scatter followed by an all-gather, moving
                    ``2 * (n-1)/n * NB`` bytes per device — the
                    bandwidth-optimal schedule, ~2x less traffic for
                    large rings at the cost of twice the hop count
``HostGather``      mesh-free: the (P, num_boundary) per-partition buffers
                    are combined on the HOST (numpy semiring fold behind
                    ``jax.pure_callback``), so the same
                    ``SemiringProgram`` runs on CPU clusters with no
                    ``shard_map``/mesh at all — the paper's §V commodity
                    cluster deployment
==================  ========================================================

Exactness contract (enforced by ``tests/test_comm_backends.py``): min-plus
combines are **bitwise identical** across all three backends (min is exact
in floats regardless of order); plus-mul (PageRank) is bitwise in stacked
and host modes (same left-fold association) while the mesh ring
**reassociates** the sum — one differently-ordered float add chain per
device, equal to the all-reduce up to low-order bits.

Backends are frozen dataclasses bound to a placement by :func:`make_comm`
(``axis_name=None`` = stacked: all partitions live on one device's leading
axis; otherwise the leading axis is the per-device shard inside
``shard_map``).  Analytic per-superstep byte costs for each backend live in
``repro.dist.collectives.boundary_exchange_bytes``; measured HLO volumes in
``collective_bytes_by_kind``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import Semiring

COMM_BACKENDS = ("dense", "ring", "ring-rs", "host")

AxisName = Optional[Union[str, Tuple[str, ...]]]


def _axes(axis_name: AxisName) -> Tuple[str, ...]:
    if axis_name is None:
        return ()
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def _stack_fold(buf: jax.Array, sr: Semiring) -> jax.Array:
    """Left-fold the leading (local partition) axis with the semiring add.
    Fixed association 0..P-1 — every backend shares it, which is what makes
    stacked-mode results bitwise comparable across backends."""
    if buf.shape[0] == 1:
        return buf[0]
    return functools.reduce(sr.add, [buf[i] for i in range(buf.shape[0])])


@dataclass(frozen=True)
class CommBackend:
    """Cross-partition combination protocol for one BSP superstep.

    ``combine_boundary`` merges the per-partition (P_local, NB) boundary
    buffers into the globally combined (NB,) buffer every partition
    consumes; ``any_changed`` globalizes the vote-to-halt flag;
    ``sum_scalar`` globalizes scalar reductions (PageRank's L1 delta).
    """

    axis_name: AxisName = None

    name: str = "abstract"

    def combine_boundary(self, buf: jax.Array, sr: Semiring) -> jax.Array:
        """buf: (P_local, NB) -> (NB,) combined over ALL partitions."""
        raise NotImplementedError

    def any_changed(self, flag: jax.Array) -> jax.Array:
        """Global OR of the per-shard convergence flag."""
        raise NotImplementedError

    def sum_scalar(self, x: jax.Array) -> jax.Array:
        """Global sum of a per-shard scalar (tolerance checks)."""
        raise NotImplementedError

    def bind_sync(self, axes: Tuple[str, ...]) -> "CommBackend":
        """Bind extra mesh axes the halt vote must synchronize over.

        The engine calls this when OTHER mesh axes run data-dependent
        superstep loops concurrently (instances sharded over ``data``).
        Backends whose collectives rendezvous globally (the ppermute ring:
        XLA schedules one collective-permute across ALL devices, not per
        replica group) must equalize while-loop trip counts across those
        axes or the permutes deadlock; extra supersteps on already
        converged shards are idempotent no-ops, so results are unchanged.
        Group-scoped backends (dense all-reduce) ignore this.
        """
        return self


@dataclass(frozen=True)
class DenseAllReduce(CommBackend):
    """Dense all-reduce of the boundary buffer (the default backend).

    Stacked mode folds the partition axis on one device; mesh mode adds one
    ``lax.pmin``/``psum`` over ``axis_name`` — O(num_boundary) collective
    bytes per superstep, lowered by XLA to its tuned all-reduce.

    >>> import jax.numpy as jnp
    >>> import numpy as np
    >>> from repro.core.semiring import MIN_PLUS
    >>> buf = jnp.asarray([[0., 7., jnp.inf],
    ...                    [jnp.inf, 2., 5.]])  # 2 partitions, 3 boundary
    >>> np.asarray(DenseAllReduce().combine_boundary(buf, MIN_PLUS))
    array([0., 2., 5.], dtype=float32)
    """

    name: str = "dense"

    def combine_boundary(self, buf: jax.Array, sr: Semiring) -> jax.Array:
        out = _stack_fold(buf, sr)
        if self.axis_name is not None:
            if sr.name == "plus_mul":
                out = jax.lax.psum(out, self.axis_name)
            else:
                out = jax.lax.pmin(out, self.axis_name)
        return out

    def any_changed(self, flag: jax.Array) -> jax.Array:
        if self.axis_name is not None:
            flag = jax.lax.pmax(flag.astype(jnp.int32), self.axis_name) > 0
        return flag

    def sum_scalar(self, x: jax.Array) -> jax.Array:
        if self.axis_name is not None:
            x = jax.lax.psum(x, self.axis_name)
        return x


@dataclass(frozen=True)
class RingExchange(CommBackend):
    """Collective-permute ring over the mesh axis (multi-pod DCI regime).

    Each device folds its local partitions, then circulates the partial
    (NB,) buffer around a ``lax.ppermute`` ring for ``n - 1`` hops,
    combining with the semiring add at every hop; after the last hop every
    device holds the full combination.  All traffic is neighbor-to-neighbor
    point-to-point — on bandwidth-asymmetric topologies (pods joined by
    DCI) each slow link carries exactly one (NB,) buffer per hop instead of
    the all-reduce tree's cross-section traffic.

    ``axis_sizes`` pins the static ring length per axis (``make_comm``
    derives it from the mesh).  In stacked mode (``axis_name=None``) there
    is no ring to walk — the backend degenerates to the same partition-axis
    left fold as :class:`DenseAllReduce`, bitwise identical.

    ``variant`` picks the hop schedule.  ``"circulate"`` (v1, backend name
    ``"ring"``) ships the whole (NB,) partial on each of the ``n - 1``
    hops: ``(n - 1) * NB`` bytes leave every device per superstep.
    ``"rs_ag"`` (v2, backend name ``"ring-rs"``) is the bandwidth-optimal
    two-phase schedule: the buffer is split into ``n`` chunks, a
    reduce-scatter walks ``n - 1`` hops combining ONE chunk per hop (after
    which device ``i`` owns the fully combined chunk ``(i + 1) % n``), and
    an all-gather walks ``n - 1`` more hops broadcasting the owned chunks —
    ``2 * (n - 1) / n * NB`` bytes per device, ~2x less than circulate for
    large ``n``, at twice the latency-bound hop count.  Per-superstep costs
    for both are modeled in
    ``repro.dist.collectives.boundary_exchange_bytes``.

    Min-plus ring results are bitwise equal to the all-reduce (min is
    order-exact, both variants); plus-mul results are REASSOCIATED — each
    device (circulate) or each chunk (rs_ag) folds the same addends in its
    own ring order, so expect low-order float bit differences vs
    ``DenseAllReduce`` on a mesh (see ``tests/test_comm_backends.py``
    tolerances).

    >>> import jax.numpy as jnp
    >>> import numpy as np
    >>> from repro.core.semiring import MIN_PLUS
    >>> buf = jnp.asarray([[0., 7., jnp.inf],
    ...                    [jnp.inf, 2., 5.]])  # 2 partitions, 3 boundary
    >>> np.asarray(RingExchange().combine_boundary(buf, MIN_PLUS))
    array([0., 2., 5.], dtype=float32)
    >>> np.asarray(RingExchange(name="ring-rs", variant="rs_ag")
    ...            .combine_boundary(buf, MIN_PLUS))  # stacked: same fold
    array([0., 2., 5.], dtype=float32)
    """

    name: str = "ring"
    axis_sizes: Tuple[int, ...] = ()
    variant: str = "circulate"  # "circulate" (v1) | "rs_ag" (v2)
    # extra axes the halt vote synchronizes over (see CommBackend.bind_sync)
    sync_axes: Tuple[str, ...] = ()

    def __post_init__(self):
        assert len(_axes(self.axis_name)) == len(self.axis_sizes), \
            "RingExchange needs one static axis size per mesh axis " \
            "(use make_comm to derive them from the mesh)"
        assert self.variant in ("circulate", "rs_ag"), \
            f"unknown ring variant {self.variant!r}"

    def bind_sync(self, axes: Tuple[str, ...]) -> "RingExchange":
        import dataclasses

        return dataclasses.replace(self, sync_axes=tuple(axes))

    def _ring(self, x: jax.Array, combine) -> jax.Array:
        """Fold ``x`` over every mesh axis with P-1 neighbor hops each."""
        for ax, n in zip(_axes(self.axis_name), self.axis_sizes):
            if n == 1:
                continue
            perm = [(i, (i + 1) % n) for i in range(n)]
            send = x
            for _ in range(n - 1):
                send = jax.lax.ppermute(send, ax, perm)
                x = combine(x, send)
        return x

    def _ring_rs_ag(self, x: jax.Array, sr: Semiring) -> jax.Array:
        """Chunked reduce-scatter + all-gather over every mesh axis.

        Phase 1 (reduce-scatter): the (NB,) buffer is padded with the
        semiring zero to a multiple of ``n`` and split into ``n`` chunks;
        on hop ``s`` each device forwards its running partial and combines
        the received partial with its LOCAL copy of that chunk, so after
        ``n - 1`` hops device ``i`` owns the fully combined chunk
        ``(i + 1) % n`` (folded in device order ``c, c+1, ..`` for chunk
        ``c`` — one fixed association per chunk).  Phase 2 (all-gather):
        the owned chunks circulate ``n - 1`` more hops, each device
        scattering arrivals back into place.  Each hop moves ``NB / n``
        elements instead of circulate's full ``NB``.
        """
        for ax, n in zip(_axes(self.axis_name), self.axis_sizes):
            if n == 1:
                continue
            nb = x.shape[0]
            pad = (-nb) % n
            xp = jnp.pad(x, (0, pad), constant_values=sr.zero) if pad else x
            chunks = xp.reshape(n, -1)
            idx = jax.lax.axis_index(ax)
            perm = [(i, (i + 1) % n) for i in range(n)]

            def take(c):
                return jax.lax.dynamic_index_in_dim(chunks, c, keepdims=False)

            # reduce-scatter: after n-1 hops device i owns chunk (i+1) % n
            send = take(idx)
            for s in range(n - 1):
                recv = jax.lax.ppermute(send, ax, perm)
                send = sr.add(recv, take(jnp.mod(idx - 1 - s, n)))
            # all-gather: broadcast the owned chunks around the same ring
            out = chunks.at[jnp.mod(idx + 1, n)].set(send)
            g = send
            for s in range(n - 1):
                g = jax.lax.ppermute(g, ax, perm)
                out = out.at[jnp.mod(idx - s, n)].set(g)
            x = out.reshape(-1)[:nb]
        return x

    def combine_boundary(self, buf: jax.Array, sr: Semiring) -> jax.Array:
        out = _stack_fold(buf, sr)
        if self.axis_name is not None:
            if self.variant == "rs_ag":
                out = self._ring_rs_ag(out, sr)
            else:
                out = self._ring(out, sr.add)
        return out

    def any_changed(self, flag: jax.Array) -> jax.Array:
        if self.axis_name is None:
            return flag
        # control stays a group-scoped all-reduce: the ring is for the
        # O(num_boundary) payload, but walking P-1 hops to reduce a 4-byte
        # vote would double the latency-bound permute chain per superstep.
        # ``sync_axes`` folds in too — equalizing trip counts with
        # concurrent data-sharded loops so the globally scheduled permutes
        # cannot deadlock (see bind_sync).
        axes = _axes(self.axis_name) + tuple(self.sync_axes)
        return jax.lax.pmax(flag.astype(jnp.int32), axes) > 0

    def sum_scalar(self, x: jax.Array) -> jax.Array:
        if self.axis_name is None:
            return x
        # scalar control reduction: all-reduce, same rationale as the vote
        return jax.lax.psum(x, self.axis_name)


def _host_fold_min(buf) -> np.ndarray:
    b = np.asarray(buf)
    out = b[0]
    for i in range(1, b.shape[0]):
        out = np.minimum(out, b[i])
    return out


def _host_fold_sum(buf) -> np.ndarray:
    b = np.asarray(buf)
    out = b[0]
    for i in range(1, b.shape[0]):
        out = out + b[i]
    return out


@dataclass(frozen=True)
class HostGather(CommBackend):
    """Mesh-free backend: combine boundary buffers on the host.

    The (P, NB) publish buffer crosses to host memory once per superstep
    (``jax.pure_callback``), is folded there with a numpy semiring
    left-fold in the SAME 0..P-1 association as the stacked device fold
    (bitwise-identical results), and the combined (NB,) buffer returns to
    the device.  No mesh, no ``shard_map``, no XLA collectives — the
    paper's §V commodity-cluster deployment shape, where the exchange is a
    host-side gather over Ethernet rather than an accelerator collective.
    On a real multi-host CPU cluster the fold site is where the MPI-style
    gather slots in; single-process it demonstrates (and tests) the
    mesh-free execution path.

    Host-gather is inherently stacked: it requires all per-partition
    buffers in one process, so ``make_comm`` rejects it when a mesh is
    given.

    >>> import jax.numpy as jnp
    >>> import numpy as np
    >>> from repro.core.semiring import MIN_PLUS, PLUS_MUL
    >>> buf = jnp.asarray([[0., 7., jnp.inf],
    ...                    [jnp.inf, 2., 5.]])  # 2 partitions, 3 boundary
    >>> np.asarray(HostGather().combine_boundary(buf, MIN_PLUS))
    array([0., 2., 5.], dtype=float32)
    >>> np.asarray(HostGather().combine_boundary(
    ...     jnp.asarray([[1., 2.], [3., 4.]]), PLUS_MUL))
    array([4., 6.], dtype=float32)
    """

    name: str = "host"

    def combine_boundary(self, buf: jax.Array, sr: Semiring) -> jax.Array:
        fold = _host_fold_sum if sr.name == "plus_mul" else _host_fold_min
        # "sequential": under the query-axis vmap each source's (P, NB)
        # buffer is folded by its own call, the same 0..P-1 left fold
        return jax.pure_callback(
            fold, jax.ShapeDtypeStruct(buf.shape[1:], buf.dtype), buf,
            vmap_method="sequential",
        )

    def any_changed(self, flag: jax.Array) -> jax.Array:
        return flag  # stacked: the flag already covers every partition

    def sum_scalar(self, x: jax.Array) -> jax.Array:
        return x


# Backwards-compatible name: the original hardcoded ``Comm`` WAS the dense
# all-reduce; existing call sites (dryrun, benches) keep working.
Comm = DenseAllReduce


def make_comm(
    backend: Union[str, CommBackend] = "dense",
    *,
    mesh=None,
    model_axes: Tuple[str, ...] = ("model",),
) -> CommBackend:
    """Bind a backend name (or pre-built instance) to a placement.

    ``mesh=None`` binds the stacked form (``axis_name=None``); with a mesh
    the backend combines over ``model_axes`` (``RingExchange`` additionally
    captures the static per-axis ring lengths from the mesh shape).
    Pre-built instances pass through, but their binding is VALIDATED
    against the placement — an unbound backend inside ``shard_map`` would
    silently fold only the local shard and never cross devices.

    >>> make_comm("dense").name
    'dense'
    >>> make_comm("ring").axis_name is None   # stacked: fold, no ring
    True
    >>> make_comm("ring-rs").variant      # v2: reduce-scatter + all-gather
    'rs_ag'
    >>> make_comm("host").name
    'host'
    >>> make_comm("nope")
    Traceback (most recent call last):
        ...
    ValueError: unknown comm backend 'nope'; pick from ('dense', 'ring', 'ring-rs', 'host')
    """
    axes = tuple(model_axes)
    if isinstance(backend, CommBackend):
        if mesh is None:
            if backend.axis_name is not None:
                raise ValueError(
                    f"comm backend {backend.name!r} is bound to mesh axes "
                    f"{backend.axis_name!r} but no mesh was given"
                )
            return backend
        if isinstance(backend, HostGather):
            raise ValueError(
                "HostGather is mesh-free (it folds all partition buffers in "
                "one host process); use 'dense' or 'ring' on a mesh"
            )
        bound = _axes(backend.axis_name)
        if not bound:
            raise ValueError(
                f"comm backend {backend.name!r} is unbound (axis_name=None) "
                f"but the engine runs on a mesh over {axes!r}: inside "
                f"shard_map it would combine only the local shard — pass "
                f"the backend NAME to bind it, or bind axis_name yourself"
            )
        missing = [a for a in bound if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"comm backend {backend.name!r} is bound to {bound!r} but "
                f"the mesh only has axes {tuple(mesh.axis_names)!r}"
            )
        if isinstance(backend, RingExchange):
            want = tuple(int(mesh.shape[a]) for a in bound)
            if backend.axis_sizes != want:
                raise ValueError(
                    f"RingExchange axis_sizes {backend.axis_sizes!r} do not "
                    f"match the mesh shape {want!r} over {bound!r}"
                )
        return backend
    axis_name = None if mesh is None else axes
    if backend == "dense":
        return DenseAllReduce(axis_name=axis_name)
    if backend in ("ring", "ring-rs"):
        variant = "rs_ag" if backend == "ring-rs" else "circulate"
        if mesh is None:
            return RingExchange(name=backend, axis_name=None, variant=variant)
        sizes = tuple(int(mesh.shape[a]) for a in axes)
        return RingExchange(
            name=backend, axis_name=axis_name, axis_sizes=sizes,
            variant=variant,
        )
    if backend == "host":
        if mesh is not None:
            raise ValueError(
                "HostGather is mesh-free (it folds all partition buffers in "
                "one host process); use 'dense' or 'ring' on a mesh"
            )
        return HostGather()
    raise ValueError(
        f"unknown comm backend {backend!r}; pick from {COMM_BACKENDS}"
    )
