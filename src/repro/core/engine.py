"""Unified temporal execution engine: one pattern-aware runner for every
semiring analytic over a blocked graph collection (paper §IV-B on TPU).

The paper's claim is that a single iBSP abstraction expresses *all*
temporal graph analytics through three execution patterns; this module is
the blocked-engine counterpart of ``repro.core.ibsp.run_ibsp``.  An
algorithm is declared as a :class:`SemiringProgram` — a semiring plus
either a *fixpoint* spec (idempotent relaxation to quiescence: SSSP,
components, reachability, N-hop) or an *iterate* spec (a fixed-count
superstep function: PageRank) — and the engine executes it under any
pattern in any placement mode:

========================  =================================================
pattern                   execution
========================  =================================================
``sequential``            one ``lax.scan`` over the instance axis carrying
                          the vertex state (incremental aggregation — the
                          previous timestep's end state seeds the next)
``independent``           every instance runs from the same initial state;
                          on a mesh, instances shard over the ``data`` axis
                          while partitions stay on ``model`` (both forms of
                          the paper's parallelism at once)
``eventually``            independent + a Merge reduction across instances
                          (``merge="mean"`` on-device; ``None`` leaves the
                          per-instance states for a host-side Merge)
========================  =================================================

Placement: ``mesh=None`` runs stacked on one device (tests, benches);
with a mesh the engine lowers to ``shard_map`` — partitions in contiguous
blocks of ``P / m`` per device over the ``m`` devices of ``model_axes``,
and for the temporally concurrent patterns instances over ``data_axis``.
Staged batches are uploaded straight to their shards (a
``NamedSharding``), never whole to one device.  The boundary exchange is
ONE combine per superstep either way, routed through a pluggable comm
backend
(``comm="dense" | "ring" | "host"`` — see ``repro.core.comm``): the dense
psum/pmin all-reduce (default), a collective-permute ring for multi-pod
DCI topologies, or a mesh-free host-side gather for CPU clusters.
Algorithms never see the difference.

Instance staging is batched: edge-attribute matrices (I, E) land in
(I, P, T, B, B) tile tensors through ``BlockedGraph.fill_local_batch`` /
``fill_boundary_batch`` (or straight from GoFS slices via
``GoFSStore.load_blocked``) — no per-instance Python fill loops.

Staging is also *layout-aware* (``layout="dense" | "sparse"``): the sparse
layout packs only each instance's ACTIVE tiles (those holding at least one
edge whose weight differs from the semiring zero) into pow2-bucket
tensors plus a per-instance tile index
(:class:`repro.core.blocked.SparseBlocked`), and the runners scan the
index alongside the values so the local SpMV gather-folds only active
tiles.  Memory and FLOPs drop from ``O(P·T·B²)`` to ``O(nnz_tiles·B²)``
per instance; results are identical (bitwise for min-plus) because
skipped tiles contribute exact semiring zeros.  The boundary buffers and
comm backends are untouched — the dense/sparse boundary is the local
SpMV.

Staging can also be *overlapped* with execution (``staging="async"`` or an
explicit ``stream=``): chunks of instances arrive from a
:class:`repro.gofs.prefetch.SlicePrefetcher` double-buffer while the device
executes the previous chunk — the paper's §V storage/compute overlap.  A
dense chunk arrives as ``(I_c, E)`` edge rows, and the engine scatters them
into its tiles on the device (per model shard on a mesh).  See
``TemporalEngine`` and ``docs/ARCHITECTURE.md`` for the pipeline diagram.

Stats are reported in the same :class:`repro.core.ibsp.BSPStats` shape as
the host engine so the two paths are directly comparable.
"""
from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.blocked import BlockedGraph, SparseBlocked
from repro.core.comm import CommBackend, make_comm
from repro.core.ibsp import BSPStats
from repro.core.semiring import INF, MIN_PLUS, PLUS_MUL, Semiring
from repro.core.superstep import (
    KERNEL_MODES,
    DeviceGraph,
    bsp_fixpoint,
    kernel_mode,
    pagerank_step,
)
from repro.dist.collectives import boundary_exchange_bytes

PATTERNS = ("sequential", "independent", "eventually")

# staged-batch device cache entries kept per engine (LRU); each entry is one
# staged instance collection, so a handful covers any run_many working set
_STAGED_CACHE_SLOTS = 4


def _device_put(x, sharding: Optional[NamedSharding] = None) -> jax.Array:
    """Host buffer -> device array (the default device, or the shards of
    ``sharding``).  All staged-value uploads route through this seam so
    tests (and the re-upload regression gate) can count them; a no-op for
    arrays already on device."""
    return jnp.asarray(x) if sharding is None else jax.device_put(x, sharding)


# ---------------------------------------------------------------------------
# Program declarations
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SemiringProgram:
    """A blocked iBSP analytic: semiring + step semantics + init.

    ``kind="fixpoint"`` iterates BSP supersteps to global quiescence
    (requires an idempotent semiring).  ``kind="iterate"`` applies ``step``
    exactly ``iters`` times — the fixed-count form keeps every instance's
    loop in lockstep, which is what lets the mesh run instances
    concurrently over the ``data`` axis.

    Programs are declarative and engine-agnostic: the same program object
    runs under any pattern, stacked or mesh, sync or async staging.  The
    two stock constructors cover the paper's workloads:

    >>> from repro.core.engine import min_plus_program, pagerank_program
    >>> min_plus_program("sssp").kind          # idempotent -> fixpoint
    'fixpoint'
    >>> min_plus_program("sssp").semiring.name
    'min_plus'
    >>> pagerank_program(100, iters=5).iters   # non-idempotent -> iterate
    5

    **What the engine's runner cache keys on** (:attr:`runner_key`):
    every field the traced runner reads — ``name``, ``semiring``,
    ``zero_fill``, ``kind``, the fixpoint knobs, ``iters`` and ``step`` —
    and never ``init``.  ``init`` runs on the host before dispatch and
    only computes ``x0``, which reaches the runner as data, so programs
    that differ only in their seed (SSSP from another source, another
    batch of sources) share one built runner.  ``step`` is traced, so it
    must compare by value: two steps that trace the same computation
    should be equal (see ``_PageRankStep``); a fresh closure per program
    is correct but builds a runner of its own.

    >>> a = min_plus_program("sssp", init=source_init(0))
    >>> b = min_plus_program("sssp", init=source_init(7))
    >>> a == b, a.runner_key == b.runner_key
    (False, True)
    >>> pagerank_program(100).runner_key == pagerank_program(100).runner_key
    True
    """

    name: str
    semiring: Semiring
    zero_fill: float  # tile value for absent edges (sr.zero of the fill op)
    kind: str = "fixpoint"  # "fixpoint" | "iterate"
    # fixpoint knobs
    subgraph_centric: bool = True
    max_supersteps: int = 64
    max_local_sweeps: int = 1024
    # iterate knobs
    iters: int = 0
    # step(x, dg, comm, use_pallas) -> x  (iterate kind only)
    step: Optional[Callable] = None
    # host-side initial state: init(bg) -> (P, Vp) float32
    init: Optional[Callable[[BlockedGraph], np.ndarray]] = None

    def __post_init__(self):
        assert self.kind in ("fixpoint", "iterate"), self.kind
        if self.kind == "fixpoint":
            assert self.semiring.idempotent, \
                "fixpoint programs need an idempotent semiring"
        else:
            assert self.step is not None and self.iters > 0

    @property
    def runner_key(self) -> Tuple:
        """The fields the engine's traced runner reads: its cache key."""
        return (self.name, self.semiring, self.zero_fill, self.kind,
                self.subgraph_centric, self.max_supersteps,
                self.max_local_sweeps, self.iters, self.step)


def source_init(source_vertex: int, pad: float = INF):
    """x0 = pad everywhere, 0 at the source (SSSP-style frontier seed)."""

    def init(bg: BlockedGraph) -> np.ndarray:
        x0 = bg.scatter_vertex(np.full(bg.part_of.shape, pad, np.float32), pad)
        x0[bg.part_of[source_vertex], bg.local_of[source_vertex]] = 0.0
        return x0

    return init


def sources_init(sources: Sequence[int], pad: float = INF):
    """Batched multi-source seed: ``x0[q]`` is ``source_init(sources[q])``,
    stacked into a (Q, P, Vp) state tensor — the *query axis* that lets Q
    concurrent SSSP/N-hop requests run as ONE vectorized engine pass.

    The engine detects the extra leading axis (``x0.ndim == 3``) and vmaps
    the per-source runner over it; each source's fixpoint halts
    independently (JAX's batched ``while_loop`` masks converged lanes), so
    every result — values, final state, superstep counts — is bitwise
    identical to Q separate single-source runs.

    >>> import numpy as np
    >>> from repro.core.blocked import build_blocked
    >>> from repro.core.graph import GraphTemplate
    >>> from repro.core.engine import sources_init
    >>> tmpl = GraphTemplate(num_vertices=4,
    ...     src=np.array([0, 1, 2, 0]), dst=np.array([1, 2, 3, 2]))
    >>> bg = build_blocked(tmpl, np.array([0, 0, 1, 1]), block_size=2)
    >>> sources_init([0, 3])(bg).shape   # (Q, P, Vp)
    (2, 2, 2)
    """
    srcs = [int(s) for s in np.asarray(sources).reshape(-1)]

    def init(bg: BlockedGraph) -> np.ndarray:
        return np.stack([source_init(s, pad)(bg) for s in srcs])

    return init


def label_init():
    """x0 = own vertex id (label propagation / components seed)."""

    def init(bg: BlockedGraph) -> np.ndarray:
        V = len(bg.part_of)
        return bg.scatter_vertex(np.arange(V, dtype=np.float32), INF)

    return init


def min_plus_program(
    name: str = "min_plus_fixpoint",
    *,
    init: Optional[Callable] = None,
    subgraph_centric: bool = True,
    max_supersteps: int = 64,
    max_local_sweeps: int = 1024,
) -> SemiringProgram:
    """Min-plus fixpoint (SSSP / reachability / label propagation)."""
    return SemiringProgram(
        name=name, semiring=MIN_PLUS, zero_fill=INF, kind="fixpoint",
        subgraph_centric=subgraph_centric, max_supersteps=max_supersteps,
        max_local_sweeps=max_local_sweeps, init=init,
    )


@dataclass(frozen=True)
class _PageRankStep:
    """PageRank's superstep as a value: programs with equal parameters
    compare equal, so they share one built runner."""

    num_vertices: int
    damping: float

    def __call__(self, x, dg, comm, use_pallas):
        return pagerank_step(
            x, dg, comm, damping=self.damping,
            num_vertices=self.num_vertices, use_pallas=use_pallas,
        )


def pagerank_program(
    num_vertices: int, *, damping: float = 0.85, iters: int = 30
) -> SemiringProgram:
    """Fixed-iteration plus-mul PageRank (independent pattern workload)."""
    num_vertices = int(num_vertices)
    step = _PageRankStep(num_vertices, float(damping))

    def init(bg: BlockedGraph) -> np.ndarray:
        valid = (bg.global_of >= 0)
        return np.where(valid, 1.0 / num_vertices, 0.0).astype(np.float32)

    return SemiringProgram(
        name="pagerank", semiring=PLUS_MUL, zero_fill=0.0, kind="iterate",
        iters=iters, step=step, init=init,
    )


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------

@dataclass
class EngineResult:
    """Gathered outputs + iBSP-comparable statistics."""

    pattern: str
    values: np.ndarray  # (I, V) per-instance vertex values (global order);
    # multi-source runs (n_sources=Q) prepend the query axis: (Q, I, V)
    final: np.ndarray  # (V,) carried end state (sequential) or values[-1];
    # (Q, V) for multi-source runs
    merged: Optional[np.ndarray]  # (V,) Merge output (eventually + on-device)
    stats: Dict[str, np.ndarray]  # {"supersteps": (I,), "local_sweeps": (I,)}
    # — (Q, I) per source for multi-source runs
    occupancy: Optional[float] = None  # active-tile fraction (sparse layout)
    warm_start: bool = False  # fixpoints seeded from the previous instance
    n_sources: Optional[int] = None  # query-axis width Q (None = unbatched)
    _n_published: int = 0  # boundary vertices published per superstep
    _n_parts: int = 0
    _num_vertices: int = 0

    def supersteps_saved(self) -> Optional[np.ndarray]:
        """Per-instance supersteps the warm seed saved, relative to the
        cold-seeded FIRST instance (which has no predecessor and always
        pays the full fixpoint — the natural in-run cold baseline for a
        slowly varying collection).  ``None`` unless the run was
        warm-started."""
        if not self.warm_start:
            return None
        ss = self.stats["supersteps"]
        # per-source baselines under the query axis ((Q, I) stats)
        return np.maximum(0, ss[..., :1].astype(np.int64) - ss.astype(np.int64))

    def bsp_stats(self) -> BSPStats:
        """The host engine's accounting shape (run_ibsp comparability):
        compute_calls = partition activations, superstep_messages =
        published boundary values, timestep_messages = carried vertex
        states (sequential), merge_messages = instances folded.  Counts
        sum over the query axis for multi-source runs."""
        ss = int(np.sum(self.stats["supersteps"]))
        I = int(self.stats["supersteps"].shape[-1])
        q = self.n_sources or 1
        return BSPStats(
            supersteps=ss,
            compute_calls=ss * self._n_parts,
            superstep_messages=ss * self._n_published,
            timestep_messages=(I - 1) * self._num_vertices * q
            if self.pattern == "sequential" else 0,
            merge_messages=I * q if self.pattern == "eventually" else 0,
        )


@dataclass(frozen=True)
class RunSpec:
    """One analytic execution inside a shared-staging ``run_many`` pass.

    Every spec in a pass executes over the SAME staged instance batch
    (tiles are filled / device-put once, then each spec's jitted runner
    consumes them), so the programs must agree on ``zero_fill`` — the one
    property of the staged values an analytic can observe."""

    program: SemiringProgram
    pattern: str
    x0: Optional[np.ndarray] = None  # overrides program.init(bg)
    merge: Optional[str] = None
    # seed instance t's fixpoint from instance t-1's converged state
    # instead of x0 (incremental recompute).  EXACT for monotone
    # semirings on monotone-improving collections (min-plus where no
    # edge's weight ever increases between consecutive instances — see
    # docs/ARCHITECTURE.md for the contract and proof sketch); fixed-
    # iterate programs (plus-mul PageRank) silently fall back to a cold
    # start, where the seed would change the result.  No-op for the
    # sequential pattern, which already carries state by definition.
    warm_start: bool = False

    def effective_warm(self) -> bool:
        """Warm seeding actually applies: requested AND the program is a
        fixpoint (iterate programs run a fixed count of non-idempotent
        steps — a warm seed would change their result, so they cold
        start)."""
        return self.warm_start and self.program.kind == "fixpoint"


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class TemporalEngine:
    """Pattern-aware runner for semiring programs over one blocked graph.

    **Pattern contracts** (paper §IV-B; identical semantics in every
    placement/staging mode):

    * ``sequential`` — *incrementally aggregated*: instance ``t``'s end
      state seeds instance ``t + 1`` (``SendToNextTimeStep``); the result's
      ``final`` is the last carried state.  Chunked/async staging preserves
      the carry across chunk boundaries.
    * ``independent`` — every instance starts from the same ``x0``;
      instances never communicate.  ``values[t]`` is instance ``t``'s
      converged state.
    * ``eventually`` — independent execution plus a Merge fold across
      instances (``merge="mean"`` computes it on device into ``merged``;
      ``merge=None`` leaves per-instance states for a host-side Merge).

    **Placement** (stacked vs mesh):

    * ``mesh=None`` — stacked: all partitions stacked on one device's
      leading axis, instances scanned (CPU tests and benchmarks).
    * ``mesh=...`` — SPMD ``shard_map``: partitions sharded in contiguous
      blocks of ``P / m`` over the ``m`` devices of ``model_axes``; for
      cold ``independent``/``eventually`` runs the instance axis
      additionally shards over ``data_axis`` (temporal parallelism)
      whenever the data-axis size divides the instance count, else
      instances are replicated (still correct, no speedup).  Staged
      batches upload straight to those shards, the vertex state too, and
      the sequential carry stays sharded from one streamed chunk to the
      next.  ``exchange_bytes`` counts the boundary bytes one device
      sends (``exchange_bytes_per_superstep`` per superstep).

    **Comm backend** (how the boundary exchange moves bytes; see
    ``repro.core.comm`` and the selection table in
    ``docs/ARCHITECTURE.md``):

    * ``comm="dense"`` — psum/pmin all-reduce of the boundary buffer
      (default; single-pod meshes and stacked mode).
    * ``comm="ring"`` — ``lax.ppermute`` ring over ``model_axes``:
      P-1 neighbor-to-neighbor hops folding semiring partials (multi-pod
      DCI regime).  Stacked mode degenerates to the dense fold.
    * ``comm="host"`` — mesh-free host-side numpy semiring fold
      (``jax.pure_callback``); requires ``mesh=None``.

    Min-plus programs are bitwise identical across backends; plus-mul
    (PageRank) reassociates the sum on the mesh ring (low-order float
    bits).  The backend changes only the collective's lowering — never
    the program, pattern, staging mode, or result semantics.

    **Layout** (how instance tiles are materialized; see the block-sparse
    section of ``docs/ARCHITECTURE.md``):

    * ``layout="dense"`` — every template tile slot per instance:
      (I, P, T, B, B) tensors.  Simple, and right when most tiles are
      active every timestep.
    * ``layout="sparse"`` — only each instance's ACTIVE tiles (holding an
      edge whose weight differs from the semiring zero) are packed into
      pow2-bucket tensors plus a per-instance (row, col) tile index
      (:class:`repro.core.blocked.SparseBlocked`); the runners scan the
      index with the values, so staging bytes and SpMV work scale with
      ``nnz_tiles`` instead of ``T``.  Results are identical — bitwise
      for min-plus — because skipped tiles contribute exact semiring
      zeros; ``result.occupancy`` reports the measured active fraction.
      Boundary buffers and comm backends are untouched (the dense/sparse
      boundary is the local SpMV).

    **Staging** (how instance tensors reach the device):

    * ``staging="sync"`` — stage the whole (I, P, T, B, B) batch, then run.
    * ``staging="async"`` — double-buffered: instances are staged in chunks
      on a background thread (:class:`repro.gofs.prefetch.SlicePrefetcher`)
      while the device executes the previous chunk; results are bitwise
      identical to sync staging (one caveat: on a mesh, the ``eventually``
      ``merge="mean"`` fold reduces in a different grouping than the
      in-``shard_map`` psum, so ``merged`` may differ in low-order float
      bits there — ``values``/``final`` stay identical).  ``run(...,
      stream=...)`` accepts an explicit prefetcher (e.g.
      ``GoFSStore.load_blocked_stream``) so disk slice reads themselves
      overlap execution; for mesh runs pick a ``chunk_instances`` that is
      a multiple of the data-axis size or the per-chunk runners fall back
      to replicated instances.

    Jitted runners are cached per (program structure, pattern, merge,
    instance count, layout, warm start, query axis): the program's part of
    the key is :attr:`SemiringProgram.runner_key`, which leaves out the
    host-only ``init``.  So a program for a new source, a new batch of
    sources or a new pass reuses the built runner, and repeated calls
    (tracking's per-timestep probes, a service's batches) rebuild nothing.
    A runner builds once per argument signature (shapes and dtypes): a new
    query-axis width on a warm runner is a build too.  ``runner_builds``
    counts those builds and ``runner_hits`` the calls that built nothing.

    Example — one tiny graph, all three patterns, sync and async staging:

    >>> import numpy as np
    >>> from repro.core.blocked import build_blocked
    >>> from repro.core.graph import GraphTemplate
    >>> from repro.core.engine import (
    ...     TemporalEngine, min_plus_program, source_init)
    >>> tmpl = GraphTemplate(num_vertices=4,
    ...     src=np.array([0, 1, 2, 0]), dst=np.array([1, 2, 3, 2]))
    >>> bg = build_blocked(tmpl, np.array([0, 0, 1, 1]), block_size=2)
    >>> eng = TemporalEngine(bg)
    >>> sssp = min_plus_program("sssp", init=source_init(0))
    >>> w = np.ones((2, 4), np.float32)     # 2 instances, unit latency
    >>> eng.run(sssp, w, pattern="sequential").final
    array([0., 1., 1., 2.], dtype=float32)
    >>> eng.run(sssp, w, pattern="independent").values.shape
    (2, 4)
    >>> eng.run(sssp, w, pattern="eventually", merge="mean").merged
    array([0., 1., 1., 2.], dtype=float32)
    >>> eng_async = TemporalEngine(bg, staging="async")
    >>> bool(np.array_equal(eng_async.run(sssp, w, pattern="sequential").final,
    ...                     eng.run(sssp, w, pattern="sequential").final))
    True
    >>> eng_host = TemporalEngine(bg, comm="host")  # mesh-free host combine
    >>> bool(np.array_equal(eng_host.run(sssp, w, pattern="sequential").final,
    ...                     eng.run(sssp, w, pattern="sequential").final))
    True
    >>> eng_sp = TemporalEngine(bg, layout="sparse")  # packed active tiles
    >>> r_sp = eng_sp.run(sssp, w, pattern="sequential")
    >>> bool(np.array_equal(r_sp.final, eng.run(sssp, w,
    ...                                         pattern="sequential").final))
    True
    >>> 0.0 < r_sp.occupancy <= 1.0  # measured active-tile fraction
    True
    """

    def __init__(
        self,
        bg: BlockedGraph,
        *,
        mesh=None,
        data_axis: str = "data",
        model_axes: Tuple[str, ...] = ("model",),
        use_pallas=False,
        kernel_interpret: Optional[bool] = None,
        staging: str = "sync",
        prefetch_depth: int = 2,
        chunk_instances: Optional[int] = None,
        comm: Union[str, CommBackend] = "dense",
        layout: str = "dense",
        cluster=None,
    ):
        assert staging in ("sync", "async"), staging
        assert layout in ("dense", "sparse"), layout
        self.bg = bg
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axes = tuple(model_axes)
        # ``cluster``: a repro.cluster.runtime.ClusterRuntime.  When it is
        # distributed, this engine becomes ONE SHARD of the run: it holds
        # only its process's contiguous partition range (structure, staged
        # tiles, and state are all sliced to it), the boundary exchange
        # and halt vote go through the inter-process ClusterGather, and
        # results are re-assembled across processes at gather time —
        # bitwise-identical to the single-process stacked run (the
        # exchange reconstructs the exact (P, NB) buffer and applies the
        # same 0..P-1 fold; the cross-process halt vote keeps superstep
        # counts lockstep).  A single-process runtime (or None) leaves
        # every path untouched.
        self.cluster = cluster if (cluster is not None
                                   and cluster.is_distributed) else None
        if self.cluster is not None:
            assert mesh is None, \
                "cluster placement is stacked per process (mesh-free); " \
                "per-process meshes are a future composition"
            self.parts: Optional[Tuple[int, int]] = \
                self.cluster.partition_shard(bg.n_parts)
            from repro.cluster.gather import ClusterGather

            if not isinstance(comm, ClusterGather):
                assert comm in ("dense", "host", "cluster"), \
                    f"cluster runs exchange through ClusterGather; " \
                    f"comm={comm!r} has no inter-process form"
                comm = ClusterGather(runtime=self.cluster)
        else:
            self.parts = None
        # ``use_pallas`` is the three-valued kernel mode ("off" | "spmv" |
        # "fused"; bools keep their historical meaning).  It is validated
        # here and passed down opaquely — ``kernel_interpret`` rides along
        # so tests can pin the interpret tier regardless of backend.
        self.kernel_mode = kernel_mode(use_pallas)[0]
        self.use_pallas = self.kernel_mode if kernel_interpret is None \
            else (self.kernel_mode, kernel_interpret)
        self.staging = staging
        self.prefetch_depth = prefetch_depth
        self.chunk_instances = chunk_instances
        self.layout = layout
        self.comm = make_comm(comm, mesh=mesh, model_axes=self.model_axes)
        out_mask = np.arange(bg.o_max)[None, :] < bg.n_out[:, None]

        def shard(a):  # partition-lead structure -> this process's rows
            return a if self.parts is None else a[self.parts[0]:self.parts[1]]

        # template structure: (rows, cols, brows, bcols) tile index + the
        # layout-independent tail.  The sparse layout replaces the first
        # four with PER-INSTANCE packed indices scanned alongside the tile
        # values; the tail is shared by both layouts.
        self._struct_tail = (
            jnp.asarray(shard(bg.out_slot)), jnp.asarray(shard(bg.out_local)),
            jnp.asarray(shard(out_mask)), jnp.asarray(shard(bg.global_of >= 0)),
        )
        self._struct = (
            jnp.asarray(shard(bg.tiles_rc[:, :, 0])),
            jnp.asarray(shard(bg.tiles_rc[:, :, 1])),
            jnp.asarray(shard(bg.btiles_rc[:, :, 0])),
            jnp.asarray(shard(bg.btiles_rc[:, :, 1])),
        ) + self._struct_tail
        self._runners: Dict[Any, Callable] = {}
        # (runner, argument signature) pairs already called: the first
        # call of each pair builds the program (see _call)
        self._built: set = set()
        self.runner_builds = 0
        self.runner_hits = 0
        self.device_fills = 0  # streamed chunks whose tiles the device filled
        self.exchange_bytes = 0  # cumulative, over this engine's runs
        # device fill (see _device_fill): the slot maps, uploaded on first
        # use, and one jitted fill per (zero, instance axis)
        self._fill_maps_dev: Optional[Tuple[jax.Array, ...]] = None
        self._fill_fns: Dict[Any, Callable] = {}
        self._merge_fns: Dict[int, Callable] = {}
        # staged-batch device cache: host-array identity (weakly held) ->
        # device arrays (see _cached_device) so repeated runs over one
        # staged batch (run_many, tracking's probes, shared-staging
        # sessions) upload once without extending the batch's lifetime
        self._staged_device: "OrderedDict[Tuple[int, ...], Tuple[Tuple[weakref.ref, ...], Tuple[jax.Array, ...]]]" = OrderedDict()

    # ------------------------------------------------------------ staging
    def stage(
        self, instance_weights: np.ndarray, zero_fill: float
    ) -> Tuple[jax.Array, jax.Array]:
        """(I, E) edge weights -> device tile tensors, batched scatter.
        A cluster-sharded engine fills only its own partition range."""
        w = np.asarray(instance_weights, np.float32)
        if w.ndim == 1:
            w = w[None]
        filled = (
            self.bg.fill_local_batch(w, zero=zero_fill, parts=self.parts),
            self.bg.fill_boundary_batch(w, zero=zero_fill, parts=self.parts),
        )
        sh = self._staged_sharding()
        return tuple(jnp.asarray(a) if sh is None else _device_put(a, sh)
                     for a in filled)

    # ---------------------------------------------------------- placement
    @property
    def _maxes(self):
        """The model axes as a ``PartitionSpec`` entry."""
        return self.model_axes if len(self.model_axes) > 1 \
            else self.model_axes[0]

    def _instance_axis(self, pattern: str, warm: bool, n_instances: int):
        """The mesh axis a runner shards the instance axis over:
        ``data_axis`` for cold temporally concurrent patterns whose
        instance count the data axis divides, else ``None`` (replicated:
        single-instance probes, ragged collections, and warm chains,
        whose instance t needs t-1's converged state)."""
        d = self._data_size()
        shard = (pattern in ("independent", "eventually") and not warm
                 and n_instances % d == 0 and n_instances >= d)
        return self.data_axis if shard else None

    def _staged_sharding(self, iaxis=None) -> Optional[NamedSharding]:
        """Where a staged (I, P, ...) batch lives: ``None`` (the default
        device) stacked; on a mesh, partitions over the model axes and
        instances over ``iaxis``."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, PartitionSpec(iaxis, self._maxes))

    def _put(self, host_arrays, iaxis=None) -> Tuple[jax.Array, ...]:
        """Upload one group of staged buffers (one ``engine.put`` span)."""
        sh = self._staged_sharding(iaxis)
        with TraceAnnotation("engine.put"):
            if sh is None:  # the one-argument seam the upload tests count
                return tuple(_device_put(a) for a in host_arrays)
            return tuple(_device_put(a, sh) for a in host_arrays)

    def _model_size(self) -> int:
        """Devices over the model axes (1 stacked)."""
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.model_axes]))

    @property
    def exchange_bytes_per_superstep(self) -> float:
        """Boundary bytes one device sends in one superstep's exchange
        (``boundary_exchange_bytes`` over the published vertices and the
        model axes' devices, under this engine's comm backend); 0 stacked,
        where the fold never leaves the device."""
        if self.mesh is None:
            return 0.0
        return float(boundary_exchange_bytes(
            self.bg.num_boundary, self._model_size(), self.comm.name,
            boundary_nnz=int(self.bg.boundary_nnz))["bytes_per_device"])

    # -------------------------------------------------------- device fill
    def _shard_parts(self) -> List[Tuple[int, int]]:
        """The partition range of each model shard, in mesh order: one
        range stacked (a cluster shard's own), contiguous blocks of
        ``P / m`` over the ``m`` devices of the model axes on a mesh."""
        if self.mesh is None:
            return [self.parts or (0, self.bg.n_parts)]
        m = self._model_size()
        assert self.bg.n_parts % m == 0, (self.bg.n_parts, m)
        pl = self.bg.n_parts // m
        return [(j * pl, (j + 1) * pl) for j in range(m)]

    def _fill_maps(self) -> Tuple[jax.Array, ...]:
        """Device copies of the instance-invariant fill maps, built once:
        (local slot, local edge id, boundary slot, boundary edge id), each
        ``(m, L)`` int32 with one row per model shard from
        ``BlockedGraph.fill_map`` over that shard's partition range.  Rows
        are padded to one length with distinct slots past the shard's
        tiles, which the scatter drops; on a mesh row ``j`` lives on model
        shard ``j``."""
        if self._fill_maps_dev is None:
            B2 = self.bg.block_size ** 2
            maps = []
            for boundary, t_count in ((False, self.bg.t_max),
                                      (True, self.bg.tb_max)):
                per = [self.bg.fill_map(boundary, r)
                       for r in self._shard_parts()]
                lo, hi = self._shard_parts()[0]
                size = (hi - lo) * t_count * B2  # one instance's slots
                width = max(len(sl) for sl, _ in per)
                assert size + width < 2 ** 31, "slot overflows int32"
                slot = np.empty((len(per), width), np.int32)
                eid = np.zeros((len(per), width), np.int32)
                for j, (sl, ei) in enumerate(per):
                    slot[j, :len(sl)] = sl
                    slot[j, len(sl):] = size + np.arange(width - len(sl))
                    eid[j, :len(ei)] = ei
                maps += [slot, eid]
            sh = None if self.mesh is None else \
                NamedSharding(self.mesh, PartitionSpec(self._maxes))
            self._fill_maps_dev = tuple(
                jnp.asarray(a) if sh is None else jax.device_put(a, sh)
                for a in maps)
        return self._fill_maps_dev

    def _make_fill(self, zero: float, iaxis):
        """The jitted device fill of one chunk: edge rows ``(n, E)`` and
        the fill maps -> (tiles, btiles), equal to the host's
        ``fill_local_batch`` / ``fill_boundary_batch``.  Every slot starts
        at ``zero``; edges scatter with ``.set`` when no two share a slot,
        else combine with the semiring add (``.min`` for an INF zero,
        ``.add`` otherwise), as the host fill does.  The instance is an
        index coordinate of its own (the slot map broadcasts over it),
        never ``instance * slots + slot``, so no coordinate outgrows one
        shard's tile set, and int32 holds at any chunk length.  Stacked
        (and a cluster shard) is the one-shard case; on a mesh each model
        shard fills its own partitions under ``shard_map``, laid out as
        ``_put`` would upload them."""
        bg = self.bg
        B = bg.block_size
        lo, hi = self._shard_parts()[0]
        pl = hi - lo  # partitions a shard fills
        combine = "min" if zero == INF else "add"
        ops = ("set" if bg._local_slots_unique() else combine,
               "set" if bg._boundary_slots_unique() else combine)

        def scatter(rows, slot, eid, t_count, op):
            n, s = rows.shape[0], slot[0]
            # one (instance, row, lane) coordinate per update: the rows of
            # B lanes keep the tiles' device layout, so the scatter writes
            # in place and the reshape is free
            at = tuple(jnp.broadcast_to(a, (n, s.shape[0])) for a in (
                jnp.arange(n)[:, None], (s // B)[None, :], (s % B)[None, :]))
            vals = jnp.full((n, pl * t_count * B, B), zero, jnp.float32)
            vals = getattr(vals.at[at], op)(rows[:, eid[0]], mode="drop",
                                            unique_indices=op == "set")
            return vals.reshape(n, pl, t_count, B, B)

        def fill(rows, lslot, leid, bslot, beid):
            return (scatter(rows, lslot, leid, bg.t_max, ops[0]),
                    scatter(rows, bslot, beid, bg.tb_max, ops[1]))

        if self.mesh is None:
            return jax.jit(fill)
        maxes = PartitionSpec(self._maxes)
        return jax.jit(jax.shard_map(
            fill, mesh=self.mesh,
            in_specs=(PartitionSpec(iaxis),) + (maxes,) * 4,
            out_specs=(PartitionSpec(iaxis, self._maxes),) * 2,
            check_vma=False,
        ))

    def _device_fill(self, ch, iaxis=None) -> Tuple[jax.Array, jax.Array]:
        """Upload one chunk's edge rows and fill its tiles on the device
        (one ``engine.put`` span: the rows' upload and the fill's
        dispatch); counted in ``device_fills``.  On a mesh the rows go to
        every model shard (instances over ``iaxis``), and no chip holds
        another's tiles."""
        key = (float(ch.zero), iaxis)
        fn = self._fill_fns.get(key)
        if fn is None:
            fn = self._fill_fns[key] = self._make_fill(*key)
        with TraceAnnotation("engine.put"):
            maps = self._fill_maps()
            if self.mesh is None:
                rows = _device_put(ch.edges)
            else:
                rows = _device_put(ch.edges, NamedSharding(
                    self.mesh, PartitionSpec(iaxis)))
            out = fn(rows, *maps)
        self.device_fills += 1
        return out

    def stage_sparse(
        self, instance_weights: np.ndarray, zero_fill: float
    ) -> SparseBlocked:
        """(I, E) edge weights -> packed active-tile batch (host arrays)."""
        return self.bg.stage_sparse(instance_weights, zero=zero_fill,
                                    parts=self.parts)

    # ------------------------------------------------------- instance step
    def _device_graph(self, tiles_l, btiles_l, struct) -> DeviceGraph:
        rows, cols, brows, bcols, out_slot, out_local, out_mask, vmask = struct
        return DeviceGraph(
            block_size=self.bg.block_size, num_boundary=self.bg.num_boundary,
            rows=rows, cols=cols, tiles=tiles_l,
            brows=brows, bcols=bcols, btiles=btiles_l,
            out_slot=out_slot, out_local=out_local,
            out_mask=out_mask, vmask=vmask,
        )

    def _run_instance(self, program: SemiringProgram, x, tiles_l, btiles_l,
                      struct, comm: CommBackend):
        """One instance's BSP on the local shard.  Returns (x, (ss, lsw))."""
        dg = self._device_graph(tiles_l, btiles_l, struct)
        if program.kind == "fixpoint":
            x, st = bsp_fixpoint(
                x, dg, program.semiring, comm=comm,
                subgraph_centric=program.subgraph_centric,
                max_supersteps=program.max_supersteps,
                max_local_sweeps=program.max_local_sweeps,
                use_pallas=self.use_pallas,
            )
            return x, (st["supersteps"], st["local_sweeps"])

        def body(r, _):
            return program.step(r, dg, comm, self.use_pallas), None

        x, _ = jax.lax.scan(body, x, None, length=program.iters)
        return x, (jnp.asarray(program.iters, jnp.int32),
                   jnp.asarray(0, jnp.int32))

    # ------------------------------------------------------------- runners
    def _scan_instances(self, program: SemiringProgram, pattern: str,
                        x0, tiles, btiles, struct,
                        comm: Optional[CommBackend] = None, idx=None,
                        warm: bool = False):
        """Scan the instance axis on the local shard.  Returns
        (xs (I, P_l, Vp), final (P_l, Vp), ss (I,), lsw (I,)).

        ``idx=None`` (dense): ``struct`` is the full 8-tuple with the
        template tile index.  Sparse: ``struct`` is the 4-tuple tail and
        ``idx`` the per-instance (rows, cols, brows, bcols) packed index,
        scanned alongside the tile values.

        ``warm=True`` seeds each instance's fixpoint from the previous
        instance's converged state rather than ``x0`` — for monotone
        fixpoints on slowly varying collections the chain converges in
        far fewer supersteps and to the identical state (RunSpec.warm_start
        documents the exactness contract)."""
        comm = self.comm if comm is None else comm

        def step(carry, tb):
            if idx is None:
                tiles_l, btiles_l = tb
                s = struct
            else:
                tiles_l, btiles_l, rows_l, cols_l, brows_l, bcols_l = tb
                s = (rows_l, cols_l, brows_l, bcols_l) + struct
            seed = carry if (pattern == "sequential" or warm) else x0
            x, (ss, lsw) = self._run_instance(
                program, seed, tiles_l, btiles_l, s, comm
            )
            return x, (x, ss, lsw)

        xs_in = (tiles, btiles) if idx is None else (tiles, btiles) + tuple(idx)
        final, (xs, ss, lsw) = jax.lax.scan(step, x0, xs_in)
        return xs, final, ss, lsw

    def _make_stacked_runner(self, program: SemiringProgram, pattern: str,
                             merge: Optional[str], sparse: bool = False,
                             warm: bool = False, multi: bool = False):
        def run_dense(tiles, btiles, x0, *struct):
            return finish(*self._scan_instances(
                program, pattern, x0, tiles, btiles, struct, warm=warm
            ))

        def run_sparse(tiles, btiles, rows, cols, brows, bcols, x0, *struct):
            return finish(*self._scan_instances(
                program, pattern, x0, tiles, btiles, struct,
                idx=(rows, cols, brows, bcols), warm=warm,
            ))

        def finish(xs, final, ss, lsw):
            if pattern == "eventually" and merge == "mean":
                merged = jnp.mean(xs, axis=0)
            else:
                merged = jnp.zeros_like(final)
            return xs, final, merged, ss, lsw

        fn = run_sparse if sparse else run_dense
        if multi:
            # query axis: vmap over the leading (Q,) dim of x0 only — tile
            # values and template structure broadcast.  Batched while_loops
            # mask converged sources lane-wise, so each source's fixpoint
            # (and its superstep count) is exactly its single-source run.
            before = 6 if sparse else 2  # positional args ahead of x0
            tail = len(self._struct_tail) if sparse else len(self._struct)
            fn = jax.vmap(fn, in_axes=(None,) * before + (0,)
                          + (None,) * tail)
        return jax.jit(fn)

    def _data_size(self) -> int:
        axes = (self.data_axis,) if isinstance(self.data_axis, str) \
            else tuple(self.data_axis)
        n = 1
        for a in axes:
            n *= int(self.mesh.shape[a])
        return n

    def _make_mesh_runner(self, program: SemiringProgram, pattern: str,
                          merge: Optional[str], n_instances: int,
                          sparse: bool = False, warm: bool = False,
                          multi: bool = False):
        P_ = PartitionSpec
        mesh = self.mesh
        maxes = self._maxes
        daxis = self.data_axis
        # temporal concurrency: shard the instance axis over data only when
        # it divides — single-instance probes (tracking, nhop hops) and
        # ragged collections fall back to replicated instances, which stays
        # correct (every data group computes the same states; the Merge
        # psum normalizes by the psum'd instance count).  Warm-started
        # fixpoints chain state from instance t-1 to t, so they replicate
        # too: every data group keeps the chain intact, bitwise-correct.
        iaxis = self._instance_axis(pattern, warm, n_instances)
        shard_instances = iaxis is not None
        # data-sharded instances run data-dependent superstep loops
        # concurrently; backends with globally scheduled collectives (the
        # ppermute ring) must equalize trip counts over the data axis or
        # the permutes deadlock (see CommBackend.bind_sync)
        comm = self.comm
        if shard_instances:
            daxes = (daxis,) if isinstance(daxis, str) else tuple(daxis)
            comm = comm.bind_sync(daxes)

        def merged_of(xs, final):
            if pattern == "eventually" and merge == "mean":
                # eventually-dependent Merge across ALL instances (data axis)
                part = jnp.sum(xs, axis=0)
                total = jax.lax.psum(part, daxis)
                n = jax.lax.psum(
                    jnp.asarray(xs.shape[0], jnp.float32), daxis
                )
                return total / n
            return jnp.zeros_like(final)

        def local_dense(tiles, btiles, x0, *struct):
            xs, final, ss, lsw = self._scan_instances(
                program, pattern, x0, tiles, btiles, struct, comm, warm=warm
            )
            return xs, final, merged_of(xs, final), ss, lsw

        def local_sparse(tiles, btiles, rows, cols, brows, bcols, x0,
                         *struct):
            xs, final, ss, lsw = self._scan_instances(
                program, pattern, x0, tiles, btiles, struct, comm,
                idx=(rows, cols, brows, bcols), warm=warm,
            )
            return xs, final, merged_of(xs, final), ss, lsw

        local = local_sparse if sparse else local_dense
        if multi:
            # query axis: the vmap sits INSIDE shard_map (vmap-of-shard_map
            # composes poorly), batching the per-shard scan over the
            # leading (Q,) of x0; the data/model sharding of tiles and
            # instances is unchanged, and collectives batch lane-wise.
            before = 6 if sparse else 2
            tail = len(self._struct_tail) if sparse else len(self._struct)
            local = jax.vmap(local, in_axes=(None,) * before + (0,)
                             + (None,) * tail)

        def lead(extra_dims: int, *front):
            return P_(*front, *([None] * extra_dims))

        q = (None,) if multi else ()  # replicated leading query axis
        if sparse:
            in_specs = (
                lead(3, iaxis, maxes),  # tiles (I, P, K, B, B)
                lead(3, iaxis, maxes),  # btiles
                lead(1, iaxis, maxes),  # rows (I, P, K)
                lead(1, iaxis, maxes),  # cols
                lead(1, iaxis, maxes),  # brows (I, P, Kb)
                lead(1, iaxis, maxes),  # bcols
                lead(1, *q, maxes),     # x0 ([Q,] P, Vp)
            ) + tuple(lead(s.ndim - 1, maxes) for s in self._struct_tail)
        else:
            in_specs = (
                lead(3, iaxis, maxes),  # tiles (I, P, T, B, B)
                lead(3, iaxis, maxes),  # btiles
                lead(1, *q, maxes),     # x0 ([Q,] P, Vp)
            ) + tuple(lead(s.ndim - 1, maxes) for s in self._struct)
        out_specs = (
            lead(1, *q, iaxis, maxes),  # xs ([Q,] I, P, Vp)
            lead(1, *q, maxes),         # final
            lead(1, *q, maxes),         # merged (replicated over data)
            P_(*q, iaxis), P_(*q, iaxis),  # ss, lsw ([Q,] I)
        )
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(fn)

    def _runner(self, program: SemiringProgram, pattern: str,
                merge: Optional[str], n_instances: int,
                sparse: bool = False, warm: bool = False,
                multi: bool = False):
        key = (program.runner_key, pattern, merge, n_instances, sparse,
               warm, multi)
        if key not in self._runners:
            # the runner keeps the traced fields only: a per-call ``init``
            # (and the sources it closes over) is not held for its lifetime
            program = dataclasses.replace(program, init=None)
            if self.mesh is None:
                self._runners[key] = self._make_stacked_runner(
                    program, pattern, merge, sparse, warm=warm, multi=multi
                )
            else:
                self._runners[key] = self._make_mesh_runner(
                    program, pattern, merge, n_instances, sparse, warm=warm,
                    multi=multi,
                )
        return self._runners[key]

    # ------------------------------------------------- cluster shard slicing
    def _shard_axis(self, a, axis: int = 1):
        """Slice a full-width partition axis to this process's range.
        No-op for a single-process engine or an already shard-local
        array (its axis is ``hi - lo`` wide)."""
        if a is None or self.parts is None:
            return a
        lo, hi = self.parts
        if a.shape[axis] == hi - lo:
            return a
        assert a.shape[axis] == self.bg.n_parts, (a.shape, axis)
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(lo, hi)
        return a[tuple(idx)]

    def _shard_sparse_batch(self, sp: SparseBlocked) -> SparseBlocked:
        """Slice a full-width pre-staged packed batch to the shard."""
        lo, hi = self.parts
        if sp.tiles.shape[1] == hi - lo:
            return sp
        return dataclasses.replace(
            sp,
            tiles=sp.tiles[:, lo:hi], btiles=sp.btiles[:, lo:hi],
            rows=sp.rows[:, lo:hi], cols=sp.cols[:, lo:hi],
            brows=sp.brows[:, lo:hi], bcols=sp.bcols[:, lo:hi],
            nnz=sp.nnz[:, lo:hi], bnnz=sp.bnnz[:, lo:hi],
        )

    # ------------------------------------------------------------ dispatch
    def _call(self, run_fn, args):
        """Call a runner.  The first call of a runner with an argument
        signature (shapes and dtypes) is a build (trace, lower, compile
        or load from the persistent cache, then enqueue), spanned as
        ``engine.build`` and counted in ``runner_builds``; later calls
        hit jit's cache, open no span and count in ``runner_hits``."""
        key = (run_fn, tuple((a.shape, a.dtype) for a in args))
        if key in self._built:
            self.runner_hits += 1
            return run_fn(*args)
        self._built.add(key)
        self.runner_builds += 1
        with TraceAnnotation("engine.build"):
            return run_fn(*args)

    def _dispatch(self, run_fn, *args):
        if self.mesh is not None:
            with self.mesh:
                return self._call(run_fn, args)
        out = self._call(run_fn, args)
        if self.parts is not None:
            # cluster mode: the runner's pure_callback exchanges ride the
            # SEQUENCED inter-process channel, and so do the host-side
            # operations that follow a dispatch (chunk consistency checks,
            # result gathers).  Draining the computation here keeps every
            # process's exchange schedule a single deterministic order —
            # an async dispatch could interleave the two streams
            # differently per process and trip the tag verification.
            out = jax.block_until_ready(out)
        return out

    def _cached_device(self, host_arrays: Tuple[Any, ...]) -> Tuple[jax.Array, ...]:
        """Device arrays for one staged batch, uploaded once per identity.

        The boundary/tile structure of a staged graph is immutable once
        handed to the engine, so the device copy is keyed on the ``id`` of
        every host array (verified against weak references, so id reuse
        cannot alias) and LRU-bounded to ``_STAGED_CACHE_SLOTS`` batches:
        ``run_many`` over one staged collection — or tracking's repeated
        probes over one tile set — re-uploads nothing.  Host batches are
        held WEAKLY: once the caller drops a staged batch (e.g. a
        ``run_many`` staging cache going out of scope) its entry — and
        the device copy it pins — is purged on the next call, so the
        cache never extends a batch's lifetime."""
        for k in [k for k, (refs, _) in self._staged_device.items()
                  if any(r() is None for r in refs)]:
            del self._staged_device[k]
        key = tuple(map(id, host_arrays))
        hit = self._staged_device.get(key)
        if hit is not None and all(r() is a for r, a in
                                   zip(hit[0], host_arrays)):
            self._staged_device.move_to_end(key)
            return hit[1]
        dev = self._put(host_arrays)
        self._staged_device[key] = (
            tuple(weakref.ref(a) for a in host_arrays), dev,
        )
        while len(self._staged_device) > _STAGED_CACHE_SLOTS:
            self._staged_device.popitem(last=False)
        return dev

    def _dispatch_sparse(self, run_fn, sp: SparseBlocked, x0):
        """Device-put a packed batch (cached on identity) and dispatch."""
        bufs = self._cached_device(
            (sp.tiles, sp.btiles, sp.rows, sp.cols, sp.brows, sp.bcols)
        )
        return self._dispatch(run_fn, *bufs, x0, *self._struct_tail)

    def _merge_mean(self, xs, axis: int = 0):
        """On-device Merge over the full instance axis (async path).
        Stacked: the same ``jnp.mean`` the sync runner computes in-graph,
        on the same (I, P, Vp) values — bitwise-identical output.  Mesh:
        the sync runner reduces as psum-of-shard-sums inside ``shard_map``,
        a different float grouping — equal up to low-order bits.
        ``axis=1`` folds the instance axis of multi-source (Q, I, …)
        states."""
        fn = self._merge_fns.get(axis)
        if fn is None:
            fn = self._merge_fns[axis] = jax.jit(
                lambda v: jnp.mean(v, axis=axis))
        if self.mesh is not None:
            with self.mesh:
                return fn(xs)
        return fn(xs)

    def _run_stream_many(self, specs: Sequence[RunSpec], chunks, x0s):
        """Consume a chunk stream (SlicePrefetcher or any iterable of
        StagedChunk) ONCE, feeding every spec's runner: each chunk is
        device-put a single time, then dispatched to all N runners before
        the next chunk is pulled — so slice reads + tile fills (on the
        prefetcher's background pool) overlap the whole fan-out, and N
        concurrent analytics cost one staging pass (the shared-scan
        amortization behind ``GopherSession.run_many``).  Sequential
        patterns carry their end state across chunk boundaries per spec;
        eventually Merges fold once over the concatenated states.
        Sparse-layout chunks (packed tiles + per-instance index) dispatch
        through the sparse runners; dense chunks through the dense ones.
        A dense chunk that carries edge rows (``StagedChunk.edges``) has
        its tiles filled on the device (``_device_fill``); one that
        carries tiles is uploaded as it is.
        Returns ([(xs, final, merged, ss, lsw)] per spec, occupancy | None).
        """
        N = len(specs)
        xs_p: List[list] = [[] for _ in range(N)]
        ss_p: List[list] = [[] for _ in range(N)]
        lsw_p: List[list] = [[] for _ in range(N)]
        carry = list(x0s)
        final: List[Optional[jax.Array]] = [None] * N
        n_total = nnz_total = 0
        sparse_seen = False
        for ch in chunks:
            # Aliasing (no copy) is safe ONLY because each chunk owns
            # its buffers (see SlicePrefetcher): JAX's device put
            # zero-copy-aliases aligned host buffers on CPU and defers
            # the host read even under copy=True, so a reused staging
            # buffer would be overwritten mid-execution.
            n = int(ch.count)
            n_total += n
            is_sparse = bool(getattr(ch, "is_sparse", False))
            # cluster shards keep only their partition rows: chunks from a
            # shard-local stream (repro.cluster.staging) are already
            # P_local-wide and pass through; full-width chunks (e.g. a
            # plain load_blocked_stream) are sliced here
            if is_sparse:
                sparse_seen = True
                nnz_total += (int(self._shard_axis(ch.nnz).sum())
                              + int(self._shard_axis(ch.bnnz).sum()))
                host = (ch.tiles, ch.btiles, ch.rows, ch.cols, ch.brows,
                        ch.bcols)
                tail = self._struct_tail
            else:
                host = None if ch.edges is not None else (ch.tiles, ch.btiles)
                tail = self._struct
            # on a mesh the chunk goes straight to its shards: partitions
            # over the model axes, instances over data when every spec's
            # runner shards them there
            iaxis = None
            if self.mesh is not None:
                axes = {self._instance_axis(s.pattern, s.effective_warm(), n)
                        for s in specs}
                iaxis = axes.pop() if len(axes) == 1 else None
            if host is None:  # edge rows: the device fills the tiles
                bufs = self._device_fill(ch, iaxis)
            else:
                bufs = self._put([self._shard_axis(a) for a in host], iaxis)
            for k, s in enumerate(specs):
                warm_k = s.effective_warm()
                # warm chunks chain exactly like sequential: the carry is
                # the last instance's converged state, which seeds the
                # next chunk's first instance inside the runner's scan
                seed = carry[k] if (s.pattern == "sequential" or warm_k) \
                    else x0s[k]
                run_fn = self._runner(s.program, s.pattern, None, n,
                                      sparse=is_sparse, warm=warm_k,
                                      multi=x0s[k].ndim == 3)
                xs, fin, _, ss, lsw = self._dispatch(
                    run_fn, *bufs, seed, *tail
                )
                carry[k] = final[k] = fin
                xs_p[k].append(xs)
                ss_p[k].append(ss)
                lsw_p[k].append(lsw)
        outs = []
        for k, s in enumerate(specs):
            assert final[k] is not None, "empty instance stream"
            # multi-source chunks stack per-chunk outputs on the instance
            # axis, which sits AFTER the leading query axis
            iax = 1 if x0s[k].ndim == 3 else 0
            xs = xs_p[k][0] if len(xs_p[k]) == 1 \
                else jnp.concatenate(xs_p[k], axis=iax)
            ss = ss_p[k][0] if len(ss_p[k]) == 1 \
                else jnp.concatenate(ss_p[k], axis=iax)
            lsw = lsw_p[k][0] if len(lsw_p[k]) == 1 \
                else jnp.concatenate(lsw_p[k], axis=iax)
            if s.pattern == "eventually" and s.merge == "mean":
                merged = self._merge_mean(xs, axis=iax)
            else:
                merged = jnp.zeros_like(final[k])
            outs.append((xs, final[k], merged, ss, lsw))
        occ = None
        if sparse_seen:
            lo, hi = self.parts or (0, self.bg.n_parts)
            total = n_total * (int(self.bg.n_tiles[lo:hi].sum())
                               + int(self.bg.n_btiles[lo:hi].sum()))
            occ = nnz_total / total if total else 0.0
        return outs, occ

    # ------------------------------------------------------ resumable state
    def resume_seed(self, final: np.ndarray, *, pad: float) -> np.ndarray:
        """Re-scatter a prior run's gathered ``EngineResult.final`` into
        the engine's padded (P, Vp) state layout — the resumable-run-state
        hook streaming ingestion uses: a tail run over appended instances
        passes this as ``RunSpec.x0`` (with ``warm_start=True`` for
        fixpoints, or under the sequential pattern, which carries state by
        definition) and continues the instance chain exactly where the
        previous run converged.  ``pad`` fills padding slots and must be
        the program's ``zero_fill``.  A (Q, V) multi-source final maps to
        a (Q, P, Vp) seed."""
        f = np.asarray(final, np.float32)
        if f.ndim == 1:
            return self.bg.scatter_vertex(f, pad)
        assert f.ndim == 2, f.shape
        return np.stack([self.bg.scatter_vertex(fi, pad) for fi in f])

    # ----------------------------------------------------------------- run
    def run(
        self,
        program: SemiringProgram,
        instance_weights: Optional[np.ndarray] = None,
        *,
        pattern: str,
        x0: Optional[np.ndarray] = None,
        tiles: Optional[jax.Array] = None,
        btiles: Optional[jax.Array] = None,
        sparse: Optional[SparseBlocked] = None,
        merge: Optional[str] = None,
        stream=None,
        staging: Optional[str] = None,
        warm_start: bool = False,
    ) -> EngineResult:
        """Execute ``program`` over the instance collection.

        Instance sources (exactly one):

        * ``instance_weights`` (I, E) — staged through the batched fill in
          the engine's ``layout`` (dense tensors or packed active tiles);
          with ``staging="async"`` (call or constructor) the fill is
          chunked behind a background prefetcher and overlaps execution.
        * pre-staged ``tiles``/``btiles`` (I, P, T|Tb, B, B) — e.g. from
          ``GoFSStore.load_blocked`` (always synchronous: already staged).
        * pre-staged ``sparse`` — a :class:`repro.core.blocked
          .SparseBlocked` packed batch (e.g. ``GoFSStore.load_blocked``
          with ``layout="sparse"``).
        * ``stream`` — an iterable of :class:`repro.gofs.prefetch
          .StagedChunk` (dense or sparse chunks; e.g.
          ``GoFSStore.load_blocked_stream``): chunks execute as they land,
          so disk reads overlap device compute.

        ``x0`` overrides ``program.init(bg)``.  ``merge="mean"`` computes
        the on-device eventually-dependent Merge.  All staging modes AND
        layouts are result-identical (bitwise for min-plus); sparse runs
        report the measured active-tile fraction in ``result.occupancy``.
        See the class docstring for pattern contracts.
        """
        return self.run_many(
            [RunSpec(program, pattern, x0=x0, merge=merge,
                     warm_start=warm_start)],
            instance_weights, tiles=tiles, btiles=btiles, sparse=sparse,
            stream=stream, staging=staging,
        )[0]

    def run_many(
        self,
        specs: Sequence[RunSpec],
        instance_weights: Optional[np.ndarray] = None,
        *,
        tiles: Optional[jax.Array] = None,
        btiles: Optional[jax.Array] = None,
        sparse: Optional[SparseBlocked] = None,
        stream=None,
        staging: Optional[str] = None,
    ) -> List[EngineResult]:
        """Execute N :class:`RunSpec` over ONE staged instance collection.

        The staging sources are the same as :meth:`run`, but the staged
        batch is materialized (and device-put) exactly once and every
        spec's runner consumes it — N concurrent analytics for one
        staging pass.  With ``stream=`` the sharing goes all the way to
        disk: a single prefetch pass feeds all N runners chunk by chunk
        (see ``_run_stream_many``).  Programs must agree on ``zero_fill``
        (the one property of the staged values an analytic observes);
        everything else — pattern, fixpoint vs iterate, x0, merge — may
        differ per spec.  Results are bitwise identical to running each
        spec alone."""
        specs = list(specs)
        assert specs, "run_many needs at least one RunSpec"
        for s in specs:
            assert s.pattern in PATTERNS, s.pattern
            assert s.merge is None or s.pattern == "eventually", \
                "merge is the eventually-dependent Merge step; " \
                "use pattern='eventually'"
        zero_fills = {s.program.zero_fill for s in specs}
        assert len(zero_fills) == 1, \
            f"programs disagree on zero_fill ({zero_fills}); they cannot " \
            f"share one staged batch — split into separate run_many calls"
        zero_fill = zero_fills.pop()
        staging = staging or self.staging
        # pre-staged batches carry their own layout: sparse= flips a dense
        # engine to the sparse runner for this call, tiles=/btiles= flip a
        # sparse engine to the dense runner — symmetric, nothing dropped
        assert sparse is None or tiles is None, \
            "pass either sparse= or tiles=/btiles=, not both"
        if sparse is not None:
            layout = "sparse"
        elif tiles is not None:
            layout = "dense"
        else:
            layout = self.layout
        x0s = []
        for s in specs:
            x0 = s.x0
            if x0 is None:
                assert s.program.init is not None, \
                    f"program {s.program.name!r} has no init; pass x0"
                x0 = s.program.init(self.bg)
            x0 = jnp.asarray(x0, jnp.float32)
            if self.mesh is not None:
                # ([Q,] P, Vp): the state's partitions over the model axes
                lead = [None] * (x0.ndim - 2)
                x0 = jax.device_put(x0, NamedSharding(
                    self.mesh, PartitionSpec(*lead, self._maxes)))
            if self.parts is not None:
                # x0 is always FULL-width ([Q,] P, Vp) — program inits and
                # resume_seed scatter globally; the shard keeps its rows
                x0 = x0[..., self.parts[0]:self.parts[1], :]
            x0s.append(x0)
        if self.parts is not None:
            # pre-staged full-width batches slice to the shard's rows too
            if sparse is not None:
                sparse = self._shard_sparse_batch(sparse)
            tiles = self._shard_axis(tiles)
            btiles = self._shard_axis(btiles)
        occ: Optional[float] = None

        if (stream is None and staging == "async" and tiles is None
                and sparse is None):
            assert instance_weights is not None, \
                "need instance_weights or pre-staged tiles+btiles"
            from repro.gofs.prefetch import SlicePrefetcher

            w = np.asarray(instance_weights, np.float32)
            if w.ndim == 1:
                w = w[None]
            # <= ~4 chunks by default: enough overlap, few compile shapes
            chunk = self.chunk_instances or max(1, -(-w.shape[0] // 4))
            if self.mesh is not None and self.chunk_instances is None:
                # keep each chunk's instance axis divisible by the data
                # axis, else per-chunk mesh runners fall back to replicated
                # instances and temporal parallelism is silently lost
                d = self._data_size()
                chunk = max(1, -(-chunk // d)) * d
            stream = SlicePrefetcher.from_weights(
                self.bg, w, zero=zero_fill,
                prefetch_depth=self.prefetch_depth, chunk_instances=chunk,
                layout=layout,
            )

        if stream is not None:
            outs, occ = self._run_stream_many(specs, stream, x0s)
        elif layout == "sparse":
            if sparse is None:
                assert instance_weights is not None, \
                    "need instance_weights, a SparseBlocked batch, or stream"
                sparse = self.stage_sparse(instance_weights, zero_fill)
            occ = sparse.occupancy()
            outs = []
            for s, x0 in zip(specs, x0s):
                run_fn = self._runner(s.program, s.pattern, s.merge,
                                      sparse.num_instances, sparse=True,
                                      warm=s.effective_warm(),
                                      multi=x0.ndim == 3)
                outs.append(self._dispatch_sparse(run_fn, sparse, x0))
        else:
            if tiles is None or btiles is None:
                assert instance_weights is not None, \
                    "need instance_weights, tiles+btiles, or stream"
                tiles, btiles = self.stage(instance_weights, zero_fill)
            elif not (isinstance(tiles, jax.Array)
                      and isinstance(btiles, jax.Array)):
                # host-staged dense batch: upload once per identity
                tiles, btiles = self._cached_device((tiles, btiles))
            outs = []
            for s, x0 in zip(specs, x0s):
                run_fn = self._runner(s.program, s.pattern, s.merge,
                                      int(tiles.shape[0]),
                                      warm=s.effective_warm(),
                                      multi=x0.ndim == 3)
                outs.append(self._dispatch(
                    run_fn, tiles, btiles, x0, *self._struct
                ))

        return [
            self._wrap_result(s.pattern, s.merge, out, occ,
                              warm=s.effective_warm(),
                              n_sources=int(x0.shape[0])
                              if x0.ndim == 3 else None)
            for s, out, x0 in zip(specs, outs, x0s)
        ]

    def _wrap_result(self, pattern: str, merge: Optional[str], out,
                     occ: Optional[float], warm: bool = False,
                     n_sources: Optional[int] = None) -> EngineResult:
        """Gather device outputs back to global vertex order + stats,
        spanned as ``engine.gather`` (on a chip this also waits for the
        run to finish)."""
        with TraceAnnotation("engine.gather"):
            return self._gather_result(pattern, merge, out, occ, warm,
                                       n_sources)

    def _gather_result(self, pattern: str, merge: Optional[str], out,
                       occ: Optional[float], warm: bool,
                       n_sources: Optional[int]) -> EngineResult:
        xs, _, merged, ss, lsw = out
        bg = self.bg
        if self.parts is not None:
            # re-assemble the global partition axis in rank order before
            # the vertex gather (contiguous shards -> plain concatenation
            # reconstructs the exact stacked layout).  Superstep stats are
            # identical on every process — the global halt vote keeps the
            # loops lockstep — so they stay local.
            cat = self.cluster.allgather_concat
            xs = cat(np.asarray(xs), axis=-2, tag="gather/xs")
            if pattern == "eventually" and merge == "mean":
                merged = cat(np.asarray(merged), axis=-2,
                             tag="gather/merged")

        def gather(x):  # (..., P, Vp) -> (..., V), any leading axes
            x = np.asarray(x)
            lead_shape = x.shape[:-2]
            flat = x.reshape((-1,) + x.shape[-2:])
            out = np.stack([bg.gather_vertex(flat[i])
                            for i in range(flat.shape[0])])
            return out.reshape(lead_shape + out.shape[-1:])

        values = gather(xs)
        # one exchange per superstep; a query axis runs every lane until
        # the slowest converges, each superstep moving all Q lanes
        ss_np = np.asarray(ss)
        steps = int(ss_np.sum()) if ss_np.ndim < 2 \
            else int(ss_np.max(axis=0).sum()) * ss_np.shape[0]
        self.exchange_bytes += int(round(
            steps * self.exchange_bytes_per_superstep))
        return EngineResult(
            pattern=pattern,
            values=values,
            # the last instance's state: the scan's carry, and (unlike a
            # data shard's own carry) right when instances are sharded
            final=values[..., -1, :],
            merged=gather(merged)
            if (pattern == "eventually" and merge == "mean") else None,
            stats={
                "supersteps": ss_np,
                "local_sweeps": np.asarray(lsw),
            },
            occupancy=occ,
            warm_start=warm,
            n_sources=n_sources,
            _n_published=int(bg.n_out.sum()),
            _n_parts=bg.n_parts,
            _num_vertices=len(bg.part_of),
        )
