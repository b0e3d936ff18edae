"""Double-buffered slice staging for the temporal engine (paper §V read
optimizations: overlap GoFS slice reads with Gopher computation).

The GoFFish paper's co-design argument is that iterative BSP execution is
only as fast as the store can feed it time-series instances; its storage
section overlaps slice materialization with computation so the engine never
waits on disk.  :class:`SlicePrefetcher` is that pipeline for the blocked
engine: it reads an edge attribute's (bin, pack) slices on a background
thread pool, applies the row-wise ``transform``, and hands chunks to the
consumer through a bounded in-order window — the same shape as the shard
prefetch in ``repro.train.data.PackedShardDataset``.  A dense chunk carries
the ``(I_chunk, E)`` edge rows, and the engine scatters them into
``(I_chunk, P, T, B, B)`` tiles on the device, so the host stages and
uploads bytes that scale with edges, not with tiles.  A sparse chunk
carries packed active tiles filled on the pool (the batched in-place
``BlockedGraph`` fills), and so does a ``stage_fn`` chunk (delta chains,
shard-local staging).

``prefetch_depth`` semantics:

* ``1``  — degenerate/synchronous: no thread is created; each chunk is
  staged on demand when the consumer asks for it.
* ``d>=2`` — double (d=2) or deeper buffering: up to ``d - 1`` chunks are
  staged ahead on the pool while the consumer processes the current one.

``inflight`` (default ``num_workers``) decouples read CONCURRENCY from
the window depth: up to ``max(prefetch_depth - 1, inflight)`` chunks are
submitted ahead, so ``num_workers`` pool threads really do read
concurrently without inflating ``prefetch_depth``.

Each chunk OWNS its buffers: they are allocated on the producer (so the
allocation cost overlaps execution too) and never rewritten after handoff,
which is what lets a device consumer alias them with no further copy
(``jnp.asarray`` zero-copy-aliases aligned host buffers on CPU, and even
``jnp.array(..., copy=True)`` defers the host read until execution —
reusing a buffer ring here corrupts in-flight chunks; the engine parity
tests pin this down).  In-flight memory stays bounded by the window: at
most ``max(prefetch_depth - 1, inflight) + 2`` chunks exist before the
consumer releases theirs.

Cancellation: ``close()`` (or exiting the ``with`` block) stops the
producer, cancels not-yet-started reads, and joins the pool — no leaked
threads; abandoning the iterator mid-stream triggers the same cleanup.

Doctest (in-memory source; the GoFS-backed form is
``GoFSStore.load_blocked_stream``):

>>> import numpy as np
>>> from repro.core.graph import GraphTemplate
>>> from repro.core.blocked import build_blocked
>>> from repro.gofs.prefetch import SlicePrefetcher
>>> tmpl = GraphTemplate(num_vertices=4,
...     src=np.array([0, 1, 2, 0]), dst=np.array([1, 2, 3, 2]))
>>> bg = build_blocked(tmpl, np.array([0, 0, 1, 1]), block_size=2)
>>> w = np.ones((5, 4), np.float32)  # 5 instances x 4 edges
>>> with SlicePrefetcher.from_weights(bg, w, zero=np.inf,
...                                   chunk_instances=2) as pf:
...     [(c.start, c.count) for c in pf]
[(0, 2), (2, 2), (4, 1)]
"""
from __future__ import annotations

import threading
from collections import deque
from concurrent.futures import CancelledError, ThreadPoolExecutor
from typing import Callable, Iterator, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

THREAD_PREFIX = "gofs-prefetch"


class StagedChunk:
    """A contiguous run of staged instances, ready for the engine.

    The chunk owns its arrays: the prefetcher never touches them again
    after handoff, so consumers may alias them (``jnp.asarray``) for as
    long as they hold the chunk.

    Dense layout, edge rows (``edges`` set): the chunk carries the
    instances' ``(count, E)`` float32 edge values, after the row-wise
    transform, and the semiring ``zero``; the engine scatters them into
    tiles on the device.  ``tiles``/``btiles`` are then filled on the host
    on first access (``BlockedGraph.fill_local_batch`` /
    ``fill_boundary_batch``) and kept, for host readers only.

    Dense layout, tiles (a ``stage_fn`` such as the shard-local staging of
    ``repro.cluster.staging``): ``tiles``/``btiles`` span the full
    template tile axis and the index fields are ``None``.  Sparse layout
    (``repro.core.blocked.SparseBlocked`` fields): the tile axes are
    packed pow2 buckets and ``rows``/``cols``/``brows``/``bcols`` carry
    the per-instance active-tile index (``-1`` padding).
    """

    def __init__(
        self,
        start: int,  # first (visible) instance index covered by this chunk
        count: int,
        tiles: Optional[np.ndarray] = None,  # (count, P, T|K, B, B) local
        btiles: Optional[np.ndarray] = None,  # (count, P, Tb|Kb, B, B)
        *,
        rows: Optional[np.ndarray] = None,  # (count, P, K) int32, sparse
        cols: Optional[np.ndarray] = None,  # (count, P, K)
        brows: Optional[np.ndarray] = None,  # (count, P, Kb)
        bcols: Optional[np.ndarray] = None,  # (count, P, Kb)
        nnz: Optional[np.ndarray] = None,  # (count, P) active local tiles
        bnnz: Optional[np.ndarray] = None,  # (count, P) active boundary
        # bytes materialized from the store for this chunk, when less than
        # the arrays' nbytes — a delta-chain reconstruction decodes each
        # unique tile payload once per chunk
        # (GoFSStore.load_blocked_stream).  None = fully materialized.
        staged_bytes: Optional[int] = None,
        edges: Optional[np.ndarray] = None,  # (count, E) float32 edge rows
        zero: Optional[float] = None,  # semiring zero of the edge rows
        bg=None,  # BlockedGraph the edge rows fill on the host
    ):
        assert (edges is not None) != (tiles is not None), \
            "a chunk carries either edge rows or tiles"
        self.start = start
        self.count = count
        self._tiles = tiles
        self._btiles = btiles
        self.rows, self.cols, self.brows, self.bcols = rows, cols, brows, bcols
        self.nnz, self.bnnz = nnz, bnnz
        self.staged_bytes = staged_bytes
        self.edges = edges
        self.zero = zero
        self._bg = bg

    @property
    def tiles(self) -> np.ndarray:
        if self._tiles is None:
            self._tiles = self._bg.fill_local_batch(self.edges,
                                                    zero=self.zero)
        return self._tiles

    @property
    def btiles(self) -> np.ndarray:
        if self._btiles is None:
            self._btiles = self._bg.fill_boundary_batch(self.edges,
                                                        zero=self.zero)
        return self._btiles

    @property
    def is_sparse(self) -> bool:
        return self.rows is not None

    @property
    def nbytes(self) -> int:
        """Bytes the host staged for the device: the edge rows, else the
        tiles and the sparse index arrays.  Never fills lazy tiles."""
        if self.edges is not None:
            return int(self.edges.nbytes)
        return int(sum(a.nbytes for a in (
            self._tiles, self._btiles, self.rows, self.cols, self.brows,
            self.bcols) if a is not None))


# reader(start, end) -> (end - start, E) float32 edge weights for the
# visible-instance span [start, end)
Reader = Callable[[int, int], np.ndarray]


class SlicePrefetcher:
    """Stage (bin, pack) attribute reads ahead of the engine run.

    Construct via :meth:`GoFSStore.load_blocked_stream
    <repro.gofs.store.GoFSStore.load_blocked_stream>` (disk slices) or
    :meth:`from_weights` (an in-memory ``(I, E)`` array — what
    ``TemporalEngine(staging="async")`` uses when handed raw weights).

    Iterating yields :class:`StagedChunk` in instance order.  The iterator
    is re-entrant: each ``iter()`` starts a fresh pass; only one pass may
    be active at a time.

    A pass is VERSION-CONSISTENT: the instance span set is pinned at
    construction, so a collection appended to mid-stream neither extends
    nor tears the pass — the stream covers exactly the instances visible
    when it was built.  A reader that wants the appended tail closes the
    stream (``close()`` is safe against an active consumer: the pass ends
    cleanly, never with a leaked ``CancelledError``) and opens a fresh one
    after ``GoFSStore.refresh()``.
    """

    def __init__(
        self,
        bg,
        reader: Optional[Reader],
        num_instances: int,
        *,
        zero: float,
        prefetch_depth: int = 2,
        chunk_instances: int = 1,
        num_workers: int = 1,
        inflight: Optional[int] = None,
        layout: str = "dense",
        bucket: Optional[int] = None,
        bbucket: Optional[int] = None,
        transform: Optional[Callable[[np.ndarray], np.ndarray]] = None,
        stage_fn: Optional[Callable[[int, int], StagedChunk]] = None,
    ):
        assert prefetch_depth >= 1, "prefetch_depth must be >= 1"
        assert chunk_instances >= 1 and num_workers >= 1
        assert layout in ("dense", "sparse"), layout
        assert reader is not None or stage_fn is not None
        self.bg = bg
        self.reader = reader
        self.num_instances = int(num_instances)
        self.zero = float(zero)
        self.prefetch_depth = int(prefetch_depth)
        self.chunk_instances = int(chunk_instances)
        self.num_workers = int(num_workers)
        # ``inflight`` decouples read concurrency from the ready-chunk
        # window: ``prefetch_depth`` alone bounded the submitted-ahead
        # count, so extra pool workers never actually overlapped reads
        # (depth=2 keeps exactly one read in flight no matter how many
        # workers).  The submit window is max(prefetch_depth - 1,
        # inflight); the default (num_workers) makes the worker count
        # mean what callers expect — num_workers concurrent reads.
        self.inflight = int(num_workers if inflight is None else inflight)
        assert self.inflight >= 1, "inflight must be >= 1"
        # block-sparse staging: pack only active tiles per chunk.  A shared
        # ``bucket``/``bbucket`` (e.g. precomputed from GoFS-recorded tile
        # maps or a whole-batch activity scan) keeps every chunk on one jit
        # shape; left None, each chunk picks its own pow2 bucket — still at
        # most O(log T) distinct shapes over the stream.
        self.layout = layout
        self.bucket = bucket
        self.bbucket = bbucket
        # ``transform``: applied to each chunk's (n, E) rows on the POOL
        # thread — row-wise derived weights (e.g. PageRank's outdegree
        # normalization) stream chunk-wise instead of forcing a full
        # (I, E) materialization up front.  Must be
        # per-instance independent: transform(w[s:e]) == transform(w)[s:e].
        # ``stage_fn``: replaces the read+fill entirely (e.g. the store's
        # delta-chain reconstruction); the windowing/cancellation machinery
        # is unchanged.
        self.transform = transform
        self.stage_fn = stage_fn
        self._spans: List[Tuple[int, int]] = [
            (s, min(s + self.chunk_instances, self.num_instances))
            for s in range(0, self.num_instances, self.chunk_instances)
        ]
        self._stop = threading.Event()
        self._lock = threading.Lock()  # guards _pool/_pending handoff
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending: deque = deque()

    # ------------------------------------------------------------ sources
    @classmethod
    def from_weights(
        cls,
        bg,
        weights: np.ndarray,
        *,
        zero: float,
        prefetch_depth: int = 2,
        chunk_instances: int = 1,
        num_workers: int = 1,
        inflight: Optional[int] = None,
        layout: str = "dense",
        bucket: Optional[int] = None,
        bbucket: Optional[int] = None,
    ) -> "SlicePrefetcher":
        """Prefetch from an in-memory (I, E) weight matrix: dense chunks
        are row slices of it, sparse chunks' packing (the expensive
        host-side scatter) overlaps the engine run."""
        w = np.asarray(weights, np.float32)
        if w.ndim == 1:
            w = w[None]
        if layout == "sparse" and bucket is None:
            # the weights are all in memory: one cheap activity scan pins
            # a batch-wide bucket so every chunk shares one jit shape
            bucket, bbucket = bg.sparse_buckets(w, zero=zero)
        return cls(
            bg, lambda s, e: w[s:e], w.shape[0], zero=zero,
            prefetch_depth=prefetch_depth, chunk_instances=chunk_instances,
            num_workers=num_workers, inflight=inflight, layout=layout,
            bucket=bucket, bbucket=bbucket,
        )

    # ------------------------------------------------------------ staging
    def _stage(self, span: Tuple[int, int]) -> StagedChunk:
        """Read one chunk (and transform its rows, or fill its sparse
        tiles) into chunk-owned buffers: on the pool, so the work overlaps
        the consumer's execution; on the caller when synchronous.  Spanned
        as ``gofs.stage`` on the profiler's trace."""
        with TraceAnnotation("gofs.stage"):
            return self._fill(*span)

    def _fill(self, s: int, e: int) -> StagedChunk:
        n = e - s
        if self.stage_fn is not None:
            return self.stage_fn(s, e)
        w = self.reader(s, e)
        if self.transform is not None:
            w = np.asarray(self.transform(w), np.float32)
            assert w.shape[0] == n, (w.shape, n)
        if self.layout == "sparse":
            out_l = out_b = None
            if self.bucket is not None and self.bbucket is not None:
                out_l, out_b = self.bg.alloc_batch_buffers(
                    n, bucket=self.bucket, bbucket=self.bbucket
                )
            tiles, rows, cols, nnz = self.bg.fill_local_batch_sparse(
                w, zero=self.zero, bucket=self.bucket, out=out_l
            )
            btiles, brows, bcols, bnnz = self.bg.fill_boundary_batch_sparse(
                w, zero=self.zero, bucket=self.bbucket, out=out_b
            )
            return StagedChunk(
                start=s, count=n, tiles=tiles, btiles=btiles,
                rows=rows, cols=cols, brows=brows, bcols=bcols,
                nnz=nnz, bnnz=bnnz,
            )
        # dense: the chunk ships its edge rows, and the engine fills the
        # tiles on the device
        return StagedChunk(start=s, count=n,
                           edges=np.asarray(w, np.float32), zero=self.zero,
                           bg=self.bg)

    def __iter__(self) -> Iterator[StagedChunk]:
        if self.prefetch_depth == 1:
            return self._iter_sync()
        return self._iter_async()

    def _iter_sync(self) -> Iterator[StagedChunk]:
        self._stop.clear()  # fresh pass
        for span in self._spans:
            if self._stop.is_set():
                return
            yield self._stage(span)

    def _iter_async(self) -> Iterator[StagedChunk]:
        assert self._pool is None, "one prefetch pass at a time"
        self._stop.clear()  # fresh pass
        pool = ThreadPoolExecutor(
            max_workers=self.num_workers, thread_name_prefix=THREAD_PREFIX
        )
        self._pool = pool
        pending = self._pending
        pending.clear()
        todo = iter(self._spans)

        def submit_one() -> None:
            with self._lock:
                if self._stop.is_set() or self._pool is not pool:
                    return  # a concurrent close() ended this pass
                try:
                    span = next(todo)
                except StopIteration:
                    return
                try:
                    pending.append(pool.submit(self._guarded_stage, span))
                except RuntimeError:  # pool shut down under us
                    return

        try:
            # keep the window full: up to max(depth-1, inflight) chunks
            # submitted ahead (inflight of them reading concurrently)
            for _ in range(max(self.prefetch_depth - 1, self.inflight)):
                submit_one()
            while True:
                try:
                    fut = pending.popleft()
                except IndexError:  # drained, or cleared by close()
                    return
                try:
                    # the consumer blocked on the next chunk
                    with TraceAnnotation("gofs.wait"):
                        chunk = fut.result()
                except CancelledError:
                    # a concurrent close() — e.g. a session observing an
                    # append mid-stream — cancelled this chunk between our
                    # popleft and its snapshot; end the pass cleanly
                    return
                # Submit BEFORE the yield: the next chunk's read + fill
                # must already be running while the consumer executes this
                # one (on CPU the jit call itself is where execution time
                # is spent, so a submit deferred to the next pull would
                # never overlap it).
                submit_one()
                if chunk is None:  # producer observed stop mid-pass
                    return
                yield chunk
        finally:
            self.close()

    def _guarded_stage(self, span) -> Optional[StagedChunk]:
        if self._stop.is_set():
            return None
        return self._stage(span)

    # ------------------------------------------------------------- cancel
    def close(self) -> None:
        """Stop producing, cancel queued reads, join the pool (idempotent).

        Safe to call mid-stream, from the consumer or any other thread
        (a lock serializes the pool/pending handoff against the consumer's
        submits): in-flight chunks finish (their buffer writes must not be
        torn), queued chunks are cancelled, and the pool threads exit
        before this returns."""
        self._stop.set()
        with self._lock:
            pool, self._pool = self._pool, None
            futs = list(self._pending)
            self._pending.clear()
        if pool is not None:
            for fut in futs:
                fut.cancel()
            pool.shutdown(wait=True)

    def __enter__(self) -> "SlicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
