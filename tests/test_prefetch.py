"""SlicePrefetcher + async engine staging: the double-buffered GoFS read
pipeline must be invisible in the results — async-vs-sync staging parity is
BITWISE on all three iBSP patterns — and clean under cancellation (no
leaked threads, prefetch_depth=1 degenerates to thread-free sync reads).

Dense chunks carry edge rows and the engine fills their tiles on the
device: that fill equals the host's ``fill_local_batch`` /
``fill_boundary_batch`` (bitwise for an INF zero, with or without parallel
edges sharing a slot), and ``device_fills`` counts one per chunk."""
import threading

import numpy as np
import pytest

from repro.core.blocked import build_blocked
from repro.core.engine import (
    TemporalEngine,
    min_plus_program,
    pagerank_program,
    source_init,
)
from repro.core.algorithms import pagerank
from repro.core.graph import GraphTemplate
from repro.gofs import GoFSStore
from repro.gofs.prefetch import THREAD_PREFIX, SlicePrefetcher, StagedChunk
from repro.gopher import GopherSession

from tests.conftest import TINY


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(THREAD_PREFIX)]


@pytest.fixture(scope="module")
def env(tiny_collection, tiny_partitioned, tiny_gofs):
    tmpl, assign, sg_ids, subs = tiny_partitioned
    bg = build_blocked(tmpl, assign, TINY.block_size)
    store = GoFSStore(tiny_gofs, cache_slots=TINY.cache_slots)
    I = len(tiny_collection)
    weights = np.stack([tiny_collection.edge_values(t, "latency")
                        for t in range(I)])
    active = np.stack([tiny_collection.edge_values(t, "active")
                       for t in range(I)])
    return tmpl, bg, store, weights, active


# ---------------------------------------------------------------- staging
def test_fill_batch_out_buffer_in_place(env):
    tmpl, bg, store, weights, active = env
    ref_l = bg.fill_local_batch(weights)
    ref_b = bg.fill_boundary_batch(weights)
    buf_l, buf_b = bg.alloc_batch_buffers(weights.shape[0])
    buf_l[...] = -7.0  # stale data from a previous ring pass
    buf_b[...] = -7.0
    got_l = bg.fill_local_batch(weights, out=buf_l)
    got_b = bg.fill_boundary_batch(weights, out=buf_b)
    assert np.array_equal(got_l, ref_l) and np.array_equal(got_b, ref_b)
    # in place: no second copy
    assert np.shares_memory(got_l, buf_l) and np.shares_memory(got_b, buf_b)


def test_edge_attr_rows_matches_matrix(env):
    tmpl, bg, store, weights, active = env
    full = store.edge_attr_matrix("latency")
    rows = store.edge_attr_rows("latency", [2, 0])
    assert np.array_equal(rows[0], full[2])
    assert np.array_equal(rows[1], full[0])


def test_stream_chunks_match_bulk_load(env):
    tmpl, bg, store, weights, active = env
    tiles, btiles = store.load_blocked(bg, "latency")
    for depth in (1, 2, 3):
        pf = store.load_blocked_stream(bg, "latency", prefetch_depth=depth,
                                       chunk_instances=2)
        got_t, got_b, starts = [], [], []
        with pf:
            for ch in pf:
                starts.append(ch.start)
                got_t.append(ch.tiles)  # chunk-owned: safe to hold
                got_b.append(ch.btiles)
        assert starts == list(range(0, store.num_timesteps(), 2))
        assert np.array_equal(np.concatenate(got_t), tiles)
        assert np.array_equal(np.concatenate(got_b), btiles)


def test_dense_chunks_carry_edge_rows_and_fill_tiles_lazily(env):
    """A dense stream chunk ships its (n, E) rows; its host tiles fill on
    first access only, and counting its bytes does not force them."""
    tmpl, bg, store, weights, active = env
    with store.load_blocked_stream(bg, "latency", chunk_instances=2) as pf:
        ch = next(iter(pf))
    rows = store.edge_attr_rows("latency", range(ch.start, ch.start + 2))
    assert ch.edges.dtype == np.float32
    assert np.array_equal(ch.edges, rows)
    assert ch.nbytes == rows.nbytes == 2 * len(tmpl.src) * 4
    assert ch._tiles is None and ch._btiles is None
    assert np.array_equal(ch.tiles, bg.fill_local_batch(rows))
    assert ch.tiles is ch.tiles  # filled once, kept


def _graph_with(parallel: bool, seed: int = 11):
    """A 48-vertex graph over 4 partitions; ``parallel`` repeats some
    local and some cut edges, so two edges share a tile slot in both
    tile sets."""
    rng = np.random.default_rng(seed)
    V = 48
    pairs = {(int(a), int(b)) for a, b in rng.integers(0, V, (160, 2))
             if a != b}
    src, dst = map(np.array, zip(*sorted(pairs)))
    assign = np.arange(V) % 4
    if parallel:
        local = np.flatnonzero(assign[src] == assign[dst])[:5]
        cut = np.flatnonzero(assign[src] != assign[dst])[:5]
        src = np.concatenate([src, src[local], src[cut]])
        dst = np.concatenate([dst, dst[local], dst[cut]])
    bg = build_blocked(GraphTemplate(num_vertices=V, src=src, dst=dst),
                       assign, block_size=4)
    w = rng.random((5, len(src))).astype(np.float32) + 0.5
    return bg, w


@pytest.mark.parametrize("zero", [np.inf, 0.0], ids=["inf", "zero"])
@pytest.mark.parametrize("parallel", [False, True],
                         ids=["unique", "shared"])
def test_device_fill_matches_host_fill(zero, parallel):
    """The engine's device fill of one chunk equals the host fill:
    bitwise for ``.set`` (unique slots) and ``.min`` (INF zero, shared
    slots), within 1e-6 for ``.add`` (0.0 zero, shared slots: the sum
    order is the scatter's)."""
    bg, w = _graph_with(parallel)
    assert bg._local_slots_unique() is not parallel
    assert bg._boundary_slots_unique() is not parallel
    eng = TemporalEngine(bg)
    tiles, btiles = eng._device_fill(
        StagedChunk(0, len(w), edges=w, zero=zero, bg=bg))
    want = (bg.fill_local_batch(w, zero=zero),
            bg.fill_boundary_batch(w, zero=zero))
    for got, ref in zip((tiles, btiles), want):
        got = np.asarray(got)
        assert got.shape == ref.shape and got.dtype == ref.dtype
        if zero == 0.0 and parallel:
            np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)
        else:
            assert np.array_equal(got, ref)
    assert eng.device_fills == 1


# ------------------------------------------------------- engine parity
def test_async_staging_bitwise_parity_all_patterns(env):
    """TemporalEngine(staging="async") == sync staging, bit for bit, on
    sequential / independent / eventually (the acceptance contract)."""
    tmpl, bg, store, weights, active = env
    sync = TemporalEngine(bg)
    async_ = TemporalEngine(bg, staging="async", chunk_instances=1)
    prog = min_plus_program("sssp", init=source_init(0))
    for pattern in ("sequential", "independent"):
        a = sync.run(prog, weights, pattern=pattern)
        b = async_.run(prog, weights, pattern=pattern)
        assert np.array_equal(a.values, b.values), pattern
        assert np.array_equal(a.final, b.final), pattern
        assert np.array_equal(a.stats["supersteps"], b.stats["supersteps"])
    pw = pagerank.edge_weights_for_instances(tmpl.src, active,
                                             tmpl.num_vertices)
    pp = pagerank_program(tmpl.num_vertices, iters=8)
    a = sync.run(pp, pw, pattern="eventually", merge="mean")
    b = async_.run(pp, pw, pattern="eventually", merge="mean")
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.merged, b.merged)


def test_async_parity_many_chunks_in_flight(env):
    """Many more chunks than the prefetch window: chunk buffers must stay
    untouched after handoff while the device aliases them (JAX's device
    put zero-copy-aliases host buffers on CPU and defers host reads even
    under copy=True — a reused staging ring corrupts in-flight chunks;
    this is the regression test that caught it)."""
    tmpl, bg, store, weights, active = env
    w9 = np.concatenate([weights, weights * 2.0, weights * 3.0])  # I=9
    sync = TemporalEngine(bg)
    # depth=2, chunk=1 -> 9 chunks stream through a 2-deep window
    async_ = TemporalEngine(bg, staging="async", prefetch_depth=2,
                            chunk_instances=1)
    prog = min_plus_program("sssp", init=source_init(0))
    for pattern in ("sequential", "independent"):
        a = sync.run(prog, w9, pattern=pattern)
        b = async_.run(prog, w9, pattern=pattern)
        assert np.array_equal(a.values, b.values), pattern
        assert np.array_equal(a.final, b.final), pattern


@pytest.mark.parametrize("pattern", ["sequential", "independent"])
@pytest.mark.parametrize("chunk", [1, 2, 3])
def test_dense_stream_fills_on_device_bitwise(env, pattern, chunk):
    """A dense GoFS stream through ``_run_stream_many`` (edge rows, device
    fill, ragged last chunk included) equals the sync host-filled run bit
    for bit, with one device fill per chunk."""
    tmpl, bg, store, weights, active = env
    prog = min_plus_program("sssp", init=source_init(0))
    a = TemporalEngine(bg).run(prog, weights, pattern=pattern)
    eng = TemporalEngine(bg)
    b = eng.run(prog, pattern=pattern, stream=store.load_blocked_stream(
        bg, "latency", chunk_instances=chunk))
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.stats["supersteps"], b.stats["supersteps"])
    assert eng.device_fills == -(-store.num_timesteps() // chunk)


def test_session_counts_device_fills(env, tiny_gofs):
    """``last_run_report["device_fills"]``: one per chunk of a dense
    stream pass; 0 for a sparse stream and for a resident
    (``load_blocked``) run, which upload host tiles."""
    tmpl, bg, store, weights, active = env
    sess = GopherSession(GoFSStore(tiny_gofs))
    chunks = -(-store.num_timesteps() // store.ipack)
    dense = sess.run(sess.plan("sssp", source=0, staging="async"))
    rep = sess.last_run_report
    assert rep["device_fills"] == chunks
    assert rep["staged_bytes"] == store.num_timesteps() * len(tmpl.src) * 4
    sparse = sess.run(sess.plan("sssp", source=0, staging="async",
                                layout="sparse"))
    assert sess.last_run_report["device_fills"] == 0
    assert np.array_equal(dense.engine.values, sparse.engine.values)
    resident = GopherSession(GoFSStore(tiny_gofs), staging_cache_bytes=1e9)
    r = resident.run(resident.plan("sssp", source=0))
    assert resident.last_run_report["device_fills"] == 0
    assert resident.last_run_report["staged_bytes"] > 0
    assert np.array_equal(dense.engine.values, r.engine.values)


def test_gofs_stream_engine_matches_sync(env):
    """End-to-end disk path: engine consuming load_blocked_stream chunks
    equals the one-shot load_blocked staging."""
    tmpl, bg, store, weights, active = env
    eng = TemporalEngine(bg)
    prog = min_plus_program("sssp", init=source_init(0))
    tiles, btiles = store.load_blocked(bg, "latency")
    a = eng.run(prog, tiles=tiles, btiles=btiles, pattern="sequential")
    b = eng.run(prog, pattern="sequential",
                stream=store.load_blocked_stream(bg, "latency"))
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.final, b.final)
    assert _prefetch_threads() == []  # pool joined at stream exhaustion


# ------------------------------------------------ depth/cancel semantics
def test_depth1_is_synchronous_no_threads(env):
    tmpl, bg, store, weights, active = env
    pf = store.load_blocked_stream(bg, "latency", prefetch_depth=1,
                                   chunk_instances=1)
    seen = 0
    for ch in pf:
        assert _prefetch_threads() == []  # no pool in degenerate mode
        seen += 1
    assert seen == store.num_timesteps()


def test_close_mid_stream_no_leaked_threads(env):
    tmpl, bg, store, weights, active = env
    pf = store.load_blocked_stream(bg, "latency", prefetch_depth=3,
                                   chunk_instances=1)
    it = iter(pf)
    first = next(it)
    assert first.start == 0
    assert _prefetch_threads() != []  # pool live mid-stream
    pf.close()
    assert _prefetch_threads() == [] or all(
        not t.is_alive() for t in _prefetch_threads()
    )
    assert list(it) == []  # cancelled stream yields nothing further


def test_close_from_another_thread(env):
    """close() may race the consumer's own submits (watchdog/timeout
    threads): the pool/pending handoff is locked, so a mid-iteration
    close from outside must neither crash the consumer nor leak."""
    tmpl, bg, store, weights, active = env
    pf = store.load_blocked_stream(bg, "latency", prefetch_depth=2,
                                   chunk_instances=1)
    closer_done = threading.Event()
    seen = []
    it = iter(pf)
    seen.append(next(it).start)

    def closer():
        pf.close()
        closer_done.set()

    t = threading.Thread(target=closer)
    t.start()
    for ch in it:  # either ends early or finishes; must not raise
        seen.append(ch.start)
    t.join(timeout=10)
    assert closer_done.is_set()
    assert all(not th.is_alive() for th in _prefetch_threads())
    assert seen == sorted(set(seen))  # in-order, no duplicates


def test_prefetcher_reiterates_after_close(env):
    tmpl, bg, store, weights, active = env
    pf = store.load_blocked_stream(bg, "latency", prefetch_depth=2,
                                   chunk_instances=2)
    it = iter(pf)
    next(it)
    pf.close()
    counts = [c.count for c in pf]  # fresh pass after cancel
    assert sum(counts) == store.num_timesteps()
    assert _prefetch_threads() == []
