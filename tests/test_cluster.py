"""Multi-process cluster runtime: exchange protocol, shard staging, parity.

Thread-level tests drive the raw :class:`TcpExchange` / runtime pair in
one process (generous socket timeouts — two peers may compile/fill at
very different speeds); the end-to-end engine parity runs REAL worker
processes through ``repro.launch.cluster_graph --check`` (the CI
multi-process lane's command), asserting bitwise-identical results and
per-host staged bytes below the single-process cost.
"""
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.cluster.runtime import (ClusterRuntime, ExchangeError,
                                   TcpExchange)
from conftest import TINY

TIMEOUT = 900.0  # compile skew between peers can be minutes, not seconds


@pytest.fixture(scope="module")
def cluster_store_root(tiny_collection, tmp_path_factory):
    from repro.gofs import deploy_collection

    root = str(tmp_path_factory.mktemp("gofs_cluster"))
    deploy_collection(tiny_collection, TINY, root)
    return root


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def two_runtimes(fn):
    """Run ``fn(runtime)`` on two in-process peers; return [r0, r1]."""
    port = free_port()
    results = [None, None]
    errors = [None, None]

    def peer(pid):
        try:
            if pid == 0:
                ex = TcpExchange.listen(port, 2, host="127.0.0.1",
                                        timeout=TIMEOUT)
            else:
                ex = TcpExchange.connect("127.0.0.1", port, pid, 2,
                                         timeout=TIMEOUT)
            rt = ClusterRuntime(pid, 2, exchange=ex)
            try:
                results[pid] = fn(rt)
            finally:
                rt.close()
        except BaseException as e:  # noqa: BLE001 - surfaced to the test
            errors[pid] = e

    ts = [threading.Thread(target=peer, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(TIMEOUT)
    assert not any(t.is_alive() for t in ts), "peer thread hung"
    for e in errors:
        if e is not None:
            raise e
    return results


# --------------------------------------------------------------- runtime

def test_partition_shard_contiguous_cover():
    from repro.cluster.runtime import shard_range

    rt = ClusterRuntime(0, 1)
    assert rt.partition_shard(5) == (0, 5)
    spans = [shard_range(7, pid, 3) for pid in range(3)]
    assert spans == [(0, 3), (3, 5), (5, 7)]  # remainder to low ranks
    # contiguous concat covers exactly 0..n_parts
    assert spans[0][0] == 0 and spans[-1][1] == 7
    for a, b in zip(spans, spans[1:]):
        assert a[1] == b[0]


def test_shard_of_partition_inverts_shards():
    from repro.cluster.runtime import shard_range

    for n_procs in (1, 2, 3):
        for n_parts in (1, 4, 7):
            if n_procs > n_parts:
                continue
            for p in range(n_parts):
                owners = [pid for pid in range(n_procs)
                          if shard_range(n_parts, pid, n_procs)[0] <= p
                          < shard_range(n_parts, pid, n_procs)[1]]
                assert len(owners) == 1  # every partition has ONE owner


def test_tcp_allgather_ordered_and_barrier():
    def body(rt):
        out = []
        for i in range(3):
            parts = rt.allgather(f"round/{i}",
                                 {"pid": rt.process_id, "i": i})
            out.append(parts)
            rt.barrier(f"b/{i}")
        return out

    r0, r1 = two_runtimes(body)
    assert r0 == r1  # every peer sees the identical rank-ordered payloads
    for i, parts in enumerate(r0):
        assert parts == [{"pid": 0, "i": i}, {"pid": 1, "i": i}]


def test_allgather_concat_rank_order():
    def body(rt):
        lo = rt.process_id * 2
        shard = np.arange(lo, lo + 2, dtype=np.float32).reshape(2, 1)
        return rt.allgather_concat(shard, axis=0, tag="cat")

    r0, r1 = two_runtimes(body)
    want = np.arange(4, dtype=np.float32).reshape(4, 1)
    assert np.array_equal(r0, want) and np.array_equal(r1, want)


def test_all_reduce_or_votes():
    def body(rt):
        return (rt.all_reduce_or(rt.process_id == 0, tag="v1"),
                rt.all_reduce_or(False, tag="v2"))

    for got in two_runtimes(body):
        assert got == (True, False)


def test_tag_divergence_raises():
    def body(rt):
        # peers disagree on what this exchange IS -> both must fail fast
        rt.allgather(f"tag-{rt.process_id}", 1)

    with pytest.raises(ExchangeError):
        two_runtimes(body)


def test_check_consistent_divergence_raises():
    def body(rt):
        rt.check_consistent("chunk/0", ("span", rt.process_id))

    with pytest.raises(ExchangeError):
        two_runtimes(body)


# ------------------------------------------------------- gather backend

def test_cluster_gather_matches_host_fold():
    """The distributed combine must be BITWISE the single-process fold."""
    import jax.numpy as jnp

    from repro.cluster.gather import ClusterGather
    from repro.core.comm import HostGather
    from repro.core.semiring import MIN_PLUS, PLUS_MUL

    rng = np.random.default_rng(7)
    buf = rng.random((4, 9), dtype=np.float32)
    buf_min = np.where(rng.random((4, 9)) < 0.3, np.inf, buf)

    for sr, full in ((MIN_PLUS, buf_min), (PLUS_MUL, buf)):
        want = np.asarray(HostGather().combine_boundary(
            jnp.asarray(full), sr))

        def body(rt, sr=sr, full=full):
            lo, hi = rt.partition_shard(4)
            cg = ClusterGather(runtime=rt)
            return np.asarray(cg.combine_boundary(
                jnp.asarray(full[lo:hi]), sr))

        for got in two_runtimes(body):
            assert np.array_equal(got, want), sr.name


# ------------------------------------------------------- shard staging

def test_edge_attr_rows_halo_completes_boundary(cluster_store_root):
    """Regression: a partition's INCOMING cut edges live in the PEER
    partitions' remote slices — without the halo read the boundary tiles
    stage as semiring-zero and cross-shard propagation dies."""
    from repro.gofs import GoFSStore
    from repro.gopher import GopherSession

    store = GoFSStore(cluster_store_root)
    sess = GopherSession(store)
    bg, P = sess.bg, sess.bg.n_parts
    I = int(store.meta["num_instances"])
    name = next(n for n, a in store._e_attrs.items() if a.constant is None)

    w = store.edge_attr_rows(name, range(I))
    full_t = bg.fill_local_batch(w, zero=np.inf)
    full_b = bg.fill_boundary_batch(w, zero=np.inf)
    # which cut edges arrive from OUTSIDE a shard range: source partition
    # of each boundary-scattered edge vs the owned range
    spart = np.asarray(bg.part_of)[sess.src[np.asarray(bg.re_edge_id)]]
    for parts in [(0, P // 2), (P // 2, P)]:
        lo, hi = parts
        wsh = store.edge_attr_rows(name, range(I), parts=range(lo, hi),
                                   fill=np.inf, halo=True)
        st = bg.fill_local_batch(wsh, zero=np.inf, parts=parts)
        sb = bg.fill_boundary_batch(wsh, zero=np.inf, parts=parts)
        assert np.array_equal(st, full_t[:, lo:hi])
        assert np.array_equal(sb, full_b[:, lo:hi])
        # and WITHOUT halo the boundary fill is incomplete exactly when
        # some owned partition has an incoming cut edge from a peer shard
        dst_in = (np.asarray(bg.re_part) >= lo) & (np.asarray(bg.re_part) < hi)
        external = bool(np.any(dst_in & ((spart < lo) | (spart >= hi))))
        wnh = store.edge_attr_rows(name, range(I), parts=range(lo, hi),
                                   fill=np.inf, halo=False)
        sb_nh = bg.fill_boundary_batch(wnh, zero=np.inf, parts=parts)
        assert np.array_equal(sb_nh, sb) == (not external)


def test_shard_stream_bytes_halve(cluster_store_root):
    """Each peer's materialized bytes are its shard fraction; the spans
    and layouts are consistency-checked at every chunk boundary."""
    from repro.cluster.staging import shard_stream
    from repro.gofs import GoFSStore
    from repro.gopher import GopherSession

    store = GoFSStore(cluster_store_root)
    sess = GopherSession(store)
    name = next(n for n, a in store._e_attrs.items() if a.constant is None)

    # single-process total (runtime=None -> full partition range)
    with shard_stream(store, sess.bg, name, None, zero=np.inf) as full:
        for _ in full:
            pass
        total = full.staged_bytes
    assert total > 0

    def body(rt):
        with shard_stream(store, sess.bg, name, rt, zero=np.inf) as st:
            for _ in st:
                pass
            return st.staged_bytes, st.chunks

    (b0, c0), (b1, c1) = two_runtimes(body)
    assert c0 == c1 > 0
    assert b0 < total and b1 < total
    assert b0 + b1 == total  # contiguous shards partition the tile bytes


def test_shard_stream_span_divergence_raises(cluster_store_root):
    from repro.cluster.staging import shard_stream
    from repro.gofs import GoFSStore
    from repro.gopher import GopherSession

    store = GoFSStore(cluster_store_root)
    sess = GopherSession(store)
    name = next(n for n, a in store._e_attrs.items() if a.constant is None)

    def body(rt):
        # peers disagree on the chunk grain -> first boundary check fails
        with shard_stream(store, sess.bg, name, rt, zero=np.inf,
                          chunk_instances=1 + rt.process_id) as st:
            for _ in st:
                pass

    with pytest.raises(ExchangeError):
        two_runtimes(body)


# ------------------------------------------------- end-to-end processes

def test_two_process_parity_end_to_end(tmp_path):
    """The tentpole acceptance: REAL worker processes, shard-local
    staging, inter-process gather — results bitwise-identical to the
    single-process run, per-host staged bytes strictly below it.
    (Same command as the CI multi-process lane, sssp-only for speed.)"""
    env = dict(os.environ, PYTHONPATH="src")
    cmd = [
        sys.executable, "-m", "repro.launch.cluster_graph",
        "--num-processes", "2", "--apps", "sssp", "--size", "tiny",
        "--deploy", str(tmp_path / "gofs"),
        "--out", str(tmp_path / "out"), "--check",
    ]
    proc = subprocess.run(cmd, env=env, cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=TIMEOUT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "parity OK" in proc.stdout


def test_unknown_transport_raises():
    """A transport is chosen, not guessed: anything but tcp/jax raises
    instead of silently falling back."""
    from repro.cluster.runtime import init_cluster

    with pytest.raises(ValueError, match="transport"):
        init_cluster("127.0.0.1:1", 2, 0, transport="auto")


def test_localhost_workers_refused_on_an_accelerator(monkeypatch):
    """Several localhost workers would each claim every chip of an
    accelerator host: the launcher probes the platform in a child and
    refuses; one process, or a CPU platform, passes."""
    from repro.launch import cluster_graph

    def probe(platform):
        def run(*_a, **_kw):
            return subprocess.CompletedProcess([], 0, stdout=f"{platform}\n")
        return run

    monkeypatch.setattr(cluster_graph.subprocess, "run", probe("tpu"))
    with pytest.raises(SystemExit, match="claim every chip"):
        cluster_graph.refuse_on_accelerator(2)
    cluster_graph.refuse_on_accelerator(1)
    monkeypatch.setattr(cluster_graph.subprocess, "run", probe("cpu"))
    cluster_graph.refuse_on_accelerator(2)
