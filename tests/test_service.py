"""GopherService: warm serving, source-axis batching, continuous batching.

Contracts pinned here:

* batching invisibility — results delivered through the service (any mix
  of analytics, any batch the admission loop happens to form) are bitwise
  identical to plain cold-session runs of the same queries.
* source-axis merging — same-analytic scalar-source queries coalesce into
  one multi-source plan ONLY when every other parameter agrees; an atomic
  ``submit_many`` on an idle service forms exactly one admission.
* warm cache — a repeated query re-stages zero bytes (the session-level
  staging cache holds the batch across requests); ``prestage`` moves the
  staging cost ahead of the first query.
* request plumbing — bad requests raise on the caller's thread, engine
  failures are delivered through ``wait()`` (the loop survives), ``stop``
  drains what was already queued, concurrent submitters all get their
  own correct answers.
"""
import threading

import numpy as np
import pytest

from repro.core.blocked import build_blocked
from repro.core.graph import GraphTemplate
from repro.gopher import GopherService, GopherSession


V, E, I, P, B = 64, 200, 5, 4, 16


def _arrays(seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, V, E)
    dst = rng.integers(0, V, E)
    w = rng.uniform(0.5, 2.0, (I, E)).astype(np.float32)
    plates = rng.integers(-1, 3, (I, V))
    bg = build_blocked(GraphTemplate(num_vertices=V, src=src, dst=dst),
                       rng.integers(0, P, V), block_size=B)
    return bg, src, dst, w, plates


def _session(**kw):
    bg, src, dst, w, plates = _arrays()
    return GopherSession.from_blocked(
        bg, weights={"latency": w}, vertex_attrs={"plate": plates},
        src=src, dst=dst, **kw)


@pytest.fixture(scope="module")
def ref_session():
    """One plain session for reference runs (results are deterministic,
    so caching state is irrelevant to the parity assertions)."""
    return _session()


@pytest.fixture()
def service():
    svc = GopherService(session=_session())
    yield svc.start()
    svc.stop()


def _assert_same_output(ref, got, label=""):
    assert set(ref.output) == set(got.output), label
    for k in ref.output:
        a, b = ref.output[k], got.output[k]
        if isinstance(a, np.ndarray):
            assert np.array_equal(a, b), (label, k)
        else:
            assert a == b, (label, k)


# --------------------------------------------------------------------------
# batching invisibility
# --------------------------------------------------------------------------

def test_batched_sssp_bitwise_matches_singles(service, ref_session):
    sources = [0, 7, 13, 42]
    refs = [ref_session.run(ref_session.plan("sssp", source=s))
            for s in sources]
    outs = service.query_many([("sssp", {"source": s}) for s in sources])
    for s, r, o in zip(sources, refs, outs):
        assert np.array_equal(r.output["final"], o.output["final"]), s
    # all four rode ONE admission -> one merged multi-source plan
    assert service.report()["widest_batch"] == 4
    assert service.report()["batches"] == 1


def test_mixed_analytic_batch_matches_singles(service, ref_session):
    reqs = [("nhop", {"source": 3, "n_hops": 2}),
            ("sssp", {"source": 9}),
            ("nhop", {"source": 9, "n_hops": 2}),
            ("tracking", {"plate": 1, "initial_vertex": 0})]
    outs = service.query_many(reqs)
    for (name, params), got in zip(reqs, outs):
        ref = ref_session.run(ref_session.plan(name, **params))
        _assert_same_output(ref, got, label=name)


def test_mismatched_params_not_merged(service, ref_session):
    """Same analytic + same source axis but different other params must
    NOT coalesce (a merged plan would silently apply one request's params
    to the other)."""
    reqs = [("sssp", {"source": 5, "max_supersteps": 64}),
            ("sssp", {"source": 5, "max_supersteps": 3})]
    outs = service.query_many(reqs)
    for (name, params), got in zip(reqs, outs):
        ref = ref_session.run(ref_session.plan(name, **params))
        _assert_same_output(ref, got, label=str(params))


def test_sequence_source_request_passes_through(service, ref_session):
    """A request that already carries a sequence source is planned as-is
    (its result keeps the (Q, V) leading axis)."""
    ref = ref_session.run(ref_session.plan("sssp", source=[2, 4]))
    got = service.query("sssp", source=[2, 4])
    assert got.output["final"].shape[0] == 2
    assert np.array_equal(ref.output["final"], got.output["final"])


# --------------------------------------------------------------------------
# warm staging cache
# --------------------------------------------------------------------------

def test_repeat_query_restages_nothing(service):
    service.query("sssp", source=1)
    service.query("sssp", source=2)  # same staged batch, different seed
    rep = service.session.last_run_report
    assert rep["staged_bytes"] == 0
    assert rep["staging_passes"] == 0
    assert rep["cache_hits"] >= 1
    stats = service.session.staging_cache_stats()
    assert stats is not None and stats["resident_bytes"] > 0


def test_prestage_moves_staging_ahead_of_first_query(service):
    service.prestage("sssp", source=0)
    service.query("sssp", source=0)
    rep = service.session.last_run_report
    assert rep["staged_bytes"] == 0 and rep["staging_passes"] == 0


def test_plain_session_is_promoted_to_warm():
    sess = GopherSession.from_blocked(
        _arrays()[0], weights={"latency": _arrays()[3]})
    assert sess._staging_cache is None
    svc = GopherService(session=sess)
    assert sess._staging_cache is not None
    assert sess._staging_cache.byte_budget is not None


# --------------------------------------------------------------------------
# admission / continuous batching
# --------------------------------------------------------------------------

def test_submit_many_forms_one_admission(service):
    tickets = service.submit_many(
        [("sssp", {"source": s}) for s in range(5)])
    for t in tickets:
        t.wait(timeout=120)
    rep = service.report()
    assert rep["batches"] == 1 and rep["widest_batch"] == 5


def test_concurrent_submitters_each_get_their_answer(service, ref_session):
    refs = {s: ref_session.run(ref_session.plan("sssp", source=s))
            .output["final"] for s in range(6)}
    errors = []

    def client(s):
        try:
            out = service.query("sssp", source=s, timeout=120)
            assert np.array_equal(out.output["final"], refs[s]), s
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append((s, e))

    threads = [threading.Thread(target=client, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
        assert not t.is_alive(), "client thread hung"
    assert not errors, errors
    rep = service.report()
    assert rep["served"] >= 6
    assert rep["p50_ms"] is not None and rep["p95_ms"] >= rep["p50_ms"]


def test_stop_drains_queued_requests():
    svc = GopherService(session=_session()).start()
    tickets = svc.submit_many([("sssp", {"source": s}) for s in range(3)])
    svc.stop()  # graceful: everything already queued is served
    for t in tickets:
        assert t.done and t.result is not None
        assert t.latency_s is not None and t.latency_s >= 0


# --------------------------------------------------------------------------
# request plumbing / errors
# --------------------------------------------------------------------------

def test_bad_requests_raise_on_caller_thread(service):
    with pytest.raises(KeyError, match="unknown analytic"):
        service.submit("ssssp", source=0)
    with pytest.raises(TypeError, match="unknown parameter"):
        service.submit("sssp", sourcee=0)
    with pytest.raises(TypeError, match="missing required"):
        service.submit("sssp")
    with pytest.raises(TypeError, match="unknown plan knob"):
        service.submit("sssp", source=0, plan_kw={"laoyut": "dense"})


def test_engine_failure_delivered_and_loop_survives(service):
    with pytest.raises(Exception):
        service.query("sssp", source=10 ** 9, timeout=120)  # out of range
    # the serve loop must still be alive and serving
    out = service.query("sssp", source=0, timeout=120)
    assert np.isfinite(out.output["final"][0])


def test_over_budget_batches_stream_from_the_store(tiny_gofs):
    """A store batch larger than the warm cache's budget could never stay
    resident: every query streams it from the store chunk by chunk (never
    materializing it whole) and the answers equal plain session runs."""
    from repro.gofs import GoFSStore

    from tests.conftest import TINY

    ref = GopherSession(GoFSStore(tiny_gofs), block_size=TINY.block_size)
    reqs = [("sssp", {"source": 0}), ("sssp", {"source": 5}),
            ("nhop", {"source": 3, "n_hops": 2})]
    store = GoFSStore(tiny_gofs)

    def no_materialize(*_a, **_kw):
        raise AssertionError("over-budget batch was materialized whole")

    store.load_blocked = no_materialize
    with GopherService(store, block_size=TINY.block_size,
                       staging_cache_bytes=1) as svc:
        got = svc.query_many(reqs)
        stats = svc.session.staging_cache_stats()
    assert stats["resident_bytes"] == 0 and stats["staged_bytes"] > 0
    for (name, params), g in zip(reqs, got):
        _assert_same_output(ref.run(ref.plan(name, **params)), g, name)
