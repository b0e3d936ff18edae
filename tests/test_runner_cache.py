"""The engine's runner cache keys on a program's traced structure
(``SemiringProgram.runner_key``), never on its host-only ``init``.

* A program for another source, or another batch of sources, reuses the
  built runner: nothing retraces, no ``engine.build`` span opens, and the
  answers are bitwise those of a fresh engine.
* A runner builds once per argument signature: a new query-axis width on a
  warm runner is one build.
* Programs whose traced fields differ (PageRank's damping or iteration
  count, a fixpoint's superstep cap) get runners of their own.
* ``runner_builds`` / ``runner_hits`` count this on the engine, per call
  in ``GopherSession.last_run_report`` and cumulatively in
  ``GopherService.report()``.
"""
import numpy as np

from repro.core.engine import (
    TemporalEngine,
    min_plus_program,
    pagerank_program,
    source_init,
    sources_init,
)
from repro.gopher import GopherService

from tests.test_service import _arrays, _session
from tests.test_spans import _traced


def _sssp(source):
    return min_plus_program("sssp", init=source_init(source))


def test_two_sources_share_one_runner_and_build_once(tmp_path):
    bg, _, _, w, _ = _arrays()
    eng = TemporalEngine(bg)
    first = eng.run(_sssp(0), w, pattern="sequential")
    assert eng.runner_builds == 1 and eng.runner_hits == 0
    second, spans = _traced(
        tmp_path, lambda: eng.run(_sssp(5), w, pattern="sequential"))
    assert "engine.build" not in spans
    assert len(eng._runners) == 1
    assert eng.runner_builds == 1 and eng.runner_hits == 1
    for source, res in ((0, first), (5, second)):
        fresh = TemporalEngine(bg).run(_sssp(source), w,
                                       pattern="sequential")
        assert np.array_equal(res.values, fresh.values)
        assert np.array_equal(res.stats["supersteps"],
                              fresh.stats["supersteps"])


def test_fifty_sources_leave_one_runner():
    bg, _, _, w, _ = _arrays()
    eng = TemporalEngine(bg)
    sources = list(range(50))
    finals = [eng.run(_sssp(s), w, pattern="sequential").final
              for s in sources]
    assert len(eng._runners) == 1
    assert eng.runner_builds == 1 and eng.runner_hits == 49
    # a fresh engine's query-axis pass is bitwise the single-source runs
    batched = TemporalEngine(bg).run(
        min_plus_program("sssp", init=sources_init(sources)), w,
        pattern="sequential")
    assert np.array_equal(np.stack(finals), batched.final)


def _engine_of(svc):
    (eng,) = svc.session._engines.values()
    return eng


def test_service_batches_of_a_warm_width_build_nothing():
    with GopherService(session=_session()) as svc:
        svc.query_many([("sssp", {"source": 0}), ("sssp", {"source": 1})])
        warm = svc.report()
        assert warm["runner_builds"] == 1
        outs = [svc.query_many([("sssp", {"source": a}),
                                ("sssp", {"source": b})])
                for a, b in ((7, 9), (11, 30))]
        rep = svc.report()
        assert rep["batches"] == 3
        assert rep["runner_builds"] == warm["runner_builds"]
        assert rep["runner_hits"] == warm["runner_hits"] + 2
        assert svc.session.last_run_report["runner_builds"] == 0
        assert svc.session.last_run_report["runner_hits"] == 1
        assert len(_engine_of(svc)._runners) == 1
    ref = _session()
    for (a, b), (ra, rb) in zip(((7, 9), (11, 30)), outs):
        for s, r in ((a, ra), (b, rb)):
            want = ref.run(ref.plan("sssp", source=s)).output["final"]
            assert np.array_equal(r.output["final"], want)


def test_a_new_width_on_a_warm_runner_is_one_build():
    with GopherService(session=_session()) as svc:
        svc.query_many([("sssp", {"source": 0}), ("sssp", {"source": 1})])
        before = svc.report()["runner_builds"]
        svc.query_many([("sssp", {"source": s}) for s in (2, 3, 4)])
        assert svc.report()["runner_builds"] == before + 1
        assert svc.session.last_run_report["runner_builds"] == 1
        # the same runner, traced for one more query-axis width
        assert len(_engine_of(svc)._runners) == 1
        svc.query_many([("sssp", {"source": s}) for s in (5, 6, 8)])
        assert svc.report()["runner_builds"] == before + 1


def test_pagerank_programs_share_a_runner_only_with_equal_parameters():
    bg, _, _, w, _ = _arrays()
    V = len(bg.part_of)
    eng = TemporalEngine(bg)
    a = eng.run(pagerank_program(V, iters=5), w, pattern="independent")
    b = eng.run(pagerank_program(V, iters=5), w, pattern="independent")
    assert len(eng._runners) == 1 and eng.runner_builds == 1
    assert np.array_equal(a.values, b.values)
    for prog in (pagerank_program(V, damping=0.5, iters=5),
                 pagerank_program(V, iters=6)):
        n = len(eng._runners)
        got = eng.run(prog, w, pattern="independent")
        assert len(eng._runners) == n + 1
        fresh = TemporalEngine(bg).run(prog, w, pattern="independent")
        assert np.array_equal(got.values, fresh.values)
        assert not np.array_equal(got.values, a.values)
    assert eng.runner_builds == 3


def test_a_fixpoint_with_another_superstep_cap_gets_its_own_runner():
    bg, _, _, w, _ = _arrays()
    eng = TemporalEngine(bg)
    full = eng.run(_sssp(0), w, pattern="sequential")
    capped = min_plus_program("sssp", init=source_init(0), max_supersteps=1)
    got = eng.run(capped, w, pattern="sequential")
    assert len(eng._runners) == 2 and eng.runner_builds == 2
    assert int(got.stats["supersteps"].max()) == 1
    assert int(full.stats["supersteps"].max()) > 1
    fresh = TemporalEngine(bg).run(capped, w, pattern="sequential")
    assert np.array_equal(got.values, fresh.values)
