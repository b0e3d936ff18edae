"""The program's profiler spans (``jax.profiler.TraceAnnotation``): each
opens where the staging, the engine and the service do their work, as
often as that work happens, with a positive duration on the trace.

* ``gofs.stage`` — one chunk's read (prefetch pool, or the caller when
  synchronous); ``gofs.wait`` — the caller blocked on it.
* ``engine.put`` — a group of uploads (a streamed chunk's edge rows and
  its device fill); ``engine.build`` — the first call of a new runner
  only; ``engine.gather`` — results back to the host.
* ``service.execute`` — one admitted batch on the serve thread.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.blocked import build_blocked
from repro.core.engine import TemporalEngine, min_plus_program, source_init
from repro.gofs import GoFSStore
from repro.gopher import GopherService

from tests.conftest import TINY
from tests.test_service import _session

SPANS = ("gofs.stage", "gofs.wait", "engine.put", "engine.build",
         "engine.gather", "service.execute")


@pytest.fixture(scope="module")
def env(tiny_collection, tiny_partitioned, tiny_gofs):
    tmpl, assign, sg_ids, subs = tiny_partitioned
    bg = build_blocked(tmpl, assign, TINY.block_size)
    store = GoFSStore(tiny_gofs, cache_slots=TINY.cache_slots)
    return bg, store


def _traced(log_dir, fn):
    """Run ``fn`` under the profiler; returns its result and the
    durations (ns) of the program's spans on the host planes, by name."""
    with jax.profiler.trace(str(log_dir)):
        out = fn()
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.setdefault(e.name, []).append(e.duration_ns)
    return out, spans


def test_async_stream_pass_opens_each_span_where_its_work_is(env, tmp_path):
    bg, store = env
    eng = TemporalEngine(bg)
    prog = min_plus_program("sssp", init=source_init(0))
    chunks = store.num_timesteps()  # one instance a chunk

    def one_pass():
        return eng.run(prog, pattern="sequential",
                       stream=store.load_blocked_stream(
                           bg, "latency", prefetch_depth=2,
                           chunk_instances=1))

    first, spans = _traced(tmp_path / "first", one_pass)
    assert len(spans["gofs.stage"]) == chunks
    assert len(spans["gofs.wait"]) == chunks
    # each chunk's edge-row upload and device fill, one span a chunk
    assert len(spans["engine.put"]) == chunks
    # every chunk has one shape: one runner, built on its first call
    assert len(spans["engine.build"]) == 1
    assert len(spans["engine.gather"]) >= 1
    assert "service.execute" not in spans
    assert all(d > 0 for ds in spans.values() for d in ds), spans

    # the same program object again: the runner cache hits, nothing builds
    again, spans = _traced(tmp_path / "again", one_pass)
    assert "engine.build" not in spans
    assert len(spans["gofs.stage"]) == chunks
    assert len(spans["engine.gather"]) >= 1
    assert np.array_equal(first.values, again.values)


def test_sync_staging_stages_on_the_caller_and_never_waits(env, tmp_path):
    bg, store = env
    eng = TemporalEngine(bg)
    prog = min_plus_program("sssp", init=source_init(0))
    _, spans = _traced(tmp_path, lambda: eng.run(
        prog, pattern="sequential", stream=store.load_blocked_stream(
            bg, "latency", prefetch_depth=1, chunk_instances=1)))
    assert len(spans["gofs.stage"]) == store.num_timesteps()
    assert "gofs.wait" not in spans


def test_a_two_query_batch_is_one_service_execute(tmp_path):
    with GopherService(session=_session()) as svc:
        outs, spans = _traced(tmp_path, lambda: svc.query_many(
            [("sssp", {"source": 0}), ("sssp", {"source": 7})]))
        assert svc.report()["batches"] == 1
    assert len(outs) == 2
    assert len(spans["service.execute"]) == 1
    assert len(spans["engine.build"]) == 1
    assert len(spans["engine.gather"]) >= 1
    assert all(d > 0 for ds in spans.values() for d in ds), spans
