"""The plain references agree with the program's jnp path at a small
size, so the reference is checked before it judges the chip; and the
control (the reference at bfloat16) fails the comparison."""
import json

import ml_dtypes
import numpy as np
import pytest

from chipbench_fixtures import TINY_SIZES  # first: sets sys.path
from chipbench import datagen, spec

SSSP = spec.load_module(spec.HERE / "checks" / "sssp.py")
PAGERANK = spec.load_module(spec.HERE / "checks" / "pagerank.py")


def bf16(a):
    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.asarray(a).dtype)


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((spec.HERE / "configs" / "tr-day.json").read_text())
    cfg.update(TINY_SIZES)
    tsg = datagen.collection(cfg, seed=2**31 + 11)
    data = {"src": tsg.template.src, "dst": tsg.template.dst,
            "num_vertices": tsg.template.num_vertices,
            "edges": {a: np.stack([tsg.edge_values(t, a)
                                   for t in range(len(tsg))])
                      for a in ("latency", "active")}}
    from repro.gopher import GopherSession

    sess = GopherSession(tsg, num_partitions=cfg["num_partitions"],
                         block_size=cfg["block_size"])
    return cfg, data, sess


def test_sssp_reference_equals_the_session_bitwise(tiny):
    cfg, data, sess = tiny
    inst = list(range(cfg["num_instances"]))
    for source in (0, 5, 300):
        res = sess.run(sess.plan("sssp", source=source))
        ref = SSSP.expected(data, {"source": source}, inst)
        got = SSSP.answer(res, per_query=False)
        assert np.array_equal(got, ref)
        assert np.array_equal(res.output["final"], ref[-1])
        numbers = SSSP.compare([got], [ref])
        assert numbers == {"missing": 0, "reach_mismatch": 0,
                           "dist_gap": 0.0}


def test_pagerank_reference_agrees_with_the_session(tiny):
    cfg, data, sess = tiny
    params = {"damping": 0.85, "iters": 10}
    res = sess.run(sess.plan("pagerank", **params))
    ref = PAGERANK.expected(data, params, list(range(cfg["num_instances"])))
    numbers = PAGERANK.compare([PAGERANK.answer(res, False)], [ref])
    assert numbers["missing"] == 0
    assert numbers["rank_gap"] < 1e-5


def test_the_bfloat16_control_fails_each_comparison(tiny):
    cfg, data, _ = tiny
    inst = list(range(cfg["num_instances"]))
    limits = {t: json.loads((spec.HERE / "traffic" / f"{t}.json")
                            .read_text())["check"]["limits"]
              for t in ("sssp-stream", "pagerank-stream")}
    ref = SSSP.expected(data, {"source": 0}, inst)
    ctl = SSSP.expected(data, {"source": 0}, inst, rnd=bf16)
    assert SSSP.compare([ctl], [ref])["dist_gap"] > \
        limits["sssp-stream"]["dist_gap"]
    params = {"damping": 0.85, "iters": 10}
    ref = PAGERANK.expected(data, params, inst)
    ctl = PAGERANK.expected(data, params, inst, rnd=bf16)
    assert PAGERANK.compare([ctl], [ref])["rank_gap"] > \
        limits["pagerank-stream"]["rank_gap"]


def test_a_missing_or_misshapen_answer_counts_as_missing(tiny):
    ref = np.zeros((2, 4), np.float32)
    assert SSSP.compare([None, ref[:1]], [ref, ref])["missing"] == 2
    assert PAGERANK.compare([None], [ref + 1])["missing"] == 1
