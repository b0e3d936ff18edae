"""Fixtures and helpers of the benchmark's own tests: a copy of
``chipbench``'s data files cut to a size the CPU runs in seconds, and the
faults planted under the timed path.  (Not a ``conftest.py``: the suite's
own ``tests/conftest.py`` is imported by name as ``conftest``.)"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402

# the cells' shapes at a size the CPU runs in seconds
TINY_SIZES = dict(num_vertices=512, num_partitions=4, block_size=32,
                  num_instances=6, instances_per_slice=2,
                  bins_per_partition=2, cache_slots=4)


def tiny_copy(dest: Path) -> Path:
    """A copy of the benchmark's data directories with every
    configuration cut to TINY_SIZES and every arrival rate raised so a
    one-second window holds queries."""
    for d in ("configs", "traffic", "checks", "metrics"):
        shutil.copytree(spec.HERE / d, dest / d)
    shutil.copy(spec.HERE / "peaks.json", dest / "peaks.json")
    for f in (dest / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c.update(TINY_SIZES)
        if c.get("serve"):
            c["serve"].update(newest_instances=2, max_batch_queries=4)
        f.write_text(json.dumps(c))
    for f in (dest / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        if t["loop"] == "open":
            t["rate_qps"] = 8.0
        f.write_text(json.dumps(t))
    return dest


@pytest.fixture(scope="module")
def tiny_here(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("chipbench_tiny"))


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


# ---- faults planted under the timed path ---------------------------------
def _state_unchanged(mp):
    """Every instance's step returns its state unchanged."""
    import jax.numpy as jnp

    from repro.core.engine import TemporalEngine

    def run_instance(self, program, x, *args, **kw):
        one = jnp.asarray(1, jnp.int32)
        return x, (one, jnp.zeros_like(one))

    mp.setattr(TemporalEngine, "_run_instance", run_instance)


def _half_batch(mp):
    """Half of each staged instance batch is left out: the other half's
    tiles stand in for it."""
    from repro.core.engine import TemporalEngine

    orig = TemporalEngine._scan_instances

    def scan(self, program, pattern, x0, tiles, btiles, *args, **kw):
        n = tiles.shape[0]
        h = (n + 1) // 2
        idx = [i % h for i in range(n)]
        return orig(self, program, pattern, x0, tiles[idx, ...],
                    btiles[idx, ...], *args, **kw)

    mp.setattr(TemporalEngine, "_scan_instances", scan)


def _no_exchange(mp):
    """The boundary exchange between partitions is left out: every
    superstep sees the semiring's zero from the other partitions."""
    from repro.core import superstep

    def publish(x, dg, sr, comm):
        return sr.full((dg.num_boundary,), x.dtype)

    mp.setattr(superstep, "_publish", publish)


def _answer_altered(mp):
    """One value of every result is altered where it is produced."""
    import numpy as np

    from repro.core.engine import TemporalEngine

    orig = TemporalEngine._wrap_result

    def wrap(self, *args, **kw):
        res = orig(self, *args, **kw)
        v = res.values.reshape(-1, res.values.shape[-1])
        hit = np.flatnonzero(np.isfinite(v[-1]) & (v[-1] != 0))
        if len(hit):
            v[-1, hit[0]] *= np.float32(1.01)
        else:  # nothing reached but the source: reach one more vertex
            v[-1, int(np.flatnonzero(v[-1] != 0)[0])] = np.float32(1.0)
        res.final = res.values[..., -1, :]
        return res

    mp.setattr(TemporalEngine, "_wrap_result", wrap)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "answer_altered": _answer_altered}


def drive(cell, here, bench, work, *, fault=None, mp=None, control=None,
          seconds=1.0, seed=2**31 + 17):
    """One run of ``cell`` at the tiny size on the CPU, past the look for
    a chip, with ``fault`` planted under the timed path."""
    from chipbench import run as run_mod

    if fault is not None:
        FAULTS[fault](mp)
    return run_mod.run_cell(cell, seed, seconds, False, bench=bench,
                            here=here, work=work, require_chip=False,
                            compile_cache=False, control=control)


def bf16(a):
    import ml_dtypes
    import numpy as np

    a = np.asarray(a)
    return a.astype(ml_dtypes.bfloat16).astype(a.dtype)
