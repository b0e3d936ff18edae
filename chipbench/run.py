"""Run one benchmark cell on the chip this process is started on.

  python3 chipbench/run.py --workload tr-day.sssp-stream --seed 7 \
      --seconds 51 --trace 0

Each run deploys the cell's collection into GoFS under the checkout
(``.chipbench/``), builds the session or service, warms up every shape the
window uses (JAX's compilation cache at ``JAX_COMPILATION_CACHE_DIR``,
else ``<checkout>/.jax_cache``), measures for
``--seconds``, compares the answers with the plain reference, and prints
one JSON result line last.  ``--trace 1`` traces the window and reports
the per-layer metrics instead of the end-to-end ones.  A run that finds no
TPU, or fewer chips than the cell asks for, exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from chipbench import endtoend, spec, traffic as traffic_gen  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

WORK = ROOT / ".chipbench"
# an answer due in the window may come up to this long after the close
LATE_S = 60.0
# seed streams: one independent generator per purpose
_SOURCES, _SAMPLE, _WARM = 1, 3, 4


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class NoChip(SystemExit):
    """JAX found no accelerator, or too few chips, for the cell."""


@dataclass
class Run:
    """What one run did and saw: the record the metrics read."""

    cell: spec.Cell
    seed: int
    seconds: float
    graph: Dict[str, int] = field(default_factory=dict)
    peaks: Dict[str, Any] = field(default_factory=dict)
    t0: float = 0.0  # window start (perf_counter)
    passes: List[Dict] = field(default_factory=list)
    queries: List[Dict] = field(default_factory=list)
    service: Dict[str, Dict[str, int]] = field(default_factory=dict)
    trace: Optional[trace_mod.Trace] = None
    # per-batch engine counters of the queries due in the window
    query_engine: List[Dict[str, int]] = field(default_factory=list)


class CompileWatch:
    """Counts backend compilations and persistent-cache loads from JAX's
    own monitoring events (a load also reports as a compilation), so those
    inside the window are logged.  One listener per process:
    ``CompileWatch.get()``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
    _one: Optional["CompileWatch"] = None

    def __init__(self):
        import jax

        self.counts = {self.COMPILE: 0, self.LOAD: 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self) -> Tuple[int, int]:
        return self.counts[self.COMPILE], self.counts[self.LOAD]


def require_chips(n: int) -> Dict[str, Any]:
    """The device line of a run; exits non-zero, before any result, when
    JAX finds no TPU or fewer than ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: JAX found no TPU (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"chipbench: the cell needs {n} chips, JAX found "
                     f"{len(devs)}")
    return device_line()


def device_line() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory() -> int:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


# ---------------------------------------------------------------- set-up
def deploy(cell: spec.Cell, seed: int, root: Path):
    """Generate the cell's collection from the seed and deploy it into
    GoFS at ``root``.  Returns the store and the generated arrays the
    plain reference reads (edge list and every edge attribute)."""
    from chipbench import datagen
    from repro.gofs import GoFSStore, deploy_collection

    cfg = cell.config
    tsg = datagen.collection(cfg, seed)
    shutil.rmtree(root, ignore_errors=True)
    deploy_collection(tsg, datagen.graph_config(cfg), str(root))
    tmpl = tsg.template
    data = {
        "src": np.asarray(tmpl.src, np.int64),
        "dst": np.asarray(tmpl.dst, np.int64),
        "num_vertices": int(tmpl.num_vertices),
        "edges": {a.name: np.stack([np.asarray(tsg.edge_values(t, a.name))
                                    for t in range(len(tsg))])
                  for a in tmpl.edge_attrs},
    }
    serve = cfg.get("serve")
    kw = {}
    if serve:
        meta = json.loads((root / "collection.json").read_text())
        ts, dur = meta["timestamps"], meta["durations"]
        first = len(ts) - int(serve["newest_instances"])
        kw["time_range"] = (ts[first], ts[-1] + dur[-1])
    store = GoFSStore(str(root), cache_slots=int(cfg["cache_slots"]),
                      vertex_projection=("plate", "outdeg_active"),
                      edge_projection=("latency", "active"), **kw)
    return store, data


def graph_counts(session) -> Dict[str, int]:
    """Vertices, edges, and local and boundary edges of the session's
    partitioning: the counts the useful-byte model needs."""
    bg = session.bg
    return {"num_vertices": int(len(bg.part_of)),
            "num_edges": int(session.num_edges),
            "local_edges": int(len(bg.le_edge_id)),
            "boundary_edges": int(len(bg.re_edge_id))}


def plan_params(traffic: Dict, source: Optional[int]) -> Dict:
    params = dict(traffic.get("params", {}))
    if traffic.get("source_param"):
        params[traffic["source_param"]] = int(source)
    return params


# ------------------------------------------------------------ closed loop
def closed_loop(run: Run, store, window: "Window") -> List:
    """Back-to-back whole-collection passes through ``GopherSession.run``
    on the planner's plan.  Returns each pass's result."""
    from repro.gopher import GopherSession

    cell, tr = run.cell, run.cell.traffic
    sess = GopherSession(store, block_size=int(cell.config["block_size"]))
    run.graph = graph_counts(sess)
    V = run.graph["num_vertices"]

    def draw(g):
        if not tr.get("sources"):
            return None
        return int(traffic_gen.draw_sources(g, tr["sources"], sess.src, V,
                                            1)[0])

    # warm-up: one whole pass, from its own seed stream
    sess.run(sess.plan(tr["analytic"],
                       **plan_params(tr, draw(rng(run.seed, _WARM)))))
    g = rng(run.seed, _SOURCES)
    results = []
    run.t0 = t = window.start()
    while True:
        params = plan_params(tr, draw(g))
        p = {"t_start": t, "params": params, "ok": False, "instances": 0,
             "supersteps": 0, "local_sweeps": 0, "staged_bytes": 0}
        res = None
        try:
            res = sess.run(sess.plan(tr["analytic"], **params))
            st = res.engine.stats
            p.update(ok=True, instances=int(st["supersteps"].shape[-1]),
                     supersteps=int(np.sum(st["supersteps"])),
                     local_sweeps=int(np.sum(st["local_sweeps"])),
                     staged_bytes=int(sess.last_run_report["staged_bytes"]))
        except Exception as e:  # noqa: BLE001 - a failed pass is recorded
            log(f"pass failed: {type(e).__name__}: {e}")
        t = time.perf_counter()
        p["t_end"] = t
        run.passes.append(p)
        results.append(res)
        if t >= run.t0 + run.seconds:
            break
    window.stop()
    log("pass seconds: " + " ".join(
        f"{p['t_end'] - p['t_start']:.3f}" for p in run.passes))
    return results


# -------------------------------------------------------------- open loop
def service(cell: spec.Cell, store):
    """The configuration's warm ``GopherService`` over ``store``."""
    from repro.gopher import GopherService

    serve = cell.config["serve"]
    return GopherService(
        store, block_size=int(cell.config["block_size"]),
        staging_cache_bytes=float(serve["staging_cache_bytes"]),
        max_batch_queries=int(serve["max_batch_queries"]),
        auto_refresh=bool(serve["auto_refresh"]))


def warm_service(svc, tr: Dict) -> None:
    """Stage the tiles, then run every batch width the service can form,
    with sink sources (each converges in one superstep), so that every
    shape the window can use is compiled or loaded before it opens."""
    sess = svc.session
    sinks = traffic_gen.sink_vertices(sess.src, len(sess.bg.part_of),
                                      svc.max_batch_queries)
    svc.prestage(tr["analytic"], **plan_params(tr, sinks[0]))
    for q in range(1, svc.max_batch_queries + 1):
        for tk in svc.submit_many([(tr["analytic"], plan_params(tr, s))
                                   for s in sinks[:q]]):
            tk.wait(LATE_S)


def offer(svc, tr: Dict, due: np.ndarray, sources: np.ndarray,
          t0: float):
    """Submit one query per due time (seconds after ``t0``), whatever the
    service is doing.  Returns (query records, tickets, the latest a
    submit came after its due time)."""
    queries, tickets, late = [], [], 0.0
    for d, s in zip(due, sources):
        now = time.perf_counter()
        if t0 + d > now:
            time.sleep(t0 + d - now)
        t_sub = time.perf_counter()
        late = max(late, t_sub - (t0 + d))
        params = plan_params(tr, s)
        tickets.append(svc.submit(tr["analytic"], **params))
        queries.append({"due": t0 + d, "t_submit": t_sub, "params": params,
                        "ok": False, "t_done": None})
    return queries, tickets, late


def collect(queries: List[Dict], tickets: List, deadline: float) -> List:
    """Wait for every ticket until ``deadline``; record each delivery.
    Returns the results, ``None`` for a query that failed or never
    came."""
    results = []
    for q, tk in zip(queries, tickets):
        try:
            results.append(
                tk.wait(max(0.0, deadline - time.perf_counter())))
            q.update(ok=True, t_done=tk.t_done)
        except Exception as e:  # noqa: BLE001 - a failure is missing
            results.append(None)
            log(f"query {q['params']} failed: {type(e).__name__}: {e}")
    return results


def open_loop(run: Run, store, window: "Window") -> List:
    """Point queries submitted to a warm ``GopherService`` at their due
    times under the traffic's arrival process; each is timed from when it
    was due to its delivery.  Returns each query's result (``None`` for
    one that failed or never came)."""
    tr = run.cell.traffic
    svc = service(run.cell, store)
    run.graph = graph_counts(svc.session)
    due = traffic_gen.arrivals(tr, run.seconds)
    sources = traffic_gen.draw_sources(
        rng(run.seed, _SOURCES), tr["sources"], svc.session.src,
        run.graph["num_vertices"], len(due))
    with svc:
        warm_service(svc, tr)
        rep = svc.report()
        run.service["start"] = {"served": rep["served"],
                                "batches": rep["batches"]}
        run.t0 = window.start()
        run.queries, tickets, late = offer(svc, tr, due, sources, run.t0)
        end = run.t0 + run.seconds
        time.sleep(max(0.0, end - time.perf_counter()))
        rep = svc.report()
        run.service["end"] = {"served": rep["served"],
                              "batches": rep["batches"]}
        results = collect(run.queries, tickets, end + LATE_S)
        window.stop()
    log(f"generator: {len(due)} queries; the latest submit came "
        f"{late * 1e3:.3f} ms after its due time; "
        f"{run.service['end']['batches'] - run.service['start']['batches']}"
        f" batches in the window")
    # engine counters of every batch that answered a query of the window
    seen = set()
    for r in results:
        if r is not None and id(r.engine) not in seen:
            seen.add(id(r.engine))
            st = r.engine.stats
            run.query_engine.append(
                {"supersteps": int(np.sum(st["supersteps"])),
                 "local_sweeps": int(np.sum(st["local_sweeps"]))})
    return results


# ---------------------------------------------------------------- window
class Window:
    """Opens and closes the measured window: the profiler over it
    (``--trace 1``) and the count of compilations inside it."""

    def __init__(self, trace: bool, log_dir: Path, watch: CompileWatch):
        self.trace, self.dir, self.watch = trace, log_dir, watch
        self.t_start = self.t_stop = 0.0
        self._at_start = (0, 0)
        self.compiles = self.loads = 0

    def start(self) -> float:
        if self.trace:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            # host events come from the runtime's own trace points; the
            # Python tracer would slow the host and so inflate idle time
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._at_start = self.watch.snapshot()
        self.t_start = time.perf_counter()
        return self.t_start

    def stop(self) -> None:
        self.t_stop = time.perf_counter()
        compiles, loads = self.watch.snapshot()
        self.compiles = compiles - self._at_start[0]
        self.loads = loads - self._at_start[1]
        if self.trace:
            import jax

            jax.profiler.stop_trace()

    def load(self) -> Optional[trace_mod.Trace]:
        if not self.trace:
            return None
        return trace_mod.load(trace_mod.find_xplane(str(self.dir)),
                              (self.t_stop - self.t_start) * 1e9)


# ------------------------------------------------------------------ check
def checked_items(run: Run, results: List) -> List[Dict]:
    """A sample, drawn from the seed, of the answers due in the window:
    each with its parameters, the instances it covers, and what the
    program delivered (``None`` when nothing came)."""
    cell = run.cell
    g = rng(run.seed, _SAMPLE)
    k = int(cell.traffic["check"]["sample"])
    n_inst = int(cell.config["num_instances"])
    if run.queries:
        first = n_inst - int(cell.config["serve"]["newest_instances"])
        pool = [(q, r, True) for q, r in zip(run.queries, results)]
        inst = list(range(first, n_inst))
    else:
        n_win = len(endtoend.pass_window(run.passes, run.t0, run.seconds))
        pool = [(p, r, False) for p, r in
                zip(run.passes[:n_win], results[:n_win])]
        inst = list(range(n_inst))
    idx = sorted(g.choice(len(pool), min(k, len(pool)),
                          replace=False).tolist())
    out = []
    for i in idx:
        item, res, per_query = pool[i]
        got = cell.check.answer(res, per_query) \
            if (item["ok"] and res is not None) else None
        out.append({"params": item["params"], "instances": inst,
                    "per_query": per_query, "got": got})
    return out


def check(run: Run, data: Dict, items: List[Dict]) -> Dict[str, Dict]:
    """Compare the sampled answers with the plain reference.  Returns
    {number: {"value", "limit"}}; a failed or missing answer anywhere in
    the window counts under ``missing``."""
    cell = run.cell
    limits = cell.traffic["check"]["limits"]
    memo: Dict[str, np.ndarray] = {}
    ref = []
    for it in items:
        key = json.dumps(it["params"], sort_keys=True)
        if key not in memo:
            memo[key] = cell.check.expected(data, it["params"],
                                            it["instances"])
        ref.append(memo[key][-1] if it["per_query"] else memo[key])
    numbers = cell.check.compare([it["got"] for it in items], ref)
    numbers["missing"] += failed(run) - sum(
        1 for it in items if it["got"] is None)
    return {k: {"value": numbers[k], "limit": limits[k]}
            for k in cell.check.NUMBERS}


def control_items(data: Dict, items: List[Dict], check_mod,
                  rnd) -> List[Dict]:
    """The control: the reference at the lower precision ``rnd`` answers
    in the program's place, for the same sampled answers."""
    out = []
    for it in items:
        got = check_mod.expected(data, it["params"], it["instances"], rnd)
        out.append({**it, "got": got[-1] if it["per_query"] else got})
    return out


def attempted(run: Run) -> int:
    if run.queries:
        return len(run.queries)
    return len(endtoend.pass_window(run.passes, run.t0, run.seconds))


def failed(run: Run) -> int:
    if run.queries:
        return sum(1 for q in run.queries if not q["ok"])
    return sum(1 for p in endtoend.pass_window(run.passes, run.t0,
                                               run.seconds)
               if not p["ok"])


def judged(numbers: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


# ---------------------------------------------------------------- metrics
def end_to_end(run: Run, setup_s: float) -> Dict[str, Dict]:
    vals: Dict[str, Optional[float]] = {"setup_s": setup_s}
    if run.passes:
        vals["evps"] = endtoend.evps(
            run.passes, run.t0, run.seconds,
            run.graph["num_vertices"] + run.graph["num_edges"])
    if run.queries:
        lat = endtoend.latencies(run.queries)
        for name, p in (("query_p50_ms", 50), ("query_p90_ms", 90)):
            v = endtoend.percentile(lat, p)
            vals[name] = None if v is None else v * 1e3
        vals["queries_per_s"] = endtoend.delivered_per_s(
            run.queries, run.t0, run.seconds)
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in run.cell.end_to_end if vals.get(m["name"]) is not None}


def per_layer(run: Run) -> Dict[str, Dict]:
    out = {}
    for m in run.cell.per_layer:
        v = run.cell.readers[m["name"]].read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ------------------------------------------------------------------- main
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[Dict] = None, here: Path = spec.HERE,
             work: Path = WORK, require_chip: bool = True,
             compile_cache: bool = True, control=None) -> Dict:
    """One whole run; returns the result object.  ``require_chip=False``
    skips the look for a TPU and ``compile_cache=False`` leaves JAX's
    compilation cache as it is (the harness's own tests on the CPU).
    ``control`` puts the reference, rounded by that function, in the
    program's place for the comparison (the control of ``correct``)."""
    cell = spec.resolve(workload, bench or spec.benchmark(), here)
    device = require_chips(cell.chips) if require_chip else device_line()
    cache = None
    if compile_cache:
        import jax

        from repro.launch.compile_cache import use_compile_cache

        cache = use_compile_cache()
        # every program, however quick to compile, goes to the cache, so
        # a second run of a cell in the same checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    run = Run(cell=cell, seed=int(seed), seconds=float(seconds))
    if device["platform"] == "tpu":
        run.peaks = spec.peaks(device["kind"], here)
    log(f"{workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={device} compile cache={cache}")
    window = Window(trace, work / "trace", CompileWatch.get())
    store, data = deploy(cell, seed, work / "deploy")
    loop = closed_loop if cell.traffic["loop"] == "closed" else open_loop
    results = loop(run, store, window)
    setup_s = run.t0 - T_PROCESS
    device["memory_peak_bytes"] = peak_memory()
    # every pass or batch builds a new program object, so the program
    # traces it again and loads its executable from the persistent cache
    log(f"inside the window: {window.loads} compilation-cache load(s), "
        f"{window.compiles - window.loads} compilation(s) besides")
    items = checked_items(run, results)
    del results, store
    gc.collect()
    out: Dict[str, Any] = {}
    if trace:
        run.trace = window.load()
        summ = trace_mod.summary(run.trace)
        device["busy_s"], device["window_s"] = summ["busy_s"], summ["window_s"]
        out["breakdown"] = summ["breakdown"]
    if control is not None:
        items = control_items(data, items, cell.check, control)
    numbers = check(run, data, items)
    for k, v in numbers.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return {
        "correct": judged(numbers),
        "attempted": attempted(run),
        "failed": failed(run),
        "metrics": per_layer(run) if trace else end_to_end(run, setup_s),
        "device": device,
        **out,
        "checks": numbers,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(str(e), file=sys.stderr, flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
