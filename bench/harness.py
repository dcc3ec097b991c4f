"""One run of one benchmark cell: set-up, measured window, check.

The cell's configuration, traffic mix and per-layer metric readers are
found by the names ``BENCHMARK.json`` gives them (``configs/<config>.json``
by its ``file``, ``traffic/<mix>.json``, ``metrics/<metric>.py``), so that
a new cell, mix or metric is a new file and no edit here.

The window drives the program's own entry, ``rads_enumerate(pg, pattern,
cfg, mode="spmd", mesh=..., return_embeddings=False, runner_cache=...)``,
as a closed loop with one client: the next query starts when the last one
returns, and the window ends with the first query that completes at or
after ``seconds``.  Every count, the warm-up's included, is compared with
the plain reference in :mod:`bench.reference`.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

from bench import graphs, trace
from bench.reference import counter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = "bench_window"      # host annotation around the measured queries
# the traced run traces at most this long a window (closed with the first
# query that ends at or after it): on one TPU v5e a second of a window of
# narrow waves made about 12 MB of trace, and reading a trace back took
# about 0.5 s per MB on a host CPU
TRACE_SECONDS = 5.0
QUERY_TID = 9                # recorder track of the harness's query spans
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devs[0].platform!r} "
                            f"devices")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


# --------------------------------------------------------------------------- #
# discovery by name
# --------------------------------------------------------------------------- #
@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    with open(os.path.join(root, c["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config_name=c["name"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def metric_reader(name: str, root: str = ROOT):
    """``read`` of ``bench/metrics/<name>.py`` (loaded by path: metric
    names may hold dots)."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #
@dataclass
class Query:
    seconds: float
    count: int | None = None
    stats: dict | None = None
    error: str | None = None


@dataclass
class Run:
    """What the per-layer readers read: the window's queries, the host
    window, the recorder's spans ``(name, start_us, dur_us)`` and the
    trace reduction (``None`` off the chip or untraced)."""
    queries: list[Query]
    window_s: float
    spans: list[tuple[str, float, float]] = field(default_factory=list)
    trace: trace.Reduction | None = None

    def span_seconds(self, name: str) -> float:
        return sum(d for n, _, d in self.spans if n == name) / 1e6


@dataclass
class Setup:
    n: int
    edges: object
    pg: object
    mesh: object
    cfg: object
    pattern: object
    cache: dict


class _CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    def __init__(self):
        self.on = False
        self.n = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.on and event in COMPILE_EVENTS:
            self.n += 1


def configure_cache() -> str:
    """JAX's persistent compile cache in its fixed checkout directory (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    from repro.runtime.compile_cache import configure_jax_cache

    path = configure_jax_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build(cell: Cell, seed: int, log) -> Setup:
    """Graph from the seed, partition, mesh and engine settings."""
    from repro.configs.rads import EngineConfig
    from repro.core import Pattern
    from repro.graph import Graph, partition
    from repro.launch.mesh import make_engine_mesh

    t0 = time.perf_counter()
    n, edges = graphs.make_edges(cell.config["generator"], seed)
    t1 = time.perf_counter()
    g = Graph.from_edges(n, edges)
    t2 = time.perf_counter()
    pg = partition(g, cell.chips, method=cell.config["partition"]["method"])
    print(f"graph {cell.config_name}: n={g.n} m={g.n_edges} max_degree="
          f"{g.max_degree} | generate {t1 - t0} s, csr {t2 - t1} s, "
          f"partition {time.perf_counter() - t2} s", file=log, flush=True)
    tr = cell.traffic
    if tr["loop"] != "closed" or int(tr["clients"]) != 1:
        raise ValueError(f"traffic {cell.traffic_name}: only a closed loop "
                         f"with one client is driven, got {tr['loop']!r} "
                         f"with {tr['clients']}")
    return Setup(n=n, edges=edges, pg=pg, mesh=make_engine_mesh(cell.chips),
                 cfg=EngineConfig(**cell.config["engine"]),
                 pattern=Pattern.from_edges([tuple(e) for e in tr["edges"]]),
                 cache={})


def query(s: Setup, tracer=None) -> Query:
    """One whole query through the program's entry; an exception is the
    query's failure, recorded with its message."""
    from repro.core import rads_enumerate

    t0 = time.perf_counter()
    try:
        res = rads_enumerate(s.pg, s.pattern, s.cfg, mode="spmd",
                             mesh=s.mesh, return_embeddings=False,
                             runner_cache=s.cache, tracer=tracer)
    except Exception as e:         # the boundary that must keep counting
        return Query(seconds=time.perf_counter() - t0,
                     error=f"{type(e).__name__}: {e}")
    return Query(seconds=time.perf_counter() - t0, count=int(res.count),
                 stats=res.stats)


def window(s: Setup, seconds: float,
           tracer=None) -> tuple[list[Query], float]:
    """Closed loop, one client, until the first query that completes at or
    after ``seconds``; returns the queries and the window's wall seconds."""
    out: list[Query] = []
    t_win = time.perf_counter()
    while True:
        t0 = tracer.now_us() if tracer is not None else 0.0
        q = query(s, tracer)
        if tracer is not None:
            tracer.complete("query", QUERY_TID, t0)
        out.append(q)
        if q.error is not None or time.perf_counter() - t_win >= seconds:
            return out, time.perf_counter() - t_win


def traced_window(s: Setup, seconds: float, log, platform: str):
    """A window of at most ``TRACE_SECONDS`` under the JAX profiler, with a
    recorder passed to the program as ``tracer``; returns (queries,
    window_s, spans, reduction)."""
    import jax

    from repro.obs.trace import TraceRecorder, now_us

    rec = TraceRecorder(capacity=1 << 21)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1      # the annotations, not the runtime's
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                a0 = now_us()
                queries, window_s = window(s, min(seconds, TRACE_SECONDS),
                                           tracer=rec)
                a1 = now_us()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        profile = trace.load(path)
        lo, hi = trace.host_annotation(profile, WINDOW)
        off = lo - a0 * 1e3      # recorder microseconds -> profiler ns
        drift = (hi - a1 * 1e3) - off
        spans = [(r[1], r[3], r[4]) for r in rec.records() if r[0] == "X"]
        print(f"trace: {os.path.getsize(path)} B, recorder {rec.n_recorded}"
              f" records ({rec.n_dropped} dropped), clock drift over the "
              f"window {drift} ns", file=log, flush=True)
        if rec.n_dropped:
            raise RuntimeError(f"trace recorder dropped {rec.n_dropped} "
                               f"records")
        try:
            red = trace.reduce(profile, WINDOW,
                               [(n, t * 1e3 + off, (t + d) * 1e3 + off)
                                for n, t, d in spans])
        except ValueError:
            if platform == "tpu":
                raise
            red = None           # off the chip there are no device planes
        return queries, window_s, spans, red
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def peak_bytes(devices) -> int | None:
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def check(reference: int, queries: list[Query]) -> dict:
    """Each number compared, with its limit: the widest gap between a
    query's count and the reference's (an exact comparison, limit 0), and
    the queries that raised or gave no count."""
    gaps = [abs(q.count - reference) for q in queries if q.count is not None]
    errors = sum(q.count is None for q in queries)
    return {"count_gap": {"value": max(gaps, default=0), "limit": 0},
            "query_errors": {"value": errors, "limit": 0}}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, devices,
             t_start: float, log=sys.stderr) -> dict:
    """Set up, warm up with one whole query, measure, check; returns the
    result line as a dict (``checks`` last)."""
    import jax

    dev = devices[0]
    counter_ = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter_)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)} | "
          f"compile cache {configure_cache()} | process start to here "
          f"{time.perf_counter() - t_start} s", file=log, flush=True)
    s = build(cell, seed, log)
    warm = query(s)
    st = warm.stats or {}
    print(f"warm-up query: {warm.seconds} s, count {warm.count}, compiles "
          f"{st.get('compiles')} ({st.get('compile_s')} s), waves "
          f"{st.get('n_waves')}, error {warm.error}", file=log, flush=True)
    spans, red = [], None
    counter_.on = True
    if warm.error is not None:
        queries, window_s, setup_s = [], 0.0, time.perf_counter() - t_start
    elif traced:
        setup_s = time.perf_counter() - t_start
        queries, window_s, spans, red = traced_window(s, seconds, log,
                                                      dev.platform)
    else:
        setup_s = time.perf_counter() - t_start
        queries, window_s = window(s, seconds)
    counter_.on = False
    mem = peak_bytes(devices)
    s.cache.clear()              # the program's state goes before the check
    reference = counter(cell.traffic["pattern"])(s.n, s.edges)
    checks = check(reference, [warm] + queries)
    failed = sum(q.count is None or q.count != reference for q in queries)
    correct = (warm.count == reference and bool(queries) and failed == 0
               and checks["count_gap"]["value"] == 0)
    window_compiles = sum(q.stats["compiles"] for q in queries
                          if q.stats is not None)
    secs = sorted(q.seconds for q in queries) or [0.0]
    print(f"window: {len(queries)} queries in {window_s} s (per query min "
          f"{secs[0]} median {secs[len(secs) // 2]} max {secs[-1]} s) | "
          f"counts {sorted(set(q.count for q in queries))} | reference "
          f"{reference} | compiles in window: program {window_compiles}, "
          f"jax events {counter_.n} | peak_bytes_in_use {mem}", file=log,
          flush=True)
    for q in queries:
        if q.error is not None:
            print(f"query error: {q.error}", file=log, flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": len(queries),
              "failed": int(failed)}
    done = [q for q in queries if q.count is not None]
    if traced:
        run = Run(queries=done, window_s=window_s, spans=spans, trace=red)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is None:
                print(f"metric {m['name']}: nothing to read", file=log,
                      flush=True)
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            result["breakdown"] = red.breakdown()
    else:
        values = {"setup_s": setup_s}
        if done and failed == 0:
            values["query_s"] = window_s / len(queries)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in values}
        result["device"] = device
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=log,
              flush=True)
    return result
