"""Asynchronous region-group wave scheduler (the RADS pipeline driver).

The engine exposes each R-Meef unit as three separately-jittable stages
over an immutable :class:`~repro.core.engine.WaveState`:

    fetch_stage  -> expand_stage -> verify_stage        (one unit)

This module pipelines those stages across *region-group waves*.  JAX's
async dispatch means a compiled stage call returns immediately with
futures; the scheduler therefore keeps up to ``EngineConfig.pipeline_depth``
waves in flight, dispatches each wave's stages **contiguously** (stages +
a jitted ``finalize_wave`` back-to-back on the device stream), and blocks
(the only sync point) solely on the single ``device_get`` that retires the
oldest wave.  With ``pipeline_depth=2`` (double buffering) the timeline is::

    wave k   : fetchV[u0] expand[u0] verifyE[u0] fetchV[u1] ...  ──┐ retire k
    wave k+1 :     fetchV[u0]  expand[u0]  verifyE[u0]     ...  ───┼────┐
    wave k+2 :                         (admitted when k retires)  ─┘    │ ...
               ── device queue: stages execute in dispatch order ──────────►

i.e. while wave ``k`` is still executing its ``verify_stage``, wave
``k+1``'s ``fetch_stage`` is already dispatched — the paper's asynchronous
region-group processing (§3, §6) without host threads.  ``pipeline_depth=1``
degrades to the old synchronous driver loop (one wave at a time).

The scheduler also owns the robustness mechanisms that used to live in the
driver's ``run_batches``:

* **overflow split** (§6 memory control): an incomplete wave is halved and
  both halves re-queued (LIFO, so sub-waves finish before new groups start);
* **capacity escalation**: a single-seed wave that still overflows doubles
  the engine capacities and re-resolves the stages (elastic capacities —
  enumeration never silently drops results; against a warm executable
  store the re-resolve is deserialization, not recompilation);
* **AOT stage resolution + persistent executable cache**: stages are
  compiled explicitly (``.lower().compile()``) through a two-level cache —
  in-process slots, then the on-disk
  :class:`~repro.runtime.compile_cache.StageExecCache` — with a background
  pre-warm of the whole ladder, so a warm server performs **zero**
  traces/compiles (``stats["compiles"] == 0``) and cold compiles move off
  the critical path;
* **steal-from-longest** (the paper's checkR/shareR): when a device's group
  queue drains before its peers', the next wave refills its slot from the
  tail of the longest surviving queue;
* **per-seed cost calibration**: trie-node counts are accumulated as a
  *running mean over every completed wave* (not the last batch), feeding
  the region-group budget of the distributed phase;
* **per-wave timing / byte stats** so benchmarks can report overlap
  efficiency (``wave_s_total`` vs ``*_pipeline_s`` wall time);
* **wave-level tracing** (:mod:`repro.obs`): with a
  :class:`~repro.obs.trace.TraceRecorder` injected, every admission /
  stage dispatch / finalize / retire records a span on a per-wave lane
  with a dispatch->retire flow arrow, and steals / splits / escalations
  become instants — all guarded by ``tracer.enabled`` so the default
  (:data:`~repro.obs.trace.NULL_TRACER`) path runs zero instrumentation;
* **adaptive pipeline depth** (``EngineConfig.pipeline_depth="auto"``):
  the achieved concurrency ``Σ wave latency / wall`` steers the in-flight
  limit up when the pipeline saturates and back down when waves stop
  overlapping — a pure host-side scheduling decision, never a recompile.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.rads import EngineConfig
from repro.core.cache import AdjCache, build_cache
from repro.core.engine import (PlanData, WaveState, expand_stage,
                               fetch_stage, finalize_wave, init_wave,
                               verify_stage)
from repro.core.exchange import ExchangeBackend
from repro.graph.storage import DeviceGraph
from repro.obs.trace import (NULL_TRACER, TRACK_PREWARM, TRACK_RETIRE,
                             TRACK_SCHED, TRACK_WAVE0, now_us)
from repro.runtime.compile_cache import (arg_signature, build_exec_cache,
                                         stage_context)

_MAX_CAP = 1 << 22
_AUTO_START_DEPTH = 2       # pipeline_depth="auto" begins double-buffered
_MAX_AUTO_DEPTH = 8


def _pad_seeds(seeds_per_dev: list[np.ndarray], ndev: int, scap: int,
               sentinel: int) -> tuple[np.ndarray, np.ndarray]:
    out = np.full((ndev, scap), sentinel, dtype=np.int32)
    mask = np.zeros((ndev, scap), dtype=bool)
    for t, s in enumerate(seeds_per_dev):
        k = min(len(s), scap)
        out[t, :k] = s[:k]
        mask[t, :k] = True
    return out, mask


# --------------------------------------------------------------------------- #
# GroupQueue: one device's FIFO of region groups, optionally lazily formed
# --------------------------------------------------------------------------- #
class GroupQueue:
    """Per-device queue of region groups.

    Backed by either a pre-formed list or a *lazy* group generator (see
    :func:`repro.core.region.iter_region_groups`): with a lazy source the
    Python-side group formation of wave ``k+1`` runs while wave ``k``
    computes on the device — grouping cost is hidden inside the pipeline.

    ``seeds_left`` (pre-formed + an estimate of unformed seeds) is the
    steal-from-longest load metric."""

    def __init__(self, groups=(), lazy=None, n_lazy_seeds: int = 0):
        self._buf: deque[np.ndarray] = deque(groups)
        self._lazy = lazy
        self._lazy_left = int(n_lazy_seeds) if lazy is not None else 0
        self.n_formed = len(self._buf)

    @property
    def seeds_left(self) -> int:
        return sum(len(g) for g in self._buf) + self._lazy_left

    def __bool__(self) -> bool:
        return self.seeds_left > 0

    def _form(self) -> np.ndarray | None:
        if self._lazy is None:
            return None
        g = next(self._lazy, None)
        if g is None:
            self._lazy_left = 0
            return None
        self._lazy_left = max(0, self._lazy_left - len(g))
        self.n_formed += 1
        return g

    def pop_head(self) -> np.ndarray | None:
        if self._buf:
            return self._buf.popleft()
        return self._form()

    def pop_tail(self) -> np.ndarray | None:
        """Steal entry point: take buffered work from the tail, else form
        the victim's next group."""
        if self._buf:
            return self._buf.pop()
        return self._form()


# --------------------------------------------------------------------------- #
# StageRunner: the jitted per-unit stage functions
# --------------------------------------------------------------------------- #
class StageRunner:
    """Holds the on-device graph (any registered ``DeviceGraph`` format)
    plus a two-level cache of **AOT-compiled** stage executables:

    1. an in-process slot table keyed ``(stage key, argument signature)``
       — the classic jit cache, now holding ``jax.stages.Compiled``
       objects resolved via ``jax.jit(...).lower(*args).compile()``;
    2. the optional persistent per-host store
       (:class:`~repro.runtime.compile_cache.StageExecCache`, enabled by
       ``EngineConfig.compile_cache_dir``) consulted on every slot miss
       *before* tracing — a populated store makes a whole run compile-free.

    Because stages are compiled explicitly, the runner knows exactly when
    XLA work happened: ``compiles``/``compile_s`` count actual stage
    compilations (a warm run must end with ``compiles == 0``) and
    ``take_hits()`` drains the number of resolutions served from the
    persistent store; the scheduler threads that count into the wave's
    jitted ``finalize_wave`` as ``exec_hits`` so it reaches the driver
    stats through the normal single retire ``device_get``.

    ``prewarm``/``prewarm_async`` resolve the full stage ladder for a seed
    capacity from *abstract* ``jax.eval_shape`` values — a background
    pre-warm moves compilation (or store deserialization) off the critical
    path while host-side group formation runs.  Resolution is thread-safe:
    concurrent resolvers of one slot rendezvous on an event instead of
    compiling twice, and ``escalate`` bumps a generation counter so a
    stale pre-warm resolution is never installed over the new capacities.

    Capacity escalation doubles the engine caps; slots are keyed by the
    capacities they were traced at, so the table survives escalation —
    old-rung entries keep serving in-flight waves, and the rung above the
    priors caps can be pre-warmed ahead of time
    (``prewarm(..., escalation_rungs=1)``) so an overflow run never
    compiles on the critical path.  The graph travels through the compiled
    stages as a pytree argument, so sharded (spmd/dist) and device-local
    formats use the same code path.

    The runner also *owns* the foreign-adjacency cache state
    (:class:`~repro.core.cache.AdjCache`): every dispatched ``fetch_stage``
    consumes ``self.cache`` and replaces it with the post-admission state
    (futures — JAX async keeps the host loop non-blocking), sequencing the
    cache through fetches in dispatch order across waves *and* across the
    capacity-escalation re-resolves (cache geometry is independent of the
    engine capacities).  Pass ``cache=`` explicitly to share or shard a
    prebuilt cache (the spmd driver does); the default builds one from
    ``cfg`` (``None`` when disabled).  ``exec_cache`` follows the same
    convention: ``"auto"`` builds the store from ``cfg.compile_cache_dir``,
    an explicit instance shares one store across runners (the benchmark
    sweep does), ``None`` disables persistence."""

    def __init__(self, g: DeviceGraph, pd: PlanData,
                 cfg: EngineConfig, exch: ExchangeBackend,
                 cache: AdjCache | None | str = "auto",
                 exec_cache="auto", tracer=NULL_TRACER):
        self.g = g
        self.pd, self.exch = pd, exch
        self.cfg = cfg
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.cache = build_cache(cfg, g) if cache == "auto" else cache
        self.exec_cache = (build_exec_cache(cfg) if exec_cache == "auto"
                           else exec_cache)
        if exch.mode in ("spmd", "dist"):
            # a Compiled executable bakes its input *shardings*, which the
            # store key (treedef + shape/dtype signature) does not capture
            # and the abstract pre-warm path cannot reproduce — spmd/dist
            # resolve concretely (shardings taken from the live args) and
            # in-process only; see prewarm()
            self.exec_cache = None
        self.compiles = 0        # stage executables actually XLA-compiled
        self.compile_s = 0.0     # wall seconds spent lowering + compiling
        self._slots: dict = {}   # (key, caps, sig) -> Compiled | pending Event
        self._lock = threading.Lock()
        self._gen = 0            # bumped by escalate(): aborts in-flight
                                 # pre-warm walks of the old capacities
        self._hits_pending = 0.0  # store hits awaiting wave attribution
        self._plan_repr = repr(pd)
        self._prewarm_threads: list[threading.Thread] = []
        self._prewarm_errors: list[Exception] = []
        self._tl = threading.local()   # per-thread last-resolve source
                                       # ("slot" | "store" | "compile")

    @property
    def n_units(self) -> int:
        return len(self.pd.unit_steps)

    @staticmethod
    def _escalated(cfg: EngineConfig) -> EngineConfig:
        """One rung up the capacity ladder — the exact replacement
        ``escalate()`` applies, shared with the rung pre-warm so a warmed
        rung lands on the same slot keys a live escalation resolves."""
        return dataclasses.replace(
            cfg, frontier_cap=min(cfg.frontier_cap * 2, _MAX_CAP),
            fetch_cap=min(cfg.fetch_cap * 2, _MAX_CAP),
            verify_cap=min(cfg.verify_cap * 2, _MAX_CAP))

    def escalate(self) -> bool:
        """Double every engine capacity (up to the ceiling) and re-resolve.

        The wire-codec stream capacities (:mod:`repro.core.wire`) are
        derived from ``fetch_cap``/``verify_cap`` inside the stages, so
        they escalate — and re-resolve — alongside the engine caps; the
        cache geometry alone stays fixed.  The slot table is *kept*: slots
        are keyed by the capacities they were traced at, so entries for
        the old rung stay valid for in-flight waves and entries pre-warmed
        for the new rung (``prewarm(..., escalation_rungs=1)``) are found
        immediately — an escalation against a warmed rung resolves without
        compiling on the critical path."""
        c = self.cfg
        if c.frontier_cap >= _MAX_CAP:
            return False
        with self._lock:
            self.cfg = self._escalated(c)
            self._gen += 1
        return True

    # -- persistent-store hit accounting ------------------------------------ #
    def take_hits(self) -> float:
        """Drain the pending persistent-store hit count; the scheduler
        attributes it to the wave whose finalize is being dispatched."""
        with self._lock:
            h, self._hits_pending = self._hits_pending, 0.0
        return h

    def credit_hits(self, h: float) -> None:
        """Re-credit hits whose wave was discarded (overflow split /
        escalation) so the run total stays exact."""
        with self._lock:
            self._hits_pending += float(h)

    def executables(self) -> dict:
        """The resolved stage executables, ``(key, caps, sig) -> Compiled``
        (or a loaded store entry) — what a caller inspects to see which
        kernels the compiler put in (``as_text()``)."""
        with self._lock:
            return {k: v for k, v in self._slots.items()
                    if not isinstance(v, threading.Event)}

    # -- stage resolution ---------------------------------------------------- #
    @staticmethod
    def _caps_key(key, cfg: EngineConfig) -> tuple:
        """The capacity-ladder component of a slot key.  ``init`` and
        ``finalize`` trace independently of the engine capacities (their
        shapes come entirely from the argument signature), so they key on
        ``()`` and survive escalations without re-resolving; every
        per-unit stage keys on the caps it closed over."""
        if key in ("init", "finalize"):
            return ()
        return (cfg.frontier_cap, cfg.fetch_cap, cfg.verify_cap)

    def _resolve(self, key, make, args, cfg: EngineConfig):
        """The stage executable for ``(key, caps(cfg), signature(args))``:
        in-process slot, else persistent store, else AOT trace + compile
        (counted).  ``cfg`` is the caller's snapshot — the closures in
        ``make`` and the slot key both use it, so a concurrent
        ``escalate`` can never mismatch a traced executable and its key.

        A second thread resolving an in-flight slot waits on the first
        instead of compiling twice.  Resolved slots are always installed:
        with capacities in the key a resolution is valid forever (an old
        rung's entry still serves in-flight waves; a pre-warmed higher
        rung's entry serves the escalation that reaches it)."""
        sig = arg_signature(args)
        skey = (key, self._caps_key(key, cfg), sig)
        while True:
            with self._lock:
                entry = self._slots.get(skey)
                if entry is None:
                    ev = threading.Event()
                    self._slots[skey] = ev
                    break
                if not isinstance(entry, threading.Event):
                    self._tl.last = "slot"
                    return entry
            entry.wait()
        fn = None
        tr = self.tracer
        t0_us = tr.now_us() if tr.enabled else 0.0
        source = "compile"
        try:
            ctx = digest = None
            if self.exec_cache is not None:
                ctx = stage_context(key, cfg, self.exch.mode,
                                    self._plan_repr)
                digest = self.exec_cache.digest(key, sig, ctx)
                fn = self.exec_cache.load(digest, sig, ctx)
                if fn is not None:
                    source = "store"
                    with self._lock:
                        self._hits_pending += 1.0
            if fn is None:
                t0 = time.perf_counter()
                fn = make().lower(*args).compile()
                dt = time.perf_counter() - t0
                with self._lock:
                    self.compiles += 1
                    self.compile_s += dt
                if self.exec_cache is not None:
                    self.exec_cache.store(digest, sig, ctx, fn)
            self._tl.last = source
            if tr.enabled:
                # resolve work (store deserialization or XLA compile) on the
                # prewarm lane when it ran on the background pre-warm thread,
                # else on the scheduler lane — all args are host scalars
                tid = (TRACK_PREWARM
                       if threading.current_thread().name
                       == "rads-stage-prewarm" else TRACK_SCHED)
                stage = key if isinstance(key, str) else ":".join(
                    str(k) for k in key)
                tr.complete(f"resolve:{stage}", tid, t0_us, source=source,
                            frontier_cap=cfg.frontier_cap)
            return fn
        finally:
            with self._lock:
                if fn is not None:
                    self._slots[skey] = fn
                elif self._slots.get(skey) is ev:
                    del self._slots[skey]
            ev.set()

    # the jax.jit(lambda ...) literals below are the stage call sites the
    # radslint call graph roots on — keep them literal
    def _make_init(self):
        return jax.jit(lambda gg, s, m: init_wave(gg, s, m))

    def _make_fetch(self, ui: int, cfg: EngineConfig):
        pd, exch = self.pd, self.exch
        # cache=None is a valid (empty) pytree argument, so one closure
        # serves both the cached and the uncached configuration
        return jax.jit(lambda gg, s, c: fetch_stage(gg, pd, cfg, exch, ui,
                                                    s, False, c))

    def _make_expand(self, ui: int, local_only: bool, cfg: EngineConfig):
        pd, exch = self.pd, self.exch
        return jax.jit(lambda gg, s, b: expand_stage(gg, pd, cfg, exch, ui,
                                                     s, b, local_only))

    def _make_verify(self, ui: int, local_only: bool, cfg: EngineConfig):
        pd, exch = self.pd, self.exch
        return jax.jit(lambda gg, s: verify_stage(gg, pd, cfg, exch, ui, s,
                                                  local_only))

    def _make_finalize(self):
        if self.exch.mode == "dist":
            # multi-process retire: the single blocking device_get at
            # _retire can only read *addressable* shards, so the finalize
            # all-gathers its outputs to every process — each host then
            # holds the full (identical) result tuple, and the downstream
            # stat merge is a pure equality check
            shard = jax.sharding.NamedSharding(
                self.exch.mesh, jax.sharding.PartitionSpec())
            return jax.jit(lambda s, h: finalize_wave(s, h),
                           out_shardings=shard)
        return jax.jit(lambda s, h: finalize_wave(s, h))

    # -- stage dispatch ------------------------------------------------------ #
    def init(self, seeds: np.ndarray, mask: np.ndarray) -> WaveState:
        args = (self.g, seeds, mask)
        return self._resolve("init", self._make_init, args, self.cfg)(*args)

    def fetch(self, ui: int, state: WaveState, local_only: bool):
        if local_only:                       # SM-E: no collectives at all
            return state, None
        cfg = self.cfg
        args = (self.g, state, self.cache)
        fn = self._resolve(("fetch", ui),
                           lambda: self._make_fetch(ui, cfg), args, cfg)
        state, bufs, self.cache = fn(*args)
        return state, bufs

    def expand(self, ui: int, state: WaveState, bufs, local_only: bool):
        cfg = self.cfg
        args = (self.g, state, bufs)
        fn = self._resolve(("expand", ui, local_only),
                           lambda: self._make_expand(ui, local_only, cfg),
                           args, cfg)
        return fn(*args)

    def verify(self, ui: int, state: WaveState, local_only: bool):
        cfg = self.cfg
        args = (self.g, state)
        fn = self._resolve(("verify", ui, local_only),
                           lambda: self._make_verify(ui, local_only, cfg),
                           args, cfg)
        return fn(*args)

    def finalize(self, state: WaveState, exec_hits: float = 0.0):
        """Dispatch the jitted drain stage (``finalize_wave``) — the wave's
        classic result tuple as device futures, with the runner's
        persistent-store hit count riding along as a traced scalar."""
        args = (state, np.float32(exec_hits))
        fn = self._resolve("finalize", self._make_finalize, args, self.cfg)
        return fn(*args)

    # -- pre-warm ------------------------------------------------------------ #
    def _prewarm_ladder(self, scap: int, local_only: bool,
                        cfg: EngineConfig, gen: int) -> int:
        """Resolve the full stage ladder at ``cfg``'s capacities from
        abstract values; returns stages resolved, 0 if aborted by a
        concurrent escalation (the rung being warmed is still installed —
        slots key on their capacities — but further walking is pointless
        work the escalated run will redo at its own caps)."""
        g, pd, exch = self.g, self.pd, self.exch
        seeds = jax.ShapeDtypeStruct((g.ndev, scap), jnp.int32)
        mask = jax.ShapeDtypeStruct((g.ndev, scap), jnp.bool_)
        args = (g, seeds, mask)
        self._resolve("init", self._make_init, args, cfg)
        state = jax.eval_shape(lambda gg, s, m: init_wave(gg, s, m), *args)
        n = 1
        for ui in range(self.n_units):
            if self._gen != gen:
                return 0
            bufs = None
            if not local_only:
                args = (g, state, self.cache)
                self._resolve(("fetch", ui),
                              lambda: self._make_fetch(ui, cfg), args, cfg)
                state, bufs, _ = jax.eval_shape(
                    lambda gg, s, c: fetch_stage(gg, pd, cfg, exch, ui, s,
                                                 False, c), *args)
                n += 1
            args = (g, state, bufs)
            self._resolve(("expand", ui, local_only),
                          lambda: self._make_expand(ui, local_only, cfg),
                          args, cfg)
            state = jax.eval_shape(
                lambda gg, s, b: expand_stage(gg, pd, cfg, exch, ui, s, b,
                                              local_only), *args)
            args = (g, state)
            self._resolve(("verify", ui, local_only),
                          lambda: self._make_verify(ui, local_only, cfg),
                          args, cfg)
            state = jax.eval_shape(
                lambda gg, s: verify_stage(gg, pd, cfg, exch, ui, s,
                                           local_only), *args)
            n += 2
        args = (state, np.float32(0.0))
        self._resolve("finalize", self._make_finalize, args, cfg)
        return n + 1

    def prewarm(self, scap: int, local_only: bool,
                escalation_rungs: int = 0) -> int:
        """Resolve the whole stage ladder for seed capacity ``scap`` from
        abstract values (``jax.eval_shape`` chains the inter-stage shapes;
        no device work happens beyond compilation itself).  Abstract and
        concrete dispatches share argument signatures, so a later real
        wave lands exactly on the slots resolved here.

        ``escalation_rungs > 0`` additionally warms that many capacity
        rungs *above* the current caps (doubled exactly as ``escalate()``
        doubles them) — slots are keyed by capacities, so a later
        escalation finds its stages already resolved and an overflow run
        never compiles on the critical path.

        Returns the number of stages resolved — 0 when aborted by a
        concurrent escalation (the ladder being warmed no longer matches
        the live capacities) or under the spmd/dist backends
        (ShapeDtypeStruct placeholders carry no mesh sharding, and a
        Compiled stage rejects calls whose input shardings differ from the
        ones it was lowered with — sharded stages must be resolved from
        the live sharded arrays)."""
        if self.exch.mode in ("spmd", "dist"):
            return 0
        gen = self._gen
        cfg = self.cfg
        with self.tracer.span("prewarm", TRACK_PREWARM, scap=int(scap),
                              local_only=bool(local_only),
                              rungs=int(escalation_rungs)):
            n = self._prewarm_ladder(scap, local_only, cfg, gen)
            for _ in range(max(0, int(escalation_rungs))):
                if n == 0 or cfg.frontier_cap >= _MAX_CAP:
                    break
                cfg = self._escalated(cfg)
                r = self._prewarm_ladder(scap, local_only, cfg, gen)
                n = n + r if r else n
        return n

    def prewarm_async(self, scap: int, local_only: bool,
                      escalation_rungs: int = 0) -> threading.Thread:
        """Run :meth:`prewarm` on a daemon thread (the driver launches this
        right before each scheduler phase, so compilation overlaps group
        formation).  Join via :meth:`join_prewarm` before reading
        ``compiles``/``compile_s``; it re-raises the first failure the
        thread recorded, so a stage the compiler refuses fails the run at
        its cause."""
        def work():
            try:
                self.prewarm(scap, local_only, escalation_rungs)
            except Exception as e:   # handed to the joining thread
                self._prewarm_errors.append(e)
        th = threading.Thread(target=work, name="rads-stage-prewarm",
                              daemon=True)
        th.start()
        self._prewarm_threads.append(th)
        return th

    def join_prewarm(self) -> None:
        """Wait for every pre-warm thread; re-raise the first failure."""
        for th in self._prewarm_threads:
            th.join()
        self._prewarm_threads.clear()
        if self._prewarm_errors:
            err = self._prewarm_errors[0]
            self._prewarm_errors.clear()
            raise err


# --------------------------------------------------------------------------- #
# Pipeline scheduler
# --------------------------------------------------------------------------- #
@dataclass
class _Wave:
    """One in-flight region-group wave: host-side batches (for the split
    loop), the device-side state futures, a stage cursor, and — once every
    stage is dispatched — the jitted-finalize result futures (``fin``)
    whose ``device_get`` is the wave's single retire sync."""
    batches: list[np.ndarray]
    mask: np.ndarray
    state: WaveState
    stages: list[tuple[str, int]]
    pos: int = 0
    bufs: object = None
    fin: object = None
    t_start: float = field(default_factory=time.perf_counter)
    seq: int = 0                # wave sequence number == trace flow id
    tid: int = 0                # trace lane (TRACK_WAVE0 + lane), 0 = untraced
    t0_us: float = 0.0          # span-clock admit time (traced runs only)


class PipelineScheduler:
    """Drives region-group waves through the staged engine with up to
    ``cfg.pipeline_depth`` waves in flight (see module docstring).

    ``stats`` may be a plain dict or a
    :class:`repro.obs.metrics.MetricsRegistry` (a ``MutableMapping``) —
    the scheduler only reads/writes mapping keys.  ``tracer`` defaults to
    the runner's (itself :data:`repro.obs.trace.NULL_TRACER` unless the
    caller injected a recorder); every hot-loop record site is guarded by
    ``tracer.enabled`` so the off path runs zero instrumentation."""

    def __init__(self, runner: StageRunner, stats: dict, consume,
                 tracer=None):
        self.runner = runner
        self.stats = stats
        self.consume = consume      # (rows, alive, counts, st, phase) -> None
        self.tracer = (tracer if tracer is not None
                       else getattr(runner, "tracer", NULL_TRACER))
        self._wave_seq = 0          # monotone wave counter (trace flow ids)
        self._free_lanes: list[int] = []
        self._n_lanes = 0

    # -- wave formation ----------------------------------------------------- #
    def _next_wave(self, queues: list[GroupQueue], retry: list,
                   scap: int, local_only: bool):
        """Pop the next wave: retries first (LIFO — finish split sub-waves
        before admitting new groups), else one group per device queue with
        steal-from-longest refill; oversized batches are chunked to scap."""
        cfg = self.runner.cfg
        empty = np.array([], dtype=np.int64)
        while True:
            if retry:
                wave = retry.pop()
            elif any(queues):
                wave = [q.pop_head() if q else None for q in queues]
                wave = [empty if b is None else b for b in wave]
                # both knobs gate the checkR/shareR analogue: --no-steal
                # (enable_work_stealing) must disable the group-queue
                # rebalance too, or the ablation silently still steals
                if (cfg.enable_work_stealing and cfg.steal_from_longest
                        and not local_only):
                    for t, b in enumerate(wave):
                        if len(b) > 0:
                            continue
                        src = max(range(len(queues)),
                                  key=lambda u: queues[u].seeds_left)
                        if queues[src]:       # this device drained early:
                            stolen = queues[src].pop_tail()
                            if stolen is not None:
                                wave[t] = stolen
                                self.stats["steal_events"] += 1
                                if self.tracer.enabled:
                                    self.tracer.instant(
                                        "steal", TRACK_SCHED, dev=t,
                                        victim=src, seeds=len(stolen))
            else:
                return None
            if max((len(b) for b in wave), default=0) == 0:
                continue
            if max(len(b) for b in wave) > scap:
                retry.append([b[scap:] for b in wave])
                wave = [b[:scap] for b in wave]
            return wave

    def _admit(self, wave: list[np.ndarray], scap: int) -> _Wave:
        g = self.runner.g
        seeds, mask = _pad_seeds(wave, g.ndev, scap, g.n)
        tr = self.tracer
        if not tr.enabled:
            state = self.runner.init(seeds, mask)
            stages = [(kind, ui) for ui in range(self.runner.n_units)
                      for kind in ("fetch", "expand", "verify")]
            return _Wave(batches=wave, mask=mask, state=state, stages=stages)
        # traced admission: allocate the smallest free wave lane, open the
        # whole-life flow (dispatch -> retire arrow) inside the init span
        if self._free_lanes:
            lane = min(self._free_lanes)
            self._free_lanes.remove(lane)
        else:
            lane = self._n_lanes
            self._n_lanes += 1
        seq = self._wave_seq
        self._wave_seq += 1
        tid = TRACK_WAVE0 + lane
        tr.name_track(tid, f"wave lane {lane}")
        t0 = tr.now_us()
        state = self.runner.init(seeds, mask)
        tr.flow_start(seq, tid)
        tr.complete("init", tid, t0, wave=seq,
                    seeds=int(sum(len(b) for b in wave)), scap=int(scap))
        stages = [(kind, ui) for ui in range(self.runner.n_units)
                  for kind in ("fetch", "expand", "verify")]
        return _Wave(batches=wave, mask=mask, state=state, stages=stages,
                     seq=seq, tid=tid, t0_us=t0)

    def _dispatch_one(self, kind: str, ui: int, w: _Wave, local_only: bool):
        if kind == "fetch":
            w.state, w.bufs = self.runner.fetch(ui, w.state, local_only)
        elif kind == "expand":
            w.state = self.runner.expand(ui, w.state, w.bufs, local_only)
            w.bufs = None
        else:
            w.state = self.runner.verify(ui, w.state, local_only)

    def _dispatch(self, w: _Wave, local_only: bool):
        kind, ui = w.stages[w.pos]
        tr = self.tracer
        if tr.enabled:
            # per-stage span on the wave's lane, annotated with unit, caps
            # rung, and how the executable resolved (slot/store/compile) —
            # every argument is a pre-fetched host scalar (dispatch returns
            # futures; nothing here blocks on the device)
            name = f"{kind}:u{ui}"
            t0 = tr.now_us()
            with tr.device_span(name):
                self._dispatch_one(kind, ui, w, local_only)
            tr.complete(name, w.tid, t0, wave=w.seq, unit=ui,
                        frontier_cap=self.runner.cfg.frontier_cap,
                        exec=getattr(self.runner._tl, "last", "slot"))
        else:
            self._dispatch_one(kind, ui, w, local_only)
        w.pos += 1

    def _drain(self, w: _Wave, local_only: bool):
        """Dispatch ALL of a wave's remaining stages, then its jitted
        finalize — contiguously, so the wave's ops sit back-to-back on the
        (in-order) device stream and the retire ``device_get`` never waits
        behind a younger wave's stages.  The old one-stage-per-tick
        interleave plus an *eager* host-side ``finalize_wave`` at retire
        time was exactly the bench's async<=sync failure: wave ``k``'s
        finalize ops were enqueued behind wave ``k+1``'s stages, so the
        blocking read paid for both waves.

        The finalize carries the runner's drained persistent-store hit
        count: every stage this wave needed was resolved during its own
        dispatches above, so attribution is exact (pre-warm hits land on
        whichever wave finalizes next — same run, same totals)."""
        while w.pos < len(w.stages):
            self._dispatch(w, local_only)
        if w.fin is None:
            tr = self.tracer
            if tr.enabled:
                t0 = tr.now_us()
                w.fin = self.runner.finalize(w.state,
                                             self.runner.take_hits())
                tr.complete("finalize", w.tid, t0, wave=w.seq)
            else:
                w.fin = self.runner.finalize(w.state,
                                             self.runner.take_hits())

    # -- retire + robustness loop ------------------------------------------- #
    def _retire(self, w: _Wave, retry: list, phase: str
                ) -> tuple[float, int]:
        """Drain point: block on the wave's finalized result tuple; consume
        on success, split/escalate on overflow.  Returns (node_cost_sum, n)."""
        # One batched device->host transfer per retired wave — the pipeline's
        # only blocking sync.  finalize_wave itself was jitted and dispatched
        # right behind the wave's last stage (_drain), so this transfers
        # already-scheduled values instead of eagerly dispatching a tail of
        # host-side ops behind the whole device queue (the old async<=sync
        # failure mode); the old scattered reads (bool(complete), eight
        # scalar float() casts in the driver's consume) stay batched too.
        tr = self.tracer
        t0 = tr.now_us() if tr.enabled else 0.0
        rows, alive, counts, complete, st = jax.device_get(w.fin)
        if tr.enabled and w.tid:
            # flow end binds (bp="e") to the enclosing retire span on the
            # retire track — Perfetto draws the dispatch->retire arrow; the
            # wave-summary span closes the wave's whole lane life
            tr.flow_end(w.seq, TRACK_RETIRE)
            tr.complete("retire", TRACK_RETIRE, t0, wave=w.seq,
                        complete=bool(complete))
            tr.complete("wave", w.tid, w.t0_us, wave=w.seq,
                        complete=bool(complete))
            self._free_lanes.append(w.tid - TRACK_WAVE0)
        if not complete:
            # a discarded wave's stats never reach consume — hand its
            # persistent-store hit credit back so the run total stays exact
            self.runner.credit_hits(float(st["compile_cache_hits"]))
            if max(len(b) for b in w.batches) <= 1:
                if not self.runner.escalate():
                    raise RuntimeError("capacity ceiling reached")
                self.stats["cap_escalations"] += 1
                retry.append(w.batches)
                if tr.enabled:
                    tr.instant("cap_escalation", TRACK_SCHED, wave=w.seq,
                               frontier_cap=self.runner.cfg.frontier_cap)
            else:
                self.stats["overflow_retries"] += 1
                retry.append([b[len(b) // 2:] for b in w.batches])
                retry.append([b[:len(b) // 2] for b in w.batches])
                if tr.enabled:
                    tr.instant("overflow_split", TRACK_SCHED, wave=w.seq)
            return 0.0, 0
        # per-real-seed trie-node counts (padding slots masked) — consumers
        # use these for the persisted node_counts histogram (priors v2)
        nc = st["node_counts"][w.mask]
        st["seed_node_counts"] = nc
        self.consume(rows, alive, counts, st, phase)
        self.stats["wave_s_total"] += time.perf_counter() - w.t_start
        return float(nc.sum()), int(nc.size)

    # -- main loop ----------------------------------------------------------- #
    def run(self, queues, scap: int,
            local_only: bool, phase: str, depth=None,
            auto_start: int | None = None) -> float | None:
        """Process per-device group queues (GroupQueue instances or plain
        lists of seed arrays) until empty.  Returns the mean trie-node cost
        per completed seed (running mean over *all* waves).

        ``depth`` overrides ``cfg.pipeline_depth`` — it is a host-side
        scheduling knob only (no recompilation), which lets benchmarks time
        sync (1) vs async (>=2) on the same warm jitted stages.

        ``pipeline_depth="auto"`` (or ``depth="auto"``) picks the depth from
        the per-wave timing stats the scheduler already collects: the ratio
        ``Σ wave latency / pipeline wall`` is the concurrency the pipeline
        *achieved*.  When it saturates the current depth the limit rises
        (up to ``_MAX_AUTO_DEPTH``); when waves stop overlapping (uniform
        runtimes, single surviving queue) it falls back toward synchronous —
        all host-side, so adaptation never recompiles a stage.
        ``auto_start`` seeds the adaptive depth (the priors cache passes the
        depth a previous run on the same workload converged to)."""
        if depth is None:
            depth = self.runner.cfg.pipeline_depth
        auto = depth == "auto"               # the "auto" setting
        if auto:
            depth = int(auto_start) if auto_start else _AUTO_START_DEPTH
            depth = max(1, min(depth, _MAX_AUTO_DEPTH))
        else:
            depth = max(1, int(depth))
        queues = [q if isinstance(q, GroupQueue) else GroupQueue(q)
                  for q in queues]
        retry: list[list[np.ndarray]] = []
        inflight: deque[_Wave] = deque()
        cost_sum, cost_n = 0.0, 0
        waves_done, wave_s_phase = 0, 0.0
        tr = self.tracer
        if tr.enabled:
            tr.name_track(TRACK_SCHED, "scheduler")
            tr.name_track(TRACK_RETIRE, "retire")
            tr.name_track(TRACK_PREWARM, "prewarm")
        t0 = time.perf_counter()
        tp0 = now_us()     # span clock — same domain as every trace event
        while True:
            # 1. fill the pipeline to ``depth``: each admitted wave
            #    dispatches ALL its stages plus its jitted finalize
            #    contiguously (see _drain), so the device stream is fed
            #    deep before the blocking read below.  Lazy Algorithm-3
            #    group formation for wave k+1 (a slow host-side Python
            #    loop) therefore overlaps wave k's already-dispatched
            #    device compute.
            while len(inflight) < depth:
                if tr.enabled:
                    # spans the lazy Algorithm-3 GroupQueue._form pull
                    # (plus steal decisions) feeding the next admission
                    t0g = tr.now_us()
                    wave = self._next_wave(queues, retry, scap, local_only)
                    tr.complete("group_form", TRACK_SCHED, t0g,
                                got=wave is not None)
                else:
                    wave = self._next_wave(queues, retry, scap, local_only)
                if wave is None:
                    break
                w = self._admit(wave, scap)
                inflight.append(w)
                self._drain(w, local_only)
                self.stats["n_waves"] += 1
                self.stats["max_inflight_waves"] = max(
                    self.stats["max_inflight_waves"], len(inflight))
            if not inflight:
                break
            # 2. retire the oldest wave — fully dispatched (finalize
            #    included) at admission, so this is the pure device_get
            #    sync.  If retiring escalates capacities, every younger
            #    in-flight wave already dispatched entirely at the old
            #    capacities; overflow is monotone and re-checked at its
            #    own retire, so a stale-capacity wave is still exact.
            oldest = inflight.popleft()
            s, n = self._retire(oldest, retry, phase)
            cost_sum += s
            cost_n += n
            waves_done += 1
            wave_s_phase += time.perf_counter() - oldest.t_start
            if auto and waves_done >= 2:
                wall = max(time.perf_counter() - t0, 1e-9)
                achieved = wave_s_phase / wall       # mean in-flight waves
                if achieved >= depth - 0.5 and depth < _MAX_AUTO_DEPTH:
                    depth += 1
                elif achieved < depth - 1.25 and depth > 1:
                    depth -= 1
                self.stats["auto_depth"] = depth
        if auto:
            self.stats["auto_depth"] = depth     # persisted via priors v2
        self.stats[f"{phase}_pipeline_s"] = (
            self.stats.get(f"{phase}_pipeline_s", 0.0)
            + time.perf_counter() - t0)
        # per-phase wall on the span clock (satellite: honest dist wall) —
        # recorded unconditionally so `wall_us` exists with tracing off and
        # max-merges across processes in merge_process_stats
        wall = now_us() - tp0
        self.stats[f"{phase}_wall_us"] = (
            self.stats.get(f"{phase}_wall_us", 0.0) + wall)
        if tr.enabled:
            tr.complete(f"phase:{phase}", TRACK_SCHED, tp0, dur_us=wall,
                        depth=depth, local_only=bool(local_only))
        return cost_sum / cost_n if cost_n else None
