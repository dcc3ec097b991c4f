"""Persistent AOT stage-executable cache — the serving latency floor killer.

The enumeration engine jits ~3 stages x units x 2 phases per workload and
``compile_us`` (seconds per stage on CPU XLA) dominates every benchmark
cell, 2-8x steady-state ``wall_us``.  All of that work is a pure function
of *static* inputs — the query plan, the engine capacities, the graph
geometry, the wire/storage/cache configuration, and the jax build — so a
warm server should never trace or compile anything.  This module is the
per-host on-disk store that makes that true:
:class:`~repro.core.scheduler.StageRunner` resolves every stage through
:class:`StageExecCache` before falling back to ``jax.jit`` tracing, and
a populated store turns a whole run into pure executable dispatch
(``stats["compiles"] == 0``).

Key schema
----------
An entry's digest is ``sha256`` over four independent layers, any of which
changing MUST invalidate the entry:

1. **environment stamp** (:func:`repro.compat.version_stamp`): exact
   jax/jaxlib versions, backend platform, visible device count — pickled
   XLA executables are only valid on the build that produced them;
2. **code fingerprint** (:func:`code_fingerprint`): sha256 over the source
   bytes of every module whose Python code is baked into a stage trace
   (engine, wire codecs, adjacency cache, exchange backends, storage
   formats, kernel ops, compat) — editing engine code invalidates the
   store wholesale, the bluntest and only safe granularity;
3. **stage context** (:func:`stage_context`): the stage key (kind, unit,
   local-only), the canonical plan/pattern repr, the exchange mode, and
   the *stage-relevant* ``EngineConfig`` fields.  Relevance is per stage
   kind so cells that genuinely share a trace share an entry: an
   ``expand`` executable does not depend on ``wire_format``, so the
   raw / varint / auto benchmark cells reuse one expand entry and pay
   only their marginal fetch/verify compiles;
4. **argument signature** (:func:`arg_signature`): the flattened treedef
   repr plus every leaf's ``(shape, dtype)``.  Custom pytree nodes
   (``DeviceGraph``, ``AdjCache``, ``WaveState``) carry their static
   geometry in treedef aux data, so graph size, storage format, cache
   geometry, seed capacity, and row width are all captured here without
   being re-listed in layer 3.

Invalidation rules
------------------
There is no in-place invalidation: every variation lands on a different
digest and stale digests simply stop being read (an external tool may
garbage-collect by mtime).  Two defensive layers turn *corruption* into a
cache miss instead of a crash: the pickled envelope stores the full key
material and :meth:`StageExecCache.load` rejects an envelope whose
recorded material mismatches the digest's (hash collision / truncated
write), and any unpickling or executable-load error is caught, warned
about once per file, and treated as a miss — the runner then falls back
to tracing and overwrites the bad entry with a fresh one.

Version stamping
----------------
Layers 1+2 are the version stamp.  They are *inside* the digest (stale
builds miss rather than load-and-crash) and *inside* the envelope (a
digest collision across builds is still refused at load time).

Pre-warm protocol
-----------------
``StageRunner.prewarm`` walks the stage ladder **abstractly** — the wave
state shapes for a seed capacity are derived with ``jax.eval_shape``, no
device work — and resolves each stage through this store from a
background thread while host-side group formation runs.  A warm store
makes pre-warming pure deserialization; a cold one moves the XLA compile
off the critical path, which is what finally lets the async pipeline win:
stage dispatch never stalls on a compile the scheduler could have paid
for during Algorithm-3 grouping.  Loaded executables are additionally
memoized in-process (keyed by absolute path + digest) so the warm
benchmark cells do not even re-read the files.

The store layout is flat: ``<dir>/<digest>.stagex``, written via
``tempfile + os.replace`` so concurrent runs on one host never observe a
torn file and duplicate writers are idempotent.

Known limitation: a ``Compiled`` executable also bakes its input
*shardings*, which the signature does not capture — the spmd backend
therefore disables both the store and the abstract pre-warm
(:class:`~repro.core.scheduler.StageRunner` forces ``exec_cache=None``)
and resolves stages from the live sharded arrays; see ROADMAP open
item 2 residuals.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import warnings
from dataclasses import dataclass, field

import jax

from repro import compat
from repro.obs.metrics import COUNTER, Instrument, MetricsRegistry

__all__ = ["StageExecCache", "arg_signature", "code_fingerprint",
           "stage_context", "build_exec_cache", "configure_jax_cache"]

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is not
# set: one fixed, git-ignored directory in the checkout (the path is part
# of what makes a later run find its entries, so it never moves)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_jax_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Entry points call this before their first compile.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is changed; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  The stage-executable store, where it is
    enabled, lives in the ``stagex`` subdirectory of the returned path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def _store_stats_registry() -> MetricsRegistry:
    """The store's typed counter set.  Declared here — not in
    :mod:`repro.obs.schema` — because these are registry-internal to the
    executable store and surface upward only as deltas through the single
    top-level ``exec_cache`` instrument; values start at 0 (not UNSET) so
    ``dict(cache.stats)`` and counter-delta arithmetic see every key."""
    reg = MetricsRegistry(Instrument(n, COUNTER, "", d) for n, d in (
        ("hits", "entries loaded (memo or disk)"),
        ("misses", "lookups with no entry"),
        ("stores", "fresh executables persisted"),
        ("errors", "corrupt/stale/unserializable entries degraded"),
        ("evictions", "LRU garbage-collected envelopes")))
    for ins in reg.instruments():
        reg[ins.name] = 0
    return reg

_ENVELOPE_VERSION = 1
_SUFFIX = ".stagex"

# in-process memo of loaded executables: (store path, digest) -> callable.
# Deserialized executables are stateless, so sharing them across
# StageRunner instances (the benchmark sweep builds many) is safe and
# makes warm resolution free of even the disk read.
_LOADED_MEMO: dict[tuple[str, str], object] = {}


def arg_signature(args: tuple) -> tuple:
    """Hashable abstract signature of a stage call's arguments.

    Works identically for concrete arrays and ``jax.ShapeDtypeStruct``
    placeholders (the pre-warm path), so an abstract pre-warm resolves to
    the same slot a concrete dispatch hits.  Treedef reprs include each
    custom node's aux data — graph/cache geometry rides along for free.
    """
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (str(treedef),
            tuple((tuple(getattr(leaf, "shape", ())),
                   str(getattr(leaf, "dtype", type(leaf).__name__)))
                  for leaf in leaves))


# modules whose source is baked into stage traces (layer 2 of the key)
_TRACED_MODULES = (
    "repro.core.engine", "repro.core.wire", "repro.core.cache",
    "repro.core.exchange", "repro.graph.storage", "repro.compat",
    "repro.kernels.membership.ops", "repro.kernels.membership.kernel",
    "repro.kernels.membership.ref", "repro.kernels.intersect.ops",
    "repro.kernels.intersect.kernel", "repro.kernels.intersect.ref",
    "repro.kernels.varint.ops", "repro.kernels.varint.kernel",
    "repro.kernels.varint.ref",
)
_CODE_FP: str | None = None


def code_fingerprint() -> str:
    """sha256 over the source bytes of every trace-relevant module.

    Memoized per process (sources cannot change under a running
    interpreter in any way the jit caches would notice either)."""
    global _CODE_FP
    if _CODE_FP is None:
        import importlib

        h = hashlib.sha256()
        for name in _TRACED_MODULES:
            mod = importlib.import_module(name)
            src = getattr(mod, "__file__", None)
            h.update(name.encode())
            if src and os.path.exists(src):
                with open(src, "rb") as f:
                    h.update(f.read())
        _CODE_FP = h.hexdigest()
    return _CODE_FP


def stage_context(stage_key, cfg, exch_mode: str, plan_repr: str) -> tuple:
    """Layer 3 of the key: everything a stage's *trace* reads that is not
    already visible in the argument signature.

    ``stage_key`` is the StageRunner jit-cache key (``"init"``,
    ``("fetch", ui)``, ``("expand", ui, local_only)``, ...).  Config
    relevance is per stage kind — see the module docstring; when in doubt
    a field belongs here (a spurious miss costs one compile, a spurious
    hit costs correctness)."""
    kind = stage_key if isinstance(stage_key, str) else stage_key[0]
    # the comm-pipelining knobs change the exchange program structure
    # (chunked vs single-shot a2a), so every stage with a collective
    # keys on them; getattr guards configs predating the knobs
    comm = (bool(getattr(cfg, "comm_pipeline", False)),
            int(getattr(cfg, "comm_chunks", 1)))
    if kind == "fetch":
        knobs = (cfg.fetch_cap, cfg.wire_format, cfg.use_pallas_kernels,
                 cfg.enable_cache, cfg.cache_slots, cfg.cache_ways,
                 cfg.cache_decay) + comm
    elif kind == "expand":
        knobs = (cfg.frontier_cap, cfg.use_pallas_kernels)
    elif kind == "verify":
        knobs = (cfg.verify_cap, cfg.wire_format,
                 cfg.use_pallas_kernels) + comm
    else:                      # init / finalize: pure shape transformers
        knobs = ()
    return (repr(stage_key), plan_repr, exch_mode, kind, knobs)


@dataclass
class StageExecCache:
    """Per-host on-disk store of serialized stage executables.

    ``stats`` counts ``hits`` (entry loaded — memo or disk), ``misses``
    (no entry), ``stores`` (fresh executables persisted), ``errors``
    (corrupt/stale/unserializable entries that degraded to a miss or a
    skipped store), and ``evictions`` (LRU garbage collection).  The
    store is inert — ``enabled`` False — when the JAX build cannot
    serialize executables; callers need no special casing, every ``load``
    just misses and every ``store`` no-ops.

    ``budget_bytes > 0`` bounds the on-disk size: after every store the
    least-recently-used ``.stagex`` envelopes (file mtime — refreshed on
    every disk *load* too, so a hot entry never looks cold) are evicted
    until the directory fits the budget.  Entries otherwise accrete per
    (pattern, caps, format) forever.  ``0`` keeps the store unbounded.
    """

    path: str
    budget_bytes: int = 0
    stats: MetricsRegistry = field(default_factory=_store_stats_registry)

    def __post_init__(self):
        self.path = os.path.abspath(self.path)
        self.enabled = compat.HAS_EXECUTABLE_SERIALIZATION
        if self.enabled:
            os.makedirs(self.path, exist_ok=True)

    # -- keying ------------------------------------------------------------- #
    def digest(self, stage_key, sig: tuple, context: tuple) -> str:
        """sha256 of the four key layers (see module docstring)."""
        material = self._material(sig, context)
        return hashlib.sha256(material.encode()).hexdigest()

    def _material(self, sig: tuple, context: tuple) -> str:
        return repr((_ENVELOPE_VERSION, compat.version_stamp(),
                     code_fingerprint(), context, sig))

    def _file(self, digest: str) -> str:
        return os.path.join(self.path, digest + _SUFFIX)

    # -- load / store ------------------------------------------------------- #
    def load(self, digest: str, sig: tuple, context: tuple):
        """Loaded executable for ``digest`` or ``None`` (miss).

        Corrupt, truncated, stale, or cross-build files are demoted to a
        miss with a warning — the engine must keep running on a damaged
        cache directory, just slower."""
        if not self.enabled:
            self.stats["misses"] += 1
            return None
        memo_key = (self.path, digest)
        fn = _LOADED_MEMO.get(memo_key)
        if fn is not None:
            self.stats["hits"] += 1
            return fn
        fname = self._file(digest)
        if not os.path.exists(fname):
            self.stats["misses"] += 1
            return None
        try:
            with open(fname, "rb") as f:
                env = pickle.load(f)
            if (not isinstance(env, dict)
                    or env.get("version") != _ENVELOPE_VERSION
                    or env.get("material") != self._material(sig, context)):
                raise ValueError("stale or mismatched cache envelope")
            fn = compat.deserialize_compiled(env["payload"])
        except Exception as e:   # corrupt pickle, stale build, bad envelope
            self.stats["errors"] += 1
            self.stats["misses"] += 1
            warnings.warn(
                f"compile cache: dropping unusable entry {fname}: {e!r} "
                f"(falling back to jit tracing)", RuntimeWarning,
                stacklevel=2)
            try:
                os.remove(fname)
            except OSError:
                pass
            return None
        try:
            os.utime(fname, None)   # LRU touch: a disk hit is recent use
        except OSError:
            pass
        _LOADED_MEMO[memo_key] = fn
        self.stats["hits"] += 1
        return fn

    def store(self, digest: str, sig: tuple, context: tuple,
              compiled) -> bool:
        """Persist a freshly compiled stage executable (atomic replace)."""
        if not self.enabled:
            return False
        try:
            payload = compat.serialize_compiled(compiled)
            env = dict(version=_ENVELOPE_VERSION,
                       material=self._material(sig, context),
                       payload=payload)
            blob = pickle.dumps(env)
        except Exception as e:   # unpicklable executable: cache-skip, run on
            self.stats["errors"] += 1
            warnings.warn(
                f"compile cache: could not serialize stage executable: "
                f"{e!r} (entry skipped)", RuntimeWarning, stacklevel=2)
            return False
        fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._file(digest))
        except OSError:
            self.stats["errors"] += 1
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        self.stats["stores"] += 1
        self._gc()
        return True

    # -- maintenance -------------------------------------------------------- #
    def _gc(self) -> int:
        """Evict least-recently-used envelopes until the store fits
        ``budget_bytes``.  The just-stored entry has the freshest mtime,
        so it is evicted last — a budget smaller than one envelope
        degrades to "keep only the newest".  Concurrent runs may race on
        removals; a vanished file is simply already-evicted."""
        if not self.enabled or self.budget_bytes <= 0:
            return 0
        try:
            files = [os.path.join(self.path, f)
                     for f in os.listdir(self.path) if f.endswith(_SUFFIX)]
            stats = []
            for f in files:
                try:
                    st = os.stat(f)
                    stats.append((st.st_mtime, st.st_size, f))
                except OSError:
                    continue
        except OSError:
            return 0
        total = sum(s for _, s, _ in stats)
        evicted = 0
        for mtime, size, fname in sorted(stats):   # oldest first
            if total <= self.budget_bytes:
                break
            try:
                os.remove(fname)
            except OSError:
                continue
            total -= size
            evicted += 1
        self.stats["evictions"] += evicted
        return evicted

    @staticmethod
    def clear_memory_memo() -> None:
        """Drop the in-process loaded-executable memo (tests use this to
        force the on-disk deserialization path)."""
        _LOADED_MEMO.clear()

    def entries(self) -> list[str]:
        """Digests currently stored on disk (sorted; diagnostics/tests)."""
        if not self.enabled or not os.path.isdir(self.path):
            return []
        return sorted(f[:-len(_SUFFIX)] for f in os.listdir(self.path)
                      if f.endswith(_SUFFIX))


def build_exec_cache(cfg) -> StageExecCache | None:
    """The store ``EngineConfig`` asks for (``None`` = disabled)."""
    if not getattr(cfg, "compile_cache_dir", ""):
        return None
    return StageExecCache(
        cfg.compile_cache_dir,
        budget_bytes=int(getattr(cfg, "compile_cache_budget_bytes", 0)))
