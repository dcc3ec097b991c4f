"""Collective-exchange backends for the R-Meef engine.

Engine state is *stacked*: every array carries a leading ``ndev`` axis.  A
backend supplies the two collectives the engine needs — ``a2a`` (the
paper's batched fetchV/verifyE request/response routing, ``out[t, s] =
x[s, t]``) and ``all_reduce_sum`` — plus the off-device byte accounting
that keeps ``stats["bytes_fetch"]``/``stats["bytes_verify"]`` comparable
across backends.

Built-in backends, selected with ``Exchange(mode)``:

* ``sim``    — whole stack on one device, a2a is an axis swap.  Bit-exact
               reference semantics for tests.
* ``spmd``   — leading axis sharded over the mesh's ``data`` axis, a2a is a
               real ``jax.lax.all_to_all`` under ``shard_map`` (resolved
               through :mod:`repro.compat`) — the single-process
               production path.
* ``gather`` — the same request/response protocol as ``sim`` implemented
               with plain device-local gathers; runs on CPU-only
               single-process hosts with no mesh at all.
* ``dist``   — ``spmd`` across **process boundaries**: the mesh spans every
               ``jax.distributed``-initialized process (one engine
               "machine" M_t per process), so the same shard_map
               ``all_to_all`` lowers to real cross-process collectives
               (gloo TCP on CPU, ICI/DCN on TPU).

Every backend also carries a **wire format** (``wire_format="raw" |
"varint"``, selected via ``EngineConfig.wire_format`` / ``--wire``): with
``"varint"`` the engine stages hand ``a2a``/``a2a_tree`` the coded ``uint8``
streams plus per-lane byte lengths from :mod:`repro.core.wire` instead of
the raw int32 slabs, and the ``bytes_wire_fetch``/``bytes_wire_verify``
accounting sums the *actual* stream lengths
(:meth:`ExchangeBackend.off_device_payload_bytes`) rather than the modeled
element sizes.  Results are wire-format-invariant (the codecs are exact).

New backends register with ``@register_exchange_backend("name")``.

The ``dist`` backend: bootstrap protocol
----------------------------------------
Launch is coordinator-based and flag-compatible with real multi-host: every
process runs the same program (:mod:`repro.launch.dist_worker`) with
``--coordinator HOST:PORT --num-processes N --process-id I``.  Each worker
(1) selects the CPU gloo collectives *before* any backend client exists,
(2) calls ``jax.distributed.initialize``, (3) builds the identical
deterministic dataset/partition/plan from the shared flags, and (4) builds
a 1-D ``("data",)`` mesh over all N processes' devices.  All of the
version-sensitive steps live in :mod:`repro.compat`
(``enable_cpu_collectives`` / ``distributed_initialize`` /
``global_shard``); this module only assumes a mesh whose ``data`` axis may
span processes.  Graph and cache pytrees become process-global arrays via
``compat.global_shard`` (each process contributes its own partition
block); everything else — seeds, scheduler decisions, retry/escalation —
is computed redundantly and identically on every process, which is the
standing SPMD contract: **every process must dispatch the same collectives
in the same order**, so the driver pins ``pipeline_depth="auto"`` to a
fixed depth under ``dist`` (timing-adaptive depth could diverge) and only
process 0 persists priors/artifacts.

Pipelined group communication (``comm_chunks``)
-----------------------------------------------
``EngineConfig.comm_pipeline`` splits each wave's a2a into ``comm_chunks``
sub-exchanges dispatched back-to-back (the pipelined adaptive-group
communication of arXiv:1804.09764): on transports with real latency the
transfer of chunk *k* overlaps the encode/decode compute of chunk *k+1*,
riding the same contiguous-drain dispatch order the scheduler already
guarantees.  The chunking contract: buffers are split **positionally along
the fixed per-peer capacity axis** (axis 2 of the ``(src, peer, cap, ...)``
request layout) *after* any wire coding, and the transpose protocol
``out[t, s] = x[s, t]`` is applied per chunk — concatenating the chunk
results is bit-identical to the unchunked exchange, and all ``bytes_*``
accounting is computed from the per-peer count/length matrices (never from
the chunk layout), so byte stats are chunk-invariant by construction.
Buffers whose capacity axis does not divide evenly (or 2-D length
matrices) go in one shot.

Why stats merge host-side: per-wave stats ride the replicated finalize
output, so every process computes identical *logical* totals (bytes,
counts, hits) — cross-process agreement is therefore a correctness check,
not a reduction.  Wall-clock and compile seconds genuinely differ per
process, so the scalability harness collects each process's stats dict and
merges them in :func:`repro.core.driver.merge_process_stats` (asserts the
logical stats agree byte-for-byte, takes the max over wall stats) instead
of burning a collective on numbers the device never needs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat


# --------------------------------------------------------------------------- #
# Backend registry
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ExchangeBackend:
    """Base class: collectives over the stacked ``(ndev, ...)`` layout."""

    mode: ClassVar[str] = "abstract"

    mesh: Mesh | None = None
    axis: str = "data"
    wire_format: str = "raw"   # 'raw' int32 slabs | 'varint' coded u8 streams
    comm_chunks: int = 1       # >1: split each a2a into that many
                               # back-to-back sub-exchanges along the
                               # per-peer capacity axis (comm pipelining —
                               # see module docstring; bit-identical)

    def a2a(self, x: jnp.ndarray) -> jnp.ndarray:
        """x: (ndev_src, ndev_dst, ...) -> out[t, s] = x[s, t].

        With ``comm_chunks > 1`` the exchange is dispatched as that many
        positional sub-exchanges along axis 2 (the fixed per-peer capacity
        axis) so chunk k's transfer overlaps chunk k+1's encode/decode on
        latency-bound transports; the transpose only permutes axes 0/1, so
        the concatenated result is bit-identical.  Buffers without an
        evenly-divisible capacity axis (e.g. the 2-D per-peer length
        matrices of the coded wire paths) go in one shot."""
        c = self.comm_chunks
        if c > 1 and x.ndim >= 3 and x.shape[2] >= c and x.shape[2] % c == 0:
            return jnp.concatenate(
                [self._a2a(part) for part in jnp.split(x, c, axis=2)],
                axis=2)
        return self._a2a(x)

    def _a2a(self, x: jnp.ndarray) -> jnp.ndarray:
        """Single-shot transport: out[t, s] = x[s, t] (backend-specific)."""
        raise NotImplementedError

    def a2a_tree(self, tree):
        """``a2a`` mapped over an arbitrary pytree of (ndev, ndev, ...)
        buffers — the engine stages exchange whole sub-states (e.g. the
        verifyE (a, b) request pair) in one call so a backend can fuse or
        coalesce the flight however it likes."""
        return compat.tree_map(self.a2a, tree)

    def all_reduce_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        """x: (ndev, ...) -> summed-over-devices, broadcast back."""
        raise NotImplementedError

    def per_device(self, fn):
        """``fn`` mapped over the leading ``ndev`` axis of every argument
        and result — ``jax.vmap`` here.  The engine runs its Pallas kernel
        calls through this: a mesh backend runs them on each device's own
        block, because the compiler cannot partition a Mosaic kernel."""
        return jax.vmap(fn)

    def off_device_bytes(self, counts: jnp.ndarray,
                         elem_bytes: float) -> jnp.ndarray:
        """Wire bytes implied by a per-peer request count matrix.

        ``counts[t, p]`` = entries device ``t`` sends to peer ``p``; the
        diagonal (self-traffic) is free on every backend.  All built-in
        backends share this *logical* accounting — sim and gather report
        the bytes the spmd path would put on the wire, so stats stay
        comparable when swapping backends.
        """
        ndev = counts.shape[0]
        off = counts * (1 - jnp.eye(ndev, dtype=counts.dtype))
        return off.sum().astype(jnp.float32) * elem_bytes

    def off_device_payload_bytes(self, byte_matrix: jnp.ndarray
                                 ) -> jnp.ndarray:
        """Like :meth:`off_device_bytes` for *pre-summed* per-peer byte
        matrices (``byte_matrix[t, p]`` = payload bytes ``t`` sends to
        ``p``) — used when entries have variable size, e.g. the modeled
        delta+varint coding of fetchV id payloads.  The diagonal
        (self-traffic) is free, identically on every built-in backend."""
        ndev = byte_matrix.shape[0]
        off = byte_matrix * (1 - jnp.eye(ndev, dtype=byte_matrix.dtype))
        return off.sum().astype(jnp.float32)

    def register_metrics(self, reg, comm_pipeline: bool | None = None
                         ) -> None:
        """Set the exchange-owned instruments on a stats registry (declared
        in :mod:`repro.obs.schema`): process topology + comm-pipelining
        knobs.  The backend owns these keys — the driver hands its registry
        over instead of poking them blind.  ``comm_pipeline`` defaults to
        whether chunking is actually active."""
        reg["process_index"] = compat.process_index()
        reg["process_count"] = compat.process_count()
        reg["comm_pipeline"] = (self.comm_chunks > 1 if comm_pipeline is None
                                else bool(comm_pipeline))
        reg["comm_chunks"] = self.comm_chunks

    def per_dev_sent_bytes(self, byte_matrix: jnp.ndarray) -> jnp.ndarray:
        """Per-device off-device *sent* bytes: row sums of a per-peer byte
        matrix (``byte_matrix[t, p]`` = payload bytes ``t`` sends to ``p``)
        with the free diagonal masked.  Returns ``(ndev,)`` f32; summing it
        recovers the matching scalar accounting exactly, which is the
        invariant the scalability harness's skew curves (max-per-process vs
        mean) are gated on."""
        ndev = byte_matrix.shape[0]
        off = byte_matrix * (1 - jnp.eye(ndev, dtype=byte_matrix.dtype))
        return off.sum(axis=1).astype(jnp.float32)


_BACKENDS: dict[str, type[ExchangeBackend]] = {}


def register_exchange_backend(name: str):
    """Class decorator: make ``Exchange(name)`` resolve to this backend."""
    def deco(cls: type[ExchangeBackend]) -> type[ExchangeBackend]:
        cls.mode = name
        _BACKENDS[name] = cls
        return cls
    return deco


def exchange_backends() -> tuple[str, ...]:
    """Registered backend names (sorted)."""
    return tuple(sorted(_BACKENDS))


def Exchange(mode: str = "sim", mesh: Mesh | None = None,
             axis: str = "data", wire_format: str = "raw",
             comm_chunks: int = 1) -> ExchangeBackend:
    """Factory kept name-compatible with the old two-branch dataclass:
    ``Exchange("sim")`` / ``Exchange(mode="spmd", mesh=mesh)``.
    ``wire_format`` selects the on-the-wire payload coding and
    ``comm_chunks`` the pipelined sub-exchange count (see module
    docstring); both are transport-independent, so every backend supports
    them."""
    try:
        cls = _BACKENDS[mode]
    except KeyError:
        raise ValueError(
            f"unknown exchange mode {mode!r}; registered backends: "
            f"{list(exchange_backends())}") from None
    if wire_format not in ("raw", "varint"):
        raise ValueError(
            f"unknown wire format {wire_format!r}; expected 'raw' or "
            f"'varint'")
    if not isinstance(comm_chunks, int) or comm_chunks < 1:
        raise ValueError(
            f"comm_chunks must be an int >= 1, got {comm_chunks!r}")
    return cls(mesh=mesh, axis=axis, wire_format=wire_format,
               comm_chunks=comm_chunks)


# --------------------------------------------------------------------------- #
# Built-in backends
# --------------------------------------------------------------------------- #
@register_exchange_backend("sim")
@dataclass(frozen=True)
class SimExchange(ExchangeBackend):
    """Single-device reference: the all-to-all is an axis swap."""

    def _a2a(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.swapaxes(x, 0, 1)

    def all_reduce_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.broadcast_to(x.sum(axis=0, keepdims=True), x.shape)


@register_exchange_backend("gather")
@dataclass(frozen=True)
class GatherExchange(ExchangeBackend):
    """Device-local gathers, no mesh, no collectives.

    Semantically identical to ``sim`` (both realize the exact transpose
    protocol) but lowers to per-destination gathers — the shape a real
    RDMA/queue-pair transport would take on a CPU-only single-process
    host, and a third registry entry proving backends are pluggable."""

    def _a2a(self, x: jnp.ndarray) -> jnp.ndarray:
        ndev = x.shape[0]
        # destination t gathers its column from every source's row
        return jax.vmap(lambda t: jnp.take(x, t, axis=1))(jnp.arange(ndev))

    def all_reduce_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        total = x.sum(axis=0)
        return jax.vmap(lambda _: total)(jnp.arange(x.shape[0]))


@register_exchange_backend("spmd")
@dataclass(frozen=True)
class SpmdExchange(ExchangeBackend):
    """Production path: leading axis sharded over ``mesh[axis]``; exchanges
    are real collectives under ``shard_map``."""

    def __post_init__(self):
        if self.mesh is None:
            raise ValueError("spmd exchange needs a mesh")

    def _spec(self, ndim: int) -> P:
        return P(self.axis, *([None] * (ndim - 1)))

    def _a2a(self, x: jnp.ndarray) -> jnp.ndarray:
        def body(xl):  # (1, ndev, ...)
            out = jax.lax.all_to_all(xl[0], self.axis, split_axis=0,
                                     concat_axis=0, tiled=True)
            return out[None]

        spec = self._spec(x.ndim)
        return compat.shard_map(body, mesh=self.mesh, in_specs=spec,
                                out_specs=spec)(x)

    def per_device(self, fn):
        # a pallas_call's outputs carry no varying-axes annotation, so the
        # body is not vma-checked (every value in it is per-device anyway)
        spec = P(self.axis)
        return compat.shard_map(jax.vmap(fn), mesh=self.mesh, in_specs=spec,
                                out_specs=spec, check_vma=False)

    def all_reduce_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        def body(xl):
            return jax.lax.psum(xl, self.axis)

        spec = self._spec(x.ndim)
        return compat.shard_map(body, mesh=self.mesh, in_specs=spec,
                                out_specs=spec)(x)


@register_exchange_backend("dist")
@dataclass(frozen=True)
class DistExchange(SpmdExchange):
    """``spmd`` across process boundaries: same shard_map collectives, but
    the mesh spans every ``jax.distributed``-initialized process, so each
    ``all_to_all`` crosses the gloo TCP transport between processes.  All
    transport mechanics are inherited — the backend exists as a distinct
    registry entry so the driver, scheduler, and wire heuristics can gate
    multi-process-only behaviour (host-side stat merging, replicated
    finalize shardings, pinned pipeline depth) on ``mode == "dist"``
    without sniffing the mesh.  Bootstrap lives in ``compat`` (see the
    module docstring's protocol note)."""


# --------------------------------------------------------------------------- #
# Static-shape primitives shared by the engines
# --------------------------------------------------------------------------- #
def compact(mask: jnp.ndarray, cap_out: int, *arrays: jnp.ndarray,
            fill: int = 0, fills: tuple | None = None) -> tuple:
    """Stable-compact rows where ``mask`` is True into ``cap_out`` slots.

    Returns (new_mask (cap_out,), overflow (bool), *gathered arrays). Rows
    beyond cap_out are dropped and flagged.  Per-device (no leading axis).
    ``fills`` overrides ``fill`` per array (one entry per array) so
    heterogeneous columns — ids, flags, payload rows — share one gather.
    """
    n = mask.shape[0]
    # slot j takes the index of the (j+1)-th True: a binary search over the
    # running count, no sort (a sort is ~20 s of TPU compile time apiece)
    csum = jnp.cumsum(mask, dtype=jnp.int32)
    take = jnp.minimum(jnp.searchsorted(
        csum, jnp.arange(1, cap_out + 1, dtype=jnp.int32)), n - 1)
    count = csum[-1]
    new_mask = jnp.arange(cap_out) < jnp.minimum(count, cap_out)
    overflow = count > cap_out
    if fills is None:
        fills = (fill,) * len(arrays)
    outs = []
    for a, fl in zip(arrays, fills):
        g = a[take]
        g = jnp.where(
            new_mask.reshape((-1,) + (1,) * (g.ndim - 1)), g, fl)
        outs.append(g)
    return (new_mask, overflow, *outs)


def membership(sorted_rows: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """sorted_rows (R, M) ascending (sentinel-padded), vals (R, K) ->
    bool (R, K): vals[r, k] in sorted_rows[r]."""
    idx = jax.vmap(jnp.searchsorted)(sorted_rows, vals)
    idx = jnp.clip(idx, 0, sorted_rows.shape[-1] - 1)
    found = jnp.take_along_axis(sorted_rows, idx, axis=-1) == vals
    return found


def unique_ids(ids: jnp.ndarray, mask: jnp.ndarray, sentinel: int
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sorted-unique of masked ids. Returns (uids (n,), umask (n,)) with
    invalid slots pushed to the back as ``sentinel``. Output length == input
    (a unique id count never exceeds the input count)."""
    x = jnp.where(mask, ids, sentinel)
    xs = jnp.sort(x)
    first = jnp.concatenate([jnp.array([True]), xs[1:] != xs[:-1]])
    valid = first & (xs < sentinel)
    umask, _, uids = compact(valid, x.shape[0], xs, fill=sentinel)
    return uids, umask


def unique_pairs(a: jnp.ndarray, b: jnp.ndarray, mask: jnp.ndarray,
                 sentinel: int) -> tuple:
    """Dedup (a, b) pairs without 64-bit keys (EVI, Def. 5).

    Returns (ua, ub, umask, rank) where (ua[j], ub[j]) are the unique pairs
    (sorted lexicographically, invalid at the back) and rank[i] gives the
    unique-slot of input pair i (undefined where ~mask, but always a safe
    index in [0, n)). Output length == input length."""
    n = a.shape[0]
    av = jnp.where(mask, a, sentinel)
    bv = jnp.where(mask, b, sentinel)
    order = jnp.lexsort((bv, av))
    a_s, b_s = av[order], bv[order]
    first = jnp.concatenate(
        [jnp.array([True]), (a_s[1:] != a_s[:-1]) | (b_s[1:] != b_s[:-1])])
    valid_s = first & (a_s < sentinel)
    # group id (in sorted order) and unique slot of each group's head
    grp = jnp.cumsum(first) - 1
    uniq_slot_of_grp = jnp.cumsum(valid_s) - 1
    # scatter unique pairs
    ucount = valid_s.sum()
    slot = jnp.where(valid_s, uniq_slot_of_grp, n - 1)
    ua = jnp.full((n,), sentinel, dtype=a.dtype).at[slot].set(
        jnp.where(valid_s, a_s, sentinel), mode="drop")
    ub = jnp.full((n,), sentinel, dtype=b.dtype).at[slot].set(
        jnp.where(valid_s, b_s, sentinel), mode="drop")
    umask = jnp.arange(n) < ucount
    # rank per input: per-group table of head slots, then invert the sort
    slot_of_grp = jnp.zeros((n,), dtype=jnp.int32).at[grp].max(
        jnp.where(first, uniq_slot_of_grp, 0).astype(jnp.int32), mode="drop")
    rank_sorted = slot_of_grp[grp]
    inv = jnp.zeros((n,), dtype=jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    rank = rank_sorted[inv]
    return ua, ub, umask, rank
