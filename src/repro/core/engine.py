"""R-Meef: region-grouped multi-round expand, verify & filter (§3, App. B).

One vectorized, static-shape engine serves four roles:

* **SM-E** (``local_only=True``): the paper's single-machine pass over seeds
  whose border distance >= span(u_start) (Prop. 1) — no collectives at all.
* **Distributed R-Meef** (``local_only=False``): per unit (= round),
  ``fetchV`` (batched foreign-adjacency fetch with dedup) then per-leaf
  expansion with local verification, then one batched ``verifyE`` exchange
  over the EVI (deduped undetermined edges; Def. 5, Prop. 2).
* the **reference** modes (``Exchange('sim')`` / ``Exchange('gather')``) on
  one device, and
* the **production** mode (``Exchange('spmd', mesh)``) where the leading
  ``ndev`` axis is sharded over the mesh and exchanges are ``all_to_all``.

All shapes are static: capacities come from ``EngineConfig``; every overflow
is *detected and flagged*, and the scheduler reacts by splitting region groups
(§6 memory control — robustness mechanism, not an error path).

The per-unit round is decomposed into three separately-jittable stages over
an immutable :class:`WaveState` pytree —

* :func:`fetch_stage`  — the batched ``fetchV`` request/response exchange,
* :func:`expand_stage` — every ``_leaf_step`` of the unit (candidate
  generation, local filters, EVI recording),
* :func:`verify_stage` — the batched ``verifyE`` exchange + alive-masking —

so that :mod:`repro.core.scheduler` can pipeline stages of *different*
region-group waves (double-buffered exchanges).  :func:`run_rounds` remains
as the synchronous composition of the stages; stage boundaries carry no
semantics, so ``run_rounds == staged pipeline`` byte-for-byte.

``fetch_stage`` additionally threads the optional device-resident
foreign-adjacency cache (:class:`~repro.core.cache.AdjCache`): unique
foreign pivots are probed *before* the a2a request is built (hits are
masked off the wire), cached rows are merged over the responses after the
exchange, and miss responses enter under the benefit-based admission rule
— all inside the jitted stage, so cache state crosses stage and wave
boundaries as a pytree with no host round-trips.  Cache state only changes
which transport delivers a row, never its bytes, so enumeration results
are cache-invariant.

Both exchanges additionally speak the pluggable **wire format**
(``ExchangeBackend.wire_format``, selected by ``EngineConfig.wire_format``):
with ``"varint"`` the request/response payloads are encoded as compact
``uint8`` streams *inside the jitted stages* (:mod:`repro.core.wire` —
delta+varint ids and rows for ``fetchV``, Elias-Fano + run-delta pairs and
bit-packed answers for ``verifyE``) and decoded on the receiving device;
``bytes_wire_fetch``/``bytes_wire_verify`` account the actual stream
lengths, while ``bytes_fetch``/``bytes_verify`` keep the raw-equivalent
accounting so the two formats stay comparable.  The codecs are exact, so
results are wire-format-invariant.

The engine reads adjacency exclusively through the pluggable
:class:`~repro.graph.storage.DeviceGraph` interface (``rows_at``/``deg_at``
over the stacked layout): the ``dense`` format is the seed's padded array,
``bucketed`` stores degree-bucketed CSR slabs — both produce byte-identical
results because ``rows_at`` reassembles the same sentinel-padded windows.

Accelerator kernels (:func:`_kernel_mode`: on TPU they always run compiled;
elsewhere the jnp refs run unless ``EngineConfig.use_pallas_kernels`` asks
for the kernels in interpret mode, the CPU test of the kernel path):

* membership tests (back-edge checks in ``_leaf_step`` on the dense layout
  and the ``verifyE`` answer path) route through
  :mod:`repro.kernels.membership.ops`;
* candidate generation on the **bucketed** layout routes the back-edge
  refinement ``C(u) ∩ adj(f(u'))`` (Alg. 1 line 6) through
  :mod:`repro.kernels.intersect.ops` instead.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.rads import EngineConfig
from repro.core.cache import AdjCache, probe_dev
from repro.core.exchange import (ExchangeBackend, compact,
                                 unique_ids, unique_pairs)
from repro.core import wire as wire_codec
from repro.core.plan import Plan
from repro.graph.storage import DeviceGraph
from repro.kernels.intersect.ops import intersect as _intersect_op
from repro.kernels.membership.ops import membership as _membership_op


def _kernel_mode(use_pallas: bool) -> tuple[bool, bool]:
    """``(use_kernel, interpret)`` for the main-path Pallas kernels.

    On TPU the compiled kernels always run, whatever the config says;
    elsewhere ``use_pallas`` (``EngineConfig.use_pallas_kernels``) picks
    the kernels in interpret mode over the jnp references."""
    on_tpu = jax.default_backend() == "tpu"
    return use_pallas or on_tpu, not on_tpu


def _membership(rows: jnp.ndarray, vals: jnp.ndarray,
                use_pallas: bool = False) -> jnp.ndarray:
    """Back-edge / verifyE membership test, kernel-gated (see
    :func:`_kernel_mode`)."""
    use_kernel, interpret = _kernel_mode(use_pallas)
    return _membership_op(rows, vals, use_kernel=use_kernel,
                          interpret=interpret)


def _backedge_mask(g: DeviceGraph, w_row: jnp.ndarray, cand: jnp.ndarray,
                   cfg: EngineConfig) -> jnp.ndarray:
    """Candidate-generation back-edge filter: is cand[r, j] in w_row[r]?

    Formats with ``intersect_backedge`` (the bucketed layout) route the
    sorted-window intersection ``C(u) ∩ adj(f(u'))`` through the Pallas
    ``intersect`` kernel (jnp ref off-kernel); the rest keep the
    ``membership`` lowering (the bit-exact seed path).  The two differ only
    where ``cand == sentinel`` — positions the caller has already
    invalidated — so the final masks are identical.
    """
    if g.intersect_backedge:
        use_kernel, interpret = _kernel_mode(cfg.use_pallas_kernels)
        mask, _ = _intersect_op(cand, w_row, sentinel=g.n,
                                use_kernel=use_kernel, interpret=interpret)
        return mask
    return _membership(w_row, cand, cfg.use_pallas_kernels)


# --------------------------------------------------------------------------- #
# Static plan data
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class StepSpec:
    col: int                      # column this leaf writes (matching order)
    piv_col: int
    unit_idx: int
    leaf: int                     # query vertex id
    leaf_deg: int                 # degree filter
    back_cols: tuple[int, ...]    # earlier cols with an edge to leaf (no pivot)
    sym_lt_cols: tuple[int, ...]  # require rows[:, c] <  cand
    sym_gt_cols: tuple[int, ...]  # require cand < rows[:, c]


@dataclass(frozen=True)
class PlanData:
    order: tuple[int, ...]
    col_of: tuple[int, ...]                  # query vertex -> column
    steps: tuple[StepSpec, ...]
    unit_piv_cols: tuple[int, ...]
    unit_steps: tuple[tuple[int, ...], ...]  # step indices per unit
    start_deg: int
    u_start: int
    span_start: int


def build_plan_data(plan: Plan) -> PlanData:
    p = plan.pattern
    order = plan.matching_order
    assert order, "plan must carry a matching order (use best_plan)"
    col_of = [0] * p.n
    for i, u in enumerate(order):
        col_of[u] = i
    cons = p.symmetry_constraints()
    steps: list[StepSpec] = []
    unit_piv_cols: list[int] = []
    unit_steps: list[tuple[int, ...]] = []
    placed = {order[0]}
    for ui, unit in enumerate(plan.units):
        piv_col = col_of[unit.piv]
        unit_piv_cols.append(piv_col)
        sids: list[int] = []
        for lf in sorted(unit.leaves, key=lambda v: col_of[v]):
            back = tuple(col_of[w] for w in p.adj(lf)
                         if w in placed and w != unit.piv)
            lt = tuple(col_of[a] for (a, b) in cons if b == lf and a in placed)
            gt = tuple(col_of[b] for (a, b) in cons if a == lf and b in placed)
            steps.append(StepSpec(col=col_of[lf], piv_col=piv_col,
                                  unit_idx=ui, leaf=lf,
                                  leaf_deg=p.degree(lf), back_cols=back,
                                  sym_lt_cols=lt, sym_gt_cols=gt))
            sids.append(len(steps) - 1)
            placed.add(lf)
        unit_steps.append(tuple(sids))
    return PlanData(order=order, col_of=tuple(col_of), steps=tuple(steps),
                    unit_piv_cols=tuple(unit_piv_cols),
                    unit_steps=tuple(unit_steps),
                    start_deg=p.degree(order[0]), u_start=order[0],
                    span_start=p.span(order[0]))


# --------------------------------------------------------------------------- #
# fetchV / verifyE exchanges
# --------------------------------------------------------------------------- #
def _per_peer_compact(ids, mask, owners, ndev: int, cap_out: int, fill: int,
                      extras: tuple = ()):
    """Split a sorted id list into per-peer request buffers (ndev, cap_out).

    ``extras``: ``(array, fill)`` pairs co-compacted with ``ids`` through
    the same argsort (the cached fetch path routes hit flags / ways /
    cached rows alongside the ids).  Returns ``(reqs, *extras_compacted,
    counts, overflow)``; order within a peer stays sorted."""
    def one_peer(p):
        m = mask & (owners == p)
        _, ov, *outs = compact(
            m, cap_out, ids, *(a for a, _ in extras),
            fills=(fill, *(f for _, f in extras)))
        return (*outs, m.sum(), ov)

    *outs, counts, ovs = jax.vmap(one_peer)(jnp.arange(ndev))
    return (*outs, counts, jnp.any(ovs))


def _varint_id_bytes(wire: jnp.ndarray, n: int) -> jnp.ndarray:
    """Modeled delta+varint size of the fetchV id payloads.

    ``wire``: (ndev, peer, fcap) request buffers — ids ascending among the
    valid (< n) entries, sentinel holes allowed (cache hits are masked to
    ``n``).  Each peer stream is delta-coded against the previous valid id
    (the first id absolute) and each delta LEB128-varint sized; returns the
    per-(src, peer) byte matrix for
    :meth:`~repro.core.exchange.ExchangeBackend.off_device_payload_bytes`.
    """
    valid = wire < n
    run = jax.lax.cummax(jnp.where(valid, wire, -1), axis=wire.ndim - 1)
    prev = jnp.concatenate(
        [jnp.full(run[..., :1].shape, -1, run.dtype), run[..., :-1]], axis=-1)
    delta = jnp.maximum(jnp.where(prev >= 0, wire - prev, wire), 0)
    # deltas >= 2^28 would take 5 LEB128 bytes; the modeled format falls
    # back to the raw 4-byte int32 for those (the escape tag is amortized
    # into the varint sizes), so compressed <= raw 4B/id always holds
    vlen = (1 + (delta >= 1 << 7).astype(jnp.int32)
            + (delta >= 1 << 14).astype(jnp.int32)
            + (delta >= 1 << 21).astype(jnp.int32))
    return jnp.where(valid, vlen, 0).sum(-1)           # (ndev, peer)


def fetch_exchange(g: DeviceGraph, exch: ExchangeBackend,
                   pivots, need, fcap: int, cache: AdjCache | None = None,
                   use_pallas: bool = False):
    """Batched fetchV (§3.2 Expand): dedup foreign pivot ids, probe the
    adjacency cache, exchange the misses, answer with local adjacency rows,
    exchange back, merge cached rows in, and admit the miss responses.

    pivots/need: (ndev, cap).  Returns ``(req_ids (ndev, ndev, fcap) sorted
    per peer — hits included, so the expand-side searchsorted lookup is
    cache-agnostic, fetched_adj (ndev, ndev, fcap, maxdeg) with cached rows
    merged in, overflow, fstats, cache')`` where ``fstats`` carries the
    per-call byte/hit accounting (``bytes_fetch`` counts only what actually
    crossed the wire; ``bytes_saved_cache`` is the hit-masked remainder;
    ``bytes_fetch_compressed`` models delta+varint id coding of the wire
    payload).  With ``cache=None`` the request path is byte-identical to
    the uncached engine and ``cache'`` is ``None``.

    With ``exch.wire_format == "varint"`` the hole-masked request lanes are
    delta+varint coded (:mod:`repro.core.wire`) and the a2a transports the
    ``uint8`` streams + per-lane lengths; the answering device decodes,
    responds with degree+delta coded rows, and the requester scatters the
    compacted responses back onto its hole positions — decoded payloads
    are bit-identical to the raw slabs, so only ``bytes_wire_fetch``
    (actual stream lengths, always <= ``bytes_fetch``) changes.
    """
    ndev, stride, n, D = g.ndev, g.stride, g.n, g.max_degree
    t_ids = jnp.arange(ndev)
    use_cache = cache is not None

    def build(t, pv, nd, ck=None, cr=None):
        foreign = nd & (pv // stride != t) & (pv < n)
        uids, umask = unique_ids(pv, foreign, n)
        if use_cache:
            hit, hway, crow = probe_dev(ck, cr, uids, n)
            hit = hit & umask
        else:
            hit = jnp.zeros(uids.shape, bool)
            hway = jnp.zeros(uids.shape, jnp.int32)
            crow = jnp.full(uids.shape + (1,), n, jnp.int32)  # placeholder
        owners = jnp.clip(uids // stride, 0, ndev - 1)
        return _per_peer_compact(uids, umask, owners, ndev, fcap, n,
                                 extras=((hit, False), (hway, 0), (crow, n)))

    if use_cache:
        (reqs, hit_c, way_c, crow_c, counts, ovs) = jax.vmap(
            build)(t_ids, pivots, need, cache.keys, cache.rows)
        # hits never cross the wire: mask them out of the a2a request
        wire = jnp.where(hit_c, n, reqs)
    else:
        (reqs, hit_c, way_c, crow_c, counts, ovs) = jax.vmap(
            build)(t_ids, pivots, need)
        wire = reqs
    # per-peer hit counts from the compacted flags: identical to the
    # pre-compaction count for every *consumed* wave (an overflowing wave's
    # stats are discarded at retire, so truncation never reaches them)
    counts_hit = hit_c.sum(-1).astype(counts.dtype)

    def answer(t, rc):
        li = jnp.clip(rc - t * stride, 0, stride - 1)
        ok = (rc // stride == t) & (rc < n)
        return jnp.where(ok[..., None], g.rows_at(t, li), n)

    if exch.wire_format == "varint":
        # coded path: compacted varint id streams out, degree+delta coded
        # row streams back, sender scatters onto its hole positions
        req_cap, degs_cap, rows_cap = wire_codec.fetch_stream_caps(fcap, D)
        use_kernel, interpret = _kernel_mode(use_pallas)
        req_s, req_len, req_raw, e_ov, model_ids = \
            wire_codec.encode_ids_lanes(wire, n, req_cap,
                                        use_pallas=use_kernel,
                                        interpret=interpret,
                                        per_device=exch.per_device)
        recv_s, recv_len, recv_raw = exch.a2a_tree((req_s, req_len, req_raw))
        dec_ids, dec_mask = wire_codec.decode_ids_lanes(
            recv_s, recv_len, recv_raw, fcap, n)
        resp = jax.vmap(answer)(t_ids, dec_ids)        # (ndev, src, fcap, D)
        dg_s, dg_len, ri_s, ri_len, resp_raw, r_ov = \
            wire_codec.encode_rows_lanes(resp, dec_mask, n, degs_cap,
                                         rows_cap)
        bk_dg, bk_dgl, bk_ri, bk_ril, bk_raw = exch.a2a_tree(
            (dg_s, dg_len, ri_s, ri_len, resp_raw))
        rows_c = wire_codec.decode_rows_lanes(bk_dg, bk_dgl, bk_ri, bk_ril,
                                              bk_raw, fcap, D, n)
        fetched = wire_codec.scatter_compacted_lanes(rows_c, wire < n, n)
        wire_stream_bytes = (
            exch.off_device_payload_bytes(req_len)
            + exch.off_device_payload_bytes(dg_len + ri_len))
        # sent-bytes attribution: requesters send the id streams, responders
        # send the row streams — row sums recover wire_stream_bytes exactly
        wire_dev = (exch.per_dev_sent_bytes(req_len)
                    + exch.per_dev_sent_bytes(dg_len + ri_len))
        wire_ov = e_ov | r_ov
    else:
        recv = exch.a2a(wire)                          # (ndev, src, fcap)
        resp = jax.vmap(answer)(t_ids, recv)           # (ndev, src, fcap, D)
        fetched = exch.a2a(resp)                       # (ndev, peer, fcap, D)
        wire_stream_bytes = None
        wire_ov = jnp.zeros((), bool)
        model_ids = None
    if use_cache:
        # merge cached rows over the (sentinel) responses of masked slots,
        # then run the admission pass over this batch's probe outcomes
        fetched = jnp.where(hit_c[..., None], crow_c, fetched)
        cache = cache.updated(reqs.reshape(ndev, -1),
                              hit_c.reshape(ndev, -1),
                              way_c.reshape(ndev, -1),
                              fetched.reshape(ndev, -1, D))

    # 4B request id + 4B * max_degree response row per off-device entry
    elem = 4 * (1 + D)
    full_bytes = exch.off_device_bytes(counts, elem)
    wire_bytes = exch.off_device_bytes(counts - counts_hit, elem) \
        if use_cache else full_bytes
    if wire_stream_bytes is None:
        # raw path per-device attribution: requester t sends 4B ids per
        # entry (eff[t, p]), responder p sends 4*D-byte rows back (eff.T);
        # the two row sums add up to wire_bytes exactly
        eff = (counts - counts_hit if use_cache else counts)
        wire_dev = (exch.per_dev_sent_bytes(eff * 4.0)
                    + exch.per_dev_sent_bytes(eff.T * (4.0 * D)))
    # the modeled column reuses the codec's sizing pass when it already ran
    comp_ids = (_varint_id_bytes(wire, n) if model_ids is None
                else model_ids)
    comp_bytes = (exch.off_device_payload_bytes(comp_ids)
                  + exch.off_device_bytes(counts - counts_hit, 4.0 * D))
    zero = jnp.zeros((), jnp.float32)
    fstats = dict(
        bytes_fetch=wire_bytes,
        bytes_fetch_compressed=comp_bytes,
        # actual on-the-wire bytes: stream lengths under 'varint', the raw
        # accounting under 'raw' (per-lane raw escape keeps this <= raw)
        bytes_wire_fetch=(wire_stream_bytes if wire_stream_bytes is not None
                          else wire_bytes),
        bytes_wire_fetch_dev=wire_dev,
        bytes_saved_cache=full_bytes - wire_bytes,
        # probe/hit counters exist only when there is a cache to probe —
        # a --no-cache run must audit as having zero cache activity
        cache_hits=counts_hit.sum().astype(jnp.float32) if use_cache
        else zero,
        cache_probes=counts.sum().astype(jnp.float32) if use_cache
        else zero)
    return reqs, fetched, jnp.any(ovs) | wire_ov, fstats, cache


def verify_exchange(g: DeviceGraph, exch: ExchangeBackend,
                    pa, pb, pmask, vcap: int, use_pallas: bool = False):
    """Batched verifyE over the EVI (§3.2). pa/pb/pmask: (ndev, R, K).
    Pairs routed to owner(pa). Returns (ok (ndev, R, K) — True where the
    edge exists or the slot is inactive, overflow, off_bytes, wire_bytes,
    wire_dev) where ``wire_dev`` is the per-device *sent*-byte attribution
    of ``wire_bytes`` (requesters send the pair streams, owners send the
    answers; its sum recovers ``wire_bytes`` exactly).

    ``off_bytes`` is the raw-equivalent accounting (8 B/pair + 1 B/answer,
    comparable across wire formats); ``wire_bytes`` is what actually
    crossed: with ``exch.wire_format == "varint"`` the sorted ``a`` column
    goes Elias-Fano, ``b`` goes run-delta varint, and the answers come
    back bit-packed (:mod:`repro.core.wire`) — with ``"raw"`` the two are
    equal."""
    ndev, stride, n = g.ndev, g.stride, g.n
    R, K = pa.shape[1], pa.shape[2]
    fa, fb, fm = (x.reshape(ndev, R * K) for x in (pa, pb, pmask))

    ua, ub, umask, rank = jax.vmap(
        lambda a, b, m: unique_pairs(a, b, m, n))(fa, fb, fm)
    owners = jnp.clip(ua // stride, 0, ndev - 1)

    def build(uaa, ubb, mm, ow):
        ra, ca, ov_a = _per_peer_compact(uaa, mm, ow, ndev, vcap, n)
        rb, _, ov_b = _per_peer_compact(ubb, mm, ow, ndev, vcap, n)
        # uniques sorted by `a` => owners non-decreasing => peers contiguous;
        # slot inside peer block = index - first index of that owner
        start = jax.vmap(lambda o: jnp.searchsorted(ow, o))(ow)
        slot = jnp.arange(uaa.shape[0]) - start
        return ra, rb, ca, slot, ov_a | ov_b

    reqs_a, reqs_b, counts, slots, ov = jax.vmap(build)(ua, ub, umask, owners)

    def owned_rows(t, ra):
        li = jnp.clip(ra - t * stride, 0, stride - 1)
        local_ok = (ra // stride == t) & (ra < n)
        return g.rows_at(t, li), local_ok              # (src, vcap, D)

    def member(rows, rb):                              # the kernel call
        D = rows.shape[-1]
        return _membership(rows.reshape(-1, D), rb.reshape(-1, 1),
                           use_pallas).reshape(rb.shape)

    def answer(ra, rb):
        rows, local_ok = jax.vmap(owned_rows)(jnp.arange(ndev), ra)
        return exch.per_device(member)(rows, rb) & local_ok

    if exch.wire_format == "varint":
        # coded path: EF(a) + run-delta varint(b) out, bit-packed bools back
        a_cap, b_cap, ans_cap = wire_codec.verify_stream_caps(vcap)
        a_s, a_len, b_s, b_len, p_raw, p_ov = wire_codec.encode_pairs_lanes(
            reqs_a, reqs_b, n, a_cap, b_cap)
        ra_s, ra_len, rb_s, rb_len, r_raw, r_counts = exch.a2a_tree(
            (a_s, a_len, b_s, b_len, p_raw, counts))
        dec_a, dec_b, _ = wire_codec.decode_pairs_lanes(
            ra_s, ra_len, rb_s, rb_len, r_raw, r_counts, vcap, n, n)
        ans = answer(dec_a, dec_b)
        ans_s, ans_len = wire_codec.pack_bools_lanes(ans, r_counts, ans_cap)
        back_s, _ = exch.a2a_tree((ans_s, ans_len))
        back = wire_codec.unpack_bools_lanes(back_s, counts, vcap)
        wire_bytes = (exch.off_device_payload_bytes(a_len + b_len)
                      + exch.off_device_payload_bytes(ans_len))
        wire_dev = (exch.per_dev_sent_bytes(a_len + b_len)
                    + exch.per_dev_sent_bytes(ans_len))
        ov = ov | p_ov
    else:
        # the (a, b) request buffers travel as one sub-state through the
        # backend
        recv_a, recv_b = exch.a2a_tree((reqs_a, reqs_b))
        ans = answer(recv_a, recv_b)
        back = exch.a2a(ans)                           # (ndev, peer, vcap)
        wire_bytes = None

    def collect(bk, ow, sl, mm, rk):
        sl_c = jnp.clip(sl, 0, vcap - 1)
        ok_unique = bk[ow, sl_c] & mm & (sl < vcap)
        return ok_unique[jnp.clip(rk, 0, ok_unique.shape[0] - 1)]

    ok_flat = jax.vmap(collect)(back, owners, slots, umask, rank)
    ok = ok_flat.reshape(ndev, R, K) | ~pmask
    # 8B pair request + 1B bool response per off-device entry
    off_bytes = exch.off_device_bytes(counts, 8 + 1)
    if wire_bytes is None:
        wire_bytes = off_bytes
        # requester t sends 8B pairs (counts[t, p]); owner p sends 1B
        # answers back (counts.T) — row sums add up to off_bytes exactly
        wire_dev = (exch.per_dev_sent_bytes(counts * 8.0)
                    + exch.per_dev_sent_bytes(counts.T * 1.0))
    return ok, jnp.any(ov), off_bytes, wire_bytes, wire_dev


# --------------------------------------------------------------------------- #
# Leaf expansion
# --------------------------------------------------------------------------- #
def _leaf_step(g: DeviceGraph, cfg: EngineConfig, exch: ExchangeBackend,
               spec: StepSpec, k_off: int, rows, alive, seed_slot,
               pend_a, pend_b, pend_m, req_ids, fetched, local_only: bool):
    """Expand one leaf: candidates = adj(pivot); filter (injectivity,
    symmetry, degree, local back-edge intersection — Alg. 1+2); compact to
    frontier_cap; record undetermined edges into the pending (EVI) buffers.
    Adjacency is read through the format-agnostic ``DeviceGraph``; the
    back-edge kernel runs through ``exch.per_device``."""
    ndev, stride, n, D = g.ndev, g.stride, g.n, g.max_degree
    cap = cfg.frontier_cap
    t_ids = jnp.arange(ndev)

    def candidates(t, rws, alv, rq, ft):
        R, w = rws.shape
        pv = rws[:, spec.piv_col]
        is_local = (pv // stride == t) & (pv < n)
        li = jnp.clip(pv - t * stride, 0, stride - 1)
        lrow = g.rows_at(t, li)                            # (R, D)
        if local_only:
            prow = jnp.where(is_local[:, None], lrow, n)
            lost = jnp.zeros((), bool)
        else:
            peer = jnp.clip(pv // stride, 0, ndev - 1)
            peer_ids = rq[peer]                            # (R, fcap)
            slot = jax.vmap(jnp.searchsorted)(peer_ids, pv[:, None])[:, 0]
            slot = jnp.clip(slot, 0, rq.shape[1] - 1)
            frow = ft[peer, slot]                          # (R, D)
            hit = jnp.take_along_axis(peer_ids, slot[:, None], 1)[:, 0] == pv
            prow = jnp.where(is_local[:, None], lrow,
                             jnp.where(hit[:, None], frow, n))
            lost = jnp.any(alv & (pv < n) & ~is_local & ~hit)

        cand = prow                                        # (R, D)
        valid = (cand < n) & alv[:, None]
        for c in range(w):                                 # injectivity
            valid &= cand != rws[:, c][:, None]
        for c in spec.sym_lt_cols:                         # symmetry breaking
            valid &= rws[:, c][:, None] < cand
        for c in spec.sym_gt_cols:
            valid &= cand < rws[:, c][:, None]
        c_local = (cand // stride == t) & (cand < n)
        c_li = jnp.clip(cand - t * stride, 0, stride - 1)
        valid &= jnp.where(c_local, g.deg_at(t, c_li) >= spec.leaf_deg, True)
        if local_only:
            valid &= c_local                               # Prop. 1 pruning
        # back-edge windows for the local checks (Alg 2 lines 3-5, 8-11)
        w_rows, w_locs = [], []
        for c in spec.back_cols:
            wv = rws[:, c]
            w_locs.append((wv // stride == t) & (wv < n))
            w_rows.append(g.rows_at(t, jnp.clip(wv - t * stride, 0,
                                                stride - 1)))
        return cand, valid, lost, tuple(w_rows), tuple(w_locs)

    if local_only:
        cand, valid, losts, w_rows, w_locs = jax.vmap(
            lambda t, rws, alv: candidates(t, rws, alv, None, None))(
            t_ids, rows, alive)
    else:
        cand, valid, losts, w_rows, w_locs = jax.vmap(candidates)(
            t_ids, rows, alive, req_ids, fetched)
    backedge = exch.per_device(
        lambda w_row, cd: _backedge_mask(g, w_row, cd, cfg))
    for k in range(len(spec.back_cols)):
        valid &= jnp.where(w_locs[k][..., None], backedge(w_rows[k], cand),
                           True)

    def compact_dev(t, rws, sslot, pa, pb, pm, cand, valid):
        R = rws.shape[0]
        # compact (R*D) -> cap
        parent = jnp.repeat(jnp.arange(R, dtype=jnp.int32), D)
        new_mask, ov, parent_c, cand_c = compact(
            valid.reshape(-1), cap, parent, cand.reshape(-1), fill=0)
        new_rows = jnp.concatenate(
            [rws[parent_c], cand_c[:, None].astype(jnp.int32)], axis=1)
        new_rows = jnp.where(new_mask[:, None], new_rows, n)
        new_slot = jnp.where(new_mask, sslot[parent_c], 0)
        pa_n, pb_n, pm_n = pa[parent_c], pb[parent_c], pm[parent_c]
        pm_n &= new_mask[:, None]

        # new pending pairs: back edges whose f(u') is foreign. Route to the
        # local endpoint if the candidate is local (paper: verify locally),
        # else to owner(f(u')).
        for k, c in enumerate(spec.back_cols):
            wv_n = new_rows[:, c]
            cd = new_rows[:, -1]
            w_loc_n = (wv_n // stride == t) & (wv_n < n)
            c_loc_n = (cd // stride == t) & (cd < n)
            need = new_mask & ~w_loc_n
            a_val = jnp.where(c_loc_n, cd, wv_n)
            b_val = jnp.where(c_loc_n, wv_n, cd)
            pa_n = pa_n.at[:, k_off + k].set(jnp.where(need, a_val, n))
            pb_n = pb_n.at[:, k_off + k].set(jnp.where(need, b_val, n))
            pm_n = pm_n.at[:, k_off + k].set(need)
        return new_rows, new_mask, new_slot, pa_n, pb_n, pm_n, ov

    rows, alive, seed_slot, pend_a, pend_b, pend_m, ovs = jax.vmap(
        compact_dev)(t_ids, rows, seed_slot, pend_a, pend_b, pend_m,
                     cand, valid)
    return (rows, alive, seed_slot, pend_a, pend_b, pend_m,
            jnp.any(ovs), jnp.any(losts))


# --------------------------------------------------------------------------- #
# WaveState: the immutable per-wave pytree threaded through the stages
# --------------------------------------------------------------------------- #
@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class WaveState:
    """Everything one region-group wave carries between pipeline stages.

    ``rows`` widens by one column per leaf step, so stage functions are
    jitted *per unit index* (each (unit, stage) pair has a distinct static
    shape).  ``pend_*`` (the EVI buffers, Def. 5) exist only on the
    expand→verify edge and are ``None`` elsewhere; ``rounds_alive`` grows by
    one per-device count per completed unit.

    Byte counters are f32 scalars (x64 is disabled), exact up to 2^24
    bytes *per wave* — per-wave traffic beyond ~16MB would round, and the
    cache conservation law (``bytes_fetch + bytes_saved_cache`` == the
    uncached ``bytes_fetch``) would then only hold approximately.  The
    driver accumulates across waves in Python floats, so only the
    single-wave total is bounded."""

    rows: jnp.ndarray            # (ndev, cap, width) partial embeddings
    alive: jnp.ndarray           # (ndev, cap) bool
    seed_slot: jnp.ndarray       # (ndev, cap) originating seed slot
    overflow: jnp.ndarray        # () bool — any capacity overflow so far
    lost: jnp.ndarray            # () bool — any dropped fetchV response
    bytes_fetch: jnp.ndarray     # () f32 — off-device fetchV wire traffic
    bytes_verify: jnp.ndarray    # () f32 — off-device verifyE traffic
    bytes_wire_fetch: jnp.ndarray   # () f32 — actual coded fetchV stream bytes
    bytes_wire_verify: jnp.ndarray  # () f32 — actual coded verifyE stream bytes
    bytes_wire_fetch_dev: jnp.ndarray   # (ndev,) f32 — fetchV wire bytes by
    # sending device (sums to bytes_wire_fetch; skew-curve source)
    bytes_wire_verify_dev: jnp.ndarray  # (ndev,) f32 — verifyE wire bytes by
    # sending device (sums to bytes_wire_verify)
    bytes_fetch_compressed: jnp.ndarray  # () f32 — modeled delta+varint wire
    bytes_saved_cache: jnp.ndarray       # () f32 — fetchV bytes hit-masked
    cache_hits: jnp.ndarray      # () f32 — unique foreign ids served by cache
    cache_probes: jnp.ndarray    # () f32 — unique foreign ids requested
    compile_cache_hits: jnp.ndarray  # () f32 — stage executables loaded, not
    # traced, for this wave's dispatches (StageRunner credits via the
    # finalize exec_hits argument; the field itself stays zero inside the
    # stages — host-side compile accounting never enters a trace)
    node_counts: jnp.ndarray     # (ndev, scap) trie nodes per seed (§6 calib)
    rounds_alive: tuple = ()     # per-unit (ndev,) alive counts
    pend_a: jnp.ndarray | None = None   # (ndev, cap, K) EVI endpoint a
    pend_b: jnp.ndarray | None = None   # (ndev, cap, K) EVI endpoint b
    pend_m: jnp.ndarray | None = None   # (ndev, cap, K) EVI slot active

    def tree_flatten(self):
        return ((self.rows, self.alive, self.seed_slot, self.overflow,
                 self.lost, self.bytes_fetch, self.bytes_verify,
                 self.bytes_wire_fetch, self.bytes_wire_verify,
                 self.bytes_wire_fetch_dev, self.bytes_wire_verify_dev,
                 self.bytes_fetch_compressed, self.bytes_saved_cache,
                 self.cache_hits, self.cache_probes,
                 self.compile_cache_hits,
                 self.node_counts, self.rounds_alive,
                 self.pend_a, self.pend_b, self.pend_m), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def init_wave(g: DeviceGraph, seeds, seed_mask) -> WaveState:
    """Stage 0: lift a padded (ndev, scap) seed block into a WaveState."""
    ndev = g.ndev
    scap = seeds.shape[1]
    return WaveState(
        rows=seeds[..., None].astype(jnp.int32),
        alive=seed_mask,
        seed_slot=jnp.broadcast_to(
            jnp.arange(scap, dtype=jnp.int32), seeds.shape),
        overflow=jnp.zeros((), bool),
        lost=jnp.zeros((), bool),
        bytes_fetch=jnp.zeros((), jnp.float32),
        bytes_verify=jnp.zeros((), jnp.float32),
        bytes_wire_fetch=jnp.zeros((), jnp.float32),
        bytes_wire_verify=jnp.zeros((), jnp.float32),
        bytes_wire_fetch_dev=jnp.zeros((ndev,), jnp.float32),
        bytes_wire_verify_dev=jnp.zeros((ndev,), jnp.float32),
        bytes_fetch_compressed=jnp.zeros((), jnp.float32),
        bytes_saved_cache=jnp.zeros((), jnp.float32),
        cache_hits=jnp.zeros((), jnp.float32),
        cache_probes=jnp.zeros((), jnp.float32),
        compile_cache_hits=jnp.zeros((), jnp.float32),
        node_counts=jnp.zeros((ndev, scap), jnp.int32))


def unit_evi_width(pd: PlanData, ui: int) -> int:
    """Number of EVI slots unit ``ui`` can emit (0 => verifyE is a no-op)."""
    return sum(len(pd.steps[s].back_cols) for s in pd.unit_steps[ui])


def fetch_stage(g: DeviceGraph, pd: PlanData, cfg: EngineConfig,
                exch: ExchangeBackend, ui: int, state: WaveState,
                local_only: bool, cache: AdjCache | None = None):
    """Pipeline stage 1 of unit ``ui``: batched fetchV on the unit pivot,
    with the foreign-adjacency cache probed before and fed after the a2a.

    Returns ``(state', bufs, cache')`` where ``bufs = (req_ids, fetched)``
    feeds ``expand_stage`` (``None`` in SM-E mode — no collectives at all)
    and ``cache'`` is the post-admission cache state the caller threads
    into the next fetch (``None`` stays ``None``)."""
    if local_only:
        return state, None, cache
    piv_col = pd.unit_piv_cols[ui]
    req_ids, fetched, f_ov, fs, cache = fetch_exchange(
        g, exch, state.rows[:, :, piv_col], state.alive,
        cfg.fetch_cap, cache, use_pallas=cfg.use_pallas_kernels)
    state = replace(
        state, overflow=state.overflow | f_ov,
        bytes_fetch=state.bytes_fetch + fs["bytes_fetch"],
        bytes_wire_fetch=state.bytes_wire_fetch + fs["bytes_wire_fetch"],
        bytes_wire_fetch_dev=(state.bytes_wire_fetch_dev
                              + fs["bytes_wire_fetch_dev"]),
        bytes_fetch_compressed=(state.bytes_fetch_compressed
                                + fs["bytes_fetch_compressed"]),
        bytes_saved_cache=state.bytes_saved_cache + fs["bytes_saved_cache"],
        cache_hits=state.cache_hits + fs["cache_hits"],
        cache_probes=state.cache_probes + fs["cache_probes"])
    return state, (req_ids, fetched), cache


def expand_stage(g: DeviceGraph, pd: PlanData, cfg: EngineConfig,
                 exch: ExchangeBackend, ui: int, state: WaveState, bufs,
                 local_only: bool) -> WaveState:
    """Pipeline stage 2 of unit ``ui``: every leaf step of the unit —
    candidate generation from (local ∪ fetched) adjacency, injectivity /
    symmetry / degree / local-membership filters, frontier compaction, and
    EVI recording into fresh ``pend_*`` buffers."""
    step_ids = pd.unit_steps[ui]
    scap = state.node_counts.shape[1]
    K = max(unit_evi_width(pd, ui), 1)
    rows, alive, seed_slot = state.rows, state.alive, state.seed_slot
    overflow, lost, node_counts = state.overflow, state.lost, state.node_counts
    pend_a = jnp.full((g.ndev, rows.shape[1], K), g.n, jnp.int32)
    pend_b = jnp.full((g.ndev, rows.shape[1], K), g.n, jnp.int32)
    pend_m = jnp.zeros((g.ndev, rows.shape[1], K), bool)
    req_ids, fetched = bufs if bufs is not None else (None, None)
    k_off = 0
    for sid in step_ids:
        spec = pd.steps[sid]
        (rows, alive, seed_slot, pend_a, pend_b, pend_m, ov_s, lost_s
         ) = _leaf_step(g, cfg, exch, spec, k_off,
                        rows, alive, seed_slot, pend_a, pend_b, pend_m,
                        req_ids, fetched, local_only)
        overflow |= ov_s
        lost |= lost_s
        k_off += len(spec.back_cols)
        inc = jax.vmap(
            # duplicate indices are intended here (many frontier rows per
            # seed) and integer .add is order-independent:
            # radslint: allow[RL003] deterministic seed-slot segment-sum
            lambda ss, al: jnp.zeros((scap,), jnp.int32)
            .at[jnp.clip(ss, 0, scap - 1)].add(al.astype(jnp.int32))
        )(seed_slot, alive)
        node_counts += inc
    return replace(state, rows=rows, alive=alive, seed_slot=seed_slot,
                   overflow=overflow, lost=lost, node_counts=node_counts,
                   pend_a=pend_a, pend_b=pend_b, pend_m=pend_m)


def verify_stage(g: DeviceGraph, pd: PlanData, cfg: EngineConfig,
                 exch: ExchangeBackend, ui: int, state: WaveState,
                 local_only: bool) -> WaveState:
    """Pipeline stage 3 of unit ``ui``: batched verifyE over the EVI, then
    alive-masking.  Consumes and clears the ``pend_*`` buffers and appends
    the unit's per-device alive count to ``rounds_alive``."""
    alive = state.alive
    overflow, bytes_verify = state.overflow, state.bytes_verify
    bytes_wire_verify = state.bytes_wire_verify
    bytes_wire_verify_dev = state.bytes_wire_verify_dev
    if (not local_only) and unit_evi_width(pd, ui) > 0:
        ok, v_ov, v_b, v_wb, v_wd = verify_exchange(
            g, exch, state.pend_a, state.pend_b, state.pend_m,
            cfg.verify_cap, use_pallas=cfg.use_pallas_kernels)
        alive = alive & jnp.all(ok, axis=-1)
        overflow = overflow | v_ov
        bytes_verify = bytes_verify + v_b
        bytes_wire_verify = bytes_wire_verify + v_wb
        bytes_wire_verify_dev = bytes_wire_verify_dev + v_wd
    return replace(state, alive=alive, overflow=overflow,
                   bytes_verify=bytes_verify,
                   bytes_wire_verify=bytes_wire_verify,
                   bytes_wire_verify_dev=bytes_wire_verify_dev,
                   rounds_alive=state.rounds_alive + (alive.sum(axis=-1),),
                   pend_a=None, pend_b=None, pend_m=None)


def finalize_wave(state: WaveState, exec_hits=0.0):
    """Drain point: WaveState -> the classic (rows, alive, counts, complete,
    stats) tuple the driver consumes.

    ``exec_hits`` is the StageRunner's count of stage dispatches this wave
    served from the persistent executable cache instead of tracing
    (:mod:`repro.runtime.compile_cache`).  It rides through the traced
    finalize as a scalar argument so the hit accounting reaches the driver
    in the same single ``device_get`` as every other wave stat."""
    counts = state.alive.sum(axis=-1)
    stats = dict(bytes_fetch=state.bytes_fetch,
                 bytes_verify=state.bytes_verify,
                 bytes_wire_fetch=state.bytes_wire_fetch,
                 bytes_wire_verify=state.bytes_wire_verify,
                 bytes_wire_fetch_dev=state.bytes_wire_fetch_dev,
                 bytes_wire_verify_dev=state.bytes_wire_verify_dev,
                 bytes_fetch_compressed=state.bytes_fetch_compressed,
                 bytes_saved_cache=state.bytes_saved_cache,
                 cache_hits=state.cache_hits,
                 cache_probes=state.cache_probes,
                 compile_cache_hits=state.compile_cache_hits + exec_hits,
                 rows_per_round=jnp.stack(state.rounds_alive),
                 node_counts=state.node_counts)
    return (state.rows, state.alive, counts,
            ~(state.overflow | state.lost), stats)


# --------------------------------------------------------------------------- #
# Full multi-round run (synchronous composition of the stages)
# --------------------------------------------------------------------------- #
def run_rounds(g: DeviceGraph, pd: PlanData, cfg: EngineConfig,
               exch: ExchangeBackend, seeds, seed_mask, local_only: bool,
               cache: AdjCache | None = None):
    """Traceable core: all units, all leaves, exchanges per round.

    seeds: (ndev, scap) global vertex ids.  Returns (rows, alive, counts,
    complete, stats).  This is exactly ``fetch→expand→verify`` per unit —
    the async scheduler runs the same stages, interleaved across waves,
    with the (optional) adjacency cache threaded through the fetches.
    The cache is per-call here: the post-run state is discarded (the
    classic return tuple is kept), so cross-wave cache warmth is the
    :class:`~repro.core.scheduler.StageRunner`'s job — ``run_rounds ==
    staged pipeline`` holds for results, not for cache temperature."""
    state = init_wave(g, seeds, seed_mask)
    for ui in range(len(pd.unit_steps)):
        state, bufs, cache = fetch_stage(g, pd, cfg, exch, ui, state,
                                         local_only, cache)
        state = expand_stage(g, pd, cfg, exch, ui, state, bufs, local_only)
        state = verify_stage(g, pd, cfg, exch, ui, state, local_only)
    return finalize_wave(state)
