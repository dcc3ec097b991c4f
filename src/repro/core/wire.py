"""On-the-wire codecs for the exchange payloads (compressed fetchV/verifyE).

RADS's headline claim is minimal communication; after PR 4's cache absorbed
most of the fetchV traffic, the verifyE pair exchange dominates the wire.
This module turns the *modeled* delta+varint column of PR 4 into real
on-the-wire coding: every codec here encodes a payload lane into a compact
``uint8`` stream *inside the jitted stage*, the streams (plus per-lane byte
lengths) travel through ``ExchangeBackend.a2a_tree``, and the receiving
device decodes them back — ``encode ∘ decode`` is exact, so enumeration
results are wire-format-invariant by construction.

Stream layout
-------------
A *lane* is one (source device, peer device) payload of a batched exchange.
All codecs are fixed-capacity: a lane encodes into a static ``cap``-byte
buffer plus a dynamic byte ``length`` (the only bytes a real transport
would put on the wire — the accounting sums lengths, never capacities).

* **fetchV request ids** (:func:`encode_ids` / :func:`decode_ids`) —
  sorted-unique vertex ids, sentinel holes allowed (cache hits are masked
  off the wire).  The wire stream drops the holes: valid ids are
  delta-coded against the previous valid id (first id absolute) and each
  delta is LEB128-varint coded (7 payload bits per byte, high bit =
  continuation).  The value boundaries are self-describing (a clear high
  bit terminates a value), so the decoder recovers the id count from the
  stream alone.  The requester remembers its hole positions and scatters
  the positional responses back (:func:`scatter_compacted`).
* **fetchV response rows** (:func:`encode_rows` / :func:`decode_rows`) —
  one sorted sentinel-padded adjacency window per valid request, as two
  streams: a varint *degree* stream (one value per row) and a flat varint
  *id* stream (per row: first neighbor absolute, then deltas).  Row
  boundaries come from the degree stream, so the id stream carries no
  padding at all — on an avg-degree-8 graph this replaces the raw
  ``4·max_degree`` bytes/row with ~``1 + 2·deg`` bytes.
* **verifyE pairs** (:func:`encode_pairs` / :func:`decode_pairs`) — the
  per-peer EVI request lanes arrive lexicographically sorted, so the ``a``
  column is monotone: it is coded Elias-Fano style (``l`` low bits packed
  contiguously, high bits in unary; ``l`` is derived from (universe,
  count) by integer bit-length arithmetic so encoder and decoder agree
  without transmitting it).  The ``b`` column is varint coded: absolute at
  the start of each equal-``a`` run, delta inside a run (unique pairs make
  in-run deltas >= 1).  Pair count rides the control plane (the ``counts``
  matrix every exchange already computes).
* **verifyE answers** (:func:`pack_bools` / :func:`unpack_bools`) — one
  bit per queried pair (``ceil(count/8)`` bytes instead of one byte per
  bool).

Capacity / escalation contract
------------------------------
Stream capacities derive from the engine capacities
(:func:`fetch_stream_caps` / :func:`verify_stream_caps`), so a scheduler
capacity escalation doubles them alongside ``fetch_cap``/``verify_cap``
and the stages re-jit with the wider streams.  Every encoder still returns
an ``overflow`` flag (ORed into the wave's overflow, handled by the same
split/escalate loop) — but with the derived capacities a coded lane is
only ever *selected* when it fits, because of the raw escape below.

Raw escape (the ``<= raw`` guarantee)
-------------------------------------
Each encoder also materializes the lane in raw little-endian ``int32``
form and picks whichever is smaller (a per-lane ``raw`` flag rides the
control plane, like a real codec's stored-block bit).  Wire bytes
therefore never exceed the raw accounting — the per-wave identity
``bytes_wire_fetch <= bytes_fetch`` holds *exactly*, even for adversarial
id distributions where varint deltas would need 5 bytes.

Why delta+varint (and EF) for ids, not quantization
---------------------------------------------------
Vertex ids are exact references — a single flipped low bit verifies the
wrong edge — so the int8-quantization machinery used for gradients
(:mod:`repro.distributed.compression`) is unusable here.  Sorted id
vectors are instead *structurally* redundant: deltas of a sorted-unique
sequence over universe ``n`` carry ~``log2(n/count)`` bits of entropy, not
32, which is exactly what delta+varint (byte-granular) and Elias-Fano
(bit-granular, for the monotone verifyE ``a`` column) exploit — lossless
by construction.

Per-lane byte lengths, pair counts, and raw flags are control-plane
metadata (a real transport's message headers), mirroring how the raw path
never charges for its implicit sentinel structure; the accounting for both
formats charges payload bytes only.

The modeled :func:`repro.core.engine._varint_id_bytes` column caps varints
at 4 bytes (its escape is amortized); the real codec emits true 5-byte
LEB128 for deltas >= 2^28, so actual and modeled fetch id bytes agree
exactly for every graph with ``n < 2^28`` (all of ours) and may differ
beyond that.

All codecs are pure jnp (scatter/gather + cumulative sums, static shapes)
so they vmap over the ``(ndev, peer)`` lane grid and pass through
``jax.jit``/``shard_map`` untouched; the delta/varint-size pass of the id
encoder — the hot fetch-path op — routes through the Pallas kernel in
:mod:`repro.kernels.varint` when ``use_pallas_kernels`` is set (the jnp
reference stays the CPU path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.varint.ops import delta_vlen
from repro.kernels.varint.ref import varint_size

WIRE_FORMATS = ("raw", "varint")

_U8 = jnp.uint8
_I32 = jnp.int32


# --------------------------------------------------------------------------- #
# Capacity helpers (derived from the engine caps => escalate together)
# --------------------------------------------------------------------------- #
def fetch_stream_caps(fcap: int, max_degree: int) -> tuple[int, int, int]:
    """(request id stream, response degree stream, response id stream) caps.

    Sized so the raw escape always fits: requests <= 4 B/id, responses
    <= 4·max_degree B/row; the coded form is only selected when smaller.
    """
    return 4 * fcap, 2 * fcap, 4 * max_degree * fcap


def verify_stream_caps(vcap: int) -> tuple[int, int, int]:
    """(a stream, b stream, answer stream) caps — raw escape fits 4 B/id
    per column; answers are bit-packed (always <= 1 B/pair)."""
    return 4 * vcap, 4 * vcap, (vcap + 7) // 8


# --------------------------------------------------------------------------- #
# Varint core (per-lane; callers vmap).  The LEB128 sizing ladder is
# shared with the kernel package (`repro.kernels.varint.ref.varint_size`)
# so the stream-length selection and the delta_vlen fast path can never
# drift apart.
# --------------------------------------------------------------------------- #
def _write_varints(vals: jnp.ndarray, vlen: jnp.ndarray, cap: int):
    """Scatter LEB128 codes into a ``cap``-byte stream.

    ``vals`` (K,) non-negative; ``vlen`` (K,) byte sizes with 0 = skip.
    Returns (stream (cap,) u8, total_bytes ()).  Entries are laid out in
    array order at offsets ``exclusive_cumsum(vlen)``; bytes past ``cap``
    are dropped (the caller's raw escape guarantees they are never
    selected)."""
    vals = vals.astype(_I32)
    offs = jnp.cumsum(vlen) - vlen
    total = vlen.sum()
    stream = jnp.zeros((cap,), _U8)
    for b in range(5):
        sel = vlen > b
        byte = ((vals >> (7 * b)) & 0x7F) | jnp.where(vlen > b + 1, 0x80, 0)
        stream = stream.at[jnp.where(sel, offs + b, cap)].set(
            byte.astype(_U8), mode="drop")
    return stream, total


def _parse_varints(stream: jnp.ndarray, length: jnp.ndarray, m_out: int):
    """Inverse of :func:`_write_varints`: fully vectorized LEB128 parse.

    Value boundaries are self-describing (a clear high bit ends a value):
    byte -> segment via a cumulative count of terminators, in-segment
    position via a running max over segment starts, then one scatter-add
    assembles the 7-bit payloads.  Returns (vals (m_out,), count ())."""
    cap = stream.shape[0]
    idx = jnp.arange(cap)
    inb = idx < length
    byte = stream.astype(_I32)
    term = inb & ((byte & 0x80) == 0)
    seg = jnp.cumsum(term.astype(_I32)) - term.astype(_I32)
    prev_term = jnp.concatenate([jnp.array([True]), term[:-1]])
    start = inb & prev_term
    sidx = jax.lax.cummax(jnp.where(start, idx, -1))
    p7 = jnp.clip(idx - sidx, 0, 4)
    contrib = (byte & 0x7F) << (7 * p7)
    vals = jnp.zeros((m_out,), _I32).at[jnp.where(inb, seg, m_out)].add(
        jnp.where(inb, contrib, 0), mode="drop")
    return vals, term.sum()


def _write_raw32(vals: jnp.ndarray, pos: jnp.ndarray, valid: jnp.ndarray,
                 cap: int, stream: jnp.ndarray | None = None):
    """Little-endian int32s at 4-byte slots ``pos`` (the raw escape)."""
    if stream is None:
        stream = jnp.zeros((cap,), _U8)
    vals = vals.astype(_I32)
    for b in range(4):
        byte = ((vals >> (8 * b)) & 0xFF).astype(_U8)
        stream = stream.at[jnp.where(valid, pos * 4 + b, cap)].set(
            byte, mode="drop")
    return stream


def _read_raw32(stream: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Gather little-endian int32s from 4-byte slots ``pos``."""
    cap = stream.shape[0]
    out = jnp.zeros(pos.shape, _I32)
    for b in range(4):
        out = out | (stream[jnp.clip(pos * 4 + b, 0, cap - 1)].astype(_I32)
                     << (8 * b))
    return out


# --------------------------------------------------------------------------- #
# fetchV request ids: delta + varint over a sorted-with-holes lane
# --------------------------------------------------------------------------- #
def _encode_ids_core(ids, delta, vlen, cap: int):
    valid = vlen > 0
    count = valid.sum()
    coded, total = _write_varints(delta, vlen, cap)
    raw_len = 4 * count
    use_raw = (total > raw_len) | (total > cap)
    rank = jnp.cumsum(valid) - 1
    raw = _write_raw32(ids, rank, valid, cap)
    stream = jnp.where(use_raw, raw, coded)
    length = jnp.where(use_raw, raw_len, total)
    return stream, length.astype(_I32), use_raw, length > cap


def encode_ids(ids: jnp.ndarray, sentinel: int, cap: int,
               use_pallas: bool = False, interpret: bool = True):
    """One lane: sorted ids with sentinel holes -> compacted varint stream.

    Returns ``(stream (cap,) u8, length (), raw (), overflow ())``."""
    delta, vlen = delta_vlen(ids[None], sentinel, use_kernel=use_pallas,
                             interpret=interpret)
    return _encode_ids_core(ids, delta[0], vlen[0], cap)


def decode_ids(stream: jnp.ndarray, length: jnp.ndarray, raw: jnp.ndarray,
               m_out: int, sentinel: int):
    """Inverse of :func:`encode_ids`: ids land compacted at the front.

    Returns ``(ids (m_out,) ascending, sentinel-filled; mask (m_out,))``."""
    deltas, count_c = _parse_varints(stream, length, m_out)
    ids_c = jnp.cumsum(deltas)
    k = jnp.arange(m_out)
    ids_r = _read_raw32(stream, k)
    count = jnp.where(raw, length // 4, count_c)
    mask = k < count
    ids = jnp.where(mask, jnp.where(raw, ids_r, ids_c), sentinel)
    return ids, mask


def scatter_compacted(rows_c: jnp.ndarray, valid: jnp.ndarray,
                      fill) -> jnp.ndarray:
    """Spread compacted per-lane responses back onto the holed request
    slots: ``out[j] = rows_c[rank(j)]`` where ``valid[j]``, else ``fill``.
    ``rows_c``: (m, ...) compacted at the front; ``valid``: (m,)."""
    rank = jnp.clip(jnp.cumsum(valid) - 1, 0, valid.shape[0] - 1)
    out = rows_c[rank]
    shape = valid.shape + (1,) * (rows_c.ndim - 1)
    return jnp.where(valid.reshape(shape), out, fill)


# --------------------------------------------------------------------------- #
# fetchV response rows: degree stream + flat delta id stream
# --------------------------------------------------------------------------- #
def encode_rows(rows: jnp.ndarray, valid: jnp.ndarray, sentinel: int,
                degs_cap: int, ids_cap: int):
    """One lane of adjacency windows ``rows (m, D)`` (sorted, sentinel
    padded; only ``valid`` rows coded, compacted to the front).

    Returns ``(degs_stream, degs_len, ids_stream, ids_len, raw, overflow)``.
    The raw escape stores the padded int32 rows in the id stream (degree
    stream empty)."""
    m, D = rows.shape
    deg = jnp.where(valid, (rows < sentinel).sum(-1), 0).astype(_I32)
    dvl = jnp.where(valid, varint_size(deg), 0)
    degs_s, degs_total = _write_varints(deg, dvl, degs_cap)

    col = jnp.arange(D)
    prev = jnp.concatenate([jnp.zeros((m, 1), _I32), rows[:, :-1]], axis=1)
    ok = valid[:, None] & (col[None, :] < deg[:, None])
    dmat = jnp.where(col[None, :] == 0, rows, rows - prev)
    dmat = jnp.where(ok, jnp.maximum(dmat, 0), 0)
    vl = jnp.where(ok, varint_size(dmat), 0)
    ids_s, ids_total = _write_varints(dmat.reshape(-1), vl.reshape(-1),
                                      ids_cap)

    count = valid.sum()
    raw_len = 4 * D * count
    use_raw = ((degs_total + ids_total > raw_len) | (ids_total > ids_cap)
               | (degs_total > degs_cap))
    rank = jnp.cumsum(valid) - 1
    rpos = rank[:, None] * D + col[None, :]
    raw_s = _write_raw32(rows.reshape(-1), rpos.reshape(-1),
                         jnp.repeat(valid, D), ids_cap)
    ids_stream = jnp.where(use_raw, raw_s, ids_s)
    degs_stream = jnp.where(use_raw, jnp.zeros_like(degs_s), degs_s)
    ids_len = jnp.where(use_raw, raw_len, ids_total).astype(_I32)
    degs_len = jnp.where(use_raw, 0, degs_total).astype(_I32)
    overflow = (ids_len > ids_cap) | (degs_len > degs_cap)
    return degs_stream, degs_len, ids_stream, ids_len, use_raw, overflow


def decode_rows(degs_s, degs_len, ids_s, ids_len, raw, m: int, D: int,
                sentinel: int) -> jnp.ndarray:
    """Inverse of :func:`encode_rows`: ``(m, D)`` windows, compacted at the
    front, sorted-then-sentinel exactly as ``DeviceGraph.rows_at`` emits."""
    degs, count_c = _parse_varints(degs_s, degs_len, m)
    rstart = jnp.cumsum(degs) - degs
    flat, _ = _parse_varints(ids_s, ids_len, m * D)
    col = jnp.arange(D)
    f = rstart[:, None] + col[None, :]
    dmat = flat[jnp.clip(f, 0, m * D - 1)]
    ok = (col[None, :] < degs[:, None]) & (jnp.arange(m)[:, None] < count_c)
    rows_c = jnp.cumsum(jnp.where(ok, dmat, 0), axis=1)
    rows_c = jnp.where(ok, rows_c, sentinel)

    count_r = ids_len // (4 * D)
    rpos = jnp.arange(m)[:, None] * D + col[None, :]
    rows_r = _read_raw32(ids_s, rpos)
    rows_r = jnp.where(jnp.arange(m)[:, None] < count_r, rows_r, sentinel)
    return jnp.where(raw, rows_r, rows_c)


# --------------------------------------------------------------------------- #
# verifyE pairs: Elias-Fano `a` column + run-delta varint `b` column
# --------------------------------------------------------------------------- #
def _bitlen(x) -> jnp.ndarray:
    """Integer bit length (floor(log2(x)) + 1 for x > 0; 0 for x <= 0) —
    pure integer compares, so encoder and decoder always agree."""
    x = jnp.asarray(x, _I32)
    out = jnp.zeros(jnp.shape(x), _I32)
    for k in range(31):
        out = out + (x >= (1 << k)).astype(_I32)
    return out


def _ef_lowbits(universe: int, count) -> jnp.ndarray:
    """EF low-bit width ~ floor(log2(universe / count)), integerized."""
    return jnp.clip(_bitlen(universe) - _bitlen(jnp.maximum(count, 1)),
                    0, 30)


def _set_bits(stream, bitpos, bit, valid, cap: int):
    """Scatter single bits (each position written at most once)."""
    byte = bitpos >> 3
    val = (bit.astype(_I32) << (bitpos & 7)).astype(_U8)
    sel = valid & (bit > 0)
    return stream.at[jnp.where(sel, byte, cap)].add(val, mode="drop")


def _get_bit(stream, bitpos):
    cap = stream.shape[0]
    return (stream[jnp.clip(bitpos >> 3, 0, cap - 1)].astype(_I32)
            >> (bitpos & 7)) & 1


def encode_pairs(a: jnp.ndarray, b: jnp.ndarray, universe: int,
                 a_cap: int, b_cap: int):
    """One verifyE lane: pairs valid-at-the-front (fill = ``universe``),
    ``a`` non-decreasing, ``b`` ascending inside equal-``a`` runs.

    Returns ``(a_stream, a_len, b_stream, b_len, raw, overflow)``; the
    pair count is control-plane metadata (the exchange's ``counts``)."""
    m = a.shape[0]
    idx = jnp.arange(m)
    valid = a < universe
    count = valid.sum()
    l = _ef_lowbits(universe, count)

    # -- a column: Elias-Fano (low bits packed, high bits unary) ------------ #
    a_s = jnp.zeros((a_cap,), _U8)
    av = jnp.where(valid, a, 0).astype(_I32)
    for j in range(31):
        a_s = _set_bits(a_s, idx * l + j, (av >> j) & 1,
                        valid & (j < l), a_cap)
    high = av >> l
    a_s = _set_bits(a_s, count * l + high + idx, jnp.ones((m,), _I32),
                    valid, a_cap)
    last_high = jnp.max(jnp.where(valid, high, -1))
    a_bits = count * l + jnp.where(count > 0, last_high + count, 0)
    a_total = (a_bits + 7) // 8

    # -- b column: varint, absolute at run starts, delta inside runs -------- #
    prev_a = jnp.concatenate([jnp.full((1,), -1, _I32), a[:-1]])
    prev_b = jnp.concatenate([jnp.zeros((1,), _I32), b[:-1]])
    new_run = a != prev_a
    bv = jnp.where(valid,
                   jnp.where(new_run, b, jnp.maximum(b - prev_b, 0)), 0)
    bvl = jnp.where(valid, varint_size(bv), 0)
    b_s, b_total = _write_varints(bv, bvl, b_cap)

    raw_len = 4 * count
    use_raw = ((a_total + b_total > 2 * raw_len) | (a_total > a_cap)
               | (b_total > b_cap))
    a_raw = _write_raw32(a, idx, valid, a_cap)
    b_raw = _write_raw32(b, idx, valid, b_cap)
    a_stream = jnp.where(use_raw, a_raw, a_s)
    b_stream = jnp.where(use_raw, b_raw, b_s)
    a_len = jnp.where(use_raw, raw_len, a_total).astype(_I32)
    b_len = jnp.where(use_raw, raw_len, b_total).astype(_I32)
    overflow = (a_len > a_cap) | (b_len > b_cap)
    return a_stream, a_len, b_stream, b_len, use_raw, overflow


def decode_pairs(a_s, a_len, b_s, b_len, raw, count, m_out: int,
                 universe: int, sentinel: int):
    """Inverse of :func:`encode_pairs`. Returns ``(a, b, mask)`` with the
    pairs valid-at-the-front and ``sentinel`` fill — positionally identical
    to the raw request buffers."""
    del a_len  # EF is sized by (universe, count); raw by count
    idx = jnp.arange(m_out)
    l = _ef_lowbits(universe, count)

    # -- a: EF decode ------------------------------------------------------- #
    low = jnp.zeros((m_out,), _I32)
    for j in range(31):
        low = low | jnp.where(j < l, _get_bit(a_s, idx * l + j) << j, 0)
    nbits = a_s.shape[0] * 8
    bidx = jnp.arange(nbits)
    bits = ((a_s[bidx >> 3].astype(_I32) >> (bidx & 7)) & 1)
    in_high = (bidx >= count * l) & (bits > 0)
    r = jnp.cumsum(in_high.astype(_I32)) - in_high.astype(_I32)
    h = bidx - count * l - r
    highs = jnp.zeros((m_out,), _I32).at[
        jnp.where(in_high, r, m_out)].set(h, mode="drop")
    a_c = (highs << l) | low

    # -- b: varint + segmented cumsum over equal-a runs --------------------- #
    bv, _ = _parse_varints(b_s, b_len, m_out)
    prev_a = jnp.concatenate([jnp.full((1,), -1, _I32), a_c[:-1]])
    new_run = a_c != prev_a
    c0 = jnp.cumsum(bv)
    sidx = jax.lax.cummax(jnp.where(new_run, idx, -1))
    c_before = jnp.where(sidx > 0, c0[jnp.clip(sidx - 1, 0, m_out - 1)], 0)
    b_c = c0 - c_before

    a_r = _read_raw32(a_s, idx)
    b_r = _read_raw32(b_s, idx)
    mask = idx < count
    a_out = jnp.where(mask, jnp.where(raw, a_r, a_c), sentinel)
    b_out = jnp.where(mask, jnp.where(raw, b_r, b_c), sentinel)
    return a_out, b_out, mask


# --------------------------------------------------------------------------- #
# verifyE answers: bit-packed bools
# --------------------------------------------------------------------------- #
def pack_bools(bits: jnp.ndarray, count, cap: int):
    """(m,) bools -> bit stream of the first ``count`` entries.
    Returns (stream (cap,) u8, length () = ceil(count/8))."""
    m = bits.shape[0]
    idx = jnp.arange(m)
    sel = bits & (idx < count)
    stream = jnp.zeros((cap,), _U8).at[
        jnp.where(sel, idx >> 3, cap)].add(
        (sel.astype(_I32) << (idx & 7)).astype(_U8), mode="drop")
    return stream, ((count + 7) // 8).astype(_I32)


def unpack_bools(stream: jnp.ndarray, count, m_out: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bools` (False past ``count``)."""
    idx = jnp.arange(m_out)
    return (_get_bit(stream, idx) > 0) & (idx < count)


# --------------------------------------------------------------------------- #
# Lane-grid wrappers (ndev, peer, ...) — what the engine stages call
# --------------------------------------------------------------------------- #
def encode_ids_lanes(wire: jnp.ndarray, sentinel: int, cap: int,
                     use_pallas: bool = False, interpret: bool = True,
                     per_device=jax.vmap):
    """``wire`` (ndev, peer, m): per-lane :func:`encode_ids`, with the
    delta/varint-size pass batched over each device's lanes (Pallas fast
    path; ``per_device`` maps it over the devices — the exchange
    backend's :meth:`~repro.core.exchange.ExchangeBackend.per_device`).

    Also returns the per-lane *modeled* byte matrix (varints capped
    at 4 B — ``engine._varint_id_bytes`` semantics) reusing the same
    sizing pass, so the jitted fetch stage never sizes the lanes twice."""
    ndev, p, m = wire.shape
    flat = wire.reshape(-1, m)
    delta, vlen = per_device(
        lambda w: delta_vlen(w, sentinel, use_kernel=use_pallas,
                             interpret=interpret))(wire)
    delta, vlen = delta.reshape(-1, m), vlen.reshape(-1, m)
    s, ln, rw, ov = jax.vmap(
        lambda i, d, v: _encode_ids_core(i, d, v, cap))(flat, delta, vlen)
    model = jnp.minimum(vlen, 4).sum(-1).reshape(ndev, p)
    return (s.reshape(ndev, p, cap), ln.reshape(ndev, p),
            rw.reshape(ndev, p), ov.any(), model)


def decode_ids_lanes(stream, length, raw, m_out: int, sentinel: int):
    return jax.vmap(jax.vmap(
        lambda s, ln, r: decode_ids(s, ln, r, m_out, sentinel)))(
        stream, length, raw)


def encode_rows_lanes(rows, valid, sentinel: int, degs_cap: int,
                      ids_cap: int):
    dg, dl, ids, il, rw, ov = jax.vmap(jax.vmap(
        lambda r, v: encode_rows(r, v, sentinel, degs_cap, ids_cap)))(
        rows, valid)
    return dg, dl, ids, il, rw, ov.any()


def decode_rows_lanes(degs_s, degs_len, ids_s, ids_len, raw, m: int,
                      D: int, sentinel: int):
    return jax.vmap(jax.vmap(
        lambda ds, dl, is_, il, r: decode_rows(ds, dl, is_, il, r, m, D,
                                               sentinel)))(
        degs_s, degs_len, ids_s, ids_len, raw)


def scatter_compacted_lanes(rows_c, valid, fill):
    return jax.vmap(jax.vmap(
        lambda r, v: scatter_compacted(r, v, fill)))(rows_c, valid)


def encode_pairs_lanes(a, b, universe: int, a_cap: int, b_cap: int):
    a_s, al, b_s, bl, rw, ov = jax.vmap(jax.vmap(
        lambda x, y: encode_pairs(x, y, universe, a_cap, b_cap)))(a, b)
    return a_s, al, b_s, bl, rw, ov.any()


def decode_pairs_lanes(a_s, a_len, b_s, b_len, raw, count, m_out: int,
                       universe: int, sentinel: int):
    return jax.vmap(jax.vmap(
        lambda as_, al, bs, bl, r, c: decode_pairs(
            as_, al, bs, bl, r, c, m_out, universe, sentinel)))(
        a_s, a_len, b_s, b_len, raw, count)


def pack_bools_lanes(bits, count, cap: int):
    return jax.vmap(jax.vmap(lambda b, c: pack_bools(b, c, cap)))(
        bits, count)


def unpack_bools_lanes(stream, count, m_out: int):
    return jax.vmap(jax.vmap(
        lambda s, c: unpack_bools(s, c, m_out)))(stream, count)


# --------------------------------------------------------------------------- #
# Measured wire-format auto-selection (EngineConfig.wire_format="auto")
# --------------------------------------------------------------------------- #
def resolve_wire_format(requested: str, mode: str, prior: dict | None = None,
                        hysteresis: float = 0.05) -> tuple[str, str]:
    """Resolve ``wire_format="auto"`` to a concrete codec for this run.

    The driver persists one *trial* per ``(exchange mode, format)`` into
    the priors entry (``wire_trials[f"{mode}:{fmt}"] = {"pipeline_s": ...,
    "wire_bytes": ...}``, compile time already subtracted) — the measured
    bytes-vs-wall tradeoff the CPU sim needs to stop paying 3x wall for
    compression whose bytes are free intra-process.  Resolution:

    * both formats measured -> the lower ``pipeline_s`` wins, with
      ``hysteresis`` sticking to the previously recorded choice unless the
      challenger is more than that fraction faster (a stable choice keeps
      warm runs on already-persisted executables — flapping would re-trace
      fetch/verify every run);
    * one format measured -> *explore* the other (deterministic, so two
      runs complete the table);
    * nothing measured -> heuristic: transports whose bytes cost real
      time (``spmd`` collectives, ``dist`` across process boundaries)
      default to ``varint``, the intra-process reference backends to
      ``raw`` (their bytes are free, codec compute is not).

    Returns ``(format, reason)`` with reason in ``{"explicit", "measured",
    "explore", "heuristic"}`` — the driver reports it as
    ``stats["wire_auto_reason"]``."""
    if requested != "auto":
        return requested, "explicit"
    trials = (prior or {}).get("wire_trials", {})
    t = {f: trials.get(f"{mode}:{f}") for f in ("raw", "varint")}
    have = [f for f in ("raw", "varint") if t[f]]
    if len(have) == 2:
        best = min(("raw", "varint"), key=lambda f: t[f]["pipeline_s"])
        prev = (prior or {}).get("wire_choice", {}).get(mode)
        if prev in ("raw", "varint") and best != prev \
                and t[best]["pipeline_s"] >= (1.0 - hysteresis) \
                * t[prev]["pipeline_s"]:
            best = prev
        return best, "measured"
    if len(have) == 1:
        return ("varint" if have[0] == "raw" else "raw"), "explore"
    return ("varint" if mode in ("spmd", "dist") else "raw"), "heuristic"


def register_wire_metrics(reg, chosen: str, requested: str,
                          reason: str) -> None:
    """Set the wire-codec instruments on a stats registry (declared in
    :mod:`repro.obs.schema`): the format actually on the wire
    (``wire_format``), what the config asked for
    (``wire_format_requested``), why auto-selection picked it
    (``wire_auto_reason``), and the modeled compressed-fetch baseline
    accumulator (``bytes_fetch_compressed``) the per-wave stats add into."""
    reg["wire_format"] = chosen
    reg["wire_format_requested"] = requested
    reg["wire_auto_reason"] = reason
    reg["bytes_fetch_compressed"] = 0.0
