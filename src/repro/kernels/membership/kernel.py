"""Pallas TPU kernel: batched sorted-set membership (verifyE / Alg 2 checks).

Given sentinel-padded *sorted* adjacency windows ``rows (B, M)`` and query
values ``vals (B, K)``, produce ``out (B, K) bool`` with
``out[b, k] = vals[b, k] in rows[b]``.

TPU adaptation (instead of the GPU binary-search-per-thread): the grid
walks ``(block_b, 128)`` query tiles; the ``(block_b, M)`` row block stays
resident in VMEM while the kernel streams it in 128-lane chunks
(``pl.ds`` ref slices) and compares every chunk against the query tile
under all 128 lane rotations (``pltpu.roll``) — an all-pairs compare with
no dynamic gather and a ``(block_b, 128)`` working set.  A chunk whose
value range cannot meet the tile's (sorted windows: sentinel padding,
disjoint id ranges) is skipped.  Hits accumulate as int32 in the output
tile; the wrapper converts to bool.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_ROWS_PAD = jnp.iinfo(jnp.int32).max   # row padding: above every value
NO_MATCH = jnp.iinfo(jnp.int32).min    # query padding: below every row entry
_VMEM_BUDGET = 4 << 20                 # bytes for the double-buffered rows
_UNROLL = 8                            # lane rotations per loop step


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def block_rows(m: int) -> int:
    """Rows per grid step for window width ``m``: the largest power of two
    (8..512) whose double-buffered ``(block_b, ceil128(m))`` int32 row block
    fits the VMEM budget."""
    mp = _ceil_to(max(m, 1), LANES)
    bb = max(_VMEM_BUDGET // (2 * 4 * mp), 8)
    return min(1 << (bb.bit_length() - 1), 512)


def _membership_kernel(rows_ref, vals_ref, out_ref, *, n_chunks: int):
    q = vals_ref[...]                       # (TB, 128) int32
    q_lo, q_hi = jnp.min(q), jnp.max(q)
    out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    def body(c, carry):
        off = pl.multiple_of(c * LANES, LANES)
        r = rows_ref[:, pl.ds(off, LANES)]  # (TB, 128) int32, sorted rows

        @pl.when((jnp.min(r) <= q_hi) & (q_lo <= jnp.max(r)))
        def _():
            def rotate(i, rh):       # compare, rotate by one lane; x8
                rs, hit = rh
                for _ in range(_UNROLL):
                    hit = hit | (q == rs).astype(jnp.int32)
                    rs = pltpu.roll(rs, 1, 1)
                return rs, hit

            _, hit = jax.lax.fori_loop(
                0, LANES // _UNROLL, rotate,
                (r, jnp.zeros(q.shape, jnp.int32)))
            out_ref[...] = out_ref[...] | hit

        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)


def membership_pallas(rows: jnp.ndarray, vals: jnp.ndarray,
                      block_b: int | None = None,
                      interpret: bool = True) -> jnp.ndarray:
    """rows (B, M) sorted int32; vals (B, K) int32 -> (B, K) bool."""
    B, M = rows.shape
    K = vals.shape[1]
    Mp = _ceil_to(max(M, 1), LANES)
    Kp = _ceil_to(max(K, 1), LANES)
    bb = block_b or block_rows(M)
    bb = min(bb, _ceil_to(max(B, 1), 8))
    Bp = _ceil_to(max(B, 1), bb)
    rows_p = jnp.pad(rows, ((0, Bp - B), (0, Mp - M)),
                     constant_values=_ROWS_PAD)
    vals_p = jnp.pad(vals, ((0, Bp - B), (0, Kp - K)),
                     constant_values=NO_MATCH)
    out = pl.pallas_call(
        partial(_membership_kernel, n_chunks=Mp // LANES),
        grid=(Bp // bb, Kp // LANES),
        in_specs=[
            pl.BlockSpec((bb, Mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, LANES), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(rows_p, vals_p)
    return out[:B, :K] != 0
