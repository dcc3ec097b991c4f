"""Pallas TPU kernel: fused delta + LEB128-size pass of the id wire codec.

The fetchV request encoder (:mod:`repro.core.wire`) needs, per lane, the
delta of every valid id against the previous valid id (sentinel holes
skipped) and the varint byte length of that delta.  On TPU this is a
running-max scan fused with elementwise threshold compares.  The grid walks
``(block_b, 128)`` tiles left to right along each row (the chunk axis is
sequential); within a tile the prefix max is a log-step ladder of lane
rotations (``pltpu.roll``), and the running max of the tiles already done
rides across grid steps in a VMEM scratch — so VMEM use is independent of
the lane width.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _shift_lanes(x: jnp.ndarray, s: int, fill: int) -> jnp.ndarray:
    """Lane ``i`` takes lane ``i - s`` (``fill`` below ``s``).  The rolled
    lane ids say which way the rotation went, so the shift is right
    whichever direction the hardware rotate takes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    came_from = pltpu.roll(lane, s, 1)
    shifted = jnp.where(came_from == lane - s, pltpu.roll(x, s, 1),
                        pltpu.roll(x, LANES - s, 1))
    return jnp.where(lane >= s, shifted, fill)


def _delta_vlen_kernel(ids_ref, delta_ref, vlen_ref, prev_ref, *,
                       sentinel: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        prev_ref[...] = jnp.full(prev_ref.shape, -1, jnp.int32)

    ids = ids_ref[...]                               # (TB, 128) int32
    lane = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
    valid = ids < sentinel
    carry = prev_ref[...]                            # running max so far
    cm = jnp.where(valid, ids, -1)
    s = 1
    while s < LANES:                                 # inclusive prefix max
        cm = jnp.maximum(cm, _shift_lanes(cm, s, -1))
        s *= 2
    cm = jnp.maximum(cm, carry)
    prev = jnp.where(lane == 0, carry, _shift_lanes(cm, 1, -1))
    d = jnp.where(prev >= 0, ids - prev, ids)
    d = jnp.where(valid, jnp.maximum(d, 0), 0)
    vl = (1 + (d >= 1 << 7).astype(jnp.int32)
          + (d >= 1 << 14).astype(jnp.int32)
          + (d >= 1 << 21).astype(jnp.int32)
          + (d >= 1 << 28).astype(jnp.int32))
    delta_ref[...] = d
    vlen_ref[...] = jnp.where(valid, vl, 0)
    prev_ref[...] = jnp.broadcast_to(jnp.max(cm, axis=1, keepdims=True),
                                     cm.shape)


def delta_vlen_pallas(ids: jnp.ndarray, sentinel: int, block_b: int = 256,
                      interpret: bool = True):
    """ids (B, M) int32 -> (delta (B, M) int32, vlen (B, M) int32)."""
    B, M = ids.shape
    Mp = -(-max(M, 1) // LANES) * LANES
    bb = min(block_b, -(-max(B, 1) // 8) * 8)
    Bp = -(-max(B, 1) // bb) * bb
    pad = jnp.pad(ids, ((0, Bp - B), (0, Mp - M)),
                  constant_values=sentinel)
    spec = pl.BlockSpec((bb, LANES), lambda i, c: (i, c))
    delta, vlen = pl.pallas_call(
        partial(_delta_vlen_kernel, sentinel=sentinel),
        grid=(Bp // bb, Mp // LANES),
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((Bp, Mp), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, Mp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bb, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pad)
    return delta[:B, :M], vlen[:B, :M]
