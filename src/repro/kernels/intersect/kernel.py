"""Pallas TPU kernel: batched sorted-list intersection (Alg 1 line 6 —
candidate refinement C(u) <- adj(piv) ∩ adj(f(u'))).

``a (B, M)`` and ``b (B, M)`` are sorted, sentinel-padded adjacency windows.
Output: ``mask (B, M) bool`` marking a-entries present in b, and
``count (B,) int32``.  "a[r, i] in b[r]" is exactly the membership test, so
this runs the membership kernel with ``b`` as the rows and ``a`` as the
queries; a's sentinel entries are mapped below every row value first, so
all-padding tiles fall to the kernel's range skip.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.membership.kernel import NO_MATCH, membership_pallas


def intersect_pallas(a: jnp.ndarray, b: jnp.ndarray, sentinel: int,
                     block_b: int | None = None, interpret: bool = True):
    live = a != sentinel
    mask = membership_pallas(b, jnp.where(live, a, NO_MATCH),
                             block_b=block_b, interpret=interpret) & live
    return mask, mask.sum(axis=-1, dtype=jnp.int32)
