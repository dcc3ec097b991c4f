from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.intersect.kernel import intersect_pallas
from repro.kernels.intersect.ref import intersect_ref
from repro.kernels.membership.kernel import LANES, block_rows


def tile_defaults(m: int) -> tuple[int, int]:
    """``(block_b, lane_chunk)`` for window width ``m``.

    The chunk is always the 128-lane VPU width (narrow windows are padded
    to it, never narrowed: Mosaic slices lanes in multiples of 128); the
    batch tile shrinks as the window widens so the resident
    ``(block_b, ceil128(m))`` row block keeps fitting VMEM.
    """
    return block_rows(m), LANES


@partial(jax.jit, static_argnames=("sentinel", "use_kernel", "interpret",
                                   "block_b"))
def intersect(a: jnp.ndarray, b: jnp.ndarray, sentinel: int,
              use_kernel: bool = False, interpret: bool = True,
              block_b: int | None = None):
    """Sorted-list intersection: (mask over a, per-row count).

    ``block_b`` tunes the Pallas batch tile; ``None`` picks
    :func:`tile_defaults` from the ``b`` window width.  The jnp reference
    ignores the tiling, so any ``block_b`` is bit-identical to
    ``use_kernel=False``.
    """
    if use_kernel:
        return intersect_pallas(a, b, sentinel,
                                block_b=block_b or tile_defaults(
                                    b.shape[-1])[0],
                                interpret=interpret)
    return intersect_ref(a, b, sentinel)
