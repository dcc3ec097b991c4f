"""Jit'd entry point: Pallas on TPU, jnp reference elsewhere."""
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.membership.kernel import membership_pallas
from repro.kernels.membership.ref import membership_ref


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def membership(rows: jnp.ndarray, vals: jnp.ndarray,
               use_kernel: bool = False, interpret: bool = True) -> jnp.ndarray:
    """Edge-existence / candidate-refinement membership test.

    ``use_kernel=True`` runs the Pallas kernel (``interpret=True`` off
    TPU), ``False`` the jnp reference; the engine decides both from the
    platform (``repro.core.engine._kernel_mode``)."""
    if use_kernel:
        return membership_pallas(rows, vals, interpret=interpret)
    return membership_ref(rows, vals)
