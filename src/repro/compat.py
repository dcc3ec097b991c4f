"""Single home for the JAX APIs the engine reaches through one name.

Written for the installed JAX 0.9.0 (see pyproject.toml): ``jax.shard_map``,
``jax.make_mesh`` with ``axis_types``, the ``jax.tree`` namespace,
``jax.experimental.serialize_executable`` (pickles the compiled XLA
executable, so a load needs no compile — what the AOT stage-executable
store in :mod:`repro.runtime.compile_cache` relies on) and the gloo CPU
collectives behind the multi-process ``dist`` backend
(:func:`enable_cpu_collectives` / :func:`distributed_initialize`, with
:func:`global_shard` / :func:`global_replicate` assembling process-global
arrays from per-host values).  Call sites import these names from here.
"""
from __future__ import annotations

import jax
from jax.experimental.serialize_executable import (
    deserialize_and_load as _deserialize_and_load,
    serialize as _serialize_executable)
from jax.sharding import AxisType, Mesh, PartitionSpec

__all__ = [
    "AxisType", "default_axis_types", "make_mesh",
    "shard_map", "tree_map", "tree_leaves", "tree_reduce",
    "tree_map_with_path", "with_sharding_constraint", "cost_analysis",
    "memory_analysis", "HAS_EXECUTABLE_SERIALIZATION", "serialize_compiled",
    "deserialize_compiled", "version_stamp", "HAS_MULTIPROCESS_CPU",
    "enable_cpu_collectives", "distributed_initialize", "process_index",
    "process_count", "global_shard", "global_replicate",
]


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map``, keyword-only."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# --------------------------------------------------------------------------- #
# Mesh construction
# --------------------------------------------------------------------------- #
def default_axis_types(n_axes: int) -> tuple:
    """``(AxisType.Auto,) * n_axes``."""
    return (AxisType.Auto,) * n_axes


def make_mesh(axis_shapes: tuple[int, ...], axis_names: tuple[str, ...],
              *, axis_types: tuple | None = None, devices=None) -> Mesh:
    """``jax.make_mesh`` (``devices=None`` takes the default devices)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types,
                         **kw)


tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_reduce = jax.tree.reduce
tree_map_with_path = jax.tree_util.tree_map_with_path


# --------------------------------------------------------------------------- #
# Sharding constraint that degrades to identity outside a mesh context
# --------------------------------------------------------------------------- #
def with_sharding_constraint(x, *spec):
    """``lax.with_sharding_constraint`` or identity when no mesh is active
    (single-device tests)."""
    try:
        return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))
    except Exception:
        return x


# --------------------------------------------------------------------------- #
# Compiled-artifact introspection (dryrun / benchmarks)
# --------------------------------------------------------------------------- #
def cost_analysis(compiled) -> dict | None:
    """``Compiled.cost_analysis()`` as a dict (or None)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None
    return cost if isinstance(cost, dict) else None


def memory_analysis(compiled):
    """``Compiled.memory_analysis()`` or None where unsupported."""
    try:
        return compiled.memory_analysis()
    except Exception:
        return None


# --------------------------------------------------------------------------- #
# Compiled-executable persistence (the AOT stage-executable cache)
# --------------------------------------------------------------------------- #
HAS_EXECUTABLE_SERIALIZATION = True


def serialize_compiled(compiled):
    """``jax.stages.Compiled`` -> picklable ``(payload, in_tree, out_tree)``.

    The triple is exactly what :func:`deserialize_compiled` needs; the
    PyTreeDefs pickle as long as every custom node type (``WaveState``,
    ``DeviceGraph``, ``AdjCache``) is import-registered at load time, which
    module import guarantees.
    """
    return _serialize_executable(compiled)


def deserialize_compiled(triple):
    """Inverse of :func:`serialize_compiled`: returns a loaded executable
    callable with the original (pytree) calling convention — no tracing,
    no XLA compilation."""
    payload, in_tree, out_tree = triple
    return _deserialize_and_load(payload, in_tree, out_tree)


def version_stamp() -> str:
    """Environment fingerprint every persisted executable is keyed under:
    a pickled executable is only valid on the exact jax/jaxlib pair and
    backend that produced it."""
    import jaxlib

    return (f"jax={jax.__version__};jaxlib={jaxlib.__version__};"
            f"backend={jax.default_backend()};ndev={jax.device_count()}")


# --------------------------------------------------------------------------- #
# Multi-process bootstrap (the `dist` exchange backend)
# --------------------------------------------------------------------------- #
HAS_MULTIPROCESS_CPU = True


def enable_cpu_collectives() -> bool:
    """Select the gloo CPU collectives implementation.

    MUST run before the CPU backend client is created (i.e. before any
    computation or ``jax.devices()`` call) — the flag is read once at
    client construction.  Returns True."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    return True


def distributed_initialize(coordinator_address: str, num_processes: int,
                           process_id: int) -> bool:
    """``jax.distributed.initialize`` with an explicit coordinator.

    Returns False instead of raising when the bootstrap fails (coordinator
    unreachable) — the caller degrades to single-process / skips."""
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
        return True
    except Exception:
        return False


def process_index() -> int:
    try:
        return int(jax.process_index())
    except Exception:                                        # pragma: no cover
        return 0


def process_count() -> int:
    try:
        return int(jax.process_count())
    except Exception:                                        # pragma: no cover
        return 1


def _spans_processes(mesh: Mesh) -> bool:
    me = process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def global_shard(tree, mesh: Mesh, axis: str = "data"):
    """Shard every leaf of ``tree`` along its leading axis over
    ``mesh[axis]`` — the one entry point that works both single-process
    (plain ``device_put``) and multi-process (each process contributes its
    contiguous block of the leading axis through
    ``multihost_utils.host_local_array_to_global_array``).  Every process
    must hold the FULL host value and call with identical shapes — the
    per-process slice is taken here."""
    import numpy as np
    from jax.sharding import NamedSharding

    multi = _spans_processes(mesh)
    if multi:
        from jax.experimental import multihost_utils

        devs = list(mesh.devices.flat)
        mine = [i for i, d in enumerate(devs)
                if d.process_index == process_index()]
        lo, hi = mine[0], mine[-1] + 1

    def put(x):
        spec = PartitionSpec(axis, *([None] * (x.ndim - 1)))
        if not multi:
            return jax.device_put(x, NamedSharding(mesh, spec))
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(x)[lo:hi], mesh, spec)

    return tree_map(put, tree)


def global_replicate(tree, mesh: Mesh):
    """Fully-replicated process-global arrays from identical host values
    (every process must pass the same data — the callers are deterministic
    host computations, which is the `dist` backend's standing contract)."""
    import numpy as np
    from jax.sharding import NamedSharding

    multi = _spans_processes(mesh)
    if multi:
        from jax.experimental import multihost_utils

    def put(x):
        if not multi:
            return jax.device_put(x, NamedSharding(mesh, PartitionSpec()))
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(x), mesh, PartitionSpec())

    return tree_map(put, tree)
