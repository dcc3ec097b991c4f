"""Compile-only rehearsal of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology, and refuses what the chip would
refuse (an unaligned lane slice, a kernel over its VMEM, a primitive
Mosaic cannot lower).  Interpret-mode tests cannot see any of that.  Each
test compiles one kernel with ``interpret=False`` at the widths
``chip_smoke.py`` runs (dblp_synth: max degree 1,796; frontier_cap 2^14;
the default fetch_cap 2^12 and verify_cap 2^14) and checks the program
holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.rads import EngineConfig
from repro.kernels.intersect.kernel import intersect_pallas
from repro.kernels.membership.kernel import membership_pallas
from repro.kernels.varint.kernel import delta_vlen_pallas

MAX_DEGREE = 1796            # dblp_synth's largest adjacency window
FRONTIER_CAP = 1 << 14       # chip_smoke.py's frontier capacity
FETCH_CAP = EngineConfig().fetch_cap
VERIFY_CAP = EngineConfig().verify_cap
SENTINEL = 317_080           # dblp_synth's vertex count on one chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


CASES = {
    # verifyE answers: one query per adjacency window
    "membership_verify": (
        lambda r, v: membership_pallas(r, v, interpret=False),
        [(VERIFY_CAP, MAX_DEGREE), (VERIFY_CAP, 1)]),
    # dense-layout back-edge check: a whole window of queries per row
    "membership_backedge": (
        lambda r, v: membership_pallas(r, v, interpret=False),
        [(FRONTIER_CAP, MAX_DEGREE), (FRONTIER_CAP, MAX_DEGREE)]),
    # bucketed-layout back-edge refinement over the whole frontier
    "intersect_backedge": (
        lambda a, b: intersect_pallas(a, b, SENTINEL, interpret=False),
        [(FRONTIER_CAP, MAX_DEGREE), (FRONTIER_CAP, MAX_DEGREE)]),
    # fetchV request ids: four peer lanes per device on four chips
    "varint_ids": (
        lambda ids: delta_vlen_pallas(ids, SENTINEL, interpret=False),
        [(4, FETCH_CAP)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
