"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing runs here: each test lowers a kernel for a v5e chip that is
described, not attached, and compiles it with the installed TPU compiler.
That catches what interpret mode cannot — primitives Mosaic has no lowering
for, blocks not aligned to the (8, 128) tiling, kernels that overrun VMEM.

Widths are those of the scale-22 drains ``chip_smoke.py`` runs: wavefronts
of 1024 and 4096 items, the merge-path budget (2**17 edges, floored at the
graph's largest degree, 163,558), and a push of budget + wavefront slots.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest-xdist worker imports
this module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.frontier_expand.kernel import lbs_pallas
from repro.kernels.queue_compact.kernel import compact_tiles_pallas
from repro.kernels.queue_compact.ops import compact

BUDGET = 163_558


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("wavefront", [1024, 4096])
def test_lbs_kernel_compiles_for_v5e(one_chip, wavefront):
    compiled = jax.jit(
        lambda scan: lbs_pallas(scan, BUDGET, interpret=False)
    ).lower(_spec((wavefront,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("entry", [compact_tiles_pallas, compact],
                         ids=["tiles", "stitched"])
def test_compaction_kernel_compiles_for_v5e(one_chip, entry):
    width = BUDGET + 4096
    compiled = jax.jit(
        lambda items, mask: entry(items, mask, interpret=False)
    ).lower(_spec((width,), jnp.int32, one_chip),
            _spec((width,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
