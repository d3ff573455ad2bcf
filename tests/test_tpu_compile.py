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


def test_lbs_kernel_is_read_by_the_benchmark_for_v5e(one_chip):
    """The benchmark finds the LBS in a trace by its op name and counts
    its bytes from the one scan operand and the owner result: the
    compiled call at the cells' widths (wavefront 4096, budget 2^17)
    still reads as the benchmark expects."""
    from jax._src.lib import xla_client

    from bench import kernels

    compiled = jax.jit(
        lambda scan: lbs_pallas(scan, 1 << 17, interpret=False)
    ).lower(_spec((4096,), jnp.int32, one_chip)).compile()
    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True  # as a device trace names the op
    text = compiled.runtime_executable().hlo_modules()[0].to_string(options)
    calls = [line.strip() for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert kernels.kernel_of(calls[0]) == "lbs_pallas"
    assert kernels.lbs_bytes(calls[0]) == 4 * 4096 + 8 * 131072


@pytest.mark.parametrize("entry", [compact_tiles_pallas, compact],
                         ids=["tiles", "stitched"])
def test_compaction_kernel_compiles_for_v5e(one_chip, entry):
    width = BUDGET + 4096
    compiled = jax.jit(
        lambda items, mask: entry(items, mask, interpret=False)
    ).lower(_spec((width,), jnp.int32, one_chip),
            _spec((width,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_drain_kernels_carry_their_phase_for_v5e(one_chip, monkeypatch):
    """The single.persistent BFS drain compiled for a v5e: the expansion
    kernel runs under ``atos.expand``, the compaction kernel under
    ``atos.push`` (their ``op_name``), so a device trace names both."""
    import re

    from repro.core import backend, SchedulerConfig
    from repro.core.scheduler import persistent_drive
    from repro.graph.generators import rmat
    from repro.obs.spans import EXPAND, PUSH
    from repro.runtime import build_program, config_for, parse_policy
    from repro.runtime.api import _shared_setup
    from repro.runtime.policy import policy_of

    monkeypatch.setattr(backend, "has_tpu", lambda: True)  # Mosaic kernels
    graph = rmat(8, edge_factor=8, seed=1)
    cfg = config_for(SchedulerConfig(num_workers=1024, backend="pallas"),
                     parse_policy("single.persistent"))
    program = build_program("bfs", graph, cfg, {"source": 0})
    queue, state, _, step, cond, _ = _shared_setup(
        program, graph, cfg, policy_of(cfg), None)
    carry0 = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                          (queue, state, jnp.int32(0), jnp.int32(0)))
    text = jax.jit(lambda c: persistent_drive(step, cond, c)).lower(
        carry0).compile().as_text()
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*"
                         r"op_name=\"([^\"]*)\"", text)
    assert kernels
    assert all(f"/{EXPAND}/" in name or f"/{PUSH}/" in name
               for name in kernels), kernels
    assert any(f"/{EXPAND}/" in name for name in kernels)
    assert any(f"/{PUSH}/" in name for name in kernels)


def test_owner_broadcast_compiles_without_gather_for_v5e(one_chip):
    """The round bodies' per-unit reads at the cells' widths (wavefront
    4096, budget 2^17): one scatter and one prefix sum for a stack of an
    int32 and a bool table, with no gather left for the TPU to run."""
    from repro.core import owner_broadcast

    w = 4096
    compiled = jax.jit(
        lambda excl, a, b: owner_broadcast(excl, 1 << 17, a, b)
    ).lower(_spec((w,), jnp.int32, one_chip),
            _spec((w,), jnp.int32, one_chip),
            _spec((w,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    assert "scatter" in text
    assert " gather(" not in text
