"""Compile-only rehearsal of the Thompson kernels for a described TPU v5e.

Nothing runs: each case compiles the kernel at the shapes the search uses
(dashcam's 22 chunks, BDD's 1000, Q=8 queries × 50 cohorts) for a
``v5e:2x2`` topology that is described, not attached, and checks that the
Mosaic kernel is in the compiled program.  The TPU compiler refuses block
shapes off the (8, 128) tiling and scalar VMEM stores that interpret mode
accepts, so these cases are what keeps the kernel compilable for the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.thompson.kernel import thompson_choose, thompson_choose_batched


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from a persistent
    # cache without that chip; keep such compiles out of any cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("cohorts,m", [(50, 22), (50, 1000)])
def test_thompson_choose_compiles_for_v5e(one_chip, cohorts, m):
    compiled = jax.jit(thompson_choose).lower(
        _f32(one_chip, m), _f32(one_chip, m), _f32(one_chip, cohorts, m)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_thompson_choose_batched_compiles_for_v5e(one_chip):
    q, cohorts, m = 8, 50, 1000
    compiled = jax.jit(thompson_choose_batched).lower(
        _f32(one_chip, q, m), _f32(one_chip, q, m),
        _f32(one_chip, q, cohorts, m),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
