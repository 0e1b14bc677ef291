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
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.thompson.kernel import thompson_choose, thompson_choose_batched


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


def test_mesh_search_holds_bdd16_on_a_v5e_2x2(topo):
    """The composed Q × shards search over the bdd16 deployment (BDD's
    40 s clips at 16×: 19.2 M frames in 16,000 chunks, 48,000 instances,
    a one-slot-per-frame cache of 16.4 GB that no chip holds) compiles for
    a described v5e 2x2 as the ``bdd16.q8s4`` cell runs it: 8 queries, 48
    cohorts, 4 shards.  Each chip builds and keeps its own quarter of the
    cache (4.8 M packed rows, about 4.9 GB), the whole program fits a
    chip's memory with room, and no op copies a store shard."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.exsample_paper import bdd
    from repro.core import init_carry_multi, init_matcher, init_state
    from repro.core.distributed import pad_chunks
    from repro.core.executor import _search_multi_sharded_device
    from repro.serve.batcher import RowLayout
    from repro.sim import generate
    from repro.sim.oracle import oracle_detect

    repo, chunks = generate(bdd(scale=16).repo)
    assert chunks.total_frames == 19_200_000
    det = lambda key, frame: oracle_detect(repo, frame, query_class=None)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "data"))
    shape = lambda x, s=rep: jax.ShapeDtypeStruct(
        np.shape(x), jnp.asarray(x).dtype, sharding=s)
    keys = jnp.stack([jax.random.PRNGKey(q) for q in range(8)])
    c = init_carry_multi(
        init_state(chunks.length, alpha0=0.1, beta0=1.0),
        init_matcher(max_results=8192, feat_dim=8, iou_thresh=0.5,
                     time_gate=900),
        keys,
    )
    padded = pad_chunks(c.sampler, 4)
    struct = jax.eval_shape(det, keys[0], jnp.zeros((), jnp.int32))
    slots = chunks.total_frames // 4
    compiled = _search_multi_sharded_device.lower(
        shape(c.key), shape(c.step), shape(c.results),
        shape(padded.n1, shard), shape(padded.n, shard),
        shape(padded.frames, shard), jax.tree.map(shape, c.matcher),
        jax.tree.map(shape, chunks), shape(np.zeros(8, np.int32)),
        None, None, shape(np.int32(0)),
        mesh=mesh, axis="data", detector=det, select=None, cohorts=48,
        sync_every=1, max_steps=60_000, alpha0=0.1, beta0=1.0,
        empty=(RowLayout.of(struct), slots),
    ).compile()
    ma = compiled.memory_analysis()
    cache_bytes = slots * (256 + 1) * 4
    assert ma.output_size_in_bytes >= cache_bytes
    per_chip = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert per_chip < 6e9, ma
    whole_shard = re.compile(
        rf"= \S+\[{slots}[,\]]\S* (copy|transpose)\(")
    assert not whole_shard.findall(compiled.as_text())
