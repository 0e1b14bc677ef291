"""Compile-only rehearsal of the search's device programs for a described
TPU v5e.

Nothing runs: each case compiles at the shapes the search uses (dashcam's
22 chunks, BDD's 1000, Q=8 queries × 50 cohorts, an 8,192-entry matcher
ring) for a ``v5e:2x2`` topology that is described, not attached.  The
Thompson cases check that the Mosaic kernel is in the compiled program:
the TPU compiler refuses block shapes off the (8, 128) tiling and scalar
VMEM stores that interpret mode accepts.  The matcher and mesh cases read
the compiled program's layouts, ops and memory.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import os
import re

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


def _while_bodies(hlo: str) -> list[list[tuple[str, str, str]]]:
    """(opcode, name, shape) of each top-level instruction of every while
    loop's body computation in compiled HLO text (fusions stay whole)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[cur] = []
        elif cur and line.startswith("  "):
            m = re.match(r"(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w-]+)\(",
                         line.strip())
            if m:
                comps[cur].append((m.group(3), m.group(1), m.group(2)))
    bodies = re.findall(r"while\(.*?body=%?([\w.\-]+)", hlo)
    return [comps[b] for b in bodies]


def test_matcher_fold_keeps_the_ring_lane_dense_on_a_v5e(one_chip):
    """The matcher fold as the Q-axis search runs it (``match_and_update``
    over a round's 50 cohorts, vmapped over Q = 8 rings of R = 8,192
    entries, 16 detections a frame), compiled for a described v5e: the
    ring leaves of the loop carry keep R minor (on the lanes), no op in
    the loop body pads, slices or copies a ring leaf, and the loop writes
    its new entries in place, with under 1 MB of temporaries."""
    from repro.core.matcher import init_matcher_multi, match_and_update

    q, r, c, d, f = 8, 8192, 50, 16, 8

    def fold(m, boxes, feats, valid, vid, fid, cid):
        def one(m, boxes, feats, valid, vid, fid, cid):
            def body(j, st):
                m, seen = st
                res = match_and_update(m, boxes[j], feats[j], valid[j],
                                       vid[j], fid[j], cid[j])
                return res.new_state, seen + res.d0 + res.d1
            return jax.lax.fori_loop(0, c, body, (m, jnp.int32(0)))
        return jax.vmap(one)(m, boxes, feats, valid, vid, fid, cid)

    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    ring = jax.tree.map(lambda x: spec(x.shape, x.dtype),
                        init_matcher_multi(q, max_results=r, feat_dim=f))
    compiled = jax.jit(fold, donate_argnums=0).lower(
        ring, _f32(one_chip, q, c, d, 4), _f32(one_chip, q, c, d, f),
        spec((q, c, d), jnp.bool_), *(spec((q, c), jnp.int32),) * 3,
    ).compile()
    hlo = compiled.as_text()
    (body,) = _while_bodies(hlo)
    ring_sized = re.compile(rf"\[[^\]]*\b({r}|{r + 1})\b")
    relaid = [(op, name, shape) for op, name, shape in body
              if op in ("pad", "slice", "copy") and ring_sized.search(shape)]
    assert not relaid, relaid
    carry = re.search(r"= \((.*?)\) while\(", hlo).group(1)
    leaves = re.findall(r"\w+\[([\d,]+)\]\{([\d,]+)", carry)
    ring_leaves = [(dims, layout) for dims, layout in leaves
                   if str(r) in dims.split(",")]
    assert len(ring_leaves) >= 6, carry
    for dims, layout in ring_leaves:
        minor = int(layout.split(",")[0])
        assert dims.split(",")[minor] == str(r), (dims, layout)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def test_mesh_search_holds_bdd16_on_a_v5e_2x2(topo):
    """The composed Q × shards search over the bdd16 deployment (BDD's
    40 s clips at 16×: 19.2 M frames in 16,000 chunks, 48,000 instances,
    a one-slot-per-frame cache of 16.4 GB that no chip holds) compiles for
    a described v5e 2x2 as the ``bdd16.q8s4`` cell runs it: 8 queries, 48
    cohorts, 4 shards.  Each chip builds and keeps its own quarter of the
    cache (4.8 M packed rows, about 4.9 GB), the whole program fits a
    chip's memory with room, no op copies a store shard, and the sync's
    matcher merge writes the 8 × 8,192-entry rings in place: no op works
    on them flattened with a pad row (8 × 8,193 = 65,544 entries)."""
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
    hlo = compiled.as_text()
    whole_shard = re.compile(
        rf"= \S+\[{slots}[,\]]\S* (copy|transpose)\(")
    assert not whole_shard.findall(hlo)
    padded_rings = re.compile(r"= \w+\[65544[,\]]\S* (\w[\w-]*)\(")
    assert not padded_rings.findall(hlo)
