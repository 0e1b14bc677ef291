"""Persistent cross-query repository index (DESIGN.md §13).

Four layers, acceptance-ordered:

* **DetectionCache aliasing** — hypothesis properties for the
  direct-mapped device tier at SMALL capacities, where ``frame %
  capacity`` collisions actually happen: an eviction overwrites the tag
  (stale frame must MISS, not phantom-hit), within-batch collisions are
  first-write-wins, and sentinel ids (−1) never hit nor insert.
* **RepositoryIndex tiers** — host-tier publish/lookup with
  ``detector_version`` isolation, disk snapshot round-trip (manifest
  written last), read-only discipline, deterministic ``warm()`` fill.
* **ChunkPriors** — ``prior_weight == 0`` returns the INPUT state object
  (cold path bit-identical by construction), injection touches ``n1``
  ONLY, geometry mismatches refuse to warm.
* **End-to-end contracts** — a COLD index with ``prior_weight = 0`` is
  bit-identical to no index at all; a WARM index replays detections
  exactly (identical results, ~0 fresh detector calls, index_hits > 0);
  a second service constructed over a warm shared index shows the saving
  in per-tenant attributed detector economics.
"""
import dataclasses

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_carry_multi, init_matcher, init_state
from repro.core.plan import Execution, IndexSpec, PlanError, SearchPlan
from repro.index import ChunkPriors, RepositoryIndex
from repro.serve.batcher import (
    DetectionCache,
    cache_insert,
    cache_lookup,
    init_detection_cache,
)
from repro.sim import RepoSpec, generate
from repro.sim.oracle import oracle_detect


@pytest.fixture(scope="module")
def world():
    spec = RepoSpec(
        video_lengths=[5_000] * 3, num_instances=100, chunk_frames=500,
        locality=4.0, seed=7,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    return repo, chunks, det


def _fresh_multi(chunks, q_n=1, max_results=512):
    keys = jnp.stack([
        jax.random.fold_in(jax.random.PRNGKey(0), q) for q in range(q_n)
    ])
    return init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=max_results),
        keys,
    )


def _plan(index=None, limit=10, max_steps=600, cohorts=4):
    return SearchPlan(
        result_limit=limit, max_steps=max_steps, cohorts=cohorts,
        execution=Execution(queries_axis=True, cache=-1, index=index),
    )


def _same_carry(a, b):
    np.testing.assert_array_equal(np.asarray(a.step), np.asarray(b.step))
    np.testing.assert_array_equal(
        np.asarray(a.results), np.asarray(b.results))
    for field in ("n", "n1"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.sampler, field)),
            np.asarray(getattr(b.sampler, field)),
        )
    np.testing.assert_array_equal(np.asarray(a.key), np.asarray(b.key))


# ---------------------------------------------------------------------------
# DetectionCache direct-mapped aliasing at small capacities (satellite 4)
# ---------------------------------------------------------------------------


def _toy_cache(capacity):
    """Cache over a scalar-leaf 'detector' whose output for frame f is f
    as f32 — collisions are detectable by value."""
    struct = jax.eval_shape(lambda f: jnp.float32(0.0), 0)
    return init_detection_cache(struct, capacity)


def _ref_model(capacity, batches):
    """Reference direct-mapped semantics: per batch, the FIRST valid
    occupant of each slot wins; later batches overwrite the tag."""
    tag = {}
    for frames, mask in batches:
        taken = set()
        for f, m in zip(frames, mask):
            slot = f % capacity
            if not m or f < 0 or slot in taken:
                continue
            taken.add(slot)
            tag[slot] = f
    return tag


@hypothesis.given(
    capacity=st.integers(min_value=1, max_value=6),
    batches=st.lists(
        st.lists(
            st.tuples(
                st.integers(min_value=-1, max_value=23), st.booleans()
            ),
            min_size=1, max_size=6,
        ),
        min_size=1, max_size=4,
    ),
)
@hypothesis.settings(deadline=None, max_examples=60)
def test_cache_alias_property(capacity, batches):
    """After ANY insert sequence, lookup(f) hits iff f is the current
    occupant of its slot in the reference model — evicted frames MISS
    (stale-tag correctness) and hits gather the occupant's own value."""
    cache = _toy_cache(capacity)
    ref_batches = []
    for batch in batches:
        frames = jnp.asarray([f for f, _ in batch], jnp.int32)
        mask = jnp.asarray([m for _, m in batch])
        cache = cache_insert(
            cache, frames, frames.astype(jnp.float32), mask
        )
        ref_batches.append(([f for f, _ in batch], [m for _, m in batch]))
    ref = _ref_model(capacity, ref_batches)
    probes = sorted({f for fs, _ in ref_batches for f in fs} | {-1})
    hit, vals = cache_lookup(cache, jnp.asarray(probes, jnp.int32))
    for i, f in enumerate(probes):
        expected = f >= 0 and ref.get(f % capacity) == f
        assert bool(hit[i]) == expected, (f, capacity, ref)
        if expected:
            assert float(vals[i]) == float(f)


def test_cache_eviction_overwrites_tag_stale_miss():
    cache = _toy_cache(4)
    f1, f2 = 3, 7          # same slot: 3 % 4 == 7 % 4
    ins = lambda c, f: cache_insert(
        c, jnp.asarray([f], jnp.int32), jnp.asarray([float(f)], jnp.float32),
        jnp.asarray([True]),
    )
    cache = ins(cache, f1)
    cache = ins(cache, f2)   # later batch overwrites: eviction
    hit, vals = cache_lookup(cache, jnp.asarray([f1, f2], jnp.int32))
    assert not bool(hit[0]), "evicted frame must go stale, not phantom-hit"
    assert bool(hit[1]) and float(vals[1]) == 7.0


def test_cache_within_batch_first_write_wins():
    cache = _toy_cache(4)
    frames = jnp.asarray([3, 7], jnp.int32)   # colliding in ONE batch
    cache = cache_insert(
        cache, frames, frames.astype(jnp.float32), jnp.asarray([True, True])
    )
    hit, vals = cache_lookup(cache, frames)
    assert bool(hit[0]) and float(vals[0]) == 3.0
    assert not bool(hit[1]), "second colliding write must lose, not race"


def _bits(x):
    return np.ascontiguousarray(np.asarray(x)).view(np.uint8)


@pytest.mark.parametrize("dtype", ["float32", "int32", "uint32", "bool"])
def test_cache_rows_round_trip_bit_exact(dtype):
    """A slot's detections packed into a row of 32-bit words and back give
    the same bits, on the host (numpy) and on the device (jax) alike:
    float NaN payloads, signed zeros and infinities, bools and integers
    at their extremes included, with leading axes of any rank; a leaf
    that is not 32 bits wide is refused, not truncated."""
    from repro.serve.batcher import RowLayout

    dt = np.dtype(dtype)
    rng = np.random.default_rng(3)
    shape = (2, 5, 16, 3)
    if dt == np.bool_:
        x = rng.random(shape) < 0.5
    else:   # every bit pattern, NaNs with payloads among them
        x = rng.integers(0, 2**32, shape, np.uint32).view(dt)
    special = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf, 1e-45, -1.5],
                       np.float32)
    dets = {"a": x, "b": np.resize(special, shape[:2] + (16,))}
    struct = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape[2:], v.dtype), dets)
    layout = RowLayout.of(struct)
    assert layout.width % 128 == 0 and layout.words == 16 * 3 + 16
    rows = layout.pack(dets)
    assert isinstance(rows, np.ndarray)
    assert rows.shape == shape[:2] + (layout.width,) and rows.dtype == np.int32
    rows_dev = layout.pack(jax.tree.map(jnp.asarray, dets))
    np.testing.assert_array_equal(np.asarray(rows_dev), rows)
    for back in (layout.unpack(rows), layout.unpack(rows_dev)):
        for k in dets:
            assert back[k].dtype == dets[k].dtype
            assert back[k].shape == dets[k].shape
            np.testing.assert_array_equal(_bits(back[k]), _bits(dets[k]))
    with pytest.raises(TypeError, match="32-bit"):
        RowLayout.of(jax.ShapeDtypeStruct((4,), jnp.bfloat16))


def test_oracle_rows_are_lane_dense_and_near_logical_size():
    """The oracle's ``Detections`` (16 × (4 + 8) f32, 16 bool, 16 i32: 852 B
    with the tag) pack into 224 words of a 256-word row: 1,028 B a slot,
    within 1.25× of the logical bytes."""
    from repro.serve.batcher import RowLayout
    from repro.sim.oracle import Detections

    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    layout = RowLayout.of(Detections(
        boxes=f(16, 4), feats=f(16, 8),
        valid=jax.ShapeDtypeStruct((16,), jnp.bool_),
        inst_id=jax.ShapeDtypeStruct((16,), jnp.int32),
    ))
    assert (layout.words, layout.width) == (224, 256)
    assert (layout.width * 4 + 4) / 852 <= 1.25


def test_shard_cache_layout_roundtrip_and_divisibility():
    from repro.serve.batcher import shard_cache_layout, unshard_cache_layout

    cache = _toy_cache(12)
    frames = jnp.asarray([0, 5, 7, 11, 17], jnp.int32)
    cache = cache_insert(
        cache, frames, frames.astype(jnp.float32), jnp.ones(5, bool)
    )
    for s in (1, 2, 3, 4, 6):
        back = unshard_cache_layout(shard_cache_layout(cache, s))
        np.testing.assert_array_equal(
            np.asarray(back.tag), np.asarray(cache.tag))
        np.testing.assert_array_equal(
            np.asarray(back.store), np.asarray(cache.store))
    with pytest.raises(ValueError, match="multiple"):
        shard_cache_layout(cache, 5)


@hypothesis.given(
    capacity_l=st.integers(min_value=1, max_value=4),
    num_shards=st.sampled_from([1, 2, 3, 4]),
    batches=st.lists(
        st.lists(
            st.tuples(
                st.integers(min_value=-1, max_value=40), st.booleans()
            ),
            min_size=1, max_size=6,
        ),
        min_size=1, max_size=3,
    ),
)
@hypothesis.settings(deadline=None, max_examples=60)
def test_sharded_cache_bit_identical_to_direct_mapped(
    capacity_l, num_shards, batches
):
    """The §14 contract: hash-sharding is a pure re-placement.  Running
    every insert batch through the per-shard halves (each shard filters
    the batch to its homed frames) and re-assembling must reproduce the
    direct-mapped cache bit for bit, and the OR of per-shard lookups must
    equal the direct-mapped lookup — hits, values, evictions, and
    within-batch collision winners included."""
    from repro.serve.batcher import (
        shard_cache_layout,
        sharded_cache_insert,
        sharded_cache_lookup,
        unshard_cache_layout,
    )

    capacity = capacity_l * num_shards
    direct = _toy_cache(capacity)
    locals_ = [
        jax.tree.map(
            lambda x: x[s * capacity_l:(s + 1) * capacity_l],
            shard_cache_layout(_toy_cache(capacity), num_shards),
        )
        for s in range(num_shards)
    ]
    for batch in batches:
        frames = jnp.asarray([f for f, _ in batch], jnp.int32)
        mask = jnp.asarray([m for _, m in batch])
        vals = frames.astype(jnp.float32)
        direct = cache_insert(direct, frames, vals, mask)
        rows = direct.layout.pack(vals)
        locals_ = [
            sharded_cache_insert(c, frames, rows, mask, s, num_shards)
            for s, c in enumerate(locals_)
        ]
    assembled = unshard_cache_layout(
        jax.tree.map(lambda *xs: jnp.concatenate(xs), *locals_)
    )
    np.testing.assert_array_equal(
        np.asarray(assembled.tag), np.asarray(direct.tag))
    np.testing.assert_array_equal(
        np.asarray(assembled.store), np.asarray(direct.store))
    probes = jnp.asarray(
        sorted({f for b in batches for f, _ in b} | {-1}), jnp.int32
    )
    d_hit, d_vals = cache_lookup(direct, probes)
    s_hits, s_vals = zip(*[
        sharded_cache_lookup(c, probes, s, num_shards)
        for s, c in enumerate(locals_)
    ])
    or_hit = np.logical_or.reduce([np.asarray(h) for h in s_hits])
    np.testing.assert_array_equal(or_hit, np.asarray(d_hit))
    for i in range(len(probes)):
        if bool(d_hit[i]):
            s = int(probes[i]) % num_shards
            got = direct.layout.unpack(s_vals[s][i])
            assert float(got) == float(d_vals[i])


def test_cache_sentinel_never_hits_nor_inserts():
    cache = _toy_cache(4)
    # a masked-True sentinel must still not insert: it would tag slot
    # capacity-1 with -1 and poison later lookups there
    cache = cache_insert(
        cache, jnp.asarray([-1], jnp.int32),
        jnp.asarray([99.0], jnp.float32), jnp.asarray([True]),
    )
    np.testing.assert_array_equal(np.asarray(cache.tag), [-1, -1, -1, -1])
    hit, _ = cache_lookup(cache, jnp.asarray([-1], jnp.int32))
    assert not bool(hit[0])
    # and a real frame in the aliasing slot is unaffected
    cache = cache_insert(
        cache, jnp.asarray([3], jnp.int32),
        jnp.asarray([3.0], jnp.float32), jnp.asarray([True]),
    )
    hit, vals = cache_lookup(cache, jnp.asarray([3, -1], jnp.int32))
    assert bool(hit[0]) and float(vals[0]) == 3.0
    assert not bool(hit[1])


# ---------------------------------------------------------------------------
# RepositoryIndex: host tier, versions, snapshot, warm()
# ---------------------------------------------------------------------------


def _toy_struct():
    return jax.eval_shape(lambda f: jnp.float32(0.0), 0)


def _publish_frames(index, frames):
    f = jnp.asarray(frames, jnp.int32)
    return index.publish(f, f.astype(jnp.float32))


def test_index_publish_lookup_and_duplicates():
    idx = RepositoryIndex(detector_version="v1")
    assert _publish_frames(idx, [4, 9, -1, 4]) == 2   # sentinel + dup skip
    assert idx.stats["duplicates"] == 1
    assert len(idx) == 2
    assert float(idx.lookup(4)[0]) == 4.0
    assert idx.lookup(5) is None
    assert idx.lookup(4, version="v2") is None, "version mismatch = miss"


def test_index_detector_version_isolation():
    idx = RepositoryIndex(detector_version="v1")
    _publish_frames(idx, [1, 2, 3])
    idx.detector_version = "v2"           # model upgrade
    assert len(idx) == 0, "new version reads an empty tier"
    _publish_frames(idx, [1])
    assert idx.entries("v1") == 3 and idx.entries("v2") == 1
    cache, warm = idx.warm(_toy_struct(), 16)
    assert warm == {1}, "warm() serves only the CURRENT version"


def test_index_snapshot_roundtrip(tmp_path):
    path = str(tmp_path / "idx")
    idx = RepositoryIndex(path, detector_version="v1")
    _publish_frames(idx, [2, 11, 7])
    idx.priors.record(0, np.asarray([1.0, 0.0]), np.asarray([4.0, 2.0]))
    idx.save()
    idx2 = RepositoryIndex(path, detector_version="v1")
    assert idx2.stats["loaded"] == 3
    assert sorted(
        f for f in (2, 7, 11) if idx2.lookup(f) is not None
    ) == [2, 7, 11]
    assert float(idx2.lookup(11)[0]) == 11.0
    np.testing.assert_array_equal(
        idx2.priors.warm_alphas(0, 2, 4.0),
        idx.priors.warm_alphas(0, 2, 4.0),
    )
    # a different detector_version over the SAME snapshot is a clean miss
    idx3 = RepositoryIndex(path, detector_version="v2")
    assert len(idx3) == 0 and idx3.entries("v1") == 3


def test_index_read_only_discipline(tmp_path):
    idx = RepositoryIndex(
        str(tmp_path / "ro"), detector_version="v1", read_only=True
    )
    assert _publish_frames(idx, [1, 2]) == 0
    assert len(idx) == 0
    with pytest.raises(ValueError, match="read_only"):
        idx.save()


def test_index_warm_empty_bitidentical_to_init():
    idx = RepositoryIndex()
    struct = _toy_struct()
    warm_cache, warm = idx.warm(struct, 8)
    cold = init_detection_cache(struct, 8)
    assert warm == frozenset()
    np.testing.assert_array_equal(
        np.asarray(warm_cache.tag), np.asarray(cold.tag))
    np.testing.assert_array_equal(
        np.asarray(warm_cache.store), np.asarray(cold.store))
    assert warm_cache.tag.dtype == cold.tag.dtype
    assert warm_cache.store.dtype == cold.store.dtype


def test_index_warm_collision_deterministic():
    idx = RepositoryIndex()
    _publish_frames(idx, [7, 3, 11])     # 3, 7, 11 all map to slot 3 % 4
    cache, warm = idx.warm(_toy_struct(), 4)
    assert warm == {3}, "ascending frame order, first occupant wins"
    hit, vals = cache_lookup(cache, jnp.asarray([3, 7, 11], jnp.int32))
    assert [bool(h) for h in hit] == [True, False, False]
    assert float(vals[0]) == 3.0


def test_index_snapshot_orphan_cleanup(tmp_path):
    """Regression: shrinking the version set between snapshots used to
    orphan the higher-numbered ``detections_<i>.npz`` forever.  After the
    second save the directory must hold exactly the manifest + files it
    references, and the torn-intermediate state (old manifest + extra
    files, before cleanup) must still load."""
    import os

    path = str(tmp_path / "idx")
    idx = RepositoryIndex(path, detector_version="v1")
    _publish_frames(idx, [1, 2])
    idx.detector_version = "v2"
    _publish_frames(idx, [3])
    idx.save()                                  # 2 versions → 2 npz files
    assert sorted(os.listdir(path)) == [
        "detections_0.npz", "detections_1.npz", "manifest.json", "priors.npz",
    ]
    # simulate the torn intermediate: extra unreferenced npz on disk
    with open(os.path.join(path, "detections_7.npz"), "wb") as fh:
        fh.write(b"torn")
    assert RepositoryIndex(path).stats["loaded"] == 3, (
        "unreferenced stray files must not break _load"
    )
    idx2 = RepositoryIndex(path, detector_version="v2")
    idx2._tiers.pop("v1")                       # version set shrinks
    idx2.save()                                 # 1 version → 1 npz file
    assert sorted(os.listdir(path)) == [
        "detections_0.npz", "manifest.json", "priors.npz",
    ], "orphans (incl. the stray) deleted after the manifest lands"
    idx3 = RepositoryIndex(path, detector_version="v2")
    assert idx3.stats["loaded"] == 1 and idx3.lookup(3) is not None


def test_index_rejects_incompatible_snapshot(tmp_path):
    path = tmp_path / "bad"
    path.mkdir()
    (path / "manifest.json").write_text('{"format": 99, "versions": {}}')
    with pytest.raises(ValueError, match="format"):
        RepositoryIndex(str(path))


# ---------------------------------------------------------------------------
# ChunkPriors: identity cold path, n1-only injection, geometry guard
# ---------------------------------------------------------------------------


def test_priors_zero_weight_returns_input_object():
    p = ChunkPriors()
    p.record(None, np.ones(4), np.full(4, 2.0))
    state = init_state(np.full(4, 100))
    out, equiv = p.warm_sampler(state, None, 0.0)
    assert out is state and equiv == 0.0
    out, equiv = p.warm_sampler(state, 5, 1.0)   # unknown class
    assert out is state and equiv == 0.0
    empty = ChunkPriors()
    out, equiv = empty.warm_sampler(state, None, 1.0)  # no evidence at all
    assert out is state and equiv == 0.0


def test_priors_inject_n1_only():
    p = ChunkPriors()
    p.record(0, np.asarray([3.0, 0.0, 1.0]), np.asarray([6.0, 0.0, 4.0]))
    state = init_state(np.full(3, 100))
    out, equiv = p.warm_sampler(state, 0, 8.0)
    assert out is not state and equiv > 0
    np.testing.assert_array_equal(np.asarray(out.n), np.asarray(state.n))
    boost = np.asarray(out.n1) - np.asarray(state.n1)
    # rate = [0.5, 0 (no evidence), 0.25] × weight 8
    np.testing.assert_allclose(boost, [4.0, 0.0, 2.0])


def test_priors_geometry_mismatch_refuses():
    p = ChunkPriors()
    p.record(0, np.ones(4), np.ones(4))
    assert p.warm_alphas(0, 5, 1.0) is None
    state = init_state(np.full(5, 100))
    out, _ = p.warm_sampler(state, 0, 1.0)
    assert out is state
    with pytest.raises(ValueError, match="chunk-count"):
        p.record(0, np.ones(3), np.ones(3))


def test_priors_record_batched_and_ingest_and_serde():
    p = ChunkPriors()
    p.record(None, np.ones((2, 3)), np.full((2, 3), 2.0))  # [Q, M] sums
    np.testing.assert_array_equal(p._n1[-1], [2.0, 2.0, 2.0])
    p.ingest(1, np.asarray([0.5, 2.0, -1.0]), weight=4.0)  # scores clip
    np.testing.assert_array_equal(p._n1[1], [2.0, 4.0, 0.0])
    np.testing.assert_array_equal(p._n[1], [4.0, 4.0, 4.0])
    assert p.classes() == [None, 1]
    q = ChunkPriors.from_arrays(p.to_arrays())
    assert q.classes() == p.classes()
    np.testing.assert_array_equal(q._n1[1], p._n1[1])
    np.testing.assert_array_equal(q._n[-1], p._n[-1])


# ---------------------------------------------------------------------------
# IndexSpec: serde round-trip + typed validation
# ---------------------------------------------------------------------------


def test_index_spec_serde_roundtrip():
    plan = _plan(index=IndexSpec(
        path="/tmp/x", detector_version="v3", read_only=True,
        prior_weight=2.5,
    ))
    back = SearchPlan.from_dict(plan.to_dict())
    assert back == plan
    assert back.execution.index.detector_version == "v3"
    assert back.execution.index.read_only is True


def test_index_spec_validation():
    with pytest.raises(PlanError, match="unknown") as e:
        IndexSpec.from_dict({"path": None, "sharding": 4})
    assert e.value.field == "sharding"
    with pytest.raises(PlanError) as e:
        _plan(index=IndexSpec(detector_version="")).resolve()
    assert e.value.field == "detector_version"
    with pytest.raises(PlanError) as e:
        _plan(index=IndexSpec(prior_weight=-1.0)).resolve()
    assert e.value.field == "prior_weight"
    with pytest.raises(PlanError) as e:
        _plan(index=IndexSpec(path=7)).resolve()
    assert e.value.field == "path"


# ---------------------------------------------------------------------------
# End-to-end: cold parity, warm replay, persisted economics
# ---------------------------------------------------------------------------


def test_cold_index_bitidentical_to_no_index(world, tmp_path):
    """A cold index with prior_weight=0 must change NOTHING: same carry,
    same traces, same detector economics as running without one."""
    _, chunks, det = world
    base = _plan().run(_fresh_multi(chunks), chunks, detector=det)
    spec = IndexSpec(path=str(tmp_path / "cold"), prior_weight=0.0)
    res = _plan(index=spec).run(_fresh_multi(chunks), chunks, detector=det)
    _same_carry(base.carry, res.carry)
    assert base.traces == res.traces
    assert base.stats.detector_invocations == res.stats.detector_invocations
    assert res.stats.index_hits == 0
    assert res.stats.persisted_detections > 0   # write-back still happened


def test_warm_index_replays_exactly(world, tmp_path):
    """Second identical run over the saved snapshot: bit-identical
    results, index hits cover the sampled frames, (near-)zero fresh
    detector calls — the ≥5× reuse economics of the headline bench."""
    _, chunks, det = world
    spec = IndexSpec(path=str(tmp_path / "warm"), prior_weight=0.0)
    r1 = _plan(index=spec).run(_fresh_multi(chunks), chunks, detector=det)
    assert r1.stats.persisted_detections > 0
    r2 = _plan(index=spec).run(_fresh_multi(chunks), chunks, detector=det)
    _same_carry(r1.carry, r2.carry)
    assert r1.traces == r2.traces
    assert r2.stats.index_hits > 0
    assert r2.stats.detector_invocations == 0, (
        "every frame of the identical trajectory was persisted by run 1")
    assert r2.stats.persisted_detections == 0   # nothing new to publish


def test_warm_start_priors_through_plan(world, tmp_path):
    """prior_weight > 0 over accumulated evidence injects Thompson
    pseudo-successes: warm_rounds_saved is reported and the query still
    terminates at its result limit."""
    _, chunks, det = world
    spec = IndexSpec(path=str(tmp_path / "pri"), prior_weight=0.0)
    _plan(index=spec).run(_fresh_multi(chunks), chunks, detector=det)
    warm_spec = dataclasses.replace(spec, prior_weight=50.0)
    res = _plan(index=warm_spec).run(
        _fresh_multi(chunks), chunks, detector=det
    )
    assert res.stats.warm_rounds_saved > 0
    assert res.results[0] == 10


def test_executor_version_mismatch_raises(world, tmp_path):
    _, chunks, det = world
    live = RepositoryIndex(detector_version="v1")
    with pytest.raises(PlanError) as e:
        _plan(index=IndexSpec(detector_version="v2")).run(
            _fresh_multi(chunks), chunks, detector=det, index=live
        )
    assert e.value.field == "detector_version"


def test_second_service_over_warm_index(world):
    """The multi-tenant saving: service #1's tenant publishes into the
    shared index at retirement; service #2 (fresh process stand-in) warms
    its device cache from it, and ITS tenant's attributed economics show
    index hits and fewer fresh detector calls."""
    from repro.serve.service import SearchService

    _, chunks, det = world

    def _svc(index):
        proto = init_carry_multi(
            init_state(chunks.length), init_matcher(max_results=64),
            jnp.stack([jax.random.PRNGKey(0)]),
        )
        return SearchService(
            proto, chunks, det, cohorts=2, num_workers=1,
            slots_per_batch=2, cache_frames=chunks.total_frames,
            index=index,
        )

    index = RepositoryIndex(detector_version="v0")
    plan = SearchPlan(
        result_limit=8, max_steps=1500, cohorts=2,
        execution=Execution(queries_axis=True),
    )
    svc1 = _svc(index)
    t1 = svc1.submit("a", plan, seed=1)
    svc1.start(pump=False)
    svc1.drain()
    svc1.stop()
    assert t1.state == "finished"
    assert len(index) > 0, "retirement published detections"
    assert np.sum(index.priors._n[-1 if t1.select_id is None else
                                  t1.select_id]) > 0

    svc2 = _svc(index)     # fresh driver warms from the shared index
    t2 = svc2.submit("b", plan, seed=1)   # same key ⇒ same trajectory
    svc2.start(pump=False)
    svc2.drain()
    svc2.stop()
    d1, d2 = t1.to_dict(), t2.to_dict()
    assert d2["results"] == d1["results"]
    assert d2["index_hits"] > 0
    assert d2["detector_invocations"] < d1["detector_invocations"]
    assert d1["detector_invocations"] >= 5 * max(
        d2["detector_invocations"], 1
    ) or d2["detector_invocations"] == 0


def test_service_rejects_warm_plan_without_index(world):
    from repro.serve.service import SearchService

    _, chunks, det = world
    proto = init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=64),
        jnp.stack([jax.random.PRNGKey(0)]),
    )
    svc = SearchService(proto, chunks, det, cohorts=2, num_workers=1)
    plan = SearchPlan(
        result_limit=4, max_steps=500,
        execution=Execution(
            queries_axis=True, index=IndexSpec(prior_weight=2.0)
        ),
    )
    with pytest.raises(PlanError) as e:
        svc.submit("a", plan)
    assert e.value.field == "index"
    svc.driver.stop()
