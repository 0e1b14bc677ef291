"""Multi-query batched driver ≡ per-query scanned driver (DESIGN.md §9).

The acceptance bar: at Q=1 ``run_search_multi`` is bit-identical in
(step, results, trace, sampler statistics, key) to ``run_search_scan``;
at Q>1 with disjoint per-query keys every query's trajectory equals its
own sequential run at the same frame budget — cross-query dedup and the
detection cache change WHICH detector invocations happen, never the
values a query consumes.  Property tests pin the dedup/scatter-back
invariants: no sampled frame is ever dropped, no detection is ever
counted into two queries' sampler deltas.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    init_carry,
    init_carry_multi,
    init_matcher,
    init_state,
    run_search_multi,
    run_search_scan,
    stack_carries,
)
from repro.core.plan import Execution, SearchPlan
from repro.core.thompson import choose_chunks, choose_chunks_batched
from repro.serve.batcher import (
    cache_insert,
    cache_lookup,
    dedup_first_index,
    init_detection_cache,
)
from repro.sim import RepoSpec, generate
from repro.sim.oracle import noisy_detect, oracle_detect


@pytest.fixture(scope="module")
def world():
    spec = RepoSpec(
        video_lengths=[6_000] * 3, num_instances=120, chunk_frames=600,
        locality=4.0, seed=7,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    return repo, chunks, det


def _fresh(chunks, key):
    return init_carry(
        init_state(chunks.length), init_matcher(max_results=512), key
    )


def _fresh_multi(chunks, keys):
    return init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=512), keys
    )


def _qkey(q):
    return jax.random.fold_in(jax.random.PRNGKey(0), q)


# ---------------------------------------------------------------------------
# Q=1 parity: bit-identical to run_search_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cohorts", [1, 8])
def test_multi_q1_bit_identical_to_scan(world, cohorts):
    _, chunks, det = world
    scan, scan_trace = run_search_scan(
        _fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det,
        result_limit=15, max_steps=1200, cohorts=cohorts, trace_every=25,
    )
    multi, traces, stats = run_search_multi(
        _fresh_multi(chunks, jax.random.PRNGKey(0)[None]), chunks,
        detector=det, result_limits=15, max_steps=1200, cohorts=cohorts,
        trace_every=25,
    )
    assert (int(scan.step), int(scan.results)) == (
        int(multi.step[0]), int(multi.results[0])
    )
    assert scan_trace == traces[0]
    np.testing.assert_array_equal(
        np.asarray(scan.sampler.n), np.asarray(multi.sampler.n[0])
    )
    np.testing.assert_array_equal(
        np.asarray(scan.sampler.n1), np.asarray(multi.sampler.n1[0])
    )
    np.testing.assert_array_equal(
        np.asarray(scan.key), np.asarray(multi.key[0])
    )
    # one query, no duplicates: every sampled frame is one detector call
    assert stats["detector_invocations"] == int(multi.step[0])


@pytest.mark.parametrize("method", ["wilson_hilferty", "pallas"])
def test_multi_q1_other_methods(world, method):
    _, chunks, det = world
    scan, _ = run_search_scan(
        _fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det,
        result_limit=10, max_steps=600, method=method,
    )
    multi, _, _ = run_search_multi(
        _fresh_multi(chunks, jax.random.PRNGKey(0)[None]), chunks,
        detector=det, result_limits=10, max_steps=600, method=method,
    )
    assert (int(scan.step), int(scan.results)) == (
        int(multi.step[0]), int(multi.results[0])
    )


# ---------------------------------------------------------------------------
# Q=4 disjoint keys: each query matches its own sequential run
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cache", [0, -1])
def test_multi_q4_each_query_matches_sequential(world, cache):
    _, chunks, det = world
    q_n, cohorts = 4, 4
    limits = [12, 12, 6, 12]   # query 2 finishes early and must mask out
    keys = jnp.stack([_qkey(q) for q in range(q_n)])
    cache_frames = chunks.total_frames if cache else 0
    multi, traces, stats = run_search_multi(
        _fresh_multi(chunks, keys), chunks, detector=det,
        result_limits=jnp.asarray(limits, jnp.int32), max_steps=900,
        cohorts=cohorts, trace_every=25, cache_frames=cache_frames,
    )
    for q in range(q_n):
        scan, scan_trace = run_search_scan(
            _fresh(chunks, _qkey(q)), chunks, detector=det,
            result_limit=limits[q], max_steps=900, cohorts=cohorts,
            trace_every=25,
        )
        assert (int(scan.step), int(scan.results)) == (
            int(multi.step[q]), int(multi.results[q])
        ), f"query {q} diverged"
        assert scan_trace == traces[q], f"query {q} trace diverged"
        np.testing.assert_array_equal(
            np.asarray(scan.sampler.n), np.asarray(multi.sampler.n[q])
        )
        np.testing.assert_array_equal(
            np.asarray(scan.key), np.asarray(multi.key[q])
        )
    # sharing can only save detector work, never add any
    assert stats["detector_invocations"] <= stats["frames_sampled"]


def test_multi_cross_chunk_decrements_match_single_query_path():
    """§3.4 through the Q-batched fold with a ring (R = 1,024) far wider
    than a frame's D = 16 detection lanes: on instances that span chunks,
    each query's final N¹ and n equal, bit for bit, those of its own
    single-query ``_process_frame`` run (``exsample_step``, one frame a
    step), and that run moved at least one result's first sighting out of
    another chunk."""
    from repro.core import exsample_step

    spec = RepoSpec(
        video_lengths=[3_000] * 2, num_instances=20, chunk_frames=500,
        duration_mu=6.5, duration_sigma=0.3, num_classes=1, seed=5,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    matcher = init_matcher(max_results=1024, time_gate=10**9, feat_thresh=0.9)
    q_n, limit, max_steps = 3, 20, 120
    keys = jnp.stack([_qkey(q) for q in range(q_n)])
    multi, _, _ = run_search_multi(
        init_carry_multi(init_state(chunks.length), matcher, keys), chunks,
        detector=det, result_limits=limit, max_steps=max_steps,
    )
    crossings = 0
    for q in range(q_n):
        c = init_carry(init_state(chunks.length), matcher, keys[q])
        while (
            int(c.results) < limit and int(c.step) < max_steps
            and not bool(jnp.all(c.sampler.exhausted()))
        ):
            prev = c
            c = exsample_step(c, chunks, detector=det)
            cid = int(np.argmax(np.asarray(c.sampler.n - prev.sampler.n)))
            seen0 = np.asarray(prev.matcher.times_seen)
            seen1 = np.asarray(c.matcher.times_seen)
            home = np.asarray(prev.matcher.chunk)
            crossings += int(((seen0 == 1) & (seen1 >= 2) & (home != cid)).sum())
        assert (int(c.step), int(c.results)) == (
            int(multi.step[q]), int(multi.results[q])
        ), f"query {q} diverged"
        np.testing.assert_array_equal(
            np.asarray(c.sampler.n1), np.asarray(multi.sampler.n1[q])
        )
        np.testing.assert_array_equal(
            np.asarray(c.sampler.n), np.asarray(multi.sampler.n[q])
        )
    assert crossings > 0


def test_stack_carries_matches_init_multi(world):
    _, chunks, _ = world
    keys = [_qkey(q) for q in range(3)]
    stacked = stack_carries([_fresh(chunks, k) for k in keys])
    built = _fresh_multi(chunks, jnp.stack(keys))
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(built)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_init_matcher_multi_layout():
    from repro.core import init_matcher_multi

    single = init_matcher(max_results=8, feat_dim=4, iou_thresh=0.3)
    multi = init_matcher_multi(3, max_results=8, feat_dim=4, iou_thresh=0.3)
    assert multi.iou_thresh == single.iou_thresh    # statics shared
    for a, b in zip(jax.tree.leaves(multi), jax.tree.leaves(single)):
        assert a.shape == (3,) + b.shape
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b))


def test_identical_queries_dedup_exactly(world):
    """Q identical queries (same key) sample identical frames every round,
    so the batched pass detects each frame exactly once: invocations =
    frames_sampled / Q, even with the cache off."""
    _, chunks, det = world
    q_n, cohorts = 4, 4
    keys = jnp.stack([jax.random.PRNGKey(3)] * q_n)
    multi, _, stats = run_search_multi(
        _fresh_multi(chunks, keys), chunks, detector=det,
        result_limits=12, max_steps=600, cohorts=cohorts,
    )
    steps = np.asarray(multi.step)
    assert (steps == steps[0]).all()
    assert stats["frames_sampled"] == int(steps.sum())
    assert stats["detector_invocations"] * q_n == stats["frames_sampled"]


# ---------------------------------------------------------------------------
# Batched Thompson choice: per-query bit-parity with the scalar path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["exact", "wilson_hilferty", "pallas"])
def test_choose_chunks_batched_parity(method):
    q_n, m, cohorts = 5, 37, 6
    rng = jax.random.PRNGKey(11)
    n1 = jnp.abs(jax.random.normal(rng, (q_n, m))) * 3
    n = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 1), (q_n, m))) * 9
    frames = jnp.full((q_n, m), 100, jnp.int32)
    # a couple of exhausted chunks per query
    n = n.at[:, 0].set(100.0)
    import dataclasses

    state = init_state(frames[0])
    batched_state = dataclasses.replace(state, n1=n1, n=n, frames=frames)
    keys = jnp.stack([_qkey(q) for q in range(q_n)])
    got = choose_chunks_batched(
        keys, batched_state, cohorts=cohorts, method=method
    )
    assert got.shape == (q_n, cohorts)
    for q in range(q_n):
        single = dataclasses.replace(
            state, n1=n1[q], n=n[q], frames=frames[q]
        )
        want = choose_chunks(keys[q], single, cohorts=cohorts, method=method)
        np.testing.assert_array_equal(np.asarray(got[q]), np.asarray(want))


# ---------------------------------------------------------------------------
# Dedup + cache properties (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(
    frames=st.lists(st.integers(0, 9), min_size=1, max_size=32),
    valid_bits=st.integers(0, 2**32 - 1),
)
def test_dedup_never_drops_never_duplicates(frames, valid_bits):
    f = jnp.asarray(frames, jnp.int32)
    valid = np.asarray(
        [(valid_bits >> i) & 1 for i in range(len(frames))], bool
    )
    first = np.asarray(dedup_first_index(f, jnp.asarray(valid)))
    is_rep = (first == np.arange(len(frames))) & valid
    for i, ok in enumerate(valid):
        if not ok:
            continue
        r = first[i]
        # never drops: every valid slot gathers a valid representative
        # holding EXACTLY the frame the query sampled
        assert valid[r] and frames[r] == frames[i]
        assert is_rep[r]
        assert r <= i
    # never double-counts: exactly one representative (one detector call)
    # per distinct valid frame
    assert is_rep.sum() == len({frames[i] for i in np.nonzero(valid)[0]})


_WORLD_CACHE = {}


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_round_sampler_deltas_isolated_per_query(seed):
    """No detection is ever double-counted across queries: after a short
    multi-query run, each query's sampler has absorbed exactly its own
    frames (Σ n-delta == its step counter) and its trajectory equals its
    solo run — a detection leaking into another query's deltas would break
    both."""
    if "w" not in _WORLD_CACHE:
        spec = RepoSpec(
            video_lengths=[2_000] * 2, num_instances=60, chunk_frames=500,
            locality=3.0, seed=5,
        )
        _WORLD_CACHE["w"] = generate(spec)
    repo, chunks = _WORLD_CACHE["w"]
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    q_n, cohorts = 3, 2
    keys = jnp.stack([
        jax.random.fold_in(jax.random.PRNGKey(seed), q) for q in range(q_n)
    ])
    multi, _, stats = run_search_multi(
        _fresh_multi(chunks, keys), chunks, detector=det,
        result_limits=8, max_steps=24, cohorts=cohorts,
        cache_frames=chunks.total_frames,
    )
    n_sum = np.asarray(multi.sampler.n).sum(axis=-1)
    steps = np.asarray(multi.step)
    np.testing.assert_array_equal(n_sum, steps.astype(n_sum.dtype))
    for q in range(q_n):
        solo, _ = run_search_scan(
            _fresh(chunks, keys[q]), chunks, detector=det,
            result_limit=8, max_steps=24, cohorts=cohorts,
        )
        assert (int(solo.step), int(solo.results)) == (
            int(multi.step[q]), int(multi.results[q])
        )


# ---------------------------------------------------------------------------
# Detection cache unit semantics
# ---------------------------------------------------------------------------


def _det_struct():
    return {
        "boxes": jax.ShapeDtypeStruct((2, 4), jnp.float32),
        "valid": jax.ShapeDtypeStruct((2,), jnp.bool_),
    }


def test_cache_roundtrip_and_eviction():
    cache = init_detection_cache(_det_struct(), capacity=4)
    frames = jnp.asarray([0, 1, 5, 2], jnp.int32)
    dets = {
        "boxes": jnp.arange(4 * 2 * 4, dtype=jnp.float32).reshape(4, 2, 4),
        "valid": jnp.ones((4, 2), bool),
    }
    cache = cache_insert(cache, frames, dets, jnp.ones((4,), bool))
    hit, vals = cache_lookup(cache, frames)
    # frame 5 collides with frame 1 (slot 1); the FIRST masked write wins,
    # so 1 survives and 5 missed
    np.testing.assert_array_equal(np.asarray(hit), [True, True, False, True])
    np.testing.assert_array_equal(
        np.asarray(vals["boxes"][0]), np.asarray(dets["boxes"][0])
    )
    # eviction: inserting frame 5 now overwrites slot 1
    cache = cache_insert(
        cache,
        jnp.asarray([5], jnp.int32),
        jax.tree.map(lambda x: x[2:3], dets),
        jnp.ones((1,), bool),
    )
    hit2, _ = cache_lookup(cache, frames)
    np.testing.assert_array_equal(np.asarray(hit2), [True, False, True, True])


def test_cache_padded_sentinel_frames_never_hit():
    """Regression: a padded/sentinel frame id of -1 maps to slot
    ``capacity-1`` (Python modulo) and compared equal to the empty-slot
    tag -1 — so padding slots of a ``RequestBatcher`` batch reported
    phantom cache hits against an EMPTY cache and gathered garbage
    detections.  Sentinels must miss on lookup and be inert on insert."""
    cache = init_detection_cache(_det_struct(), capacity=4)
    padded = jnp.asarray([0, -1, -1, 2], jnp.int32)   # Batch.frame_ids style
    hit, _ = cache_lookup(cache, padded)
    np.testing.assert_array_equal(np.asarray(hit), [False] * 4)

    # harden cache_insert the same way: seed slot capacity-1 with a real
    # frame, then insert a padded batch whose mask (wrongly) covers the
    # sentinels — the real entry must survive and the sentinel never lands
    dets = {
        "boxes": jnp.ones((4, 2, 4), jnp.float32),
        "valid": jnp.ones((4, 2), bool),
    }
    cache = cache_insert(
        cache, jnp.asarray([7], jnp.int32),
        jax.tree.map(lambda x: x[:1], dets), jnp.ones((1,), bool),
    )
    cache = cache_insert(cache, padded, dets, jnp.ones((4,), bool))
    assert int(cache.tag[3]) == 7                     # not clobbered to -1
    hit2, _ = cache_lookup(cache, jnp.asarray([7, -1], jnp.int32))
    np.testing.assert_array_equal(np.asarray(hit2), [True, False])


def test_cache_masked_insert_is_noop():
    cache = init_detection_cache(_det_struct(), capacity=4)
    dets = {
        "boxes": jnp.ones((1, 2, 4), jnp.float32),
        "valid": jnp.ones((1, 2), bool),
    }
    cache = cache_insert(
        cache, jnp.asarray([3], jnp.int32), dets, jnp.zeros((1,), bool)
    )
    hit, _ = cache_lookup(cache, jnp.asarray([3], jnp.int32))
    assert not bool(hit[0])


# ---------------------------------------------------------------------------
# What a profile and the stats can see of a round
# ---------------------------------------------------------------------------

STAGES = ("choose", "detect", "dedup_cache", "match", "update")


@pytest.mark.parametrize("program", ["resident", "slots"])
def test_lowered_round_programs_name_every_stage(world, program):
    """Every stage of a multi-query round carries its ``jax.named_scope``
    in the lowered program: the resident loop holds all five, the slot
    runtime's process half all but ``choose`` (issued on the driver)."""
    from repro.core.exsample import _search_multi_device, multi_round_choose
    from repro.core.runtime import _process_slots

    _, chunks, det = world
    mc = _fresh_multi(chunks, jax.vmap(_qkey)(jnp.arange(2)))
    struct = jax.eval_shape(det, _qkey(0), jnp.zeros((), jnp.int32))
    cache = init_detection_cache(struct, 64)
    if program == "resident":
        lowered = _search_multi_device.lower(
            mc, chunks, jnp.full((2,), 5, jnp.int32), cache, None,
            detector=det, select=None, cohorts=3, method="exact",
            max_steps=60, trace_every=0,
        )
        want = set(STAGES)
    else:
        choice = multi_round_choose(mc, chunks, cohorts=3, method="exact")
        lowered = _process_slots.lower(
            mc, cache, chunks, jnp.arange(2, dtype=jnp.int32),
            jnp.ones((2,), bool), choice, detector=det, select=None,
        )
        want = set(STAGES) - {"choose"}
    text = lowered.as_text(debug_info=True)
    # a scope leads an op's location name, or sits inside its path
    assert {s for s in STAGES if f'"{s}/' in text or f"/{s}/" in text} == want


@pytest.mark.parametrize("cache", [None, -1])
def test_detector_lanes_count_every_evaluated_lane(world, cache, round_needs):
    """``detector_lanes`` on the resident lowering is what the detector
    evaluated: each round's needed lanes rounded up to whole chunks, 8
    lanes a chunk for Q × C = 12, and no lane in a round that needs none."""
    _, chunks, det = world
    plan = SearchPlan(
        queries=3, result_limit=10, max_steps=400, cohorts=4,
        execution=Execution(queries_axis=True, cache=cache),
    )
    res = plan.run(
        _fresh_multi(chunks, jax.vmap(_qkey)(jnp.arange(3))), chunks,
        detector=lambda key, frame: det(key, frame),
    )
    jax.effects_barrier()
    st = res.stats
    assert res.kind == "multi" and len(round_needs) == st.rounds > 0
    assert sum(round_needs) == st.detector_invocations
    assert st.detector_lanes == sum(-(-n // 8) * 8 for n in round_needs)
    assert 0 < st.detector_invocations <= st.detector_lanes


# ---------------------------------------------------------------------------
# The compacted detector batch: only the round's needed lanes are detected
# ---------------------------------------------------------------------------


def _full_batch(detector, keys, frames, need):
    """The round's detector batch before compaction: every lane detected."""
    return jax.vmap(detector)(keys, frames), jnp.int32(frames.shape[0])


# case: (queries, cohorts, lanes a chunk, detector, cache preloaded by a
# cold run of the same search)
DETECT_EDGES = {
    "no_needed_lane": (3, 4, 8, "oracle", True),
    "every_lane_needed": (1, 12, 8, "oracle", False),
    "exact_chunk_multiple": (1, 16, 8, "oracle", False),
    "narrow_batch": (2, 3, 6, "oracle", False),
    "key_dependent": (3, 4, 8, "noisy", False),
}


@pytest.mark.parametrize("case", list(DETECT_EDGES))
def test_compacted_detect_matches_full_batch(
    world, case, round_needs, monkeypatch
):
    """Detecting only the unique, uncached, live lanes, in chunks, leaves
    every value a query consumes as the full batch left it: the final
    carry, the traces, the final cache and the detector counters equal,
    bit for bit, those of the same search with the detector on every lane,
    and, for the deterministic detector, each query's own solo run.  The
    detector evaluates each round's needed lanes rounded up to whole
    chunks: none in a round the cache serves whole, one chunk of B lanes
    below 8 lanes, a full and a padded chunk where 12 lanes are needed,
    two full chunks where 16 are."""
    from repro.core import exsample

    repo, chunks, _ = world
    q_n, cohorts, width, kind, warm = DETECT_EDGES[case]
    if kind == "oracle":
        base = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    else:
        base = lambda key, frame: noisy_detect(
            key, repo, frame, query_class=0
        )
    keys = jnp.stack([_qkey(q) for q in range(q_n)])
    kw = dict(
        result_limits=10, max_steps=400, cohorts=cohorts, trace_every=25,
        cache_frames=chunks.total_frames,
    )

    def search(cache):
        return exsample._multi_search(
            _fresh_multi(chunks, keys), chunks,
            detector=lambda key, frame: base(key, frame), cache=cache, **kw,
        )

    cache = search(None)[2]["final_cache"] if warm else None
    jax.effects_barrier()
    del round_needs[:]
    out, traces, st = search(cache)
    jax.effects_barrier()
    needs = list(round_needs)
    monkeypatch.setattr(exsample, "detect_needed", _full_batch)
    ref, ref_traces, ref_st = search(cache)

    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert traces == ref_traces
    for a, b in zip(
        jax.tree.leaves(st["final_cache"]), jax.tree.leaves(ref_st["final_cache"])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ("detector_invocations", "cache_hits", "rounds"):
        assert st[k] == ref_st[k], k
    assert len(needs) == st["rounds"] > 0
    assert sum(needs) == st["detector_invocations"]
    assert st["detector_lanes"] == sum(-(-n // width) * width for n in needs)
    assert ref_st["detector_lanes"] == st["rounds"] * q_n * cohorts

    if case == "no_needed_lane":
        assert max(needs) == 0 and st["detector_lanes"] == 0
        assert st["cache_hits"] > 0
    elif case in ("every_lane_needed", "exact_chunk_multiple"):
        # one query never samples a frame twice: an empty cache serves
        # none of its lanes
        assert needs[0] == q_n * cohorts
        assert (needs[0] % width == 0) == (case == "exact_chunk_multiple")
    if kind == "oracle":
        solo_det = lambda key, frame: base(key, frame)
        for q in range(q_n):
            solo, solo_trace = run_search_scan(
                _fresh(chunks, _qkey(q)), chunks, detector=solo_det,
                result_limit=10, max_steps=400, cohorts=cohorts,
                trace_every=25,
            )
            assert solo_trace == traces[q]
            for a, b in zip(jax.tree.leaves(solo), jax.tree.leaves(out)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[q])
