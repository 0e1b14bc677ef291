"""Matcher semantics: d0/d1 counting, dedup, cross-chunk, ring buffer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.matcher import (
    init_matcher,
    match_and_update,
    merge_matcher_checked,
    pairwise_iou,
)


def _box(x, y, w=0.1, h=0.1):
    return [x, y, x + w, y + h]


def _dets(boxes, valid=None):
    boxes = jnp.asarray(boxes, jnp.float32)
    d = boxes.shape[0]
    feats = jnp.zeros((d, 8), jnp.float32)
    if valid is None:
        valid = jnp.ones((d,), bool)
    return boxes, feats, jnp.asarray(valid)


def test_pairwise_iou_known_values():
    a = jnp.asarray([_box(0, 0, 0.2, 0.2)], jnp.float32)
    b = jnp.asarray([_box(0, 0, 0.2, 0.2), _box(0.1, 0.1, 0.2, 0.2), _box(0.5, 0.5)], jnp.float32)
    iou = np.asarray(pairwise_iou(a, b))
    assert abs(iou[0, 0] - 1.0) < 1e-6
    assert abs(iou[0, 1] - (0.01 / 0.07)) < 1e-5
    assert iou[0, 2] == 0.0


def test_new_then_repeat_then_third():
    m = init_matcher(max_results=16)
    b, f, v = _dets([_box(0.3, 0.3)])
    r1 = match_and_update(m, b, f, v, jnp.int32(0), jnp.int32(100), jnp.int32(0))
    assert int(r1.d0) == 1 and int(r1.d1) == 0
    r2 = match_and_update(r1.new_state, b, f, v, jnp.int32(0), jnp.int32(110), jnp.int32(0))
    assert int(r2.d0) == 0 and int(r2.d1) == 1          # seen-once → seen-twice
    r3 = match_and_update(r2.new_state, b, f, v, jnp.int32(0), jnp.int32(120), jnp.int32(0))
    assert int(r3.d0) == 0 and int(r3.d1) == 0          # third sighting: no change


def test_time_gate_separates_instances():
    m = init_matcher(max_results=16, time_gate=50)
    b, f, v = _dets([_box(0.3, 0.3)])
    r1 = match_and_update(m, b, f, v, jnp.int32(0), jnp.int32(0), jnp.int32(0))
    r2 = match_and_update(r1.new_state, b, f, v, jnp.int32(0), jnp.int32(1000), jnp.int32(0))
    assert int(r2.d0) == 1                               # beyond gate ⇒ new result


def test_different_video_is_new():
    m = init_matcher(max_results=16)
    b, f, v = _dets([_box(0.3, 0.3)])
    r1 = match_and_update(m, b, f, v, jnp.int32(0), jnp.int32(0), jnp.int32(0))
    r2 = match_and_update(r1.new_state, b, f, v, jnp.int32(1), jnp.int32(5), jnp.int32(0))
    assert int(r2.d0) == 1


def test_cross_chunk_repeat_decrements_home(case_frames=30):
    m = init_matcher(max_results=16)
    b, f, v = _dets([_box(0.3, 0.3)])
    r1 = match_and_update(m, b, f, v, jnp.int32(0), jnp.int32(0), jnp.int32(0))
    r2 = match_and_update(
        r1.new_state, b, f, v, jnp.int32(0), jnp.int32(case_frames), jnp.int32(1)
    )
    assert int(r2.d1) == 1 and int(r2.cross_chunk) == 1
    homes = np.asarray(r2.cross_home)
    assert (homes >= 0).sum() == 1 and homes.max() == 0  # home chunk is 0


def test_invalid_slots_ignored():
    m = init_matcher(max_results=16)
    b, f, _ = _dets([_box(0.3, 0.3), _box(0.6, 0.6)])
    v = jnp.asarray([True, False])
    r = match_and_update(m, b, f, v, jnp.int32(0), jnp.int32(0), jnp.int32(0))
    assert int(r.d0) == 1


def test_multiple_new_in_one_frame():
    m = init_matcher(max_results=16)
    b, f, v = _dets([_box(0.1, 0.1), _box(0.5, 0.5), _box(0.8, 0.1)])
    r = match_and_update(m, b, f, v, jnp.int32(0), jnp.int32(0), jnp.int32(0))
    assert int(r.d0) == 3
    assert int((r.new_state.times_seen > 0).sum()) == 3


def test_ring_buffer_wraps():
    m = init_matcher(max_results=2)
    for i in range(4):
        b, f, v = _dets([_box(0.05 + 0.22 * i, 0.05)])
        r = match_and_update(
            m, b, f, v, jnp.int32(0), jnp.int32(i * 2000), jnp.int32(0)
        )
        m = r.new_state
        assert int(r.d0) == 1
    assert int((m.times_seen > 0).sum()) == 2            # capacity bound holds
    assert int(m.total_inserted) == 4    # monotone, unlike the ring cursor


def _insert_n(m, n, *, start=0):
    """n distinct single-detection frames, far beyond the time gate."""
    for i in range(start, start + n):
        b, f, v = _dets([_box(0.05, 0.05)])
        m = match_and_update(
            m, b, f, v, jnp.int32(0), jnp.int32(i * 2000), jnp.int32(0)
        ).new_state
    return m


def test_merge_surfaces_high_water_insertions():
    snap = init_matcher(max_results=8)
    src = _insert_n(snap, 3)
    dst = _insert_n(snap, 2, start=100)
    merged, stats = merge_matcher_checked(dst, src, snap)
    assert int(stats.inserted) == 3
    assert not bool(stats.overflow)
    assert int(stats.clobbered) == 0
    assert int(merged.total_inserted) == 5
    assert int((merged.times_seen > 0).sum()) == 5


def test_merge_overflow_flagged_not_silently_wrapped():
    """Ring-wrap guard (ROADMAP, test-first): a worker inserting ≥ capacity
    results between snapshot and merge wraps its ring — the cursor delta
    aliases mod capacity and the old merge silently appended only
    ``inserted % capacity`` entries.  The monotone insertion counter makes
    the overflow observable so callers can raise/flag instead."""
    cap = 4
    snap = init_matcher(max_results=cap)
    src = _insert_n(snap, cap + 2)       # 6 insertions into a 4-ring
    merged, stats = merge_matcher_checked(init_matcher(max_results=cap), src, snap)
    assert int(stats.inserted) == cap + 2
    assert bool(stats.overflow)
    # the silent-wrap symptom the flag guards against: the merge window
    # aliased to 2 entries, 4 results are unrecoverable
    assert int((merged.times_seen > 0).sum()) == 2


def test_merge_clobber_counts_live_dst_overwrites():
    cap = 4
    snap = init_matcher(max_results=cap)
    src = _insert_n(snap, 3)             # appended at dst.cursor == 3
    dst = _insert_n(snap, 3, start=100)  # dst holds 3 live entries
    _, stats = merge_matcher_checked(dst, src, snap)
    assert not bool(stats.overflow)
    # slots [3, 0, 1): wraps onto dst's live entries 0 and 1
    assert int(stats.clobbered) == 2


def _dense_cross_home(state, boxes, valid, video_id, frame_id, chunk_id):
    """Per-entry §3.4 homes, ``where(crossed, chunk, -1)`` over all R ring
    entries, recomputed in numpy from the pre-frame state, and how many
    detections matched each entry."""
    seen = np.asarray(state.times_seen)
    occupied = seen > 0
    iou = np.asarray(pairwise_iou(jnp.asarray(boxes), state.boxes))
    eligible = (
        occupied[None, :]
        & (np.asarray(state.video)[None, :] == video_id)
        & (np.abs(np.asarray(state.frame)[None, :] - frame_id) <= state.time_gate)
        & (iou >= state.iou_thresh)
    )
    scores = np.where(eligible, iou, -1e9)
    best = scores.argmax(axis=1)
    has_match = eligible[np.arange(len(best)), best] & valid
    bump = np.zeros_like(seen)
    np.add.at(bump, best, has_match.astype(seen.dtype))
    went_twice = occupied & (seen == 1) & (seen + bump >= 2)
    chunk = np.asarray(state.chunk)
    return np.where(went_twice & (chunk != chunk_id), chunk, -1), bump


@pytest.mark.parametrize("capacity", [16, 256])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cross_home_per_detection_matches_dense_entries(seed, capacity):
    """§3.4 homes travel one lane per detection: over random frames that
    repeat a few box positions across chunks, the [D] lanes hold exactly
    the homes of the dense per-entry rule, with the same multiplicity."""
    rng = np.random.default_rng(seed)
    d_n, num_chunks, frames = 16, 4, 300
    anchors = np.asarray([_box(0.1 + 0.2 * i, 0.1 + 0.15 * (i % 3)) for i in range(5)])
    step = jax.jit(match_and_update)
    m = init_matcher(max_results=capacity, time_gate=300)
    crossings = doubled = 0
    for t in range(frames):
        pick = rng.integers(0, len(anchors), d_n)
        boxes = (anchors[pick] + rng.uniform(-0.005, 0.005, (d_n, 4))).astype(np.float32)
        valid = rng.random(d_n) < 0.3
        video_id, chunk_id = int(rng.integers(0, 2)), int(rng.integers(0, num_chunks))
        frame_id = 50 * t
        dense, bump = _dense_cross_home(
            m, boxes, valid, video_id, frame_id, chunk_id
        )
        r = step(
            m, jnp.asarray(boxes), jnp.zeros((d_n, 8), jnp.float32),
            jnp.asarray(valid), jnp.int32(video_id), jnp.int32(frame_id),
            jnp.int32(chunk_id),
        )
        homes = np.asarray(r.cross_home)
        assert homes.shape == (d_n,)
        assert int((homes >= 0).sum()) == int(r.cross_chunk)
        np.testing.assert_array_equal(
            np.bincount(homes[homes >= 0], minlength=num_chunks),
            np.bincount(dense[dense >= 0], minlength=num_chunks),
        )
        crossings += int((dense >= 0).sum())
        doubled += int(((dense >= 0) & (bump >= 2)).sum())
        m = r.new_state
    assert crossings > 0
    assert doubled > 0   # an entry two detections moved crossed: one lane
