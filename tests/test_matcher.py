"""Matcher semantics: d0/d1 counting, dedup, cross-chunk, ring buffer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.matcher import (
    ResultLog,
    broadcast_leading,
    eviction_mask,
    init_matcher,
    match_and_update,
    merge_matcher,
    merge_matcher_checked,
    pairwise_iou,
    ring_iou,
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
    iou = np.asarray(pairwise_iou(jnp.asarray(boxes), state.boxes.T))
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


# ---------------------------------------------------------------------------
# The coordinate-major ring against a numpy model of the row-major ring:
# entries on rows, new results written at cursor, cursor + 1, ... in
# detection order, appends of a merge copied slot by slot.
# ---------------------------------------------------------------------------

_LEAVES = ("boxes", "feats", "video", "frame", "chunk", "times_seen")


def _np_iou(a, b):
    """float32 IoU [D, R] of a [D, 4] against row-major b [R, 4]."""
    area_a = np.maximum(a[:, 2] - a[:, 0], 0) * np.maximum(a[:, 3] - a[:, 1], 0)
    area_b = np.maximum(b[:, 2] - b[:, 0], 0) * np.maximum(b[:, 3] - b[:, 1], 0)
    lt = np.maximum(a[:, None, :2], b[None, :, :2])
    rb = np.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = np.maximum(rb - lt, np.float32(0))
    inter = wh[..., 0] * wh[..., 1]
    union = area_a[:, None] + area_b[None, :] - inter
    return inter / np.maximum(union, np.float32(1e-9))


def _rows(state):
    """A (unbatched) device ring as the row-major numpy model holds it."""
    ring = {f: np.array(getattr(state, f)).T for f in _LEAVES}
    ring["cursor"] = int(state.cursor)
    ring["total"] = int(state.total_inserted)
    return ring


def _assert_ring_equals(state, ring):
    for f in _LEAVES:
        np.testing.assert_array_equal(np.asarray(getattr(state, f)).T, ring[f], f)
    assert int(state.cursor) == ring["cursor"]
    assert int(state.total_inserted) == ring["total"]


def _np_match(ring, boxes, feats, valid, video_id, frame_id, chunk_id,
              *, iou_thresh=0.5, time_gate=300):
    """One frame on the row-major model; updates ``ring`` in place and
    returns (d0, d1, cross_chunk, cross_home, is_new)."""
    cap = ring["times_seen"].shape[0]
    seen = ring["times_seen"]
    occupied = seen > 0
    iou = _np_iou(boxes, ring["boxes"])
    eligible = (
        occupied[None, :]
        & (ring["video"][None, :] == video_id)
        & (np.abs(ring["frame"][None, :] - frame_id) <= time_gate)
        & (iou >= iou_thresh)
    )
    best = np.where(eligible, iou, -1e9).argmax(axis=1)
    has_match = eligible[np.arange(len(best)), best] & valid
    is_new = valid & ~has_match
    bump = np.zeros(cap, seen.dtype)
    np.add.at(bump, best, has_match.astype(seen.dtype))
    new_seen = seen + np.where(occupied, bump, 0)
    went_twice = occupied & (seen == 1) & (new_seen >= 2)
    crossed = went_twice & (ring["chunk"] != chunk_id)
    cross_home = np.full(len(best), -1)
    for d in np.flatnonzero(has_match):
        if crossed[best[d]] and not np.any(has_match[:d] & (best[:d] == best[d])):
            cross_home[d] = ring["chunk"][best[d]]
    ring["times_seen"] = new_seen
    for d in np.flatnonzero(is_new):
        r = ring["cursor"]
        ring["boxes"][r], ring["feats"][r] = boxes[d], feats[d]
        ring["video"][r], ring["frame"][r] = video_id, frame_id
        ring["chunk"][r], ring["times_seen"][r] = chunk_id, 1
        ring["cursor"] = (r + 1) % cap
        ring["total"] += 1
    return (int(is_new.sum()), int(went_twice.sum()), int(crossed.sum()),
            cross_home, is_new)


def _np_merge(dst, src, snap):
    """``merge_matcher`` on row-major models, slot by slot."""
    cap = dst["times_seen"].shape[0]
    out = {f: dst[f].copy() for f in _LEAVES}
    n_new = (src["cursor"] - snap["cursor"]) % cap
    src_inserted = np.zeros(cap, bool)
    for i in range(n_new):
        src_inserted[(snap["cursor"] + i) % cap] = True
    same = ((dst["video"] == snap["video"]) & (dst["frame"] == snap["frame"])
            & (snap["times_seen"] > 0))
    out["times_seen"] = dst["times_seen"] + np.where(
        same & ~src_inserted, src["times_seen"] - snap["times_seen"], 0)
    for i in range(n_new):
        r, s = (dst["cursor"] + i) % cap, (snap["cursor"] + i) % cap
        for f in _LEAVES:
            out[f][r] = src[f][s]
    out["cursor"] = (dst["cursor"] + n_new) % cap
    out["total"] = dst["total"] + src["total"] - snap["total"]
    return out


_ANCHORS = np.asarray(
    [_box(0.1 + 0.2 * i, 0.1 + 0.15 * (i % 3)) for i in range(5)], np.float32
)


def _frame(rng, t, d_n=16, f_n=8):
    """Detections around a few anchors, so frames both insert and match."""
    boxes = (_ANCHORS[rng.integers(0, len(_ANCHORS), d_n)]
             + rng.uniform(-0.005, 0.005, (d_n, 4))).astype(np.float32)
    feats = rng.normal(size=(d_n, f_n)).astype(np.float32)
    valid = rng.random(d_n) < 0.4
    return (boxes, feats, valid, int(rng.integers(0, 2)), 50 * t,
            int(rng.integers(0, 4)))


def _ring_at(capacity, cursor):
    """An empty ring whose cursor starts at ``cursor``."""
    return dataclasses.replace(
        init_matcher(max_results=capacity, time_gate=300),
        cursor=jnp.int32(cursor),
    )


def test_ring_iou_is_pairwise_iou_bit_for_bit():
    rng = np.random.default_rng(0)
    a = rng.uniform(0, 1, (16, 4)).astype(np.float32)
    b = rng.uniform(0, 1, (300, 4)).astype(np.float32)
    a[:, 2:] += a[:, :2]
    b[:, 2:] = np.where(rng.random((300, 2)) < 0.5, b[:, :2] + 0.1, b[:, 2:])
    np.testing.assert_array_equal(
        np.asarray(ring_iou(jnp.asarray(a), jnp.asarray(b.T))),
        np.asarray(pairwise_iou(jnp.asarray(a), jnp.asarray(b))),
    )
    np.testing.assert_allclose(
        np.asarray(pairwise_iou(jnp.asarray(a), jnp.asarray(b))),
        _np_iou(a, b), rtol=1e-6, atol=1e-7,
    )


@pytest.mark.parametrize("capacity", [16, 256])
@pytest.mark.parametrize("seed", [0, 1])
def test_lane_dense_ring_matches_row_major_model(seed, capacity):
    """Frame by frame from a cursor near R: the same d₀, d₁, §3.4 homes and
    novelty flags, and the ring's contents are the model's rows
    transposed, through many wraps."""
    rng = np.random.default_rng(seed)
    m = _ring_at(capacity, capacity - 3)
    ring = _rows(m)
    step = jax.jit(match_and_update)
    wraps = 0
    for t in range(120):
        boxes, feats, valid, vid, fid, cid = _frame(rng, t)
        d0, d1, cross, homes, is_new = _np_match(
            ring, boxes, feats, valid, vid, fid, cid)
        r = step(m, jnp.asarray(boxes), jnp.asarray(feats), jnp.asarray(valid),
                 jnp.int32(vid), jnp.int32(fid), jnp.int32(cid))
        assert (int(r.d0), int(r.d1), int(r.cross_chunk)) == (d0, d1, cross)
        np.testing.assert_array_equal(np.asarray(r.cross_home), homes)
        np.testing.assert_array_equal(np.asarray(r.is_new), is_new)
        wraps += int(r.new_state.cursor) < int(m.cursor)
        m = r.new_state
        assert m.boxes.shape == (4, capacity) and m.feats.shape == (8, capacity)
        _assert_ring_equals(m, ring)
    assert wraps >= 1


@pytest.mark.parametrize("capacity", [16, 256])
def test_lane_dense_ring_under_vmap_matches_model_per_query(capacity):
    """Q = 3 rings with different cursors, vmapped as the multi-query fold
    runs them: each query's ring tracks its own model."""
    q_n = 3
    rng = np.random.default_rng(capacity)
    cursors = [capacity - 1, capacity - 7, 2]
    m = broadcast_leading(init_matcher(max_results=capacity, time_gate=300), q_n)
    m = dataclasses.replace(m, cursor=jnp.asarray(cursors, jnp.int32))
    assert m.boxes.shape == (q_n, 4, capacity) and m.capacity == capacity
    rings = [_rows(jax.tree.map(lambda x, q=q: x[q], m)) for q in range(q_n)]
    step = jax.jit(jax.vmap(match_and_update))
    for t in range(80):
        frames = [_frame(rng, t) for _ in range(q_n)]
        want = [_np_match(rings[q], *frames[q]) for q in range(q_n)]
        cols = list(zip(*frames))
        r = step(m, jnp.asarray(np.stack(cols[0])), jnp.asarray(np.stack(cols[1])),
                 jnp.asarray(np.stack(cols[2])), *(jnp.asarray(c, jnp.int32)
                                                   for c in cols[3:]))
        m = r.new_state
        for q in range(q_n):
            d0, d1, cross, homes, is_new = want[q]
            assert (int(r.d0[q]), int(r.d1[q]), int(r.cross_chunk[q])) == (
                d0, d1, cross)
            np.testing.assert_array_equal(np.asarray(r.cross_home[q]), homes)
            np.testing.assert_array_equal(np.asarray(r.is_new[q]), is_new)
            _assert_ring_equals(jax.tree.map(lambda x, q=q: x[q], m), rings[q])


def _advance(m, rng, frames, t0):
    step = jax.jit(match_and_update)
    for t in range(t0, t0 + frames):
        boxes, feats, valid, vid, fid, cid = _frame(rng, t)
        m = step(m, jnp.asarray(boxes), jnp.asarray(feats), jnp.asarray(valid),
                 jnp.int32(vid), jnp.int32(fid), jnp.int32(cid)).new_state
    return m


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("capacity", [16, 256])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_matches_row_major_model_over_wrapping_windows(seed, capacity,
                                                             batched):
    """Appends land at dst's cursor in src's insertion order and bumps to
    snapshot entries add, exactly as the slot-by-slot model does, with
    both the source window and the destination window wrapping."""
    rng = np.random.default_rng(seed)
    snap = _advance(_ring_at(capacity, 0), rng, 6, 0)
    snap = dataclasses.replace(snap, cursor=jnp.int32(capacity - 3))
    src, t = snap, 100
    while (int(src.cursor) - int(snap.cursor)) % capacity < 4:
        src, t = _advance(src, rng, 1, t), t + 1
    dst = _advance(snap, rng, 3, 200)
    dst = dataclasses.replace(dst, cursor=jnp.int32(capacity - 2))
    want = _np_merge(_rows(dst), _rows(src), _rows(snap))
    n_new = (want["cursor"] - int(dst.cursor)) % capacity
    assert 4 <= n_new < capacity and want["cursor"] < int(dst.cursor)
    assert int(src.cursor) < int(snap.cursor)          # both windows wrap
    if batched:
        stack = lambda *ms: jax.tree.map(lambda *x: jnp.stack(x), *ms)
        other = _ring_at(capacity, 1)
        got = jax.jit(jax.vmap(merge_matcher))(
            stack(dst, other), stack(src, other), stack(snap, other))
        _assert_ring_equals(jax.tree.map(lambda x: x[1], got), _rows(other))
        got = jax.tree.map(lambda x: x[0], got)
    else:
        got = merge_matcher(dst, src, snap)
    _assert_ring_equals(got, want)


def test_merge_window_wraps_in_both_rings():
    """Hand-built: src inserted slots 6, 7, 0 of an 8-ring since the
    snapshot, dst's cursor is 7, so they land in dst slots 7, 0, 1."""
    cap = 8
    snap = _ring_at(cap, 6)
    src = _insert_n(snap, 3)
    dst = _ring_at(cap, 7)
    merged = merge_matcher(dst, src, snap)
    np.testing.assert_array_equal(
        np.asarray(merged.frame)[[7, 0, 1]], np.asarray(src.frame)[[6, 7, 0]])
    np.testing.assert_array_equal(
        np.asarray(merged.boxes)[:, [7, 0, 1]], np.asarray(src.boxes)[:, [6, 7, 0]])
    assert int((merged.times_seen > 0).sum()) == 3 and int(merged.cursor) == 2


def test_result_log_rows_are_first_sightings():
    """Spilled entries come back as host rows boxes [k, 4], feats [k, F]:
    each the box and feature of the detection that first found it."""
    cap, f_n = 4, 8
    rng = np.random.default_rng(3)
    m = init_matcher(max_results=cap, feat_dim=f_n)
    log, first = ResultLog(), {}
    step = jax.jit(match_and_update)
    for i in range(10):
        box = np.asarray([_box(0.05 + 0.08 * i, 0.1)], np.float32)
        feat = rng.normal(size=(1, f_n)).astype(np.float32)
        first[i * 2000] = (box[0], feat[0])
        log.spill(m, eviction_mask(m, 1))
        m = step(m, jnp.asarray(box), jnp.asarray(feat), jnp.ones((1,), bool),
                 jnp.int32(0), jnp.int32(i * 2000), jnp.int32(0)).new_state
    rows = log.as_arrays()
    assert len(log) == 10 - cap
    assert rows["boxes"].shape == (len(log), 4)
    assert rows["feats"].shape == (len(log), f_n)
    for j, frame in enumerate(rows["frame"]):
        np.testing.assert_array_equal(rows["boxes"][j], first[int(frame)][0])
        np.testing.assert_array_equal(rows["feats"][j], first[int(frame)][1])
    assert sorted(rows["frame"]) == [i * 2000 for i in range(10 - cap)]
