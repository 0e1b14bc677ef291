"""Detection matcher (paper §2.3, Algorithm 1 line 12).

The matcher decides which detections are *new* results (d₀) and which are
the *second* sighting of a known result (d₁) — the only two quantities the
ExSample update consumes.  Production implementation: a fixed-capacity
result memory of (box, feature, video, frame, times_seen) entries, matched
by IoU in frame-space plus temporal gating (SORT-style) and optional
appearance-feature cosine similarity.

Everything is statically shaped so the whole match-update step jits; the
result memory is a ring buffer of capacity ``max_results``, stored with
the entry axis R last (boxes ``[4, R]``, features ``[F, R]``) so that R
lies on the TPU's 128 lanes.  Every frame reads the ring lane-dense and
writes only the lanes that change: a new result lands in its slot by a
select against a lane iota, never by a pad, a slice or a scatter along
the ring (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG = -1e9


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MatcherState:
    """Ring-buffer result memory (capacity R), entry axis last."""

    boxes: jax.Array        # f32[4, R]  — (x0, y0, x1, y1) of first sighting
    feats: jax.Array        # f32[F, R]  — appearance feature of first sighting
    video: jax.Array        # i32[R]     — video id of first sighting
    frame: jax.Array        # i32[R]     — global frame id of first sighting
    chunk: jax.Array        # i32[R]     — chunk of first sighting (§3.4)
    times_seen: jax.Array   # i32[R]     — 0 = empty slot
    cursor: jax.Array       # i32[]      — ring insert position
    total_inserted: jax.Array  # i32[]   — monotone insertion count (never wraps)
    iou_thresh: float = dataclasses.field(metadata=dict(static=True), default=0.5)
    time_gate: int = dataclasses.field(metadata=dict(static=True), default=900)
    feat_thresh: float = dataclasses.field(metadata=dict(static=True), default=-1.0)

    @property
    def capacity(self) -> int:
        return self.times_seen.shape[-1]


def init_matcher(
    *,
    max_results: int,
    feat_dim: int = 8,
    iou_thresh: float = 0.5,
    time_gate: int = 900,
    feat_thresh: float = -1.0,
) -> MatcherState:
    return MatcherState(
        boxes=jnp.zeros((4, max_results), jnp.float32),
        feats=jnp.zeros((feat_dim, max_results), jnp.float32),
        video=jnp.full((max_results,), -1, jnp.int32),
        frame=jnp.full((max_results,), -(10**9), jnp.int32),
        chunk=jnp.full((max_results,), -1, jnp.int32),
        times_seen=jnp.zeros((max_results,), jnp.int32),
        cursor=jnp.zeros((), jnp.int32),
        total_inserted=jnp.zeros((), jnp.int32),
        iou_thresh=iou_thresh,
        time_gate=time_gate,
        feat_thresh=feat_thresh,
    )


def broadcast_leading(tree, num_queries: int):
    """Leading-[Q] broadcast of every array leaf — the shared layout
    transform behind the multi-query carry (DESIGN.md §9); static/aux
    fields pass through untouched."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (num_queries,) + x.shape), tree
    )


def init_matcher_multi(num_queries: int, **kwargs) -> MatcherState:
    """Q independent result memories as ONE pytree with a leading [Q] axis
    on every array leaf — the matcher half of the multi-query carry
    (DESIGN.md §9).  Static thresholds are shared across queries."""
    return broadcast_leading(init_matcher(**kwargs), num_queries)


def pairwise_iou(a: jax.Array, b: jax.Array) -> jax.Array:
    """IoU matrix f32[D, R] for boxes a f32[D,4], b f32[R,4] (x0,y0,x1,y1)."""
    return ring_iou(a, b.T)


def ring_iou(a: jax.Array, b: jax.Array) -> jax.Array:
    """IoU matrix f32[D, R] for boxes a f32[D,4] against a coordinate-major
    ring b f32[4,R]: each coordinate is one lane-dense row of b."""
    area_a = jnp.maximum(a[:, 2] - a[:, 0], 0.0) * jnp.maximum(a[:, 3] - a[:, 1], 0.0)
    area_b = jnp.maximum(b[2] - b[0], 0.0) * jnp.maximum(b[3] - b[1], 0.0)
    w = jnp.maximum(
        jnp.minimum(a[:, 2, None], b[2]) - jnp.maximum(a[:, 0, None], b[0]), 0.0
    )
    h = jnp.maximum(
        jnp.minimum(a[:, 3, None], b[3]) - jnp.maximum(a[:, 1, None], b[1]), 0.0
    )
    inter = w * h
    union = area_a[:, None] + area_b[None, :] - inter
    return inter / jnp.maximum(union, 1e-9)


class MatchResult(NamedTuple):
    d0: jax.Array           # i32[] — detections matching nothing (new results)
    d1: jax.Array           # i32[] — results transitioning seen-once → seen-twice
    cross_chunk: jax.Array  # i32[] — of d1, how many were first seen elsewhere (§3.4)
    cross_home: jax.Array   # i32[D] — per detection, the home chunk to decrement (-1 = none)
    is_new: jax.Array       # bool[D] — per-detection novelty flag
    new_state: "MatcherState"


def match_and_update(
    state: MatcherState,
    boxes: jax.Array,       # f32[D, 4]
    feats: jax.Array,       # f32[D, F]
    valid: jax.Array,       # bool[D] — detector emits fixed D slots, some invalid
    video_id: jax.Array,    # i32[]
    frame_id: jax.Array,    # i32[]
    chunk_id: jax.Array,    # i32[]
) -> MatchResult:
    """Match one frame's detections against the result memory and update it.

    Semantics (statically shaped, single frame):
      - a detection *matches* memory entry r iff same video, |Δframe| ≤
        time_gate, IoU ≥ iou_thresh, and (optionally) feature cosine ≥
        feat_thresh.  Ties go to the highest IoU entry.
      - unmatched valid detections are new results → inserted (times_seen=1).
      - matched detections bump times_seen of their entry;  d₁ counts
        entries whose times_seen went exactly 1 → 2 this frame.
    """
    with jax.named_scope("match"):
        return _match_frame(
            state, boxes, feats, valid, video_id, frame_id, chunk_id
        )


def _match_frame(state, boxes, feats, valid, video_id, frame_id, chunk_id):
    cap = state.capacity
    occupied = state.times_seen > 0
    iou = ring_iou(boxes, state.boxes)
    same_video = state.video[None, :] == video_id
    in_gate = jnp.abs(state.frame[None, :] - frame_id) <= state.time_gate
    match_ok = iou >= state.iou_thresh
    score_val = iou
    if state.feat_thresh > -1.0:
        # appearance re-identification: long-range duplicates (an object
        # re-seen after drifting across the frame, or across chunks §3.4)
        # can't match by IoU — cosine similarity substitutes for overlap,
        # the role the paper's tracker-based matcher plays.
        an = feats / jnp.maximum(jnp.linalg.norm(feats, axis=-1, keepdims=True), 1e-9)
        bn = state.feats / jnp.maximum(
            jnp.linalg.norm(state.feats, axis=0, keepdims=True), 1e-9
        )
        sim = an @ bn
        match_ok = match_ok | (sim >= state.feat_thresh)
        score_val = jnp.maximum(iou, sim)
    eligible = occupied[None, :] & same_video & in_gate & match_ok
    scores = jnp.where(eligible, score_val, NEG)

    best = jnp.argmax(scores, axis=-1)                       # i32[D]
    has_match = jnp.take_along_axis(scores, best[:, None], axis=-1)[:, 0] > NEG / 2
    has_match = has_match & valid
    is_new = valid & ~has_match

    # --- bump times_seen for matched entries ---
    ring_lane = jnp.arange(cap, dtype=jnp.int32)
    bump = jnp.sum(
        (best[:, None] == ring_lane) & has_match[:, None], axis=0, dtype=jnp.int32
    )
    new_seen = state.times_seen + jnp.where(occupied, bump, 0)
    went_twice = occupied & (state.times_seen == 1) & (new_seen >= 2)
    d1 = jnp.sum(went_twice).astype(jnp.int32)
    # §3.4 cross-chunk: entry first seen in another chunk ⇒ its home chunk's
    # N¹ must be decremented instead of this one's.
    crossed = went_twice & (state.chunk != chunk_id)
    cross_chunk = jnp.sum(crossed).astype(jnp.int32)
    # Only an entry some detection matched can go seen-once → seen-twice, so
    # the homes travel one lane per detection (D), not per entry (R): lane d
    # carries best[d]'s home iff d is the first detection matching it.
    lane = jnp.arange(best.shape[0])
    earlier_same = (
        has_match[None, :]
        & (best[None, :] == best[:, None])
        & (lane[None, :] < lane[:, None])
    )
    first = has_match & ~jnp.any(earlier_same, axis=1)
    cross_home = jnp.where(first & crossed[best], state.chunk[best], -1)

    # --- insert new results at cursor, cursor+1, ... (ring) ---
    d0 = jnp.sum(is_new).astype(jnp.int32)
    num_new = d0
    # per-detection slot ids via exclusive cumsum over is_new; -1 = none
    order = jnp.cumsum(is_new.astype(jnp.int32)) - is_new.astype(jnp.int32)
    slot = jnp.where(is_new, (state.cursor + order) % cap, -1)
    # In place: ring lane r takes the detection whose slot is r (src[r],
    # -1 = none), else keeps its value — elementwise over the lane-dense ring.
    src = jnp.max(
        jnp.where(slot[:, None] == ring_lane, lane[:, None], -1), axis=0
    )
    written = src >= 0

    def put(mem, rows):         # mem [k, R] ← rows [D, k]
        for d in range(rows.shape[0]):
            mem = jnp.where(src == d, rows[d, :, None], mem)
        return mem

    boxes_mem = put(state.boxes, boxes)
    feats_mem = put(state.feats, feats)
    video_mem = jnp.where(written, video_id, state.video)
    frame_mem = jnp.where(written, frame_id, state.frame)
    chunk_mem = jnp.where(written, chunk_id, state.chunk)
    seen_mem = jnp.where(written, 1, new_seen)

    new_state = dataclasses.replace(
        state,
        boxes=boxes_mem,
        feats=feats_mem,
        video=video_mem,
        frame=frame_mem,
        chunk=chunk_mem,
        times_seen=seen_mem,
        cursor=(state.cursor + num_new) % cap,
        total_inserted=state.total_inserted + num_new,
    )
    return MatchResult(
        d0=d0,
        d1=d1,
        cross_chunk=cross_chunk,
        cross_home=cross_home,
        is_new=is_new,
        new_state=new_state,
    )


def _ring_window(cursor, n, cap: int) -> jax.Array:
    """bool[R] — the ring slots ``[cursor, cursor + n) mod cap``."""
    return (jnp.arange(cap, dtype=jnp.int32) - cursor) % cap < n


def num_results(state: MatcherState) -> jax.Array:
    return jnp.sum(state.times_seen > 0).astype(jnp.int32)


class MergeStats(NamedTuple):
    """Ring-pressure diagnostics of one ``merge_matcher`` application."""

    inserted: jax.Array   # i32[] — TRUE insertions src made since snap
    overflow: jax.Array   # bool[] — insertions ≥ capacity: the src ring
    #                       wrapped and silently dropped entries, so the
    #                       merge window (a mod-capacity cursor delta)
    #                       aliases and cannot recover them
    clobbered: jax.Array  # i32[] — live dst entries this merge overwrites


def merge_stats(dst: MatcherState, src: MatcherState, snap: MatcherState) -> MergeStats:
    """Ring-wrap guard (ROADMAP): ``merge_matcher`` assumes fewer insertions
    per merge than capacity; the cursor delta it appends from is taken mod
    capacity, so an overflowing worker silently loses ``capacity·k``
    entries.  The monotone ``total_inserted`` counter makes the true
    insertion count observable — callers surface it as a high-water mark
    and flag overflow instead of wrapping (see the ring-pressure
    accounting of ``repro.core.executor._search_multi_sharded_device``)."""
    cap = dst.capacity
    inserted = src.total_inserted - snap.total_inserted
    dst_slot_hit = _ring_window(dst.cursor, inserted % cap, cap)
    clobbered = jnp.sum(dst_slot_hit & (dst.times_seen > 0)).astype(jnp.int32)
    return MergeStats(
        inserted=inserted, overflow=inserted >= cap, clobbered=clobbered
    )


@jax.jit
def merge_matcher_checked(
    dst: MatcherState, src: MatcherState, snap: MatcherState
) -> tuple[MatcherState, MergeStats]:
    """``merge_matcher`` plus its ``MergeStats`` — one fused jitted call."""
    return merge_matcher(dst, src, snap), merge_stats(dst, src, snap)


def eviction_mask(dst: MatcherState, n_new) -> jax.Array:
    """bool[R] — the live ``dst`` entries that appending ``n_new`` fresh
    insertions at ``dst.cursor`` will overwrite (the ring-spill contract,
    DESIGN.md §11).

    This is the append window ``[dst.cursor, dst.cursor + n_new) mod R``
    restricted to occupied slots — exactly the entries
    ``merge_stats.clobbered`` counts.  Callers extract them to a host-side
    :class:`ResultLog` *before* the merge/replacement lands, so a fixed
    device ring supports unbounded result sets with zero loss (as long as
    a single merge window inserts fewer than ``R`` entries; beyond that
    the source ring itself wrapped and the entries are unrecoverable —
    ``MergeStats.overflow``)."""
    cap = dst.capacity
    window = _ring_window(dst.cursor, jnp.minimum(n_new, cap), cap)
    return window & (dst.times_seen > 0)


class ResultLog:
    """Append-only host-side log of results evicted from a device ring.

    The matcher ring is a *recent window*; entries pushed out by new
    insertions drain here at merge boundaries (``spill``), so the total
    distinct-result set of a long search is ``ring live entries +
    len(log)`` with nothing dropped.  Host-side numpy on purpose: spills
    happen on the driver thread between device calls, and the log never
    re-enters jit."""

    _FIELDS = ("boxes", "feats", "video", "frame", "chunk", "times_seen")

    def __init__(self):
        self._chunks: list[dict] = []
        self.count = 0

    def __len__(self) -> int:
        return self.count

    def spill(self, matcher: MatcherState, mask) -> int:
        """Append ``matcher``'s entries selected by ``mask`` (bool[R]);
        returns how many were spilled."""
        import numpy as np

        mask_np = np.asarray(mask)
        k = int(mask_np.sum())
        if k:
            # ring leaves keep the entry axis last; host rows put it first
            self._chunks.append({
                f: np.asarray(getattr(matcher, f))[..., mask_np].T
                for f in self._FIELDS
            })
            self.count += k
        return k

    def as_arrays(self) -> dict:
        """The whole log as one dict of concatenated numpy arrays."""
        import numpy as np

        if not self._chunks:
            return {
                "boxes": np.zeros((0, 4), np.float32),
                "feats": np.zeros((0, 0), np.float32),
                "video": np.zeros((0,), np.int32),
                "frame": np.zeros((0,), np.int32),
                "chunk": np.zeros((0,), np.int32),
                "times_seen": np.zeros((0,), np.int32),
            }
        return {
            f: np.concatenate([c[f] for c in self._chunks])
            for f in self._FIELDS
        }


@jax.jit
def merge_matcher(
    dst: MatcherState, src: MatcherState, snap: MatcherState
) -> MatcherState:
    """Merge a worker's matcher ``src`` into the shared ``dst``, where both
    diverged from snapshot ``snap`` (async runtime, DESIGN.md §5).

    Replacement (``dst := src``) is last-writer-wins: with overlapping
    workers it drops every entry a concurrent merge added.  Instead:

      * entries ``src`` INSERTED since the snapshot (the ring slots
        ``[snap.cursor, src.cursor)``) are appended at ``dst.cursor`` —
        no worker's insertions are ever lost;
      * ``times_seen`` bumps to pre-existing entries are merged
        *additively*, applied only where ``dst`` still holds the same
        entry as the snapshot (identified by (video, frame) of first
        sighting) — commutative, and exact in the sequential case.

    Duplicate entries across overlapping workers remain possible (two
    workers can both insert the same object); that is the documented
    at-most-once-*effect* tolerance.  Assumes fewer insertions per merge
    than ``capacity`` (cohort sizes ≪ ring capacity) — violations are
    detectable via ``merge_stats``/``merge_matcher_checked`` (overflow
    flag + high-water insertion count) rather than silently wrapping."""
    cap = dst.capacity
    n_new = (src.cursor - snap.cursor) % cap
    src_inserted = _ring_window(snap.cursor, n_new, cap)
    window = _ring_window(dst.cursor, n_new, cap)
    # dst slot dst.cursor + i takes src slot snap.cursor + i: a rotation
    shift = (snap.cursor - dst.cursor) % cap

    # --- additive seen-count bumps for entries that existed at snapshot ---
    same_as_snap = (
        (dst.video == snap.video)
        & (dst.frame == snap.frame)
        & (snap.times_seen > 0)
    )
    bump = jnp.where(
        same_as_snap & ~src_inserted, src.times_seen - snap.times_seen, 0
    )
    times = dst.times_seen + bump

    # --- append src's new entries at dst's cursor, in place ---------------
    def put(d, s):
        rotated = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([s, s], axis=-1), shift, cap, axis=-1
        )
        return jnp.where(window, rotated, d)

    return dataclasses.replace(
        dst,
        boxes=put(dst.boxes, src.boxes),
        feats=put(dst.feats, src.feats),
        video=put(dst.video, src.video),
        frame=put(dst.frame, src.frame),
        chunk=put(dst.chunk, src.chunk),
        times_seen=put(times, src.times_seen),
        cursor=(dst.cursor + n_new) % cap,
        total_inserted=dst.total_inserted
        + (src.total_inserted - snap.total_inserted),
    )
