"""Request batcher for the detector serving path.

ExSample produces cohorts of frame ids; real deployments also take ad-hoc
detection requests.  The batcher merges both into fixed-size device
batches (static shapes ⇒ one compilation), padding with sentinel frames
whose results are dropped.  It also implements the straggler policy from
DESIGN.md §5: a cohort is *never* a barrier — late frames just join a
later batch, which is sound because sampler updates commute (§3.7.1).

The device-side half of the same machinery serves the Q-axis lowerings of
``SearchPlan`` — the single-device multi-query driver (DESIGN.md §9) and,
per shard, the composed Q×shards driver (DESIGN.md §10):
``dedup_first_index`` collapses the union of several queries' cohort
frames into one detector batch without dropping any slot, and
``DetectionCache`` is a direct-mapped, device-resident cache of raw
detector output so a frame decoded+detected for one query is reused by
every later query that samples it (the Focus/EKO shared-ingest
economics).  A slot's detections are packed into one lane-dense row of
32-bit words (``RowLayout``), so the store is a 2-D array whose row
gathers and scatters never relay it.  The composed driver HASH-SHARDS
one logical cache over the mesh (DESIGN.md §14): frame ``f`` lives only
on shard ``f % S`` at local
slot ``(f // S) % (capacity // S)``, and per-round lookups/inserts route
between requester and home shard with ``all_to_all`` collectives.  With
``capacity % S == 0`` that placement is a pure transposition of the
direct-mapped slot map, so contents, evictions, and hit/miss outcomes are
bit-identical to a single direct-mapped cache of the same capacity —
``shard_cache_layout`` / ``unshard_cache_layout`` are the two sides of
that bijection, and ``sharded_cache_lookup`` / ``sharded_cache_insert``
are the per-shard halves the drivers run inside ``shard_map``.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PendingFrame:
    frame_id: int
    chunk_id: int
    cohort: int
    enqueue_round: int


@dataclasses.dataclass
class Batch:
    frame_ids: np.ndarray     # i64[B] (sentinel = -1 padding)
    chunk_ids: np.ndarray     # i64[B]
    valid: np.ndarray         # bool[B]
    cohorts: np.ndarray       # i64[B]


class RequestBatcher:
    def __init__(self, batch_size: int, *, max_wait_rounds: int = 0):
        self.batch_size = batch_size
        self.max_wait_rounds = max_wait_rounds
        self._queue: collections.deque[PendingFrame] = collections.deque()
        self._round = 0
        self.stats = {"batches": 0, "padded_slots": 0, "frames": 0}

    def submit(self, frame_ids: Iterable[int], chunk_ids: Iterable[int], cohort: int) -> None:
        for f, c in zip(frame_ids, chunk_ids):
            self._queue.append(PendingFrame(int(f), int(c), cohort, self._round))

    def ready(self) -> bool:
        if not self._queue:
            return False
        if len(self._queue) >= self.batch_size:
            return True
        oldest = self._queue[0].enqueue_round
        return (self._round - oldest) >= self.max_wait_rounds

    def next_batch(self) -> Optional[Batch]:
        """Emit up to batch_size frames, padding the remainder."""
        self._round += 1
        if not self._queue:
            return None
        take = min(self.batch_size, len(self._queue))
        items = [self._queue.popleft() for _ in range(take)]
        pad = self.batch_size - take
        self.stats["batches"] += 1
        self.stats["padded_slots"] += pad
        self.stats["frames"] += take
        return Batch(
            frame_ids=np.asarray(
                [i.frame_id for i in items] + [-1] * pad, np.int64
            ),
            chunk_ids=np.asarray(
                [i.chunk_id for i in items] + [-1] * pad, np.int64
            ),
            valid=np.asarray([True] * take + [False] * pad, bool),
            cohorts=np.asarray([i.cohort for i in items] + [-1] * pad, np.int64),
        )

    @property
    def occupancy(self) -> float:
        """Fraction of emitted device slots that carried real frames —
        defined as ``1 − padding_fraction()`` so the two ratios are
        consistent BY CONSTRUCTION, including before any batch has been
        emitted (occupancy 1.0, padding 0.0: an empty history wastes no
        slots)."""
        return 1.0 - self.padding_fraction()

    def padding_fraction(self) -> float:
        """Fraction of emitted device slots that were sentinel padding
        (0.0 before any batch has been emitted)."""
        b = self.stats["batches"]
        if not b:
            return 0.0
        return self.stats["padded_slots"] / (b * self.batch_size)


# ---------------------------------------------------------------------------
# Device-side dedup + detection cache (multi-query driver, DESIGN.md §9)
# ---------------------------------------------------------------------------


def dedup_first_index(frame_ids: jax.Array, valid: jax.Array) -> jax.Array:
    """i32[B] — for each slot, the index of the FIRST valid slot holding the
    same frame id (its dedup representative); invalid slots map to
    themselves.

    Every valid slot therefore gathers detections of exactly its own frame
    (no frame a query sampled is ever dropped), and ``first_idx[i] == i``
    marks the one representative per distinct valid frame (no frame is
    detected, or counted, twice in a batch).  O(B²) compare — B = Q·C
    cohort slots, small by construction.
    """
    with jax.named_scope("dedup_cache"):
        b = frame_ids.shape[0]
        idx = jnp.arange(b, dtype=jnp.int32)
        same = (frame_ids[:, None] == frame_ids[None, :]) & valid[None, :]
        first = jnp.min(
            jnp.where(same, idx[None, :], b), axis=1
        ).astype(jnp.int32)
        return jnp.where(valid & (first < b), first, idx)


LANES = 128   # a row of the store is a whole number of vector lanes


def _words(x, xp):
    """``x`` as int32 words, one per element, bit for bit: 32-bit leaves
    bit-cast, bools as 0/1."""
    dt = np.dtype(x.dtype)
    if dt == np.bool_:
        return x.astype(np.int32)
    if dt.itemsize != 4:
        raise TypeError(f"a cache row holds 32-bit words; {dt} does not pack")
    return _bitcast(x, np.int32, xp)


def _unwords(w, dt, xp):
    """Inverse of :func:`_words` for a leaf of dtype ``dt``."""
    return w != 0 if dt == np.bool_ else _bitcast(w, dt, xp)


def _bitcast(x, dt, xp):
    if xp is np:
        return np.ascontiguousarray(x).view(dt)
    return jax.lax.bitcast_convert_type(x, dt)


@dataclasses.dataclass(frozen=True)
class RowLayout:
    """How one slot's detections pack into a row of 32-bit words.

    Every leaf of the detector's single-frame output is flattened, taken
    word for word (32-bit leaves bit-cast, bools as 0/1), and the leaves
    are laid side by side; the row is padded to a whole number of
    128-word lanes.  The store is then a 2-D ``i32[S, width]`` array
    whose minor axis is dense, so gathering or scattering
    slots moves whole rows and never relays the store (a leaf such as
    ``f32[S, 16, 8]`` has a minor axis of 8, which the TPU pads to 128
    lanes and relays on every row gather).  The oracle's ``Detections``
    (16 × (4 + 8 + 1 + 1) words) take 224 words in a 256-word row.
    Round trips are bit-exact; the layout is hashable, so jitted programs
    take it as static structure.
    """

    treedef: Any
    shapes: tuple      # per leaf, its single-frame shape
    dtypes: tuple      # per leaf, its numpy dtype
    width: int         # words in a row, a multiple of LANES

    @classmethod
    def of(cls, det_struct: Any) -> "RowLayout":
        """Layout for a detector whose single-frame output shapes are
        ``det_struct`` (e.g. from ``jax.eval_shape(detector, key, frame)``)."""
        leaves, treedef = jax.tree.flatten(det_struct)
        shapes = tuple(tuple(s.shape) for s in leaves)
        dtypes = tuple(np.dtype(s.dtype) for s in leaves)
        for dt in dtypes:
            _words(np.zeros((), dt), np)      # refuses what cannot pack
        words = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
        return cls(treedef, shapes, dtypes, max(-(-words // LANES), 1) * LANES)

    @property
    def words(self) -> int:
        """Words of a row that hold data (the rest is padding)."""
        return sum(int(np.prod(s, dtype=np.int64)) for s in self.shapes)

    def pack(self, dets: Any):
        """Detections with any leading axes ``[...]`` → rows ``i32[..., width]``
        (numpy in, numpy out; otherwise jax)."""
        leaves = self.treedef.flatten_up_to(dets)
        xp = np if isinstance(leaves[0], np.ndarray) else jnp
        lead = tuple(leaves[0].shape[: leaves[0].ndim - len(self.shapes[0])])
        cols = [
            xp.reshape(_words(leaf, xp), lead + (-1,)) for leaf in leaves
        ]
        pad = self.width - self.words
        if pad:
            cols.append(xp.zeros(lead + (pad,), np.int32))
        return xp.concatenate(cols, axis=-1)

    def unpack(self, rows):
        """Rows ``i32[..., width]`` → detections with leading axes ``[...]``."""
        xp = np if isinstance(rows, np.ndarray) else jnp
        lead = tuple(rows.shape[:-1])
        leaves, off = [], 0
        for shape, dt in zip(self.shapes, self.dtypes):
            n = int(np.prod(shape, dtype=np.int64))
            w = rows[..., off:off + n]
            leaves.append(xp.reshape(_unwords(w, dt, xp), lead + shape))
            off += n
        return self.treedef.unflatten(leaves)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DetectionCache:
    """Direct-mapped device-resident cache of raw detector output.

    ``tag[s]`` holds the frame id cached in slot ``s`` (-1 = empty);
    ``store[s]`` is that frame's detections packed into one row of
    ``layout`` (:class:`RowLayout`).  Frames map to slots by ``frame %
    capacity``, so a capacity ≥ the repository's frame count is exact
    while smaller capacities trade memory for evictions — the production
    knob.

    ``shards`` says how the slots are ordered: 1 is the direct-mapped
    order; S > 1 is the hash-sharded order of DESIGN.md §14 (shard ``s``'s
    ``capacity / S`` slots contiguous, :func:`shard_cache_layout`), which
    a mesh keeps split over its shards.
    """

    tag: jax.Array    # i32[S] — cached frame id, -1 = empty
    store: jax.Array  # i32[S, layout.width] — packed detections
    layout: RowLayout = dataclasses.field(metadata=dict(static=True))
    shards: int = dataclasses.field(default=1, metadata=dict(static=True))

    @property
    def capacity(self) -> int:
        return self.tag.shape[0]


def empty_cache(layout: RowLayout, capacity: int, shards: int = 1) -> DetectionCache:
    """A cache of ``capacity`` empty slots (built wherever it is traced:
    inside a ``shard_map`` program it is one shard's part)."""
    return DetectionCache(
        tag=jnp.full((capacity,), -1, jnp.int32),
        store=jnp.zeros((capacity, layout.width), jnp.int32),
        layout=layout, shards=shards,
    )


def init_detection_cache(det_struct: Any, capacity: int) -> DetectionCache:
    """Empty cache for a detector whose (single-frame) output shapes are
    ``det_struct`` (e.g. from ``jax.eval_shape(detector, key, frame)``)."""
    return empty_cache(RowLayout.of(det_struct), capacity)


def cache_lookup(cache: DetectionCache, frame_ids: jax.Array):
    """(hit bool[B], detections pytree with leading [B]) for each frame.

    Sentinel/padding slots (``frame_ids < 0``) NEVER hit: a padded frame id
    of -1 maps to slot ``capacity-1`` and would compare equal to the
    empty-slot tag -1, reporting a phantom hit whose gathered "detections"
    are garbage (zeros or whatever real frame lives there).  Only the
    ``[B]`` gathered rows are unpacked."""
    with jax.named_scope("dedup_cache"):
        slot = frame_ids % cache.capacity
        hit = (frame_ids >= 0) & (jnp.asarray(cache.tag)[slot] == frame_ids)
        vals = cache.layout.unpack(jnp.asarray(cache.store)[slot])
    return hit, vals


def cache_insert(
    cache: DetectionCache, frame_ids: jax.Array, dets: Any, mask: jax.Array
) -> DetectionCache:
    """Insert ``dets`` (leading [B]) for masked frames.  When two distinct
    masked frames collide on one cache slot within a batch the first wins —
    scatter order over duplicate indices is otherwise unspecified.
    Sentinel frames (``frame_ids < 0``) never insert, whatever ``mask``
    says: a -1 padding id would otherwise tag slot ``capacity-1`` with -1
    and poison every later lookup of a real frame in that slot."""
    with jax.named_scope("dedup_cache"):
        s = cache.capacity
        slot = (frame_ids % s).astype(jnp.int32)
        valid = mask & (frame_ids >= 0)
        first = dedup_first_index(slot, valid)
        keep = valid & (first == jnp.arange(slot.shape[0], dtype=jnp.int32))
        tgt = jnp.where(keep, slot, s)
        tag = jnp.asarray(cache.tag).at[tgt].set(frame_ids, mode="drop")
        store = jnp.asarray(cache.store).at[tgt].set(
            cache.layout.pack(dets), mode="drop"
        )
    return dataclasses.replace(cache, tag=tag, store=store)


# ---------------------------------------------------------------------------
# Hash-sharded cache: one logical copy across the mesh (DESIGN.md §14)
# ---------------------------------------------------------------------------
#
# Placement: with total capacity S·L (L = capacity // num_shards), frame f
# lives on home shard ``f % S`` at local slot ``(f // S) % L``.  Writing
# r = f % (S·L) for the direct-mapped slot, the home is ``r % S`` and the
# local slot is ``r // S`` — i.e. the sharded layout is EXACTLY the
# direct-mapped slot array reshaped [L, S] and transposed to [S, L].  Two
# frames collide under the sharded placement iff f1 ≡ f2 (mod S·L), the
# same collision classes as the direct-mapped cache, so per-slot contents,
# evictions, and hit/miss outcomes are bit-identical at equal capacity —
# only WHERE each slot physically lives changes.  A mesh builds, keeps and
# returns the cache in this order; only consumers that need the
# direct-mapped view (the index publish, an elastic reshard) convert it,
# on the host (:func:`host_direct_mapped`).


def _cache_local_cap(capacity: int, num_shards: int) -> int:
    if capacity % num_shards:
        raise ValueError(
            f"hash-sharded cache capacity {capacity} must be a multiple of "
            f"{num_shards} shards — pad the capacity before init/warm "
            "(a non-divisible capacity would silently mis-place frames)"
        )
    return capacity // num_shards


def _transpose_slots(cache: DetectionCache, rows: int, cols: int, shards: int):
    """Slot array viewed ``[rows, cols]``, transposed (numpy or jax alike)."""
    cap = cache.capacity
    perm = lambda x: (
        x.reshape((rows, cols) + x.shape[1:])
        .swapaxes(0, 1)
        .reshape((cap,) + x.shape[1:])
    )
    return dataclasses.replace(
        cache, tag=perm(cache.tag), store=perm(cache.store), shards=shards
    )


def shard_cache_layout(cache: DetectionCache, num_shards: int) -> DetectionCache:
    """Permute a direct-mapped cache into the hash-sharded global layout:
    index ``s·L + j`` of the result holds direct-mapped slot ``j·S + s``,
    so sharding the leading axis over the mesh hands shard ``s`` exactly
    its home entries (frames with ``f % S == s``) at local slot
    ``(f // S) % L``.  A pure transposition — bit-exact inverse of
    :func:`unshard_cache_layout`.  Host (numpy) caches stay on the host."""
    if cache.shards != 1:
        raise ValueError(
            f"cache is already in a {cache.shards}-shard layout")
    local = _cache_local_cap(cache.capacity, num_shards)
    return _transpose_slots(cache, local, num_shards, num_shards)


def unshard_cache_layout(cache: DetectionCache) -> DetectionCache:
    """Inverse of :func:`shard_cache_layout`: back to the direct-mapped
    layout every host-side consumer (``cache_lookup``, index publish,
    parity tests) understands."""
    s = cache.shards
    local = _cache_local_cap(cache.capacity, s)
    return _transpose_slots(cache, s, local, 1)


def host_direct_mapped(cache: DetectionCache) -> DetectionCache:
    """``cache`` on the host (numpy leaves) in the direct-mapped layout:
    one device→host copy, and the permutation done there, so a cache too
    large for one chip is never gathered onto one."""
    host = jax.device_get(cache)
    return unshard_cache_layout(host) if host.shards > 1 else host


def reshard_cache_host(cache: DetectionCache, new_capacity: int) -> DetectionCache:
    """Re-place a cache into a NEW capacity (host-side, numpy out):
    occupied entries re-map to ``frame % new_capacity`` in ascending
    frame-id order, first occupant wins — the same deterministic fill
    convention as ``RepositoryIndex.warm``, so an elastic mesh shrink
    that changes the divisibility-padded capacity replays identically on
    every survivor.  Returns the direct-mapped host copy when the capacity
    already matches."""
    cache = host_direct_mapped(cache)
    if new_capacity == cache.capacity:
        return cache
    if new_capacity < 1:
        raise ValueError(f"new_capacity must be >= 1, got {new_capacity}")
    tag_h = np.asarray(cache.tag)
    new_tag = np.full((new_capacity,), -1, np.int32)
    new_store = np.zeros((new_capacity,) + cache.store.shape[1:], np.int32)
    occupied = np.flatnonzero(tag_h >= 0)
    for src in occupied[np.argsort(tag_h[occupied], kind="stable")]:
        f = int(tag_h[src])
        slot = f % new_capacity
        if new_tag[slot] != -1:
            continue
        new_tag[slot] = f
        new_store[slot] = cache.store[src]
    return dataclasses.replace(cache, tag=new_tag, store=new_store)


def sharded_cache_lookup(
    cache_local: DetectionCache,
    frame_ids: jax.Array,
    shard_id: jax.Array,
    num_shards: int,
):
    """Home-shard half of the routed lookup, run per shard inside
    ``shard_map``: serve exactly the probes homed here (``frame % S ==
    shard_id``); everything else — sentinels included — reports a miss
    with unread gathered rows.  ``frame_ids`` may be any shape ``[...]``;
    returns ``(hit bool[...], rows i32[..., width])`` — rows still packed,
    so routing them takes one collective, and the requester unpacks."""
    with jax.named_scope("dedup_cache"):
        local = cache_local.capacity
        mine = (frame_ids >= 0) & (frame_ids % num_shards == shard_id)
        slot = (frame_ids // num_shards) % local
        hit = mine & (cache_local.tag[slot] == frame_ids)
        rows = cache_local.store[slot]
    return hit, rows


def sharded_cache_insert(
    cache_local: DetectionCache,
    frame_ids: jax.Array,
    rows: jax.Array,
    mask: jax.Array,
    shard_id: jax.Array,
    num_shards: int,
) -> DetectionCache:
    """Home-shard half of the routed insert (flat [B] batch of packed
    ``rows``, already routed here): store masked frames homed on this
    shard at their local slots, first-write-wins on within-batch slot
    collisions in batch order — the same winner the direct-mapped
    :func:`cache_insert` picks over the equivalent global batch."""
    with jax.named_scope("dedup_cache"):
        local = cache_local.capacity
        valid = (
            mask & (frame_ids >= 0) & (frame_ids % num_shards == shard_id)
        )
        slot = ((frame_ids // num_shards) % local).astype(jnp.int32)
        first = dedup_first_index(slot, valid)
        keep = valid & (first == jnp.arange(slot.shape[0], dtype=jnp.int32))
        tgt = jnp.where(keep, slot, local)
        tag = cache_local.tag.at[tgt].set(frame_ids, mode="drop")
        store = cache_local.store.at[tgt].set(rows, mode="drop")
    return dataclasses.replace(cache_local, tag=tag, store=store)
