"""ExSample Algorithm 1 — single-step, batched-cohort and scanned drivers.

The loop is expressed as a pure step function over an ``ExSampleCarry``
pytree so it can be (a) jitted and scanned for simulation-scale benchmarks,
(b) driven frame-by-frame from the host around a real serving stack, and
(c) sharded (see ``repro.core.distributed``).

Three driver implementations share the step/process machinery (DESIGN.md
§7, §9): ``_host_search`` is the host reference loop (one dispatch + one
sync per step), ``_scan_search`` is the device-resident
``lax.while_loop`` production driver — identical (step, results)
trajectory, one host sync total — and ``_multi_search`` advances Q
concurrent queries (leading-[Q] carry) sharing one deduplicated + cached
detector pass per round (DESIGN.md §9).  The mesh loop
(``repro.core.executor.run_search_multi_sharded``, DESIGN.md §8/§10) and
the async slot runtime (``repro.core.runtime``, DESIGN.md §11) reuse the
Q-axis round.  The ONE public entry point over all of them is
``repro.core.plan.SearchPlan`` (DESIGN.md §10); the legacy
``run_search*`` functions at the bottom of this module are deprecated
shims over the equivalent plans.

Detector plug-in protocol:  ``detector(key, frame_id) -> Detections``
(see ``repro.sim.oracle.Detections``).  The oracle/noisy/neural detectors
all satisfy it.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import TYPE_CHECKING, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import thompson
from repro.core.chunks import ChunkIndex, randomplus_frame
from repro.core.matcher import MatcherState, match_and_update
from repro.core.state import (
    SamplerState,
    apply_cross_chunk_decrement,
    apply_update,
)

if TYPE_CHECKING:  # avoid core ↔ sim import cycle; Detections is a pytree
    from repro.sim.oracle import Detections

DetectorFn = Callable[[jax.Array, jax.Array], "Detections"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ExSampleCarry:
    sampler: SamplerState
    matcher: MatcherState
    key: jax.Array
    step: jax.Array            # i32[] — total frames processed
    results: jax.Array         # i32[] — distinct results found so far


def init_carry(
    sampler: SamplerState, matcher: MatcherState, key: jax.Array
) -> ExSampleCarry:
    return ExSampleCarry(
        sampler=sampler,
        matcher=matcher,
        key=key,
        step=jnp.zeros((), jnp.int32),
        results=jnp.zeros((), jnp.int32),
    )


def _process_frame(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    detector: DetectorFn,
    chunk_id: jax.Array,
    det_key: jax.Array,
) -> ExSampleCarry:
    """Algorithm 1 lines 9-16 for one frame of ``chunk_id``."""
    # line 9: within-chunk random+ sample; the per-chunk counter n doubles
    # as the low-discrepancy rank so no extra state is needed.
    with jax.named_scope("choose"):
        rank = carry.sampler.n[chunk_id].astype(jnp.int32)
        frame_id = randomplus_frame(chunks, chunk_id, rank)
    video_id = chunks.video_id[chunk_id]

    # lines 10-11: io + decode + detect (the expensive part)
    with jax.named_scope("detect"):
        dets = detector(det_key, frame_id)

    # line 12: matcher
    m = match_and_update(
        carry.matcher,
        dets.boxes,
        dets.feats,
        dets.valid,
        video_id,
        frame_id,
        chunk_id,
    )

    # lines 13-14: state update.  §3.4: matches whose first sighting lives in
    # a different chunk decrement *that* chunk's N¹, not this one's.
    d1_local = m.d1 - m.cross_chunk
    sampler = apply_update(carry.sampler, chunk_id, m.d0, d1_local)
    sampler = apply_cross_chunk_decrement(sampler, m.cross_home)
    return dataclasses.replace(
        carry,
        sampler=sampler,
        matcher=m.new_state,
        step=carry.step + 1,
        results=carry.results + m.d0,
    )


@partial(jax.jit, static_argnames=("detector", "method"))
def exsample_step(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    method: str = "exact",
) -> ExSampleCarry:
    """One full iteration of Algorithm 1 (choose → process → update)."""
    key, k_choice, k_det = jax.random.split(carry.key, 3)
    carry = dataclasses.replace(carry, key=key)
    chunk_id = thompson.choose_chunks(
        k_choice, carry.sampler, cohorts=1, method=method
    )[0]
    return _process_frame(carry, chunks, detector, chunk_id, k_det)


@partial(jax.jit, static_argnames=("detector", "cohorts", "method"))
def exsample_batch_step(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    cohorts: int,
    method: str = "exact",
) -> ExSampleCarry:
    """§3.7.1 batched execution: B Thompson cohorts pick B frames which are
    processed as one device batch; statistics update once at the end
    (additive, order-independent).

    The matcher update is inherently sequential in its ring buffer, so the
    B frames' detections are folded with ``lax.fori_loop`` — the expensive
    detector work is still batched, matching the paper's GPU batching story.
    """
    key, k_choice, k_det = jax.random.split(carry.key, 3)
    carry = dataclasses.replace(carry, key=key)
    chunk_ids = thompson.choose_chunks(
        k_choice, carry.sampler, cohorts=cohorts, method=method
    )
    det_keys = jax.random.split(k_det, cohorts)

    def body(i, c):
        return _process_frame(c, chunks, detector, chunk_ids[i], det_keys[i])

    return jax.lax.fori_loop(0, cohorts, body, carry)


def _host_search(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    result_limit: int,
    max_steps: int,
    cohorts: int = 1,
    method: str = "exact",
    trace_every: int = 0,
):
    """Host driver: iterate until ``result_limit`` distinct results,
    ``max_steps`` frames, or repository exhaustion.  Returns
    (final_carry, trace) where trace is a list of (frames_processed,
    results) checkpoints for recall curves.

    One jitted step is dispatched per iteration and ``carry.results`` is
    synced to the host every step, so framework overhead dominates at
    simulation scale — kept as the reference/debugging driver; use
    ``run_search_scan`` (DESIGN.md §7) when throughput matters.

    Checkpoints fire on *boundary crossings* of ``trace_every`` (the step
    counter advances by ``cohorts`` per iteration, so ``step %
    trace_every == 0`` could silently skip every boundary).
    """
    trace = []
    step_fn = (
        partial(exsample_step, detector=detector, method=method)
        if cohorts == 1
        else partial(
            exsample_batch_step, detector=detector, cohorts=cohorts, method=method
        )
    )
    while (
        int(carry.results) < result_limit
        and int(carry.step) < max_steps
        and not bool(jnp.all(carry.sampler.exhausted()))
    ):
        prev_step = int(carry.step)
        carry = step_fn(carry, chunks)
        if trace_every and (int(carry.step) // trace_every) > (prev_step // trace_every):
            trace.append((int(carry.step), int(carry.results)))
    trace.append((int(carry.step), int(carry.results)))
    return carry, trace


@partial(
    jax.jit,
    static_argnames=("detector", "cohorts", "method", "max_steps", "trace_every"),
)
def _search_scan_device(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    result_limit: jax.Array,
    *,
    detector: DetectorFn,
    cohorts: int,
    method: str,
    max_steps: int,
    trace_every: int,
):
    """Device-resident search loop (DESIGN.md §7).

    The whole choose→process→update iteration runs under one
    ``lax.while_loop`` so no per-step host round-trip or dispatch happens.
    Early exit mirrors ``run_search`` exactly: stop when ``results ≥
    result_limit`` OR ``step ≥ max_steps`` OR every chunk is exhausted,
    checked *before* each (cohort) step.  Recall-curve checkpoints are
    scattered into a preallocated i32[cap, 2] buffer on boundary
    crossings of ``trace_every``; the host syncs the buffer once at the
    end.
    """
    # worst case one crossing per trace_every frames, final step may
    # overshoot max_steps by cohorts-1, plus the unconditional final entry
    cap = (max_steps + cohorts - 1) // trace_every + 1 if trace_every else 1
    buf0 = jnp.zeros((cap, 2), jnp.int32)
    n0 = jnp.zeros((), jnp.int32)

    if cohorts == 1:
        step_fn = partial(exsample_step, detector=detector, method=method)
    else:
        step_fn = partial(
            exsample_batch_step, detector=detector, cohorts=cohorts, method=method
        )

    def cond(state):
        c, _, _ = state
        return (
            (c.results < result_limit)
            & (c.step < max_steps)
            & ~jnp.all(c.sampler.exhausted())
        )

    def body(state):
        c, buf, n = state
        c2 = step_fn(c, chunks)
        if trace_every:
            crossed = (c2.step // trace_every) > (c.step // trace_every)
            entry = jnp.stack([c2.step, c2.results])
            buf = buf.at[jnp.where(crossed, n, cap)].set(entry, mode="drop")
            n = n + crossed.astype(jnp.int32)
        return c2, buf, n

    carry, buf, n = jax.lax.while_loop(cond, body, (carry, buf0, n0))
    # unconditional final checkpoint, as in run_search
    final = jnp.stack([carry.step, carry.results])
    buf = buf.at[jnp.minimum(n, cap - 1)].set(final, mode="drop")
    n = jnp.minimum(n + 1, cap)
    return carry, buf, n


def _scan_search(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    result_limit: int,
    max_steps: int,
    cohorts: int = 1,
    method: str = "exact",
    trace_every: int = 0,
):
    """Device-resident drop-in for the host driver — same signature, same
    (step, results) trajectory for the same PRNG key, one host sync total.

    ``max_steps``/``cohorts``/``trace_every`` are compile-time constants
    (they size the trace buffer and the cohort batch); ``result_limit``
    stays dynamic so sweeping recall targets reuses one executable.
    """
    with jax.profiler.TraceAnnotation("exsample.dispatch"):
        carry, buf, n = _search_scan_device(
            carry,
            chunks,
            jnp.asarray(result_limit, jnp.int32),
            detector=detector,
            cohorts=cohorts,
            method=method,
            max_steps=max_steps,
            trace_every=trace_every,
        )
    with jax.profiler.TraceAnnotation("exsample.readback"):
        buf_host = np.asarray(buf)  # the single device→host sync
        trace = [(int(s), int(r)) for s, r in buf_host[: int(n)]]
    return carry, trace


# ---------------------------------------------------------------------------
# Multi-query batched driver (§3.7.1 amortized across queries, DESIGN.md §9)
# ---------------------------------------------------------------------------

# per-query detection predicate: (query index i32[], single-frame Detections)
# -> bool[D] keep-mask, applied on top of the detector's own validity
SelectFn = Callable[[jax.Array, "Detections"], jax.Array]


def stack_carries(carries) -> ExSampleCarry:
    """Stack Q independent ``ExSampleCarry`` trees into one multi-query
    carry with a leading [Q] axis on every leaf (static fields must agree)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *carries)


def init_carry_multi(
    sampler: SamplerState, matcher: MatcherState, keys: jax.Array
) -> ExSampleCarry:
    """Fresh Q-query carry: ``keys`` is a [Q]-leading PRNG key array; the
    (single-query) sampler and matcher are broadcast to every query
    (``matcher.broadcast_leading``, same layout as ``init_matcher_multi``)."""
    from repro.core.matcher import broadcast_leading

    q = keys.shape[0]
    return ExSampleCarry(
        sampler=broadcast_leading(sampler, q),
        matcher=broadcast_leading(matcher, q),
        key=keys,
        step=jnp.zeros((q,), jnp.int32),
        results=jnp.zeros((q,), jnp.int32),
    )


class RoundChoice(NamedTuple):
    """The choose half of one multi-query round (DESIGN.md §9/§11): every
    per-query decision that depends only on round-start state.  Precomputing
    it is what lets the async slot scheduler issue a *cohort slot* — chunk
    winners, rank base, key split — and hand the expensive process half to
    a worker while the driver state stays authoritative."""

    key_next: jax.Array    # key[Q] — per-query key after this round
    chunk_ids: jax.Array   # i32[Q, C] — Thompson winners
    ranks: jax.Array       # i32[Q, C] — random+ rank (n0 + within-round occ)
    frame_ids: jax.Array   # i32[Q, C] — sampled frames
    det_keys: jax.Array    # key[Q, C] — per-slot detector keys


class RoundAux(NamedTuple):
    """Process-half byproducts the resident loop discards but the async
    merge needs: the flat frame batch, which slots were freshly detected
    (``need`` — unique, uncached, live representatives) and the raw
    detector outputs, so fresh detections can be published into the shared
    :class:`~repro.serve.batcher.DetectionCache` at the merge boundary."""

    flat_frames: jax.Array   # i32[Q*C]
    need: jax.Array          # bool[Q*C]
    fresh: "Detections"      # detector output on ``need`` lanes, 0 elsewhere
    rep_hit: jax.Array       # bool[Q*C] — representatives served by the cache
    lanes: jax.Array         # i32[] — lanes the detector evaluated


def detector_chunk(b: int) -> int:
    """Lanes per detector trip for a batch of ``b`` lanes: about b/8, a
    multiple of 8 sublanes, ``b`` itself below 8."""
    return min(b, 8 * -(-b // 64))


def detect_needed(
    detector: DetectorFn,
    keys: jax.Array,         # key[B] — each lane's own detector key
    frames: jax.Array,       # i32[B]
    need: jax.Array,         # bool[B] — lanes whose detections are consumed
):
    """Run ``detector`` on the ``need`` lanes only (DESIGN.md §9).

    The needed lanes, in lane order, are compacted into chunks of
    ``detector_chunk(B)`` lanes; a ``while_loop`` runs one vmapped detector
    call a chunk, ``ceil(count / s)`` trips, and the outputs scatter back
    to lane order.  Each needed lane keeps its own key, so its detections
    are the full batch's bit for bit; every other lane reads 0.  Returns
    ``(fresh, lanes)``: detections with a leading ``[B]`` and the lanes
    the detector evaluated, ``trips · s``."""
    b = frames.shape[0]
    s = detector_chunk(b)
    nb = -(-b // s) * s
    with jax.named_scope("detect"):
        pos = jnp.cumsum(need.astype(jnp.int32)) - 1   # rank among needed
        trips = (pos[-1] + s) // s
        src = jnp.zeros((nb,), jnp.int32).at[jnp.where(need, pos, nb)].set(
            jnp.arange(b, dtype=jnp.int32), mode="drop"
        )
        det = jax.vmap(detector)
        struct = jax.eval_shape(det, keys[:s], frames[:s])
        buf = jax.tree.map(
            lambda x: jnp.zeros((nb,) + x.shape[1:], x.dtype), struct
        )

        def trip(i, buf):
            lanes = jax.lax.dynamic_slice_in_dim(src, i * s, s)
            out = det(keys[lanes], frames[lanes])
            return jax.tree.map(
                lambda bx, ox: jax.lax.dynamic_update_slice_in_dim(
                    bx, ox, i * s, 0
                ),
                buf, out,
            )

        buf = jax.lax.fori_loop(0, trips, trip, buf)
        at = jnp.maximum(pos, 0)
        fresh = jax.tree.map(
            lambda x: jnp.where(
                need.reshape((b,) + (1,) * (x.ndim - 1)), x[at],
                jnp.zeros((), x.dtype),
            ),
            buf,
        )
    return fresh, trips * s


def multi_round_choose(
    mc: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    cohorts: int,
    method: str,
) -> RoundChoice:
    """Choose phase of one multi-query round: split every query's key,
    draw ``cohorts`` Thompson winners per query from round-start
    statistics, advance within-round random+ ranks (``occ``) and derive
    the per-slot detector keys.  Pure function of the carry — bit-for-bit
    the choice ``_multi_round`` used to compute inline."""
    c = cohorts
    with jax.named_scope("choose"):
        keys = jax.vmap(lambda k: jax.random.split(k, 3))(mc.key)
        key_next, k_choice, k_det = keys[:, 0], keys[:, 1], keys[:, 2]

        chunk_ids = thompson.choose_chunks_batched(
            k_choice, mc.sampler, cohorts=c, method=method
        )                                                    # i32[Q, C]
        # within-round rank advance: cohort j of query q reads n AFTER its
        # own earlier same-chunk picks incremented it (exsample_batch_step's
        # sequential _process_frame order), so occ is the per-query count
        # of earlier cohorts that picked the same chunk
        eq = chunk_ids[:, :, None] == chunk_ids[:, None, :]  # [Q, C, C]
        occ = jnp.sum(jnp.tril(eq, -1), axis=-1)             # [Q, C]
        n0 = jnp.take_along_axis(mc.sampler.n, chunk_ids, axis=-1)
        ranks = (n0 + occ.astype(n0.dtype)).astype(jnp.int32)
        frame_ids = randomplus_frame(chunks, chunk_ids, ranks)  # i32[Q, C]

        if c == 1:
            det_keys = k_det[:, None]    # exsample_step uses k_det unsplit
        else:
            det_keys = jax.vmap(lambda k: jax.random.split(k, c))(k_det)
    return RoundChoice(
        key_next=key_next, chunk_ids=chunk_ids, ranks=ranks,
        frame_ids=frame_ids, det_keys=det_keys,
    )


def multi_round_process(
    mc: ExSampleCarry,
    cache,
    chunks: ChunkIndex,
    active: jax.Array,       # bool[Q] — round-start liveness per query
    choice: RoundChoice,
    *,
    detector: DetectorFn,
    select: SelectFn | None,
    query_ids: jax.Array | None = None,   # i32[Q] — global query indices
):
    """Process phase of one multi-query round: dedup the union of the Q·C
    chosen frames, resolve them through the shared ``DetectionCache``, run
    the detector on the unique, uncached, live frames only
    (:func:`detect_needed`) and fold each query's slots sequentially into
    its own matcher/sampler.  ``query_ids`` carries the GLOBAL query index of
    each carry row into ``select`` (the async scheduler processes gathered
    row subsets, whose positional index is not the query id; the resident
    loop passes ``arange(Q)`` implicitly).

    Returns ``(mc', cache', fresh_calls, cache_hits, aux)`` — see
    :class:`RoundAux`."""
    from repro.serve.batcher import cache_insert, cache_lookup, dedup_first_index

    q_n = mc.key.shape[0]
    c = choice.chunk_ids.shape[1]
    b = q_n * c
    if query_ids is None:
        query_ids = jnp.arange(q_n, dtype=jnp.int32)
    key_next = choice.key_next
    chunk_ids, frame_ids, det_keys = (
        choice.chunk_ids, choice.frame_ids, choice.det_keys
    )
    det_keys_flat = det_keys.reshape((b,) + det_keys.shape[2:])
    flat_frames = frame_ids.reshape(b)
    flat_valid = jnp.repeat(active, c)

    # ---- cross-query dedup + cache: one detector batch for the union ----
    first_idx = dedup_first_index(flat_frames, flat_valid)
    is_rep = (first_idx == jnp.arange(b, dtype=jnp.int32)) & flat_valid
    with jax.named_scope("dedup_cache"):
        if cache is not None:
            hit, cached = cache_lookup(cache, flat_frames)
        else:
            hit = jnp.zeros((b,), bool)
        need = is_rep & ~hit
    fresh, lanes = detect_needed(detector, det_keys_flat, flat_frames, need)
    with jax.named_scope("dedup_cache"):
        if cache is not None:
            expand = lambda m, x: m.reshape(m.shape + (1,) * (x.ndim - 1))
            resolved = jax.tree.map(
                lambda cv, fv: jnp.where(expand(hit, fv), cv, fv),
                cached, fresh,
            )
            cache = cache_insert(cache, flat_frames, fresh, need)
        else:
            resolved = fresh
        # scatter-back: every slot gathers its representative's detections,
        # so each query consumes detections of exactly the frame it sampled
        dets_flat = jax.tree.map(lambda x: x[first_idx], resolved)
    fresh_calls = jnp.sum(need).astype(jnp.int32)
    cache_hits = jnp.sum(is_rep & hit).astype(jnp.int32)

    # ---- per-query sequential matcher/sampler fold over own slots only ----
    dets_q = jax.tree.map(
        lambda x: x.reshape((q_n, c) + x.shape[1:]), dets_flat
    )

    def fold_query(qi, sampler, matcher, results, dets_c, cids, fids, act):
        def bodyj(j, st):
            sampler, matcher, results = st
            d = jax.tree.map(lambda x: x[j], dets_c)
            valid = d.valid & act
            if select is not None:
                valid = valid & select(qi, d)
            mres = match_and_update(
                matcher, d.boxes, d.feats, valid,
                chunks.video_id[cids[j]], fids[j], cids[j],
            )
            d1_local = mres.d1 - mres.cross_chunk
            sampler = apply_update(
                sampler, cids[j], mres.d0, d1_local,
                samples=act.astype(sampler.n.dtype),
            )
            sampler = apply_cross_chunk_decrement(sampler, mres.cross_home)
            return sampler, mres.new_state, results + mres.d0

        return jax.lax.fori_loop(0, c, bodyj, (sampler, matcher, results))

    sampler, matcher, results = jax.vmap(fold_query)(
        query_ids, mc.sampler, mc.matcher, mc.results,
        dets_q, chunk_ids, frame_ids, active,
    )
    mc = ExSampleCarry(
        sampler=sampler,
        matcher=matcher,
        # finished queries keep their key frozen so their final carry is
        # bit-identical to their own solo run
        key=jnp.where(active[:, None], key_next, mc.key),
        step=mc.step + c * active.astype(jnp.int32),
        results=results,
    )
    aux = RoundAux(
        flat_frames=flat_frames, need=need, fresh=fresh,
        rep_hit=is_rep & hit, lanes=lanes,
    )
    return mc, cache, fresh_calls, cache_hits, aux


def _multi_round(
    mc: ExSampleCarry,
    cache,
    chunks: ChunkIndex,
    active: jax.Array,       # bool[Q] — round-start liveness per query
    *,
    detector: DetectorFn,
    select: SelectFn | None,
    cohorts: int,
    method: str,
):
    """One synchronized multi-query round (DESIGN.md §9).

    Every active query draws ``cohorts`` Thompson picks from ITS OWN
    statistics (one batched ``choose_chunks_batched`` call), the union of
    the Q·C sampled frames is deduplicated — and filtered through the
    shared ``DetectionCache`` when enabled — into one detector pass, and
    the detections scatter back so each query matches/updates against
    exactly its own cohort's slots.  Per query the fold replicates
    ``exsample_batch_step`` bit-for-bit: chunk choice from round-start
    statistics, within-round random+ ranks advancing sequentially
    (``occ``), matcher folded frame-by-frame, additive sampler deltas.

    Finished queries stay shape-stable: their slots are excluded from the
    dedup (never detected on their behalf), their detections are masked
    invalid, their sampler/step/key updates are gated to zero.

    The round is the composition of :func:`multi_round_choose` and
    :func:`multi_round_process` — the same two halves the async slot
    scheduler (DESIGN.md §11) runs at issue / process time, so the
    resident loop and the async workers share one round body.

    Returns ``(mc', cache', fresh_detections i32[], cache_hits i32[],
    aux)`` — ``fresh_detections`` counts the frames sent through the
    detector this round (unique, uncached, live).  Only those run: the
    needed lanes are compacted into chunks of ``detector_chunk(Q·C)``
    lanes (about Q·C/8, a multiple of 8), so the detector evaluates
    ``ceil(fresh / s) · s`` lanes, ``aux.lanes``, and none in a round the
    cache serves whole.  ``aux`` is the round's :class:`RoundAux` (the
    resident loop also uses it to attribute cache hits to a warm
    repository-index preload, DESIGN.md §13).
    """
    choice = multi_round_choose(mc, chunks, cohorts=cohorts, method=method)
    mc, cache, fresh_calls, cache_hits, aux = multi_round_process(
        mc, cache, chunks, active, choice, detector=detector, select=select,
    )
    return mc, cache, fresh_calls, cache_hits, aux


@partial(
    jax.jit,
    static_argnames=(
        "detector", "select", "cohorts", "method", "max_steps", "trace_every",
        "empty",
    ),
)
def _search_multi_device(
    mc: ExSampleCarry,
    chunks: ChunkIndex,
    result_limits: jax.Array,    # i32[Q]
    cache,
    warm_tag,                    # i32[S] index-preload tag snapshot, or None
    *,
    detector: DetectorFn,
    select: SelectFn | None,
    cohorts: int,
    method: str,
    max_steps: int,
    trace_every: int,
    empty=None,
):
    """Device-resident multi-query loop: runs rounds until EVERY query is
    finished; per query the continue / trace semantics mirror
    ``_search_scan_device`` exactly (same cap formula, boundary-crossing
    checkpoints, unconditional final entry).  With no ``cache`` and
    ``empty = (layout, slots)`` the program builds its empty cache
    itself, so the device holds one copy of it, not an input and a loop
    copy.

    ``warm_tag`` is a snapshot of the cache tag as the repository index
    preloaded it (DESIGN.md §13): a cache hit whose slot still tags the
    preloaded frame is an INDEX hit (a detector call a past search paid
    for), counted separately from within-run reuse.  Eviction-correct by
    construction — an evicted preload cannot hit at all, and a colliding
    run-inserted frame fails the ``warm_tag`` compare."""
    q_n = mc.step.shape[0]
    cap = (max_steps + cohorts - 1) // trace_every + 1 if trace_every else 1
    buf0 = jnp.zeros((q_n, cap, 2), jnp.int32)
    if cache is None and empty is not None:
        from repro.serve.batcher import empty_cache

        with jax.named_scope("cache_init"):
            cache = empty_cache(*empty)
    n0 = jnp.zeros((q_n,), jnp.int32)
    z32 = jnp.zeros((), jnp.int32)

    def live_mask(c):
        return (
            (c.results < result_limits)
            & (c.step < max_steps)
            & ~jnp.all(c.sampler.exhausted(), axis=-1)
        )

    def cond(state):
        return jnp.any(live_mask(state[0]))

    def body(state):
        c, cache, buf, n, calls, hits, ihits, lanes, rounds = state
        active = live_mask(c)
        c2, cache, fresh, hit, aux = _multi_round(
            c, cache, chunks, active,
            detector=detector, select=select, cohorts=cohorts, method=method,
        )
        if warm_tag is not None:
            wslot = aux.flat_frames % warm_tag.shape[0]
            whit = aux.rep_hit & (warm_tag[wslot] == aux.flat_frames)
            ihits = ihits + jnp.sum(whit).astype(jnp.int32)
        if trace_every:
            crossed = (c2.step // trace_every) > (c.step // trace_every)
            entry = jnp.stack([c2.step, c2.results], axis=-1)   # [Q, 2]
            idx = jnp.where(crossed, n, cap)
            buf = jax.vmap(lambda bq, i, e: bq.at[i].set(e, mode="drop"))(
                buf, idx, entry
            )
            n = n + crossed.astype(jnp.int32)
        return (c2, cache, buf, n, calls + fresh, hits + hit, ihits,
                lanes + aux.lanes, rounds + 1)

    c, cache, buf, n, calls, hits, ihits, lanes, rounds = jax.lax.while_loop(
        cond, body, (mc, cache, buf0, n0, z32, z32, z32, z32, z32)
    )
    final = jnp.stack([c.step, c.results], axis=-1)
    buf = jax.vmap(lambda bq, i, e: bq.at[i].set(e, mode="drop"))(
        buf, jnp.minimum(n, cap - 1), final
    )
    n = jnp.minimum(n + 1, cap)
    return c, cache, buf, n, calls, hits, ihits, lanes, rounds


def _multi_search(
    carries: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    result_limits,
    max_steps: int,
    cohorts: int = 1,
    method: str = "exact",
    trace_every: int = 0,
    select: SelectFn | None = None,
    cache_frames: int = 0,
    cache=None,
    warm_tag=None,
):
    """Q concurrent queries over one repository, one decode/detect pass per
    round (DESIGN.md §9).

    ``carries`` is a stacked ``ExSampleCarry`` (leading [Q] axis on every
    leaf — ``init_carry_multi`` / ``stack_carries``); each query owns its
    sampler statistics, matcher memory, PRNG key, result counter and
    ``result_limits[q]``.  Per round the union of the Q cohorts' frames is
    deduplicated (plus an optional cross-round ``DetectionCache`` of
    ``cache_frames`` slots) into one compacted detector batch
    (:func:`detect_needed`); each query then
    matches and updates against its own cohort's slots only.  Queries that
    hit their limit / the step budget / exhaustion mask out of
    choose/sample but stay shape-stable until every query finishes.

    ``select(q, dets) -> bool[D]`` optionally restricts a shared
    class-agnostic detector to each query's predicate (the Focus-style
    share-one-ingest-pass economics); ``None`` keeps the detector's own
    validity.

    Per query the trajectory is bit-identical to its own
    ``run_search_scan`` run with the same key and a deterministic detector
    — dedup and caching change WHICH invocations happen, never the values
    a query consumes (with stochastic detectors, frames shared within a
    round or served from cache reuse one draw; that sharing is the point).

    Returns ``(carries', traces, stats)``: per-query recall traces (same
    semantics as ``run_search_scan``) and accounting —
    ``detector_invocations`` (unique, uncached frames actually detected),
    ``cache_hits``, ``detector_lanes`` (lanes the detector evaluated: the
    invocations rounded up to whole chunks, :func:`detect_needed`),
    ``rounds``, ``frames_sampled`` (Σ per-query steps, what Q sequential
    runs would have paid).

    ``cache`` overrides internal cache construction (a repository-index
    preload, DESIGN.md §13) and ``warm_tag`` — the preloaded cache's tag
    snapshot — splits ``index_hits`` out of ``cache_hits``; the final
    cache rides back in ``stats["final_cache"]`` so the executor can
    publish fresh detections into the index.
    """
    q_n = int(carries.step.shape[0])
    with jax.profiler.TraceAnnotation("exsample.prepare"):
        limits = jnp.broadcast_to(
            jnp.asarray(result_limits, jnp.int32), (q_n,)
        )
        empty = None
        if cache is None and cache_frames:
            from repro.serve.batcher import RowLayout

            struct = jax.eval_shape(
                detector, jax.random.PRNGKey(0), jnp.zeros((), jnp.int32)
            )
            empty = (RowLayout.of(struct), cache_frames)
    with jax.profiler.TraceAnnotation("exsample.dispatch"):
        (out, cache, buf, n, calls, hits, ihits, lanes,
         rounds) = _search_multi_device(
            carries,
            chunks,
            limits,
            cache,
            warm_tag,
            detector=detector,
            select=select,
            cohorts=cohorts,
            method=method,
            max_steps=max_steps,
            trace_every=trace_every,
            empty=empty,
        )
    with jax.profiler.TraceAnnotation("exsample.readback"):
        buf_host = np.asarray(buf)  # the single device→host sync
        n_host = np.asarray(n)
        traces = [
            [(int(s), int(r)) for s, r in buf_host[q][: int(n_host[q])]]
            for q in range(q_n)
        ]
        stats = {
            "detector_invocations": int(calls),
            "cache_hits": int(hits),
            "index_hits": int(ihits),
            "detector_lanes": int(lanes),
            "rounds": int(rounds),
            "frames_sampled": int(np.asarray(out.step).sum()),
            "final_cache": cache,
        }
    return out, traces, stats


# ---------------------------------------------------------------------------
# Deprecated shims — the legacy entry points now lower through ONE
# SearchPlan (repro.core.plan, DESIGN.md §10).  Each shim builds the plan
# whose home-config lowering is the identical driver, so results stay
# bit-for-bit what the legacy function returned.
# ---------------------------------------------------------------------------


def _warn_deprecated(name: str) -> None:
    warnings.warn(
        f"{name}() is deprecated: build a repro.core.plan.SearchPlan and "
        "call .run() (DESIGN.md §10) — this shim lowers to the identical "
        "driver",
        DeprecationWarning,
        stacklevel=3,
    )


def run_search(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    result_limit: int,
    max_steps: int,
    cohorts: int = 1,
    method: str = "exact",
    trace_every: int = 0,
):
    """Deprecated shim over ``SearchPlan`` (strategy='host'); identical
    semantics to the legacy host reference loop."""
    from repro.core.plan import Execution, SearchPlan

    _warn_deprecated("run_search")
    res = SearchPlan(
        result_limit=result_limit, max_steps=max_steps, cohorts=cohorts,
        method=method, trace_every=trace_every,
        execution=Execution(strategy="host"),
    ).run(carry, chunks, detector=detector)
    return res.carry, res.traces[0]


def run_search_scan(
    carry: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    result_limit: int,
    max_steps: int,
    cohorts: int = 1,
    method: str = "exact",
    trace_every: int = 0,
):
    """Deprecated shim over ``SearchPlan`` (strategy='scan'); identical
    semantics to the legacy device-resident driver (DESIGN.md §7)."""
    from repro.core.plan import Execution, SearchPlan

    _warn_deprecated("run_search_scan")
    res = SearchPlan(
        result_limit=result_limit, max_steps=max_steps, cohorts=cohorts,
        method=method, trace_every=trace_every,
        execution=Execution(strategy="scan"),
    ).run(carry, chunks, detector=detector)
    return res.carry, res.traces[0]


def run_search_multi(
    carries: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    detector: DetectorFn,
    result_limits,
    max_steps: int,
    cohorts: int = 1,
    method: str = "exact",
    trace_every: int = 0,
    select: SelectFn | None = None,
    cache_frames: int = 0,
):
    """Deprecated shim over ``SearchPlan`` (queries_axis=True); identical
    semantics to the legacy Q-batched driver (DESIGN.md §9), including the
    legacy ``stats`` dict shape."""
    from repro.core.plan import Execution, SearchPlan

    _warn_deprecated("run_search_multi")
    q_n = int(carries.step.shape[0])
    if isinstance(result_limits, int):
        limits: int | tuple = result_limits
    else:
        vals = np.asarray(result_limits).reshape(-1)
        limits = int(vals[0]) if vals.size == 1 else tuple(
            int(v) for v in vals
        )
    res = SearchPlan(
        queries=q_n, result_limit=limits, max_steps=max_steps,
        cohorts=cohorts, method=method, trace_every=trace_every,
        execution=Execution(
            queries_axis=True,
            cache=cache_frames if cache_frames else None,
        ),
    ).run(carries, chunks, detector=detector, select=select)
    stats = {
        "detector_invocations": res.stats.detector_invocations,
        "cache_hits": res.stats.cache_hits,
        "rounds": res.stats.rounds,
        "frames_sampled": res.stats.frames_sampled,
    }
    return res.carry, res.traces, stats
