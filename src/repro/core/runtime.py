"""Asynchronous search runtime — the production service around Algorithm 1.

The paper sketches asynchronous distributed execution (§3.7.1: "workers
processing a batch of frames at a time without waiting for other workers…
all updates are commutative").  This module is that sketch made concrete,
as a slot-based elastic scheduler over a leading-``[Q]`` carry,
:class:`AsyncMultiSearchDriver` (DESIGN.md §11): workers check out
per-query *cohort slots* (query id, chunk winners, rank base, key split —
a precomputed :class:`~repro.core.exsample.RoundChoice`) instead of whole
carries, process whichever slots are in flight through ONE shared dedup +
:class:`DetectionCache` detector batch (``multi_round_process``), and the
driver applies each query's delta back into its row under the
pending-set/at-most-once discipline (DESIGN.md §5).  At most one slot per
query is in flight, so per-query rounds serialize and every query's
trajectory is bit-identical to its solo ``scan`` plan at ANY worker count
(deterministic detector).  Finished queries retire their slots; new
queries join mid-flight (``admit``) via the same finished-query masking
machinery.  A single-query plan with ``async_workers`` runs here at Q = 1.

Matcher-ring evictions spill to an append-only host-side
:class:`~repro.core.matcher.ResultLog` at merge boundaries, so result sets
are unbounded while the device ring stays fixed (the ring-spill contract,
DESIGN.md §11).

The runtime is deterministic under a virtual clock for testing; the
worker pool is threads (the detector releases the GIL under jax) — on a
real deployment each worker is a pod client.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import queue
import threading
import time
from functools import partial
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunks import ChunkIndex
from repro.core.exsample import (
    ExSampleCarry,
    RoundAux,
    RoundChoice,
    SelectFn,
    multi_round_choose,
    multi_round_process,
    stack_carries,
)
from repro.core.matcher import ResultLog, eviction_mask
from repro.core.state import SamplerState
from repro.distributed.fault_tolerance import HeartbeatMonitor
from repro.serve.batcher import cache_insert, init_detection_cache

# Slot rounds whose (issued, taken, done, merged) stamps the elastic driver
# keeps for ``recent_rounds``; older rounds fall off the end.
ROUND_HISTORY = 256


@dataclasses.dataclass
class WorkerFailure:
    """Put on a results queue in place of a result when a worker raised.
    The scheduling thread re-raises it, so a failed round (a compile
    refusal, an out-of-memory, a detector error) fails the whole run
    instead of silently ending the worker thread."""

    worker_id: int
    error: Exception

    def reraise(self):
        raise RuntimeError(
            f"search worker {self.worker_id} failed: {self.error!r}"
        ) from self.error


# How long ``run()`` waits for any worker result before it gives up on a
# worker stuck in a device call: far longer than a cold compile.
STALL_TIMEOUT_S = 600.0


def _raise_if_stalled(since: float, inflight) -> None:
    """TimeoutError naming the in-flight work once no result has arrived
    for ``STALL_TIMEOUT_S`` seconds since ``since``."""
    waited = time.monotonic() - since
    if waited > STALL_TIMEOUT_S:
        raise TimeoutError(
            f"no search worker result for {waited:.1f} s (limit "
            f"{STALL_TIMEOUT_S:g} s); in flight: {sorted(inflight)}"
        )


def _next_result(results: queue.Queue, threads, timeout: float):
    """Next worker result, or None when ``timeout`` passes with a worker
    still alive to deliver one.  A worker failure is re-raised here, and
    so is a timeout after every worker has exited."""
    try:
        res = results.get(timeout=timeout)
    except queue.Empty:
        if not any(t.is_alive() for t in threads):
            raise RuntimeError(
                "every search worker exited with work in flight"
            ) from None
        return None
    if isinstance(res, WorkerFailure):
        res.reraise()
    return res


# ---------------------------------------------------------------------------
# Slot-based elastic scheduler over a leading-[Q] carry (DESIGN.md §11)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cohorts", "method"))
def _issue_slots(
    sub: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    cohorts: int,
    method: str,
) -> RoundChoice:
    """Choose phase for a gathered batch of query rows — the content of a
    cohort slot: chunk winners, random+ rank base, per-slot key split."""
    return multi_round_choose(sub, chunks, cohorts=cohorts, method=method)


@partial(jax.jit, static_argnames=("detector", "select"))
def _process_slots(
    sub: ExSampleCarry,
    cache,
    chunks: ChunkIndex,
    query_ids: jax.Array,
    active: jax.Array,
    choice: RoundChoice,
    *,
    detector: Callable,
    select: Optional[SelectFn],
):
    """Process phase for whichever slots are in flight: ONE shared dedup +
    ``DetectionCache`` detector batch for the gathered rows, then each
    query's sequential matcher/sampler fold.  Identical round body to the
    resident ``_search_multi_device`` loop (``multi_round_process``), so
    per-lane results are bit-identical to the solo drivers."""
    return multi_round_process(
        sub, cache, chunks, active, choice,
        detector=detector, select=select, query_ids=query_ids,
    )


@dataclasses.dataclass
class SlotBatch:
    """A checked-out set of per-query cohort slots (at most one per query).

    ``carry`` holds the gathered rows at issue time — authoritative, since
    a query has at most one slot in flight — and ``choice`` is the
    precomputed choose phase, so a re-issued straggler batch reprocesses
    the IDENTICAL work item."""

    batch_id: int
    query_rows: np.ndarray      # i32[B] — driver row index per lane
    carry: ExSampleCarry        # gathered rows, leading [B]
    choice: RoundChoice         # leading [B]
    active: np.ndarray          # bool[B] — False = padding lane
    select_ids: np.ndarray = None   # i32[B] — id handed to select() per lane
    issue_count: int = 0        # >1 ⇒ re-issued (straggler/death)
    issued_s: float = 0.0       # monotonic stamp at first issue


@dataclasses.dataclass
class SlotResult:
    """A processed slot batch and the round's ``time.monotonic`` stamps:
    issued (driver), taken and done (worker), merged (driver, at the end
    of the merge; 0.0 until then)."""

    batch_id: int
    worker_id: int
    carry: ExSampleCarry        # post-round rows, leading [B]
    fresh_calls: int            # unique, uncached frames detected
    cache_hits: int
    aux: RoundAux               # fresh detections for cache publication
    issued_s: float = 0.0
    taken_s: float = 0.0
    done_s: float = 0.0
    merged_s: float = 0.0


def round_summary(rounds) -> dict:
    """Median round time (issued to merged) and 90th percentile of the
    time a round sat in queues — for a worker (issued to taken), then for
    the pump and its merge (done to merged) — over ``recent_rounds()``;
    None without rounds."""
    def rank(values, q):
        v = sorted(values)
        return v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)] if v else None

    return {
        "rounds": len(rounds),
        "round_p50_s": rank([m - i for i, _, _, m in rounds], 50),
        "slot_wait_p90_s": rank(
            [(t - i) + (m - d) for i, t, d, m in rounds], 90
        ),
    }


@dataclasses.dataclass
class _QueryRow:
    """One query's slot in the elastic pool.

    Beyond the carry itself the row holds the per-tenant accounting the
    service front reports: detector economics attributed to this query
    (``fresh_calls``/``cache_hits`` — by dedup representative, so a frame
    two tenants sampled in one batch bills the first), wall-clock result
    stamps for SLO tracking, and the admission metadata.  ``select_id`` is
    the id handed to the ``select`` predicate instead of the row index, so
    a service can bind a tenant's predicate (e.g. its query class) at
    admission without recompiling anything; ``vacant`` marks a released
    slot ``admit()`` may reuse."""

    carry: ExSampleCarry        # single-query carry (scalar step/results)
    limit: int                  # distinct-result target
    budget: int                 # frame budget (max steps for THIS query)
    trace: list
    log: ResultLog
    active: bool = True         # False = retired (finished or failed)
    inflight: bool = False      # a slot for this query is checked out
    rounds: int = 0             # rounds merged so far
    vacant: bool = False        # released slot, reusable by admit()
    select_id: Optional[int] = None   # id passed to select() (default: row)
    fresh_calls: int = 0        # detector invocations attributed to this row
    cache_hits: int = 0         # cache hits attributed to this row
    index_hits: int = 0         # cache hits served by index-warmed frames
    warm_rounds_saved: int = 0  # prior-injection warm-up equivalent (rounds)
    admitted_s: float = 0.0     # monotonic wall-clock at admit/construction
    first_result_s: float = 0.0  # monotonic stamp of the first result merge
    finished_s: float = 0.0     # monotonic stamp at retire


class AsyncMultiSearchDriver:
    """Elastic slot scheduler: async workers × a leading-[Q] carry.

    The driver owns Q query rows (sampler, matcher, key, counters — one
    lane of the §9 multi-query carry each).  ``_issue_ready`` checks out a
    *cohort slot* per issuable query — the precomputed
    :class:`~repro.core.exsample.RoundChoice` (chunk winners, rank base,
    key split) plus the row snapshot — and packs up to ``slots_per_batch``
    slots into one :class:`SlotBatch` work item.  Workers run the shared
    dedup + cache + detector batch (``_process_slots``) for whichever
    slots are in flight; ``_merge`` applies each query's post-round row
    back under the pending-set/at-most-once discipline, publishes fresh
    detections into the shared :class:`DetectionCache`, spills
    ring-evicted results to the per-query host
    :class:`~repro.core.matcher.ResultLog` and re-issues freed queries.

    Scheduling invariant: AT MOST ONE slot per query in flight — round
    r+1 of a query is only chosen after round r merged.  Per-query rounds
    therefore serialize, and with a deterministic detector each query's
    (step, results, trace, sampler, key) trajectory is bit-identical to
    its solo ``run_search_scan`` run at ANY worker count: concurrency
    comes from different queries' rounds overlapping, amortization from
    the shared per-batch dedup and the cross-round cache (which change
    WHICH detector invocations happen, never the values a query
    consumes).  Sampler deltas never cross queries and each row is
    replaced wholesale by its own serialized round, so Q-axis merges
    commute trivially (DESIGN.md §11 vs the §8/§9 argument for shared
    state).

    Elasticity: a finished query retires its row (masked out of issue,
    shape-stable); ``admit()`` installs a fresh query mid-flight with a
    frame budget debited by the pool rounds it missed.  Batch shapes are
    fixed at ``slots_per_batch`` (padded with inactive lanes), so neither
    retirement nor admission recompiles anything.

    No source ring can wrap between issue and merge: the constructor
    rejects configurations whose per-round insertion bound (cohorts ×
    detector slots per frame) reaches the ring capacity, which is the only
    way it could.
    """

    def __init__(
        self,
        carries: ExSampleCarry,
        chunks: ChunkIndex,
        detector: Callable,
        *,
        cohorts: int = 1,
        num_workers: int = 4,
        result_limits: Union[int, Sequence[int]] = 50,
        max_steps: int = 100_000,
        method: str = "exact",
        select: Optional[SelectFn] = None,
        cache_frames: int = 0,
        trace_every: int = 0,
        slots_per_batch: Optional[int] = None,
        straggler_factor: float = 4.0,
        index=None,
    ):
        if jnp.ndim(carries.step) != 1:
            raise ValueError(
                "AsyncMultiSearchDriver needs a leading-[Q] carry "
                "(init_carry_multi / stack_carries); got a single-query "
                "carry"
            )
        q_n = int(carries.step.shape[0])
        if isinstance(result_limits, (int, np.integer)):
            limits = [int(result_limits)] * q_n
        else:
            limits = [int(v) for v in np.asarray(result_limits).reshape(-1)]
            if len(limits) != q_n:
                raise ValueError(
                    f"result_limits has {len(limits)} entries for a "
                    f"{q_n}-query carry"
                )
        self.chunks = chunks
        self.detector = detector
        self.select = select
        self.cohorts = cohorts
        self.method = method
        self.max_steps = max_steps
        self.trace_every = trace_every
        self.num_workers = num_workers
        self.slots_per_batch = (
            max(1, math.ceil(q_n / max(num_workers, 1)))
            if slots_per_batch is None
            else max(1, slots_per_batch)
        )
        self.monitor = HeartbeatMonitor(straggler_factor=straggler_factor)
        self._lock = threading.Lock()
        self._work: "queue.Queue[Optional[SlotBatch]]" = queue.Queue()
        self._results: "queue.Queue[SlotResult]" = queue.Queue()
        self._next_batch = 0
        self._inflight: dict[int, SlotBatch] = {}
        now0 = time.monotonic()
        self.rows = [
            _QueryRow(
                carry=jax.tree.map(lambda x, q=q: x[q], carries),
                limit=limits[q],
                budget=max_steps,
                trace=[],
                log=ResultLog(),
                admitted_s=now0,
            )
            for q in range(q_n)
        ]
        self._threads: list[threading.Thread] = []
        # no-overflow guarantee for the composed path: a round inserts at
        # most cohorts × (detector slots per frame) entries per query, and
        # a merge window is exactly one round — keep it under capacity so
        # the source ring can never wrap
        struct = jax.eval_shape(
            detector, jax.random.PRNGKey(0), jnp.zeros((), jnp.int32)
        )
        det_slots = (
            int(struct.valid.shape[-1]) if hasattr(struct, "valid") else None
        )
        capacity = int(carries.matcher.times_seen.shape[-1])
        if det_slots is not None and cohorts * det_slots >= capacity:
            raise ValueError(
                f"matcher capacity {capacity} does not cover one round's "
                f"insertion bound (cohorts={cohorts} × {det_slots} detector "
                "slots per frame): the ring could wrap inside a merge "
                "window, which no spill can recover — raise max_results or "
                "lower cohorts"
            )
        self.index = index
        self._warm_frames: frozenset = frozenset()
        if cache_frames:
            if index is not None:
                # preload the device tier from the repository index — an
                # empty tier yields a cache bit-identical to
                # init_detection_cache (the cold-path contract, §13)
                warm, self._warm_frames = index.warm(struct, cache_frames)
                self.cache = jax.device_put(warm)
            else:
                self.cache = init_detection_cache(struct, cache_frames)
        else:
            self.cache = None
        self._warm_arr = (
            np.asarray(sorted(self._warm_frames), np.int64)
            if self._warm_frames else None
        )
        # every counter exists from construction so LoweredPlan.run() can
        # package uniform SearchStats even for a run that never merged
        self.stats = {
            "slots": 0, "merges": 0, "reissues": 0, "duplicate_drops": 0,
            "merge_high_water": 0, "rounds": 0, "spilled": 0,
            "detector_invocations": 0, "cache_hits": 0, "index_hits": 0,
            # detector-batch occupancy accounting (RequestBatcher semantics
            # over slot lanes): how many lanes of each emitted SlotBatch
            # carried a live query vs sentinel padding
            "lanes_issued": 0, "lanes_padded": 0,
            # lanes the detector evaluated in every processed batch,
            # duplicate completions included: the batch's fresh calls
            # rounded up to whole chunks (exsample.detect_needed)
            "detector_lanes": 0,
        }
        # (issued_s, taken_s, done_s, merged_s) of the latest merged rounds
        self._rounds: collections.deque = collections.deque(
            maxlen=ROUND_HISTORY
        )

    # ---- row liveness / elasticity ----------------------------------------

    def _row_live(self, row: _QueryRow) -> bool:
        """The solo driver's continue condition, per row (checked before
        each round, exactly like ``_search_scan_device``'s ``cond``)."""
        return (
            int(row.carry.results) < row.limit
            and int(row.carry.step) < row.budget
            and not bool(jnp.all(row.carry.sampler.exhausted()))
        )

    def _retire(self, row: _QueryRow) -> None:
        """Mask a finished query out of issue and close its trace with the
        unconditional final checkpoint (``run_search_scan`` semantics)."""
        row.active = False
        row.finished_s = time.monotonic()
        row.trace.append((int(row.carry.step), int(row.carry.results)))

    def vacate(self, q: int) -> _QueryRow:
        """Release row ``q``'s slot for reuse by a later ``admit()``.

        The caller (a persistent service) harvests the row's results
        first — the returned row object keeps its carry/trace/log, but the
        SLOT index now belongs to whichever tenant ``admit()`` installs
        next.  Only a row with no slot in flight can be vacated; an
        active row is force-retired (masked out of issue) without the
        final trace checkpoint, which is the prototype-row case of a
        service that starts with an empty pool."""
        with self._lock:
            row = self.rows[q]
            if row.inflight:
                raise RuntimeError(
                    f"row {q} has a slot in flight; merge it before vacating"
                )
            row.active = False
            row.vacant = True
            return row

    def pool_rounds(self) -> int:
        """Pool progress clock: rounds completed by the furthest-ahead
        query.  ``admit`` debits a late joiner's default frame budget by
        ``cohorts × pool_rounds()`` — the frames it missed."""
        return max((r.rounds for r in self.rows), default=0)

    def admit(
        self,
        key: jax.Array,
        *,
        result_limit: int,
        max_steps: Optional[int] = None,
        base_max_steps: Optional[int] = None,
        select_id: Optional[int] = None,
        sampler_init: Optional[SamplerState] = None,
        warm_rounds_saved: int = 0,
    ) -> int:
        """Join a fresh query mid-flight; returns its row index.

        The new row starts from zeroed sampler statistics and an empty
        matcher (same geometry/thresholds as the pool) and is issuable
        from the next ``_issue_ready`` call.  Its frame budget defaults to
        ``base − cohorts × pool_rounds()`` where ``base`` is
        ``base_max_steps`` (a tenant's own requested budget) or the
        pool's ``max_steps`` — a query admitted at round r behaves exactly
        like one present from round 0 whose budget was reduced by the
        frames it missed (the join/retire property,
        tests/test_async_compose.py).  ``max_steps`` overrides the debit
        entirely.  ``select_id`` is handed to the ``select`` predicate in
        place of the row index (tenant→predicate binding, no recompile).
        ``sampler_init`` replaces the zeroed sampler statistics wholesale
        (the index warm-start path: the service injects Thompson priors
        and remains responsible for subtracting them back out when it
        records evidence); ``warm_rounds_saved`` annotates the row's
        accounting.  Vacated slots (``vacate``) are reused before the
        pool grows."""
        proto = self.rows[0].carry
        m0 = proto.matcher
        fresh_matcher = dataclasses.replace(
            m0,
            boxes=jnp.zeros_like(m0.boxes),
            feats=jnp.zeros_like(m0.feats),
            video=jnp.full_like(m0.video, -1),
            frame=jnp.full_like(m0.frame, -(10**9)),
            chunk=jnp.full_like(m0.chunk, -1),
            times_seen=jnp.zeros_like(m0.times_seen),
            cursor=jnp.zeros((), jnp.int32),
            total_inserted=jnp.zeros((), jnp.int32),
        )
        s0 = proto.sampler
        fresh_sampler = dataclasses.replace(
            s0, n1=jnp.zeros_like(s0.n1), n=jnp.zeros_like(s0.n)
        )
        if sampler_init is not None:
            fresh_sampler = sampler_init
        carry = ExSampleCarry(
            sampler=fresh_sampler,
            matcher=fresh_matcher,
            key=key,
            step=jnp.zeros((), jnp.int32),
            results=jnp.zeros((), jnp.int32),
        )
        with self._lock:
            base = self.max_steps if base_max_steps is None else base_max_steps
            budget = (
                max(0, base - self.cohorts * self.pool_rounds())
                if max_steps is None
                else max_steps
            )
            row = _QueryRow(
                carry=carry, limit=int(result_limit), budget=budget,
                trace=[], log=ResultLog(), select_id=select_id,
                admitted_s=time.monotonic(),
                warm_rounds_saved=int(warm_rounds_saved),
            )
            slot = next(
                (i for i, r in enumerate(self.rows) if r.vacant), None
            )
            if slot is None:
                self.rows.append(row)
                return len(self.rows) - 1
            self.rows[slot] = row
            return slot

    # ---- driver side -------------------------------------------------------

    def _issue_ready(self) -> list:
        """Check out a cohort slot for every issuable query (active, live,
        no slot in flight), packed into fixed-shape batches.  Queries that
        are no longer live retire here instead of issuing."""
        with jax.profiler.TraceAnnotation("exsample.issue") as span, \
                self._lock:
            issuable = []
            for i, row in enumerate(self.rows):
                if not row.active or row.inflight:
                    continue
                if not self._row_live(row):
                    self._retire(row)
                    continue
                issuable.append(i)
            batches = []
            bsz = self.slots_per_batch
            for g in range(0, len(issuable), bsz):
                group = issuable[g:g + bsz]
                pad = bsz - len(group)
                lanes = group + [group[0]] * pad
                active = np.asarray([True] * len(group) + [False] * pad)
                sub = stack_carries([self.rows[i].carry for i in lanes])
                choice = _issue_slots(
                    sub, self.chunks, cohorts=self.cohorts, method=self.method
                )
                select_ids = np.asarray(
                    [
                        self.rows[i].select_id
                        if self.rows[i].select_id is not None
                        else i
                        for i in lanes
                    ],
                    np.int32,
                )
                batch = SlotBatch(
                    batch_id=self._next_batch,
                    query_rows=np.asarray(lanes, np.int32),
                    carry=sub,
                    choice=choice,
                    active=active,
                    select_ids=select_ids,
                    issued_s=time.monotonic(),
                )
                self._next_batch += 1
                self.stats["lanes_issued"] += len(group)
                self.stats["lanes_padded"] += pad
                for i in group:
                    self.rows[i].inflight = True
                self._inflight[batch.batch_id] = batch
                self.stats["slots"] += 1
                batches.append(batch)
            if batches:
                # one span a pass: its first batch id, the live lanes issued
                span.set_metadata(
                    batch=batches[0].batch_id, lanes=len(issuable)
                )
        for batch in batches:
            self._work.put(batch)
        return batches

    def _merge(self, res: SlotResult) -> None:
        """Apply one slot batch back into the Q-axis rows — at most once.

        The pending set is ``self._inflight``: the first completion of a
        batch removes it under the lock, any later completion (straggler
        re-issue) is dropped and counted.  Fresh detections publish into
        the shared cache (first-write-wins; a concurrent worker detecting
        the same frame re-inserts identical values under a deterministic
        detector), then every active lane's row is REPLACED by its
        post-round state — sound because that lane's rounds are
        serialized, so the worker's output is the row's unique successor.
        Live ring entries the round evicted spill to the row's host
        ``ResultLog`` before the replacement lands.  The round's stamps,
        ``merged_s`` last, go to ``recent_rounds``."""
        now = time.monotonic()
        with jax.profiler.TraceAnnotation(
            "exsample.merge", batch=res.batch_id, lanes=self.slots_per_batch
        ), self._lock:
            self.stats["detector_lanes"] += int(res.aux.lanes)
            batch = self._inflight.pop(res.batch_id, None)
            if batch is None:
                self.stats["duplicate_drops"] += 1
                return
            if self.cache is not None:
                self.cache = cache_insert(
                    self.cache, res.aux.flat_frames, res.aux.fresh,
                    res.aux.need,
                )
            self.stats["detector_invocations"] += res.fresh_calls
            self.stats["cache_hits"] += res.cache_hits
            self.stats["merges"] += 1
            self.stats["rounds"] += 1
            # per-lane detector economics: reshape the flat [B = lanes*C]
            # dedup bookkeeping back to (lanes, cohorts) and attribute each
            # fresh detector call / cache hit to the lane that REPRESENTED
            # the frame (duplicates within the batch ride for free, which
            # is exactly the shared-ingest story the service reports).
            lanes_n = len(batch.query_rows)
            need_l = np.asarray(res.aux.need).reshape(lanes_n, -1)
            rep_hit_l = np.asarray(res.aux.rep_hit).reshape(lanes_n, -1)
            if self._warm_arr is not None:
                frames_l = np.asarray(res.aux.flat_frames).reshape(
                    lanes_n, -1
                )
                warm_l = rep_hit_l & np.isin(frames_l, self._warm_arr)
            else:
                warm_l = None
            for lane, qrow in enumerate(batch.query_rows):
                if not batch.active[lane]:
                    continue
                row = self.rows[int(qrow)]
                row.fresh_calls += int(need_l[lane].sum())
                row.cache_hits += int(rep_hit_l[lane].sum())
                if warm_l is not None:
                    lane_ihits = int(warm_l[lane].sum())
                    row.index_hits += lane_ihits
                    self.stats["index_hits"] += lane_ihits
                new_carry = jax.tree.map(
                    lambda x, lane=lane: x[lane], res.carry
                )
                inserted = int(
                    new_carry.matcher.total_inserted
                    - row.carry.matcher.total_inserted
                )
                self.stats["merge_high_water"] = max(
                    self.stats["merge_high_water"], inserted
                )
                if inserted:
                    self.stats["spilled"] += row.log.spill(
                        row.carry.matcher,
                        eviction_mask(row.carry.matcher, inserted),
                    )
                if self.trace_every:
                    s0, s1 = int(row.carry.step), int(new_carry.step)
                    if (s1 // self.trace_every) > (s0 // self.trace_every):
                        row.trace.append((s1, int(new_carry.results)))
                grew = int(new_carry.results) > int(row.carry.results)
                if grew and not row.first_result_s:
                    row.first_result_s = now
                row.carry = new_carry
                row.rounds += 1
                row.inflight = False
                if not self._row_live(row):
                    self._retire(row)
            res.merged_s = time.monotonic()
            self._rounds.append(
                (res.issued_s, res.taken_s, res.done_s, res.merged_s)
            )

    def _reissue(self, batch_id: int) -> None:
        with self._lock:
            batch = self._inflight.get(batch_id)
            if batch is None:
                return
            batch.issue_count += 1
            self.stats["reissues"] += 1
        self._work.put(batch)

    # ---- worker side -------------------------------------------------------

    def _process_batch(self, wid: int, batch: SlotBatch) -> SlotResult:
        """Run the shared dedup + cache + detector round for the slots in
        flight.  Pure of scheduling concerns (tests drive duplicate
        completions synchronously); reads only the batch's own row
        snapshots plus a cache snapshot — never the live rows, which may
        be mid-merge on another thread."""
        taken_s = time.monotonic()
        with self._lock:
            cache = self.cache
        # query_ids only feeds ``select(qi, dets)`` in the round body, so a
        # tenant's select_id re-binds which predicate its lane evaluates
        # without changing shapes (no recompile); None falls back to the
        # row index, preserving the solo-parity contract.
        qids = jnp.asarray(
            batch.select_ids
            if batch.select_ids is not None
            else batch.query_rows,
            jnp.int32,
        )
        active = jnp.asarray(batch.active)
        out, _cache, fresh_calls, cache_hits, aux = _process_slots(
            batch.carry, cache, self.chunks, qids, active, batch.choice,
            detector=self.detector, select=self.select,
        )
        return SlotResult(
            batch_id=batch.batch_id,
            worker_id=wid,
            carry=out,
            fresh_calls=int(fresh_calls),
            cache_hits=int(cache_hits),
            aux=aux,
            issued_s=batch.issued_s,
            taken_s=taken_s,
            done_s=time.monotonic(),
        )

    def _worker(self, wid: int) -> None:
        self.monitor.register(wid, now=time.monotonic())
        while True:
            batch = self._work.get()
            if batch is None:
                return
            t0 = time.monotonic()
            self.monitor.assign(wid, batch.batch_id, now=t0)
            try:
                with jax.profiler.TraceAnnotation(
                    "exsample.process", batch=batch.batch_id,
                    lanes=len(batch.query_rows),
                ):
                    res = self._process_batch(wid, batch)
            except Exception as e:  # noqa: BLE001 — re-raised by the scheduler
                self._results.put(WorkerFailure(wid, e))
                return
            self._results.put(res)
            now = time.monotonic()
            self.monitor.heartbeat(wid, now)
            self.monitor.record_completion(wid, now - t0, now=now)

    # ---- run loop ----------------------------------------------------------

    def start(self) -> None:
        """Spawn the worker pool once; idempotent.  Service mode keeps the
        pool alive across many ``admit``/``vacate`` cycles — workers block
        on the work queue between batches, they do not poll."""
        if self._threads:
            return
        self._threads = [
            threading.Thread(target=self._worker, args=(w,), daemon=True)
            for w in range(self.num_workers)
        ]
        for t in self._threads:
            t.start()

    def stop(self) -> None:
        """Drain the worker pool (None sentinels) and join; idempotent."""
        threads, self._threads = self._threads, []
        for _ in threads:
            self._work.put(None)
        for t in threads:
            t.join(timeout=5.0)

    def idle(self) -> bool:
        """True when nothing is in flight and no row wants more rounds."""
        with self._lock:
            return not self._inflight and not any(
                r.active for r in self.rows
            )

    def recent_rounds(self) -> list:
        """``(issued_s, taken_s, done_s, merged_s)`` of the latest
        ``ROUND_HISTORY`` merged slot rounds, oldest first."""
        with self._lock:
            return list(self._rounds)

    def service_tick(self, timeout: float = 0.1) -> bool:
        """One scheduler heartbeat: issue what is issuable, merge at most
        one completed batch, sweep for stragglers.  Returns True if a
        result was merged (False = the wait timed out — callers use this
        to interleave admission work without busy-spinning).  A worker's
        exception is re-raised here, in the scheduling thread."""
        self._issue_ready()
        res = _next_result(self._results, self._threads, timeout=timeout)
        if res is None:
            return False
        self._merge(res)
        actions = self.monitor.sweep(time.monotonic())
        for bid in actions["reissue_cohorts"]:
            self._reissue(bid)
        self._issue_ready()
        return True

    def run(self) -> ExSampleCarry:
        """Drive every query to completion; returns the stacked [Q] carry
        (retired rows keep their final state).  Per-query traces are in
        ``self.traces``, spilled results in ``self.logs``.  A worker's
        exception is raised from here, and so is a ``TimeoutError`` when
        no batch completes for ``STALL_TIMEOUT_S`` seconds; no partial
        carry is returned."""
        self.start()
        last = time.monotonic()
        try:
            self._issue_ready()
            while not self.idle():
                if self.service_tick(timeout=min(60.0, STALL_TIMEOUT_S)):
                    last = time.monotonic()
                else:
                    _raise_if_stalled(last, self._inflight)
        finally:
            self.stop()
        return stack_carries([row.carry for row in self.rows])

    @property
    def traces(self) -> list:
        return [row.trace for row in self.rows]

    @property
    def logs(self) -> list:
        return [row.log for row in self.rows]


class ElasticShardedRunner:
    """Elastic mesh-shrink recovery for the composed sharded driver
    (DESIGN.md §14).

    Runs ``run_search_multi_sharded`` in bounded slices of ``sync_windows``
    sync windows.  Every slice returns a fully resumable state (carry +
    the hash-sharded cache, still split over the mesh, which the next
    slice takes back as it is), so between slices the
    runner heartbeats the live workers and sweeps the
    :class:`~repro.distributed.fault_tolerance.HeartbeatMonitor`.  When a
    sweep returns a dead verdict the runner *drains at the boundary it is
    already standing on* — the in-flight window always completes and its
    merged results are never lost — then shrinks the mesh:

      1. pick the largest shard count ``k`` ≤ surviving workers with
         ``cohorts % k == 0``, validated through
         :func:`repro.distributed.elastic.plan_resize` (empty schema — the
         search carries no sharded params; the check is the data-parallel
         batch divisibility);
      2. re-place the sampler chunk statistics with
         :func:`repro.distributed.elastic.resize_chunk_stats` (strip the
         old shard padding, re-pad for ``k`` — padding never stacks
         across successive shrinks);
      3. re-place the detection cache: it is copied to the host in the
         direct-mapped order, carried forward as-is when its capacity
         already divides by ``k``, otherwise
         :func:`repro.serve.batcher.reshard_cache_host` re-hashes it to the
         padded capacity (memoization state — a collision under the new
         modulus costs a future detector call, never correctness), and the
         next slice places it shard by shard;
         ``warm_tag`` is left untouched (its index-hit check uses its own
         capacity modulus);
      4. rebuild a ``("data",)`` mesh over the first ``k`` devices and
         resume — the next slice re-lowers for the new mesh automatically.

    Because dead verdicts are only *acted on* at slice boundaries, a
    worker dying mid-window is deferred to the next boundary by
    construction, and a death during the final window simply never
    triggers a reshard — the search completes on the survivors' already
    merged state.

    Determinism: the random+ sampling stream is keyed per query/round,
    not per shard, and the hash-sharded cache content is a pure
    re-placement of the direct-mapped layout — so replaying the same
    death schedule yields the same result multiset.
    """

    def __init__(
        self,
        carries: ExSampleCarry,
        chunks: ChunkIndex,
        *,
        detector: Callable,
        result_limits,
        max_steps: int,
        num_shards: int,
        cohorts: Optional[int] = None,
        sync_every: int = 1,
        select: Optional[SelectFn] = None,
        cache_frames: int = 0,
        cache=None,
        warm_tag=None,
        monitor: Optional[HeartbeatMonitor] = None,
        clock: Callable[[], float] = time.monotonic,
        sync_windows: int = 1,
    ):
        from repro.launch.mesh import make_data_mesh

        if sync_windows < 1:
            raise ValueError(f"sync_windows={sync_windows} must be >= 1")
        self.carry = carries
        self.chunks = chunks
        self.detector = detector
        self.max_steps = int(max_steps)
        self.num_shards = int(num_shards)
        self.cohorts = int(cohorts) if cohorts is not None else self.num_shards
        self.sync_every = int(sync_every)
        self.select = select
        self.cache_frames = int(cache_frames)
        self.warm_tag = warm_tag
        self.sync_windows = int(sync_windows)
        self.clock = clock
        self.monitor = monitor if monitor is not None else HeartbeatMonitor()
        self.mesh = make_data_mesh(self.num_shards)
        q_n = int(carries.step.shape[0])
        self.result_limits = np.broadcast_to(
            np.asarray(result_limits, np.int32), (q_n,)
        ).copy()
        # workers currently heartbeating; kill_worker() silences one (on a
        # real cluster the process died — heartbeats simply stop arriving)
        self.alive: set[int] = set(range(self.num_shards))
        now = self.clock()
        for w in sorted(self.alive):
            self.monitor.register(w, now)
        self._cache = cache          # hash-sharded between slices
        if cache is not None:
            from repro.serve.batcher import reshard_cache_host

            cap = int(cache.tag.shape[0])
            self._cache = reshard_cache_host(
                cache, cap + (-cap) % self.num_shards
            )
        self._first_call = True
        self.traces: list[list] = [[] for _ in range(q_n)]
        self.stats = {
            "detector_invocations": 0, "cache_hits": 0, "index_hits": 0,
            "detector_lanes": 0, "rounds": 0, "merges": 0,
            "merge_high_water": 0,
            "merge_overflow": False, "frames_sampled": 0,
            "reshard_events": [], "final_cache": None,
        }

    # ---- liveness ----------------------------------------------------------

    def kill_worker(self, worker: int) -> None:
        """Stop heartbeating ``worker`` — the monitor's silence window
        starts now; the dead verdict lands at a later boundary sweep."""
        self.alive.discard(worker)

    def _live_queries(self) -> np.ndarray:
        """Host mirror of the device ``live_mask`` predicate."""
        res = np.asarray(self.carry.results)
        step = np.asarray(self.carry.step)
        n = np.asarray(self.carry.sampler.n)
        frames = np.asarray(self.carry.sampler.frames).astype(n.dtype)
        exhausted = (n >= frames).all(axis=-1)
        return (res < self.result_limits) & (step < self.max_steps) & ~exhausted

    # ---- mesh shrink -------------------------------------------------------

    def _shrink(self, dead: list) -> None:
        from repro.distributed.elastic import plan_resize, resize_chunk_stats
        from repro.launch.mesh import make_data_mesh

        survivors = sorted(self.alive)
        if not survivors:
            raise RuntimeError("elastic shrink: no surviving workers")
        new_shards = None
        for k in range(min(len(survivors), self.num_shards), 0, -1):
            if self.cohorts % k:
                continue
            plan = plan_resize(
                {}, make_data_mesh(k), global_batch=self.cohorts
            )
            if plan.feasible:
                new_shards = k
                break
        if new_shards is None:
            raise RuntimeError(
                f"elastic shrink: no feasible shard count <= "
                f"{len(survivors)} survivors for cohorts={self.cohorts}"
            )
        n1, n, frames = resize_chunk_stats(
            self.carry.sampler.n1,
            self.carry.sampler.n,
            self.carry.sampler.frames,
            new_shards,
        )
        # every leaf still lives on the OLD mesh's devices; pull to host so
        # the next slice's lowering re-places it on the survivors' mesh
        self.carry = jax.tree.map(
            np.asarray,
            dataclasses.replace(
                self.carry,
                sampler=dataclasses.replace(
                    self.carry.sampler, n1=n1, n=n, frames=frames
                ),
            ),
        )
        if self._cache is not None:
            from repro.serve.batcher import reshard_cache_host

            cap = int(self._cache.tag.shape[0])
            self._cache = reshard_cache_host(
                self._cache, cap + (-cap) % new_shards
            )
        if self.warm_tag is not None:
            self.warm_tag = np.asarray(self.warm_tag)
        self.stats["reshard_events"].append({
            "window": self.stats["merges"],
            "from_shards": self.num_shards,
            "to_shards": new_shards,
            "dead": sorted(dead),
        })
        self.num_shards = new_shards
        self.mesh = make_data_mesh(new_shards)

    # ---- execution ---------------------------------------------------------

    def step(self) -> bool:
        """Run one bounded slice + one boundary sweep.  Returns True while
        live queries remain."""
        from repro.core.executor import run_search_multi_sharded

        # a query already stopped takes no window in this slice; its trace
        # ended in the slice that stopped it (or, stopped from the start,
        # in the first slice), as in one unbounded call
        traced = self._live_queries() | self._first_call
        out, traces, stats = run_search_multi_sharded(
            self.carry,
            self.chunks,
            mesh=self.mesh,
            detector=self.detector,
            result_limits=self.result_limits,
            max_steps=self.max_steps,
            cohorts=self.cohorts,
            sync_every=self.sync_every,
            select=self.select,
            cache_frames=self.cache_frames if self._first_call else 0,
            cache=self._cache,
            warm_tag=self.warm_tag,
            window_limit=self.sync_windows,
        )
        self._first_call = False
        self.carry = out
        self._cache = stats["final_cache"]
        for q, t in enumerate(traces):
            if traced[q]:
                self.traces[q].extend(t)
        self.stats["detector_invocations"] += stats["detector_invocations"]
        self.stats["cache_hits"] += stats["cache_hits"]
        self.stats["index_hits"] += stats["index_hits"]
        self.stats["detector_lanes"] += stats["detector_lanes"]
        self.stats["rounds"] += stats["rounds"]
        self.stats["merges"] += stats["merges"]
        self.stats["merge_high_water"] = max(
            self.stats["merge_high_water"], stats["merge_high_water"]
        )
        self.stats["merge_overflow"] |= stats["merge_overflow"]
        if not self._live_queries().any():
            return False
        now = self.clock()
        for w in sorted(self.alive):
            self.monitor.heartbeat(w, now)
        verdict = self.monitor.sweep(now)
        dead = [w for w in verdict["dead"] if w < self.num_shards]
        if dead:
            self._shrink(dead)
        return True

    def run(self):
        """Drive every query to completion; returns ``(carry, traces,
        stats)`` with the same shapes as ``run_search_multi_sharded`` plus
        ``stats["reshard_events"]``."""
        # a live query advances `cohorts` steps every window, so this many
        # slices always suffice; exceeding it means the driver stalled
        budget = self.max_steps // (self.cohorts * self.sync_windows) + 2
        while self.step():
            budget -= 1
            if budget < 0:
                raise RuntimeError("elastic runner made no progress")
        self.stats["frames_sampled"] = int(
            np.asarray(self.carry.step).sum()
        )
        self.stats["final_cache"] = self._cache
        return self.carry, self.traces, self.stats
