"""Elastic slot scheduler: async workers × Q-axis carry (DESIGN.md §11).

The acceptance bar mirrors the solo drivers': with a deterministic
detector every query's (step, results, trace, sampler statistics, key)
trajectory through :class:`AsyncMultiSearchDriver` is bit-identical to
its own ``run_search_scan`` run at ANY worker count — per-query rounds
serialize (at most one slot in flight per query), so concurrency only
overlaps DIFFERENT queries' rounds.  Property tests pin the elastic
join/retire semantics (a query admitted at round r ≡ a solo run whose
frame budget was debited the frames it missed), the at-most-once merge
discipline under forced straggler re-issue, and the ring-spill contract:
a tiny device ring never wraps a source ring and never loses a result —
evicted entries land in the per-query host ``ResultLog``.  A single-query
plan with ``async_workers`` runs this runtime at Q = 1 (its carry lifted
to ``[1]``), so the contracts that reach ``run()`` are also checked
through such a plan.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    AsyncMultiSearchDriver,
    init_carry,
    init_carry_multi,
    init_matcher,
    init_state,
    run_search_scan,
    stack_carries,
)
from repro.core import runtime
from repro.core.plan import Execution, SearchPlan
from repro.sim import RepoSpec, generate
from repro.sim.oracle import oracle_detect

warnings.filterwarnings("ignore", message="run_search_scan")


@pytest.fixture(scope="module")
def world():
    spec = RepoSpec(
        video_lengths=[6_000] * 3, num_instances=120, chunk_frames=600,
        locality=4.0, seed=7,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    return repo, chunks, det


def _qkey(q):
    return jax.random.fold_in(jax.random.PRNGKey(0), q)


def _fresh_multi(chunks, q_n, max_results=64):
    keys = jax.vmap(_qkey)(jnp.arange(q_n))
    return init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=max_results), keys
    )


def _solo(chunks, det, q, *, result_limit, max_steps, cohorts=1,
          trace_every=0, max_results=64):
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=max_results),
        _qkey(q),
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return run_search_scan(
            carry, chunks, detector=det, result_limit=result_limit,
            max_steps=max_steps, cohorts=cohorts, trace_every=trace_every,
        )


def _single_plan(chunks, det, *, workers, max_results=64, **plan_kw):
    """Query 0 through a single-query plan with ``async_workers``; returns
    the result with its carry stacked back to ``[1]`` so the row helpers
    below read it like a driver's."""
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=max_results),
        _qkey(0),
    )
    plan = SearchPlan(**plan_kw, execution=Execution(async_workers=workers))
    assert plan.resolve() == ("async_multi", "exact")
    res = plan.run(carry, chunks, detector=det)
    assert jnp.ndim(res.carry.step) == 0   # a single-query carry back
    return dataclasses.replace(res, carry=stack_carries([res.carry]))


def _assert_row_equals_solo(out, trace, q, solo_out, solo_trace):
    assert int(out.step[q]) == int(solo_out.step)
    assert int(out.results[q]) == int(solo_out.results)
    assert bool(jnp.all(out.key[q] == solo_out.key))
    np.testing.assert_array_equal(out.sampler.n[q], solo_out.sampler.n)
    np.testing.assert_array_equal(out.sampler.n1[q], solo_out.sampler.n1)
    np.testing.assert_array_equal(
        out.matcher.times_seen[q], solo_out.matcher.times_seen
    )
    assert trace == solo_trace


# ---------------------------------------------------------------------------
# Bit-parity vs solo run_search_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers, q_n", [(1, 3), (4, 3), (4, 1)])
def test_composed_bit_parity_vs_solo_scan(world, workers, q_n):
    """Each query through the slot scheduler ≡ its solo scanned run —
    at ANY worker count, since per-query rounds serialize.  Q = 1 runs
    through a single-query plan."""
    _, chunks, det = world
    if q_n == 1:
        res = _single_plan(
            chunks, det, workers=workers, cohorts=2, result_limit=8,
            max_steps=1500, trace_every=25,
        )
        out, traces = res.carry, res.traces
    else:
        driver = AsyncMultiSearchDriver(
            _fresh_multi(chunks, q_n), chunks, det,
            cohorts=2, num_workers=workers, result_limits=8,
            max_steps=1500, trace_every=25,
        )
        out, traces = driver.run(), driver.traces
    for q in range(q_n):
        solo_out, solo_trace = _solo(
            chunks, det, q, result_limit=8, max_steps=1500, cohorts=2,
            trace_every=25,
        )
        _assert_row_equals_solo(out, traces[q], q, solo_out, solo_trace)


@pytest.mark.parametrize("q_n", [4, 1])
def test_composed_parity_through_search_plan(world, q_n):
    """The async_multi lowering reaches the same per-query fixed points
    through the declarative SearchPlan, with uniform SearchStats
    populated: Q = 4 on the Q axis with a cache, and a single-query plan
    (no Q axis, so no cache) lifted to Q = 1."""
    _, chunks, det = world
    kw = dict(cohorts=2, result_limit=8, max_steps=1500, trace_every=25)
    if q_n == 1:
        res = _single_plan(chunks, det, workers=2, **kw)
    else:
        plan = SearchPlan(
            queries=q_n, **kw,
            execution=Execution(queries_axis=True, async_workers=2,
                                cache=-1),
        )
        assert plan.resolve() == ("async_multi", "exact")
        res = plan.run(_fresh_multi(chunks, q_n), chunks, detector=det)
    for q in range(q_n):
        solo_out, solo_trace = _solo(
            chunks, det, q, result_limit=8, max_steps=1500, cohorts=2,
            trace_every=25,
        )
        _assert_row_equals_solo(res.carry, res.traces[q], q, solo_out,
                                solo_trace)
    assert res.stats.merges == res.stats.rounds > 0
    assert res.stats.frames_sampled == int(np.asarray(res.carry.step).sum())
    assert res.stats.results_spilled == 0
    # the shared cache + per-batch dedup amortize detector invocations:
    # never more fresh calls than frames sampled
    assert res.stats.detector_invocations <= res.stats.frames_sampled


# ---------------------------------------------------------------------------
# Synchronous pump harness (no worker threads — deterministic scheduling)
# ---------------------------------------------------------------------------


def _drain(driver):
    items = []
    while True:
        try:
            item = driver._work.get_nowait()
        except Exception:
            break
        if item is not None:
            items.append(item)
    return items


def _pump_round(driver):
    """Issue every ready slot and merge it synchronously; returns the
    number of batches processed."""
    driver._issue_ready()
    batches = _drain(driver)
    for batch in batches:
        driver._merge(driver._process_batch(0, batch))
    return len(batches)


def _pump_to_completion(driver, max_pumps=10_000):
    for _ in range(max_pumps):
        if not _pump_round(driver) and not driver._inflight:
            if not any(r.active for r in driver.rows):
                return
    raise AssertionError("driver did not converge")


# ---------------------------------------------------------------------------
# Elastic join/retire property
# ---------------------------------------------------------------------------


@settings(max_examples=5, deadline=None)
@given(r=st.integers(1, 4))
def test_admitted_query_equals_reduced_budget_solo(world, r):
    """A query admitted after r pool rounds behaves exactly like one
    present from round 0 with its frame budget reduced by the frames it
    missed — i.e. a solo run at ``max_steps − cohorts × r``."""
    _, chunks, det = world
    cohorts = 2
    driver = AsyncMultiSearchDriver(
        _fresh_multi(chunks, 2), chunks, det,
        cohorts=cohorts, num_workers=1, result_limits=50,
        max_steps=200, slots_per_batch=2,
    )
    for _ in range(r):
        assert _pump_round(driver) == 1
    assert driver.pool_rounds() == r
    row_idx = driver.admit(_qkey(9), result_limit=8)
    budget = driver.rows[row_idx].budget
    assert budget == 200 - cohorts * r
    _pump_to_completion(driver)
    out = stack_carries([row.carry for row in driver.rows])
    solo_out, _ = _solo(chunks, det, 9, result_limit=8, max_steps=budget,
                        cohorts=cohorts)
    assert int(out.step[row_idx]) == int(solo_out.step)
    assert int(out.results[row_idx]) == int(solo_out.results)
    assert bool(jnp.all(out.key[row_idx] == solo_out.key))
    np.testing.assert_array_equal(out.sampler.n[row_idx], solo_out.sampler.n)
    np.testing.assert_array_equal(out.sampler.n1[row_idx],
                                  solo_out.sampler.n1)


def test_retired_rows_frozen_and_masked(world):
    """A finished query retires: its row stops issuing and its carry no
    longer changes while the rest of the pool keeps running."""
    _, chunks, det = world
    driver = AsyncMultiSearchDriver(
        _fresh_multi(chunks, 2), chunks, det,
        cohorts=1, num_workers=1,
        result_limits=[1, 30],       # q0 finishes almost immediately
        max_steps=400, slots_per_batch=1,
    )
    while driver.rows[0].active:
        assert _pump_round(driver)
    frozen = driver.rows[0].carry
    for _ in range(5):
        _pump_round(driver)
    assert int(driver.rows[0].carry.step) == int(frozen.step)
    assert bool(jnp.all(driver.rows[0].carry.key == frozen.key))
    # retire closed the trace with the unconditional final checkpoint
    assert driver.rows[0].trace[-1] == (
        int(frozen.step), int(frozen.results)
    )
    _pump_to_completion(driver)
    assert not any(row.active for row in driver.rows)


# ---------------------------------------------------------------------------
# Straggler re-issue: at-most-once merge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["pump", "plan"])
def test_forced_reissue_merges_at_most_once(world, monkeypatch, path):
    """A re-issued slot batch reprocesses the identical work item; the
    second completion is dropped by the pending set and the committed
    state equals a single merge.  Through a single-query plan every batch
    is re-issued before its first completion merges, and the query still
    ends bit-identical to its solo scanned run."""
    _, chunks, det = world
    if path == "plan":
        merge = AsyncMultiSearchDriver._merge
        seen = set()

        def reissue_then_merge(self, res):
            if res.batch_id not in seen:
                seen.add(res.batch_id)
                self._reissue(res.batch_id)
            merge(self, res)

        monkeypatch.setattr(
            AsyncMultiSearchDriver, "_merge", reissue_then_merge
        )
        res = _single_plan(chunks, det, workers=1, cohorts=1,
                           result_limit=20, max_steps=300)
        st_ = res.stats
        assert st_.merges == st_.rounds > 1
        assert st_.reissues >= st_.merges
        # one worker takes each duplicate before the next round's batch,
        # so every duplicate but the last is completed and dropped
        assert st_.merges - 1 <= st_.duplicate_drops <= st_.reissues
        solo_out, solo_trace = _solo(chunks, det, 0, result_limit=20,
                                     max_steps=300, cohorts=1)
        _assert_row_equals_solo(res.carry, res.traces[0], 0, solo_out,
                                solo_trace)
        return
    driver = AsyncMultiSearchDriver(
        _fresh_multi(chunks, 2), chunks, det,
        cohorts=1, num_workers=1, result_limits=20,
        max_steps=300, slots_per_batch=2,
    )
    driver._issue_ready()
    (batch,) = _drain(driver)
    res_first = driver._process_batch(0, batch)
    driver._reissue(batch.batch_id)
    (dup,) = _drain(driver)
    assert dup.batch_id == batch.batch_id and dup.issue_count == 1
    res_dup = driver._process_batch(1, dup)
    driver._merge(res_first)
    snapshot = [jax.tree.map(np.asarray, row.carry) for row in driver.rows]
    merges_after_first = driver.stats["merges"]
    driver._merge(res_dup)
    assert driver.stats["duplicate_drops"] == 1
    assert driver.stats["reissues"] == 1
    assert driver.stats["merges"] == merges_after_first
    for row, snap in zip(driver.rows, snapshot):
        assert int(row.carry.step) == int(snap.step)
        np.testing.assert_array_equal(
            np.asarray(row.carry.sampler.n), snap.sampler.n
        )
    _pump_to_completion(driver)


# ---------------------------------------------------------------------------
# Ring-spill contract: overflow-free, zero result loss
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q_n", [2, 1])
def test_tiny_ring_spills_without_loss(world, q_n):
    """With a ring far smaller than the result count the slot runtime
    never wraps a source ring and never loses a result: every distinct
    insertion is live on-device or in the host ResultLog.  Q = 1 runs
    through a single-query plan, whose stats carry the spill count."""
    repo, chunks, _ = world
    det = lambda key, frame: oracle_detect(
        repo, frame, query_class=0, max_dets=4
    )
    if q_n == 1:
        res = _single_plan(chunks, det, workers=2, max_results=8, cohorts=1,
                           result_limit=40, max_steps=3000)
        out = res.carry
        live = int(np.sum(np.asarray(out.matcher.times_seen[0]) > 0))
        assert res.stats.results_spilled > 0
        assert int(out.results[0]) == live + res.stats.results_spilled
        assert int(out.matcher.total_inserted[0]) == int(out.results[0])
        return
    driver = AsyncMultiSearchDriver(
        _fresh_multi(chunks, q_n, max_results=8), chunks, det,
        cohorts=1, num_workers=2, result_limits=40, max_steps=3000,
    )
    out = driver.run()    # must not raise
    assert driver.stats["spilled"] > 0
    total_logged = 0
    for q in range(q_n):
        live = int(np.sum(np.asarray(out.matcher.times_seen[q]) > 0))
        logged = len(driver.logs[q])
        assert int(out.results[q]) == live + logged
        assert int(out.matcher.total_inserted[q]) == int(out.results[q])
        total_logged += logged
    assert driver.stats["spilled"] == total_logged
    # the log carries real result payloads, not placeholders
    arrs = driver.logs[0].as_arrays()
    assert arrs["frame"].shape[0] == len(driver.logs[0])
    assert np.all(arrs["times_seen"] >= 1)
    # host rows [k, 4] / [k, F], each the detection that first sighted it
    k = len(driver.logs[0])
    assert arrs["boxes"].shape == (k, 4) and arrs["feats"].shape == (k, 8)
    for box, feat, frame in zip(arrs["boxes"], arrs["feats"], arrs["frame"]):
        d = det(None, jnp.int32(frame))
        same = np.asarray(d.valid) & np.all(
            np.isclose(np.asarray(d.boxes), box, rtol=0, atol=1e-6), axis=1)
        assert same.any(), (frame, box)
        assert np.any(np.all(np.asarray(d.feats)[same] == feat, axis=1))


def test_overflow_impossible_by_construction(world):
    """Configurations whose one-round insertion bound reaches the ring
    capacity are rejected up front — the only way the composed path
    could wrap a source ring inside a merge window."""
    repo, chunks, _ = world
    det = lambda key, frame: oracle_detect(
        repo, frame, query_class=0, max_dets=8
    )
    with pytest.raises(ValueError, match="capacity"):
        AsyncMultiSearchDriver(
            _fresh_multi(chunks, 2, max_results=8), chunks, det,
            cohorts=1, num_workers=1, result_limits=4, max_steps=100,
        )


def test_stats_keys_exist_at_construction(world):
    """LoweredPlan.run() packages SearchStats straight from the stats
    dict — every counter must exist from construction, not first merge."""
    _, chunks, det = world
    driver = AsyncMultiSearchDriver(
        _fresh_multi(chunks, 2), chunks, det, num_workers=1,
    )
    assert driver.stats == {
        "slots": 0, "merges": 0, "reissues": 0, "duplicate_drops": 0,
        "merge_high_water": 0, "rounds": 0, "spilled": 0,
        "detector_invocations": 0, "cache_hits": 0, "index_hits": 0,
        "lanes_issued": 0, "lanes_padded": 0, "detector_lanes": 0,
    }


@pytest.mark.parametrize("pump", ["synchronous", "threads"])
def test_round_stamps_ordered_and_history_bounded(world, monkeypatch, pump):
    """Every merged round leaves its ``time.monotonic`` stamps in order —
    issued ≤ taken ≤ done ≤ merged — and the driver keeps only the latest
    ``ROUND_HISTORY`` of them.  ``detector_lanes`` counts the lanes the
    detector evaluated in every processed batch: its needed lanes rounded
    up to a whole chunk, which is the batch's 4 lanes here."""
    _, chunks, det = world
    monkeypatch.setattr(runtime, "ROUND_HISTORY", 3)
    driver = AsyncMultiSearchDriver(
        _fresh_multi(chunks, 3), chunks, det, cohorts=2, num_workers=2,
        result_limits=5, max_steps=120, slots_per_batch=2,
    )
    needs = []
    process = driver._process_batch

    def spy(wid, batch):
        res = process(wid, batch)
        needs.append(int(np.asarray(res.aux.need).sum()))
        return res

    driver._process_batch = spy
    if pump == "synchronous":
        _pump_to_completion(driver)
    else:
        driver.run()
    rounds = driver.recent_rounds()
    assert driver.stats["merges"] > 3 and len(rounds) == 3
    for issued, taken, done, merged in rounds:
        assert 0.0 < issued <= taken <= done <= merged
    assert [r[3] for r in rounds] == sorted(r[3] for r in rounds)
    summary = runtime.round_summary(rounds)
    assert summary["rounds"] == 3
    assert summary["round_p50_s"] > 0.0 and summary["slot_wait_p90_s"] >= 0.0
    assert runtime.round_summary([])["round_p50_s"] is None
    processed = driver.stats["merges"] + driver.stats["duplicate_drops"]
    assert len(needs) == processed
    assert driver.stats["detector_lanes"] == sum(-(-n // 4) * 4 for n in needs)


def _failing_detector(det):
    """``det`` whose every execution raises on the host — the shape of a
    failure that only shows when a worker runs its round (a compile
    refusal, an out-of-memory, a detector service error)."""

    def boom(frame):
        raise RuntimeError("detector down")

    def failing(key, frame):
        frame = jax.pure_callback(
            boom, jax.ShapeDtypeStruct((), jnp.int32), frame,
            vmap_method="sequential",
        )
        return det(key, frame)

    return failing


@pytest.mark.parametrize("q_n", [2, 1])
def test_worker_failure_fails_run(world, q_n):
    """Regression: a worker exception used to end its daemon thread in
    silence, and ``run()`` returned the partial carry after one timeout as
    if the search had finished.  It must raise from ``run()`` instead —
    and from a single-query plan's ``run()`` at Q = 1."""
    _, chunks, det = world
    with pytest.raises(RuntimeError, match="search worker .* failed"):
        if q_n == 1:
            _single_plan(chunks, _failing_detector(det), workers=2,
                         cohorts=2, result_limit=8, max_steps=1500)
        else:
            AsyncMultiSearchDriver(
                _fresh_multi(chunks, q_n), chunks, _failing_detector(det),
                cohorts=2, num_workers=2, result_limits=8, max_steps=1500,
            ).run()


def _hanging_detector(det, release):
    """``det`` whose execution blocks until ``release`` is set — a worker
    stuck in a device call."""

    def hang(frame):
        release.wait(timeout=60.0)
        return frame

    def hanging(key, frame):
        frame = jax.pure_callback(
            hang, jax.ShapeDtypeStruct((), jnp.int32), frame,
            vmap_method="sequential",
        )
        return det(key, frame)

    return hanging


@pytest.mark.parametrize("q_n", [2, 1])
def test_stuck_worker_times_out_run(world, monkeypatch, q_n):
    """A worker that never returns must not hang ``run()``: once no batch
    completes for the stall limit it raises, naming the in-flight work —
    also from a single-query plan's ``run()`` at Q = 1."""
    import threading

    _, chunks, det = world
    release = threading.Event()
    hanging = _hanging_detector(det, release)
    monkeypatch.setattr(runtime, "STALL_TIMEOUT_S", 1.0)
    try:
        with pytest.raises(TimeoutError, match=r"in flight: \[0"):
            if q_n == 1:
                _single_plan(chunks, hanging, workers=2, cohorts=2,
                             result_limit=8, max_steps=1500)
            else:
                AsyncMultiSearchDriver(
                    _fresh_multi(chunks, q_n), chunks, hanging,
                    cohorts=2, num_workers=2, result_limits=8,
                    max_steps=1500,
                ).run()
    finally:
        release.set()
