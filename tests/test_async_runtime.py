"""Async search runtime: barrier-free cohorts, commutative merges."""
import jax
import pytest

from repro.core import init_carry, init_matcher, init_state
from repro.core import runtime
from repro.core.runtime import AsyncSearchDriver
from repro.sim import RepoSpec, generate
from repro.sim.oracle import oracle_detect


@pytest.fixture(scope="module")
def world():
    spec = RepoSpec(
        video_lengths=[10_000] * 4, num_instances=150, chunk_frames=1_000,
        locality=4.0, seed=5,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    return repo, chunks, det


def test_async_driver_finds_results(world):
    repo, chunks, det = world
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=1024),
        jax.random.PRNGKey(0),
    )
    driver = AsyncSearchDriver(
        carry, chunks, det, cohort_size=4, num_workers=3,
        result_limit=15, max_frames=3_000,
    )
    out = driver.run()
    assert int(out.results) >= 15
    assert driver.stats["cohorts"] >= 4
    assert driver.stats["merges"] >= 4
    # counters stay consistent under concurrency
    assert int(out.step) == int(jax.numpy.sum(out.sampler.n))


def test_async_driver_merge_is_atomic_under_contention(world):
    """Regression for the snapshot/merge races: with many workers racing,
    frame counters must still exactly equal the merged sampler statistics
    and every merged result delta must be non-negative (the old code read
    ``self.carry.results`` outside the lock, double-counting results, and
    clobbered the matcher after merges)."""
    repo, chunks, det = world
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=2048),
        jax.random.PRNGKey(3),
    )
    driver = AsyncSearchDriver(
        carry, chunks, det, cohort_size=8, num_workers=8,
        result_limit=40, max_frames=4_000,
    )
    seen_deltas = []
    orig_merge = driver._merge

    def spy_merge(res):
        seen_deltas.append(res.new_results)
        orig_merge(res)

    driver._merge = spy_merge
    out = driver.run()
    assert int(out.results) >= 40 or int(out.step) >= 4_000
    # counters merged exactly once per frame
    assert int(out.step) == int(jax.numpy.sum(out.sampler.n))
    # snapshot-based delta: never negative (old code read the live carry
    # after processing, which could go negative under contention)
    assert all(d >= 0 for d in seen_deltas), seen_deltas
    # matcher MERGE, not replacement: every merged worker's insertions
    # survive, so occupied result-memory slots equal the counted results.
    # Last-writer-wins replacement fails this whenever two workers'
    # processing windows overlapped (the final matcher then only holds the
    # last worker's view).
    occupied = int(jax.numpy.sum(out.matcher.times_seen > 0))
    assert occupied == int(out.results), (occupied, int(out.results))


def test_async_driver_drops_duplicate_completions(world):
    """Regression for the double-merge bug: ``HeartbeatMonitor`` re-issues
    a straggler's cohort, so two completions of the SAME cohort can land.
    The old ``_merge`` folded every WorkerResult in — sampler deltas,
    ``step``, ``results`` and matcher insertions all double-counted.  A
    cohort must merge at most once; the duplicate is dropped and counted."""
    repo, chunks, det = world
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=1024),
        jax.random.PRNGKey(7),
    )
    driver = AsyncSearchDriver(
        carry, chunks, det, cohort_size=4, num_workers=1,
        result_limit=10**9, max_frames=10**9,
    )
    driver._issue_cohort()
    cohort = driver._work.get_nowait()
    first = driver._process_one(0, cohort)
    # force a re-issue (what the monitor does for a straggler) and let a
    # second worker complete the same cohort
    driver._reissue(cohort.cohort_id)
    dup = driver._work.get_nowait()
    second = driver._process_one(1, dup)
    driver._merge(first)
    driver._merge(second)
    assert driver.stats["reissues"] == 1
    assert driver.stats["duplicate_drops"] == 1
    # step equals DISTINCT frames processed, not completions merged
    assert int(driver.carry.step) == len(cohort.chunk_ids)
    assert int(driver.carry.step) == int(jax.numpy.sum(driver.carry.sampler.n))
    occupied = int(jax.numpy.sum(driver.carry.matcher.times_seen > 0))
    assert occupied == int(driver.carry.results)


def test_async_driver_merge_high_water_and_overflow_guard(world):
    """Ring-wrap guard: merges surface their insertion high-water mark, and
    a worker matcher that overflowed its ring (≥ capacity insertions since
    the snapshot) raises instead of silently aliasing the append window."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core.runtime import MatcherRingOverflow, WorkerResult

    repo, chunks, det = world
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=8),
        jax.random.PRNGKey(1),
    )
    driver = AsyncSearchDriver(
        carry, chunks, det, cohort_size=2, num_workers=1,
        result_limit=10**9, max_frames=10**9,
    )
    driver._issue_cohort()
    cohort = driver._work.get_nowait()
    res = driver._process_one(0, cohort)
    driver._merge(res)
    assert driver.stats["merge_high_water"] == int(
        res.matcher.total_inserted - res.snap_matcher.total_inserted
    )
    # fabricate an overflowed worker: total_inserted advanced past capacity
    driver._issue_cohort()
    cohort2 = driver._work.get_nowait()
    res2 = driver._process_one(0, cohort2)
    overflowed = dataclasses.replace(
        res2.matcher,
        total_inserted=res2.snap_matcher.total_inserted + jnp.int32(9),
    )
    bad = WorkerResult(
        cohort_id=res2.cohort_id, worker_id=0,
        delta_n1=res2.delta_n1, delta_n=res2.delta_n,
        new_results=res2.new_results, frames=res2.frames,
        matcher=overflowed, snap_matcher=res2.snap_matcher,
    )
    step_before = int(driver.carry.step)
    import pytest as _pytest

    with _pytest.raises(MatcherRingOverflow):
        driver._merge(bad)
    # the poisoned merge must not have been committed
    assert int(driver.carry.step) == step_before


def test_async_driver_single_worker_equivalent_semantics(world):
    """1-worker async == serialized batched search (same state algebra)."""
    repo, chunks, det = world
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=1024),
        jax.random.PRNGKey(0),
    )
    driver = AsyncSearchDriver(
        carry, chunks, det, cohort_size=2, num_workers=1,
        result_limit=10, max_frames=2_000,
    )
    out = driver.run()
    assert int(out.results) >= 10
    assert driver.stats["reissues"] == 0


def test_stuck_worker_times_out_run(world, monkeypatch):
    """A worker that never returns must not hang ``run()`` (the old loop
    returned a partial carry after 60 s; a bare retry would wait forever):
    once no cohort completes for the stall limit it raises, naming the
    cohorts in flight."""
    import threading

    import jax.numpy as jnp

    repo, chunks, det = world
    release = threading.Event()

    def hang(frame):
        release.wait(timeout=60.0)
        return frame

    def hanging(key, frame):
        frame = jax.pure_callback(
            hang, jax.ShapeDtypeStruct((), jnp.int32), frame,
            vmap_method="sequential",
        )
        return det(key, frame)

    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=1024),
        jax.random.PRNGKey(0),
    )
    driver = AsyncSearchDriver(
        carry, chunks, hanging, cohort_size=4, num_workers=2,
        result_limit=15, max_frames=3_000,
    )
    monkeypatch.setattr(runtime, "STALL_TIMEOUT_S", 1.0)
    try:
        with pytest.raises(TimeoutError, match=r"in flight: \[0, 1, 2\]"):
            driver.run()
    finally:
        release.set()
