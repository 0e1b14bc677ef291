"""Device-resident scanned driver ≡ host reference driver (DESIGN.md §7).

The equivalence is the acceptance bar of the scanned driver: identical
(step, results) trajectory AND identical trace checkpoints for the same
PRNG key, across cohort sizes and Thompson methods.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    init_carry,
    init_matcher,
    init_state,
    run_search,
    run_search_scan,
)
from repro.sim import RepoSpec, generate
from repro.sim.oracle import oracle_detect


@pytest.fixture(scope="module")
def world():
    spec = RepoSpec(
        video_lengths=[6_000] * 3, num_instances=120, chunk_frames=600,
        locality=4.0, seed=7,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    return repo, chunks, det


def _fresh(chunks, seed=0):
    return init_carry(
        init_state(chunks.length), init_matcher(max_results=512),
        jax.random.PRNGKey(seed),
    )


@pytest.mark.parametrize("cohorts", [1, 8])
def test_scan_matches_host_bit_identical(world, cohorts):
    _, chunks, det = world
    host, host_trace = run_search(
        _fresh(chunks), chunks, detector=det, result_limit=15,
        max_steps=1200, cohorts=cohorts, trace_every=25,
    )
    scan, scan_trace = run_search_scan(
        _fresh(chunks), chunks, detector=det, result_limit=15,
        max_steps=1200, cohorts=cohorts, trace_every=25,
    )
    assert (int(host.step), int(host.results)) == (int(scan.step), int(scan.results))
    assert host_trace == scan_trace
    np.testing.assert_array_equal(np.asarray(host.sampler.n), np.asarray(scan.sampler.n))
    np.testing.assert_array_equal(np.asarray(host.sampler.n1), np.asarray(scan.sampler.n1))
    np.testing.assert_array_equal(np.asarray(host.key), np.asarray(scan.key))


@pytest.mark.parametrize("method", ["wilson_hilferty", "pallas"])
def test_scan_matches_host_other_methods(world, method):
    _, chunks, det = world
    host, _ = run_search(
        _fresh(chunks), chunks, detector=det, result_limit=10,
        max_steps=600, method=method,
    )
    scan, _ = run_search_scan(
        _fresh(chunks), chunks, detector=det, result_limit=10,
        max_steps=600, method=method,
    )
    assert (int(host.step), int(host.results)) == (int(scan.step), int(scan.results))


@pytest.mark.parametrize("driver", [run_search, run_search_scan])
def test_trace_fires_on_boundary_crossings_with_cohorts(world, driver):
    """Regression: with cohorts=8 and trace_every=7 the step counter never
    lands on a multiple of 7 below lcm(8,7)·k, so the old ``step %
    trace_every == 0`` recorded nothing; boundary-crossing semantics must
    checkpoint every crossed multiple."""
    _, chunks, det = world
    result_limit = 10**9  # never satisfied — run to max_steps
    final, trace = driver(
        _fresh(chunks), chunks, detector=det, result_limit=result_limit,
        max_steps=40, cohorts=8, trace_every=7,
    )
    assert int(final.step) == 40
    # crossings at steps 8, 16, 24, 32, 40 (floors 1..5) + final entry
    steps = [s for s, _ in trace]
    assert steps == [8, 16, 24, 32, 40, 40], trace
    # results column is consistent with the final carry
    assert trace[-1] == (int(final.step), int(final.results))


@pytest.mark.parametrize("driver", [run_search, run_search_scan])
def test_trace_unit_cohort_matches_every_multiple(world, driver):
    _, chunks, det = world
    _, trace = driver(
        _fresh(chunks), chunks, detector=det, result_limit=10**9,
        max_steps=30, cohorts=1, trace_every=10,
    )
    assert [s for s, _ in trace] == [10, 20, 30, 30]


@pytest.mark.parametrize("driver", [run_search, run_search_scan])
def test_all_chunks_exhausted_stops_early(driver):
    """A repository with fewer frames than max_steps must stop once every
    chunk is exhausted instead of resampling frames forever."""
    spec = RepoSpec(
        video_lengths=[64], num_instances=2, chunk_frames=16,
        num_classes=1, seed=3,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    final, _ = driver(
        _fresh(chunks), chunks, detector=det, result_limit=10**9,
        max_steps=10_000,
    )
    assert int(final.step) == 64, int(final.step)
    assert bool(jnp.all(final.sampler.exhausted()))


def test_scan_trace_disabled_returns_final_only(world):
    _, chunks, det = world
    final, trace = run_search_scan(
        _fresh(chunks), chunks, detector=det, result_limit=5, max_steps=200,
    )
    assert trace == [(int(final.step), int(final.results))]


def test_lowered_scan_program_names_its_stages(world):
    """The scanned program's ops carry the stage scopes a profile reads
    (``jax.named_scope``); a single query has no dedup or cache stage."""
    from repro.core.exsample import _search_scan_device

    _, chunks, det = world
    text = _search_scan_device.lower(
        _fresh(chunks), chunks, jnp.asarray(10, jnp.int32), detector=det,
        cohorts=4, method="exact", max_steps=100, trace_every=0,
    ).as_text(debug_info=True)
    stages = ("choose", "detect", "dedup_cache", "match", "update")
    # a scope leads an op's location name, or sits inside its path
    assert {s for s in stages if f'"{s}/' in text or f"/{s}/" in text} == {
        "choose", "detect", "match", "update",
    }
