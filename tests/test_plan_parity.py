"""SearchPlan lowering ≡ legacy drivers, the one lift, and the composed
Q×shards lowering (DESIGN.md §10).

Three contracts:

* **Home-config parity** — a plan lowered to each legacy driver's home
  configuration (scan Q=1, host, multi Q=4) reproduces the legacy entry
  point bit-identically: (step, results), trace, sampler statistics,
  final PRNG key.  The legacy ``run_search_*`` functions are deprecated
  shims over the SAME lowering, so this also pins the shims.
* **The lift** — a single-query mesh or async plan runs its Q-axis
  lowering at Q = 1: it is bit-identical (carry, trace, key) to the same
  plan with ``queries_axis=True`` on the stacked carry, and an async one
  equals the ``scan`` plan.
* **Composed lowering parity** — ``run_search_multi_sharded`` (plans with
  queries_axis + shards) is bit-identical PER QUERY to that query's own
  single-query mesh plan on the same mesh with the same key, at any Q,
  with a deterministic detector: cross-query dedup and the per-shard
  detection cache change WHICH detector invocations happen, never the
  values a query consumes.  The in-process tests run the whole composed
  shard_map machinery on a 1-way mesh every tier-1 run; the slow
  subprocess test forces 8 host devices.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Execution,
    SearchPlan,
    init_carry,
    init_carry_multi,
    init_matcher,
    init_state,
    run_search,
    run_search_multi,
    run_search_multi_sharded,
    run_search_scan,
    stack_carries,
)
from repro.launch.mesh import make_data_mesh
from repro.sim import RepoSpec, generate
from repro.sim.oracle import oracle_detect


@pytest.fixture(scope="module")
def world():
    spec = RepoSpec(
        video_lengths=[5_000] * 3, num_instances=100, chunk_frames=500,
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


def _same_carry(a, b, qa=None, qb=None):
    pick = lambda x, q: x if q is None else jax.tree.map(lambda l: l[q], x)
    a, b = pick(a, qa), pick(b, qb)
    assert (int(a.step), int(a.results)) == (int(b.step), int(b.results))
    np.testing.assert_array_equal(np.asarray(a.sampler.n),
                                  np.asarray(b.sampler.n))
    np.testing.assert_array_equal(np.asarray(a.sampler.n1),
                                  np.asarray(b.sampler.n1))
    np.testing.assert_array_equal(np.asarray(a.key), np.asarray(b.key))


# ---------------------------------------------------------------------------
# Home-config parity: plan lowering ≡ legacy entry point, bit for bit
# ---------------------------------------------------------------------------


def test_plan_scan_parity_and_shim_deprecation(world):
    _, chunks, det = world
    with pytest.warns(DeprecationWarning, match="run_search_scan"):
        legacy, legacy_trace = run_search_scan(
            _fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det,
            result_limit=15, max_steps=900, cohorts=4, trace_every=50,
        )
    res = SearchPlan(
        result_limit=15, max_steps=900, cohorts=4, trace_every=50,
    ).run(_fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det)
    assert res.kind == "scan"
    _same_carry(legacy, res.carry)
    assert legacy_trace == res.trace
    assert res.stats.detector_invocations == res.steps[0]
    assert res.stats.matcher_capacity == 512


def test_plan_host_parity(world):
    _, chunks, det = world
    with pytest.warns(DeprecationWarning, match="run_search"):
        legacy, legacy_trace = run_search(
            _fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det,
            result_limit=8, max_steps=200, trace_every=25,
        )
    res = SearchPlan(
        result_limit=8, max_steps=200, trace_every=25,
        execution=Execution(strategy="host"),
    ).run(_fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det)
    assert res.kind == "host"
    _same_carry(legacy, res.carry)
    assert legacy_trace == res.trace


def test_plan_multi_parity(world):
    _, chunks, det = world
    q_n, limits = 4, (12, 12, 6, 12)
    keys = jnp.stack([_qkey(q) for q in range(q_n)])
    with pytest.warns(DeprecationWarning, match="run_search_multi"):
        legacy, ltraces, lstats = run_search_multi(
            _fresh_multi(chunks, keys), chunks, detector=det,
            result_limits=jnp.asarray(limits, jnp.int32), max_steps=600,
            cohorts=4, trace_every=25, cache_frames=chunks.total_frames,
        )
    res = SearchPlan(
        queries=q_n, result_limit=limits, max_steps=600, cohorts=4,
        trace_every=25, execution=Execution(queries_axis=True, cache=-1),
    ).run(_fresh_multi(chunks, keys), chunks, detector=det)
    assert res.kind == "multi"
    for q in range(q_n):
        _same_carry(legacy, res.carry, qa=q, qb=q)
        assert ltraces[q] == res.traces[q]
    assert lstats["detector_invocations"] == res.stats.detector_invocations
    assert lstats["cache_hits"] == res.stats.cache_hits
    assert res.stats.frames_sampled == sum(res.steps)
    assert 0.0 <= res.stats.cache_hit_rate <= 1.0


def _lift_pair(plan, chunks, det, mesh, key):
    """Run ``plan`` on a single-query carry and the same plan with
    ``queries_axis=True`` on that carry stacked to ``[1]``."""
    solo = plan.run(_fresh(chunks, key), chunks, detector=det, mesh=mesh)
    stacked = dataclasses.replace(
        plan,
        execution=dataclasses.replace(plan.execution, queries_axis=True),
    ).run(stack_carries([_fresh(chunks, key)]), chunks, detector=det,
          mesh=mesh)
    assert solo.kind == stacked.kind == "multi_sharded"
    return solo, stacked


def test_plan_sharded_parity_1way(world, round_needs):
    _, chunks, det = world
    solo, stacked = _lift_pair(
        SearchPlan(
            result_limit=10, max_steps=400, cohorts=2,
            execution=Execution(strategy="sharded", sync_every=2),
        ),
        chunks, lambda key, frame: det(key, frame), make_data_mesh(1),
        jax.random.PRNGKey(0),
    )
    # the lift hands back a single-query carry and a single-query trace
    assert jnp.ndim(solo.carry.step) == 0
    _same_carry(solo.carry, stacked.carry, qb=0)
    assert solo.trace == stacked.traces[0]
    assert solo.stats == stacked.stats
    # merge ring pressure surfaced uniformly; every executed sync window
    # appended one trace entry here (no cap hit)
    assert solo.stats.merges == len(solo.trace)
    assert solo.stats.merge_high_water >= 1  # results were found and merged
    assert not solo.stats.merge_overflow
    # the detector evaluates each round's needed lanes rounded up to a
    # whole chunk, here the shard's whole batch of 2 lanes; both runs
    # went through the one compiled program, so each round counted twice
    jax.effects_barrier()
    assert len(round_needs) == 2 * solo.stats.rounds
    assert 2 * solo.stats.detector_lanes == sum(
        -(-n // 2) * 2 for n in round_needs
    )


@pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 host devices (CI 8-dev legs)"
)
def test_plan_sharded_parity_2way_in_process(world):
    _, chunks, det = world
    solo, stacked = _lift_pair(
        SearchPlan(
            result_limit=12, max_steps=400, cohorts=4,
            execution=Execution(shards=2),
        ),
        chunks, det, make_data_mesh(2), jax.random.PRNGKey(0),
    )
    _same_carry(solo.carry, stacked.carry, qb=0)
    assert solo.trace == stacked.traces[0]


# ---------------------------------------------------------------------------
# Composed lowering: per-query bit-parity with the single-query mesh plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cache", [0, -1])
def test_composed_each_query_matches_solo_sharded_1way(world, cache):
    _, chunks, det = world
    mesh = make_data_mesh(1)
    q_n, cohorts, sync_every = 3, 2, 2
    limits = [10, 5, 10]   # query 1 finishes early and must freeze
    keys = jnp.stack([_qkey(q) for q in range(q_n)])
    res = SearchPlan(
        queries=q_n, result_limit=tuple(limits), max_steps=400,
        cohorts=cohorts,
        execution=Execution(
            strategy="sharded", sync_every=sync_every,
            cache=cache if cache else None,
        ),
    ).run(_fresh_multi(chunks, keys), chunks, detector=det, mesh=mesh)
    assert res.kind == "multi_sharded"
    for q in range(q_n):
        solo = SearchPlan(
            result_limit=limits[q], max_steps=400, cohorts=cohorts,
            execution=Execution(strategy="sharded", sync_every=sync_every),
        ).run(_fresh(chunks, keys[q]), chunks, detector=det, mesh=mesh)
        _same_carry(solo.carry, res.carry, qb=q)
        assert solo.trace == res.traces[q], f"query {q} trace diverged"
    # sharing can only save detector work, never add any
    assert res.stats.detector_invocations <= res.stats.frames_sampled
    assert res.stats.frames_sampled == sum(res.steps)


def test_composed_identical_queries_dedup_exactly(world):
    """Q identical queries sample identical frames every round; the
    per-shard dedup collapses them to ONE invocation each even with the
    cache off: invocations · Q == frames sampled."""
    _, chunks, det = world
    q_n = 4
    keys = jnp.stack([jax.random.PRNGKey(3)] * q_n)
    out, _, stats = run_search_multi_sharded(
        _fresh_multi(chunks, keys), chunks, mesh=make_data_mesh(1),
        detector=det, result_limits=10, max_steps=200, cohorts=2,
    )
    steps = np.asarray(out.step)
    assert (steps == steps[0]).all()
    assert stats["detector_invocations"] * q_n == stats["frames_sampled"]


def test_composed_rejects_bad_geometry(world):
    _, chunks, det = world
    keys = jnp.stack([_qkey(q) for q in range(2)])
    carries = _fresh_multi(chunks, keys)
    with pytest.raises(ValueError, match="cohorts"):
        run_search_multi_sharded(
            carries, chunks, mesh=make_data_mesh(1), detector=det,
            result_limits=5, max_steps=16, cohorts=0,
        )
    with pytest.raises(ValueError, match="sync_every"):
        run_search_multi_sharded(
            carries, chunks, mesh=make_data_mesh(1), detector=det,
            result_limits=5, max_steps=16, cohorts=1, sync_every=0,
        )


def test_plan_async_lowering(world):
    """async_workers>0 on a single-query carry runs the slot runtime at
    Q = 1: it equals the scan plan's carry and trace, and its scheduler
    counters surface through the SAME SearchStats container."""
    _, chunks, det = world
    kw = dict(result_limit=12, max_steps=2_000, cohorts=4, trace_every=50)
    scan = SearchPlan(**kw).run(
        _fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det)
    res = SearchPlan(**kw, execution=Execution(async_workers=2)).run(
        _fresh(chunks, jax.random.PRNGKey(0)), chunks, detector=det)
    assert res.kind == "async_multi"
    _same_carry(scan.carry, res.carry)
    np.testing.assert_array_equal(np.asarray(scan.carry.matcher.times_seen),
                                  np.asarray(res.carry.matcher.times_seen))
    assert res.trace == scan.trace
    assert res.results[0] >= 12
    assert res.stats.merges == res.stats.rounds >= 1
    assert res.stats.merge_high_water >= 1
    assert res.stats.frames_sampled == res.steps[0]


COMPOSED_8DEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (Execution, SearchPlan, init_carry,
                            init_carry_multi, init_matcher, init_state)
    from repro.launch.mesh import make_data_mesh
    from repro.sim import RepoSpec, generate
    from repro.sim.oracle import oracle_detect

    spec = RepoSpec(video_lengths=[8_000] * 4, num_instances=150,
                    chunk_frames=800, locality=4.0, seed=5)
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    fresh = lambda k: init_carry(init_state(chunks.length),
                                 init_matcher(max_results=2048), k)
    fresh_multi = lambda ks: init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=2048), ks)
    q_n, cohorts, sync_every, budget = 4, 8, 2, 768
    limits = (25, 25, 10, 25)
    keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(0), q)
                      for q in range(q_n)])
    mesh = make_data_mesh(8)
    res = SearchPlan(
        queries=q_n, result_limit=limits, max_steps=budget,
        cohorts=cohorts,
        execution=Execution(shards=8, sync_every=sync_every, cache=-1),
    ).run(fresh_multi(keys), chunks, detector=det, mesh=mesh)
    assert res.kind == "multi_sharded"
    for q in range(q_n):
        one = SearchPlan(
            result_limit=limits[q], max_steps=budget, cohorts=cohorts,
            execution=Execution(shards=8, sync_every=sync_every),
        ).run(fresh(keys[q]), chunks, detector=det, mesh=mesh)
        solo, solo_trace = one.carry, one.trace
        assert (int(solo.step), int(solo.results)) == (
            res.steps[q], res.results[q]), (q, int(solo.step), res.steps[q])
        assert solo_trace == res.traces[q], q
        np.testing.assert_array_equal(
            np.asarray(solo.sampler.n), np.asarray(res.carry.sampler.n[q]))
        np.testing.assert_array_equal(
            np.asarray(solo.sampler.n1), np.asarray(res.carry.sampler.n1[q]))
        np.testing.assert_array_equal(
            np.asarray(solo.key), np.asarray(res.carry.key[q]))
        print(f"composed q={q}: bit-identical to its own mesh plan "
              f"({res.steps[q]} steps, {res.results[q]} results)")
    assert res.stats.detector_invocations < res.stats.frames_sampled
    print("invocations", res.stats.detector_invocations,
          "of", res.stats.frames_sampled, "frames sampled")
    print("ALL_OK")
    """
)


@pytest.mark.slow
def test_composed_parity_multidevice():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", COMPOSED_8DEV_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert "ALL_OK" in r.stdout, r.stdout[-3000:] + "\n" + r.stderr[-3000:]
