"""SearchPlan validation, serde round-trip, and lowering-rule tests
(DESIGN.md §10).

Every invalid plan must fail with a *typed* ``PlanError`` whose message
names the offending option; any VALID plan must survive
``from_dict(to_dict(plan)) == plan`` exactly (property-tested with
hypothesis).
"""
import warnings

import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    Execution,
    PlanCompatibilityError,
    PlanError,
    PlanValueError,
    SearchPlan,
    SearchStats,
    lower,
)


# ---------------------------------------------------------------------------
# Typed validation errors with actionable messages
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "plan, err, needle",
    [
        # option values invalid on their own
        (SearchPlan(queries=0), PlanValueError, "queries"),
        (SearchPlan(max_steps=0), PlanValueError, "max_steps"),
        (SearchPlan(cohorts=0), PlanValueError, "cohorts"),
        (SearchPlan(trace_every=-1), PlanValueError, "trace_every"),
        (SearchPlan(result_limit=0), PlanValueError, "result_limit"),
        (SearchPlan(queries=2, result_limit=(5, 5, 5)), PlanValueError,
         "result_limit"),
        (SearchPlan(method="gibbs"), PlanValueError, "method"),
        (SearchPlan(execution=Execution(strategy="warp")), PlanValueError,
         "strategy"),
        (SearchPlan(execution=Execution(shards=0)), PlanValueError, "shards"),
        (SearchPlan(execution=Execution(sync_every=0)), PlanValueError,
         "sync_every"),
        (SearchPlan(execution=Execution(async_workers=-1)), PlanValueError,
         "async_workers"),
        (SearchPlan(queries=2, execution=Execution(cache=0)), PlanValueError,
         "cache"),
        (SearchPlan(queries=2, execution=Execution(cache=-7)), PlanValueError,
         "cache"),
        # individually-valid options that no lowering can combine
        (SearchPlan(execution=Execution(async_workers=2, shards=4)),
         PlanCompatibilityError, "async_workers"),
        (SearchPlan(execution=Execution(strategy="async")),
         PlanCompatibilityError, "async_workers"),
        (SearchPlan(execution=Execution(cache=128)),
         PlanCompatibilityError, "queries_axis"),
        (SearchPlan(queries=4, execution=Execution(strategy="scan")),
         PlanCompatibilityError, "strategy"),
        (SearchPlan(queries=4, execution=Execution(strategy="host")),
         PlanCompatibilityError, "strategy"),
        (SearchPlan(execution=Execution(strategy="scan", shards=4)),
         PlanCompatibilityError, "strategy"),
        (SearchPlan(execution=Execution(sync_every=4)),
         PlanCompatibilityError, "sync_every"),
        (SearchPlan(cohorts=3, execution=Execution(shards=2)),
         PlanCompatibilityError, "cohorts"),
        (SearchPlan(cohorts=2, method="exact",
                    execution=Execution(shards=2)),
         PlanCompatibilityError, "method"),
        (SearchPlan(cohorts=2, method="pallas",
                    execution=Execution(shards=2)),
         PlanCompatibilityError, "method"),
        (SearchPlan(method="pallas",
                    execution=Execution(async_workers=2)),
         PlanCompatibilityError, "method"),
    ],
)
def test_invalid_plans_raise_typed_errors(plan, err, needle):
    with pytest.raises(err, match=needle):
        plan.resolve()
    # every PlanError is a ValueError (legacy except-clauses keep working)
    # and carries the offending field for tooling
    with pytest.raises(ValueError):
        plan.lower()
    try:
        plan.resolve()
    except PlanError as e:
        assert e.field is not None


def test_unknown_keys_rejected():
    with pytest.raises(PlanValueError, match="max_step"):
        SearchPlan.from_dict({"max_step": 100})
    with pytest.raises(PlanValueError, match="shard"):
        SearchPlan.from_dict({"execution": {"shard": 4}})


# ---------------------------------------------------------------------------
# Lowering rules (DESIGN.md §10 table)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "plan, kind, method",
    [
        (SearchPlan(), "scan", "exact"),
        (SearchPlan(execution=Execution(strategy="host")), "host", "exact"),
        (SearchPlan(method="pallas"), "scan", "pallas"),
        (SearchPlan(cohorts=8, execution=Execution(shards=8)),
         "multi_sharded", "wilson_hilferty"),
        (SearchPlan(execution=Execution(strategy="sharded")),
         "multi_sharded", "wilson_hilferty"),
        (SearchPlan(queries=4), "multi", "exact"),
        (SearchPlan(execution=Execution(queries_axis=True)), "multi",
         "exact"),
        (SearchPlan(execution=Execution(queries_axis=True, cache=-1)),
         "multi", "exact"),
        (SearchPlan(queries=4, cohorts=8, execution=Execution(shards=8)),
         "multi_sharded", "wilson_hilferty"),
        (SearchPlan(execution=Execution(queries_axis=True, cache=64,
                                        strategy="sharded")),
         "multi_sharded", "wilson_hilferty"),
        (SearchPlan(execution=Execution(async_workers=2)), "async_multi",
         "exact"),
        (SearchPlan(trace_every=16, execution=Execution(async_workers=2)),
         "async_multi", "exact"),
        (SearchPlan(queries=4, execution=Execution(async_workers=2)),
         "async_multi", "exact"),
        (SearchPlan(execution=Execution(queries_axis=True, async_workers=1,
                                        cache=-1)),
         "async_multi", "exact"),
        (SearchPlan(queries=2, trace_every=16,
                    execution=Execution(async_workers=2)),
         "async_multi", "exact"),
    ],
)
def test_lowering_kind(plan, kind, method):
    lp = lower(plan)
    assert (lp.kind, lp.method) == (kind, method)


def test_uniform_stats_fields():
    """Every lowering reports through the SAME SearchStats container —
    the fields the async/multi paths used to scatter across ad-hoc dicts."""
    s = SearchStats()
    for field in (
        "detector_invocations", "cache_hits", "rounds", "frames_sampled",
        "merge_high_water", "merge_overflow", "merges", "reissues",
        "duplicate_drops", "results_spilled", "matcher_inserted",
        "matcher_capacity", "detector_lanes",
    ):
        assert hasattr(s, field)
    assert s.cache_hit_rate == 0.0
    assert SearchStats(cache_hits=3, detector_invocations=9).cache_hit_rate \
        == pytest.approx(0.25)
    assert SearchStats(frames_sampled=80,
                       detector_invocations=10).amortization == 8.0


# ---------------------------------------------------------------------------
# Serde round-trip property: any valid plan survives to_dict/from_dict
# ---------------------------------------------------------------------------


def _maybe_valid_plan(q, limit, per_query, max_steps, cohorts_per_shard,
                      method, trace_every, strategy, shards, queries_axis,
                      sync_every, async_workers, cache):
    ex = Execution(
        strategy=strategy, shards=shards, queries_axis=queries_axis,
        sync_every=sync_every, async_workers=async_workers, cache=cache,
    )
    rl = tuple(limit + i for i in range(q)) if per_query else limit
    return SearchPlan(
        queries=q, result_limit=rl, max_steps=max_steps,
        cohorts=cohorts_per_shard * shards, method=method,
        trace_every=trace_every, execution=ex,
    )


@settings(max_examples=80)
@given(
    q=st.integers(1, 5),
    limit=st.integers(1, 100),
    per_query=st.booleans(),
    max_steps=st.integers(1, 10_000),
    cohorts_per_shard=st.integers(1, 4),
    method=st.sampled_from(["auto", "exact", "wilson_hilferty", "pallas"]),
    trace_every=st.integers(0, 64),
    strategy=st.sampled_from(["auto", "host", "scan", "sharded", "async"]),
    shards=st.sampled_from([1, 2, 8]),
    queries_axis=st.booleans(),
    sync_every=st.integers(1, 4),
    async_workers=st.integers(0, 3),
    cache=st.sampled_from([None, -1, 1, 4096]),
)
def test_plan_roundtrips_to_dict(q, limit, per_query, max_steps,
                                 cohorts_per_shard, method, trace_every,
                                 strategy, shards, queries_axis, sync_every,
                                 async_workers, cache):
    plan = _maybe_valid_plan(
        q, limit, per_query, max_steps, cohorts_per_shard, method,
        trace_every, strategy, shards, queries_axis, sync_every,
        async_workers, cache,
    )
    try:
        kind, meth = plan.resolve()
    except PlanError:
        return  # invalid combination — only valid plans must round-trip
    d = plan.to_dict()
    # the dict is json-plain: no tuples, a nested execution dict
    assert isinstance(d["execution"], dict)
    assert not isinstance(d["result_limit"], tuple)
    back = SearchPlan.from_dict(d)
    assert back == plan
    assert back.resolve() == (kind, meth)
    # and the round-trip is a fixed point
    assert SearchPlan.from_dict(back.to_dict()) == back


def test_from_dict_accepts_json_lists():
    plan = SearchPlan.from_dict(
        {"queries": 2, "result_limit": [3, 4],
         "execution": {"queries_axis": True}}
    )
    assert plan.result_limit == (3, 4)
    assert plan == SearchPlan(
        queries=2, result_limit=(3, 4),
        execution=Execution(queries_axis=True),
    )


def test_run_reconciles_mesh_with_plan_geometry():
    """A caller-supplied mesh must provide exactly the validated shards on
    the declared axis, and a non-'data' axis cannot be auto-built."""
    from repro.core import init_carry, init_matcher, init_state
    from repro.launch.mesh import make_data_mesh
    from repro.sim import RepoSpec, generate

    _, chunks = generate(RepoSpec(
        video_lengths=[500], num_instances=10, chunk_frames=100, seed=0))
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=32),
        jax.random.PRNGKey(0),
    )
    det = lambda key, frame: None
    plan2 = SearchPlan(cohorts=2, execution=Execution(shards=2))
    with pytest.raises(PlanError, match="shards"):
        plan2.run(carry, chunks, detector=det, mesh=make_data_mesh(1))
    with pytest.raises(PlanError, match="axis"):
        SearchPlan(execution=Execution(strategy="sharded", axis="model")) \
            .run(carry, chunks, detector=det)


def test_legacy_cli_flags_build_valid_plans():
    """The deprecated launch flags must keep translating into VALID plans
    — including --sync-every without --mesh, which the old CLI silently
    ignored (regression: the planner rejects sync_every>1 off the mesh)."""
    import argparse

    from repro.launch.search import build_plan

    base = dict(
        plan="", mesh=1, sync_every=1, queries=None, cache_frames=-1,
        driver="scan", limit=10, max_steps=100, cohorts=4,
    )
    mk = lambda **kw: argparse.Namespace(**{**base, **kw})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert build_plan(mk(sync_every=4)).resolve() == ("scan", "exact")
        assert build_plan(mk(mesh=2, sync_every=4, cohorts=4)).resolve() \
            == ("multi_sharded", "wilson_hilferty")
        assert build_plan(mk(mesh=2, cohorts=5)).execution.shards == 2
        assert build_plan(mk(queries=[0, 1])).resolve() == ("multi", "exact")
        assert build_plan(
            mk(queries=[0, 1], mesh=2, cohorts=4)
        ).resolve() == ("multi_sharded", "wilson_hilferty")
        assert build_plan(mk(driver="host")).resolve() == ("host", "exact")
    # every legacy driver-shaping combination warns
    with pytest.warns(DeprecationWarning, match="--plan"):
        build_plan(mk(sync_every=4))


def test_plan_run_rejects_mismatched_carry():
    """Carry shape must agree with the plan's query axis."""
    import jax.numpy as jnp

    from repro.core import init_carry, init_carry_multi, init_matcher, \
        init_state
    from repro.sim import RepoSpec, generate

    _, chunks = generate(RepoSpec(
        video_lengths=[500], num_instances=10, chunk_frames=100, seed=0))
    single = init_carry(
        init_state(chunks.length), init_matcher(max_results=32),
        jax.random.PRNGKey(0),
    )
    multi = init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=32),
        jnp.stack([jax.random.PRNGKey(0)] * 2),
    )
    det = lambda key, frame: None
    with pytest.raises(PlanError, match="leading"):
        SearchPlan(queries=2).run(single, chunks, detector=det)
    with pytest.raises(PlanError, match="queries"):
        SearchPlan().run(multi, chunks, detector=det)
    # a single-query plan on a Q-axis lowering takes a single-query carry
    # (the executor lifts it); a plan that asks for the Q axis needs [Q]
    with pytest.raises(PlanError, match="queries"):
        SearchPlan(execution=Execution(async_workers=1)).run(
            multi, chunks, detector=det)
    with pytest.raises(PlanError, match="leading"):
        SearchPlan(execution=Execution(queries_axis=True, async_workers=1)) \
            .run(single, chunks, detector=det)
    with pytest.raises(PlanError, match="select"):
        SearchPlan().run(single, chunks, detector=det,
                         select=lambda q, d: d.valid)


@pytest.mark.parametrize("queries", [1, 2])
def test_batch_run_marks_its_host_phases(tmp_path, queries):
    """A plan's host wrapper leaves ``exsample.*`` spans a profile reads:
    the set-up of the Q-axis carry and cache, the dispatch of the device
    program, then the readback of its results, in that order."""
    import glob

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.core import init_carry, init_carry_multi, init_matcher, \
        init_state
    from repro.sim import RepoSpec, generate
    from repro.sim.oracle import oracle_detect

    repo, chunks = generate(RepoSpec(
        video_lengths=[2_000], num_instances=20, chunk_frames=500, seed=3))
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    state, matcher = init_state(chunks.length), init_matcher(max_results=64)
    if queries == 1:
        plan = SearchPlan(result_limit=3, max_steps=40, cohorts=2)
        carry = init_carry(state, matcher, jax.random.PRNGKey(0))
        want = ["exsample.dispatch", "exsample.readback"]
    else:
        plan = SearchPlan(
            queries=2, result_limit=3, max_steps=40, cohorts=2,
            execution=Execution(queries_axis=True, cache=-1),
        )
        carry = init_carry_multi(
            state, matcher, jnp.stack([jax.random.PRNGKey(q) for q in (0, 1)])
        )
        want = ["exsample.prepare", "exsample.dispatch", "exsample.readback"]
    plan.run(carry, chunks, detector=det)   # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan.run(carry, chunks, detector=det)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = sorted(
        (e.start_ns, e.name)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("exsample.")
    )
    names = [n for _, n in spans]
    # the packaging of the result reads back once more at the end
    assert names[:len(want)] == want and names[-1] == "exsample.readback"


def test_ensure_host_devices_forces_virtual_devices_only_on_cpu(monkeypatch):
    """A mesh wider than the host re-execs onto virtual CPU devices only
    when the caller pinned JAX_PLATFORMS=cpu; on any other platform it
    raises, naming the count needed, instead of moving to the CPU."""
    import subprocess

    from repro.launch import mesh

    calls = []
    monkeypatch.setattr(
        subprocess, "call", lambda argv, env: calls.append(env) or 0
    )
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    with pytest.raises(SystemExit):
        mesh.ensure_host_devices(4, argv=["child"])
    assert "device_count=4" in calls[0]["XLA_FLAGS"]
    assert calls[0]["JAX_PLATFORMS"] == "cpu"
    # a forced count that is still short is the repeat guard: no re-exec
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    with pytest.raises(RuntimeError, match="need 4"):
        mesh.ensure_host_devices(4, argv=["child"])
    assert len(calls) == 1
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("XLA_FLAGS")
    with pytest.raises(RuntimeError, match="need 4 devices"):
        mesh.ensure_host_devices(4, argv=["child"])
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        mesh.virtual_device_env(4)
    assert len(calls) == 1
