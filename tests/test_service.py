"""Multi-tenant search service: admission + SLO scheduling (DESIGN.md §12).

The acceptance bar composes the driver's (tests/test_async_compose.py):
admission control must price plans with the §4.6 cost model and debit a
race-free ledger; slots must be REUSED across tenant generations rather
than growing the pool; and multi-tenancy must not perturb any tenant's
search — each admitted tenant's trajectory is bit-identical to its solo
``run_search_scan`` run at its debited frame budget.  The E2E test drives
four tenants through the ``repro.launch.serve_search`` front onto one
live driver with admission rejections/queueing and verifies zero result
loss (``results == ring live entries + len(ResultLog)`` per tenant).
"""
import argparse
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    init_carry,
    init_carry_multi,
    init_matcher,
    init_state,
    run_search_scan,
)
from repro.core.plan import Execution, PlanError, SearchPlan, ServiceConfig
from repro.sim import RepoSpec, generate
from repro.sim.costmodel import CostRates, plan_projected_cost
from repro.sim.oracle import class_select, oracle_detect
from repro.serve.service import (
    FINISHED,
    QUEUED,
    REJECTED,
    RUNNING,
    SearchService,
)

warnings.filterwarnings("ignore", message="run_search_scan")

RATES = CostRates()
# default rates: 1/detect_fps + 1/random_read_fps = 0.12 s per sampled frame
FRAME_S = 1.0 / RATES.detect_fps + 1.0 / RATES.random_read_fps


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


def _proto(chunks, max_results=64):
    return init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=max_results),
        jnp.stack([jax.random.PRNGKey(0)]),
    )


def _service(chunks, det, **kw):
    kw.setdefault("cohorts", 2)
    kw.setdefault("num_workers", 1)
    kw.setdefault("slots_per_batch", 2)
    return SearchService(_proto(chunks), chunks, det, rates=RATES, **kw)


def _plan(max_steps=1500, limit=8, service=None, cohorts=2):
    return SearchPlan(
        result_limit=limit, max_steps=max_steps, cohorts=cohorts,
        execution=Execution(queries_axis=True, service=service),
    )


def _drain_sync(svc, deadline_s=120.0):
    svc.start(pump=False)
    svc.drain(deadline_s=deadline_s)
    svc.stop()


# ---------------------------------------------------------------------------
# Admission control: accept / reject / queue matrix under CostRates budgets
# ---------------------------------------------------------------------------


def test_admission_accept_reject_matrix(world):
    """Projected cost vs remaining budget decides accept/queue/reject —
    priced BEFORE anything runs, so no tick is needed to observe it."""
    _, chunks, det = world
    svc = _service(chunks, det, budget_s=1000 * FRAME_S)

    a = svc.submit("a", _plan(max_steps=600), key=_qkey(0))
    assert a.state == RUNNING
    assert a.projected_s == pytest.approx(600 * FRAME_S)
    assert svc.budget.committed_s == pytest.approx(600 * FRAME_S)

    # fits the total but not the remainder: rejected without queue_on_reject
    b = svc.submit("b", _plan(max_steps=600), key=_qkey(1))
    assert b.state == REJECTED and "remaining" in b.reason

    # same projection, queue_on_reject: parked, budget NOT debited
    c = svc.submit(
        "c", _plan(max_steps=600, service=ServiceConfig(queue_on_reject=True)),
        key=_qkey(2),
    )
    assert c.state == QUEUED
    assert svc.budget.committed_s == pytest.approx(600 * FRAME_S)

    # can never fit: rejected outright even with queue_on_reject (queueing
    # it would deadlock the drain)
    d = svc.submit(
        "d",
        _plan(max_steps=100_000, service=ServiceConfig(queue_on_reject=True)),
        key=_qkey(3),
    )
    assert d.state == REJECTED and "total" in d.reason

    # multi-query plans are not admissible service units
    with pytest.raises(PlanError, match="single-query"):
        svc.submit("e", SearchPlan(queries=2, execution=Execution(
            queries_axis=True)), key=_qkey(4))
    with pytest.raises(PlanError, match="already submitted"):
        svc.submit("a", _plan(), key=_qkey(0))


def test_projection_matches_costmodel(world):
    plan = _plan(max_steps=777)
    assert plan_projected_cost(plan, RATES).total_s == pytest.approx(
        777 * FRAME_S)


def test_warm_plan_admitted_where_cold_projection_rejects(world):
    """Regression (warm-plan over-pricing): a plan whose detections are
    ~90% persisted in the shared index was still priced as if every frame
    paid a fresh detector call, so admission rejected it under budgets it
    trivially fits.  The coverage-discounted projection must admit it,
    stay ≥ the scan-only floor, and settle normally with the credit
    surfaced in per-tenant economics."""
    from repro.core.plan import IndexSpec
    from repro.index.store import RepositoryIndex

    _, chunks, det = world
    index = RepositoryIndex(detector_version="v1")
    covered = int(0.9 * chunks.total_frames)
    f = jnp.arange(covered, dtype=jnp.int32)
    index.publish(f, f.astype(jnp.float32))
    coverage = covered / chunks.total_frames

    ms = 1500
    cold = plan_projected_cost(_plan(max_steps=ms), RATES).total_s
    assert cold == pytest.approx(ms * FRAME_S)

    warm_plan = SearchPlan(
        result_limit=8, max_steps=ms, cohorts=2,
        execution=Execution(
            queries_axis=True, index=IndexSpec(detector_version="v1"),
        ),
    )
    warm = plan_projected_cost(
        warm_plan, RATES, index=index, total_frames=chunks.total_frames
    ).total_s
    scan_floor = ms / RATES.random_read_fps
    assert warm == pytest.approx(
        ms * ((1 - coverage) / RATES.detect_fps + 1 / RATES.random_read_fps))
    assert scan_floor <= warm < cold

    # a budget between warm and cold: rejects the cold projection,
    # admits the coverage-discounted one
    budget = 0.5 * (warm + cold)
    svc = _service(chunks, det, budget_s=budget, index=index)
    t = svc.submit("warm", warm_plan, key=_qkey(0))
    assert t.state == RUNNING
    assert t.projected_s == pytest.approx(warm)
    assert svc.budget.committed_s == pytest.approx(warm)
    _drain_sync(svc)
    assert t.state == FINISHED
    assert svc.budget.committed_s == pytest.approx(0.0)
    steps = int(t.row_obj.carry.step)
    assert t.actual_s == pytest.approx(steps * FRAME_S)
    econ = t.to_dict()["projected_vs_settled"]
    assert econ["projected_s"] == pytest.approx(warm)
    assert econ["settled_s"] == pytest.approx(t.actual_s)
    assert econ["credited_s"] == pytest.approx(warm - t.actual_s)


def test_warm_projection_requires_index_binding(world):
    """No IndexSpec on the plan, or no live index/total_frames at the
    call, keeps the cold upper bound — the discount never applies by
    accident."""
    from repro.index.store import RepositoryIndex

    _, chunks, _ = world
    index = RepositoryIndex(detector_version="v1")
    f = jnp.arange(100, dtype=jnp.int32)
    index.publish(f, f.astype(jnp.float32))
    plan = _plan(max_steps=500)                # no IndexSpec
    cold = 500 * FRAME_S
    assert plan_projected_cost(
        plan, RATES, index=index, total_frames=chunks.total_frames
    ).total_s == pytest.approx(cold)
    from repro.core.plan import IndexSpec
    bound = SearchPlan(
        result_limit=8, max_steps=500,
        execution=Execution(
            queries_axis=True, index=IndexSpec(detector_version="v1"),
        ),
    )
    assert plan_projected_cost(bound, RATES).total_s == pytest.approx(cold)
    assert plan_projected_cost(
        bound, RATES, index=index, total_frames=0
    ).total_s == pytest.approx(cold)
    # wrong detector version reads an empty tier: no discount
    assert plan_projected_cost(
        dataclasses.replace(
            bound,
            execution=Execution(
                queries_axis=True, index=IndexSpec(detector_version="v9"),
            ),
        ),
        RATES, index=index, total_frames=chunks.total_frames,
    ).total_s == pytest.approx(cold)


def test_budget_settles_actual_and_credits_unspent(world):
    """The admission debit is an upper bound; retirement settles the
    realized sampling cost and credits the rest back to headroom."""
    _, chunks, det = world
    svc = _service(chunks, det, budget_s=10_000 * FRAME_S)
    t = svc.submit("a", _plan(max_steps=5_000, limit=4), key=_qkey(0))
    _drain_sync(svc)
    assert t.state == FINISHED
    assert svc.budget.committed_s == pytest.approx(0.0)
    steps = int(t.row_obj.carry.step)
    assert t.actual_s == pytest.approx(steps * FRAME_S)
    assert svc.budget.spent_s == pytest.approx(t.actual_s)
    assert t.actual_s < t.projected_s          # limit hit early ⇒ credit
    assert svc.budget.remaining_s == pytest.approx(
        10_000 * FRAME_S - t.actual_s)


# ---------------------------------------------------------------------------
# Slot reuse + queued admission
# ---------------------------------------------------------------------------


def test_slot_reuse_after_retire(world):
    """Sequential tenants reuse the same Q-axis slot: the pool's device
    footprint tracks concurrency, not tenant count."""
    _, chunks, det = world
    svc = _service(chunks, det)
    a = svc.submit("a", _plan(limit=3), key=_qkey(0))
    _drain_sync(svc)
    b = svc.submit("b", _plan(limit=3), key=_qkey(1))
    _drain_sync(svc)
    assert a.state == b.state == FINISHED
    assert a.row == b.row                     # same slot, two generations
    assert len(svc.driver.rows) == 1          # proto slot only, never grew
    # harvested rows stay distinct objects with their own results
    assert a.row_obj is not b.row_obj
    assert int(a.row_obj.carry.results) >= 3
    assert int(b.row_obj.carry.results) >= 3


def test_queued_tenants_admit_by_priority_when_capacity_frees(world):
    """Capacity freed by a retirement admits parked plans highest-priority
    first (FIFO within a level), and the head blocks the tail."""
    _, chunks, det = world
    svc = _service(chunks, det, budget_s=1000 * FRAME_S)
    t1 = svc.submit("t1", _plan(max_steps=900, limit=3), key=_qkey(0))
    lo = svc.submit(
        "lo", _plan(max_steps=900, limit=3,
                    service=ServiceConfig(queue_on_reject=True, priority=0)),
        key=_qkey(1))
    hi = svc.submit(
        "hi", _plan(max_steps=900, limit=3,
                    service=ServiceConfig(queue_on_reject=True, priority=5)),
        key=_qkey(2))
    assert t1.state == RUNNING and lo.state == QUEUED and hi.state == QUEUED
    _drain_sync(svc)
    assert {t.state for t in (t1, lo, hi)} == {FINISHED}
    # hi (later submit, higher priority) was admitted before lo
    assert hi.row_obj.admitted_s < lo.row_obj.admitted_s


def test_queued_plan_that_can_never_fit_is_rejected_not_stuck(world):
    """Regression: ``spent_s`` is never credited back, so a parked plan
    whose projection exceeds ``total − spent`` can never be admitted.  It
    used to sit QUEUED forever once earlier tenants settled their spend —
    ``busy()`` stayed True and ``drain()`` span to TimeoutError.  The pump
    must re-reject it the moment the shrunken ceiling rules it out."""
    _, chunks, det = world
    svc = _service(chunks, det, budget_s=1000 * FRAME_S)
    # `a` fits and will exhaust its whole 600-frame budget (limit is
    # unreachable), settling spent_s ≈ 600 frames
    a = svc.submit("a", _plan(max_steps=600, limit=64), key=_qkey(0))
    b = svc.submit(
        "b", _plan(max_steps=600, limit=3,
                   service=ServiceConfig(queue_on_reject=True)),
        key=_qkey(1))
    assert a.state == RUNNING and b.state == QUEUED
    _drain_sync(svc, deadline_s=60.0)          # pre-fix: TimeoutError here
    assert a.state == FINISHED
    assert int(a.row_obj.carry.step) == 600    # spend settled at 600 frames
    # after settling, total − spent = 400 frames < b's 600-frame projection
    assert b.state == REJECTED and "never fit" in b.reason
    assert svc.budget.committed_s == pytest.approx(0.0)


def test_rejected_tenant_can_resubmit_under_same_id(world):
    """A rejection is terminal for the PLAN, not the tenant id: the same
    tenant may come back with a smaller plan (and a finished id may be
    reused), while QUEUED/RUNNING ids stay exclusive."""
    _, chunks, det = world
    svc = _service(chunks, det, budget_s=1000 * FRAME_S)
    r = svc.submit("a", _plan(max_steps=100_000), key=_qkey(0))
    assert r.state == REJECTED
    t = svc.submit("a", _plan(max_steps=500, limit=3), key=_qkey(0))
    assert t.state == RUNNING
    with pytest.raises(PlanError, match="already submitted"):
        svc.submit("a", _plan(max_steps=500, limit=3), key=_qkey(0))
    _drain_sync(svc)
    assert t.state == FINISHED
    again = svc.submit("a", _plan(max_steps=500, limit=3), key=_qkey(1))
    assert again.state == RUNNING
    _drain_sync(svc)
    assert again.state == FINISHED
    # the service keeps ONE record per id: the latest generation
    assert svc.tenants["a"] is again
    # terminal records can be evicted so a persistent service stays bounded
    assert svc.evict_terminal() == 1
    assert not svc.tenants and not svc.busy()


def test_running_tenant_slo_visible_before_retire(world):
    """Regression: SLO attainment must be visible for in-flight tenants —
    the driver stamps ``first_result_s`` at the merge, but the report used
    to read a ``row_obj`` only bound at reap time, so a RUNNING tenant
    whose first result had already merged reported ``ttfr_s=None``."""
    _, chunks, det = world
    svc = _service(chunks, det)
    t = svc.submit(
        "a", _plan(max_steps=1500, limit=64,
                   service=ServiceConfig(slo_latency_s=300.0)),
        key=_qkey(0))
    svc.start(pump=False)
    for _ in range(200):
        svc.tick(timeout=5.0)
        if t.state != RUNNING or t.row_obj.first_result_s:
            break
    assert t.state == RUNNING              # limit 64 is not hit this fast
    rep = t.slo_report()
    assert rep["ttfr_s"] is not None and rep["ttfr_s"] > 0
    assert rep["slo_met"] is True
    assert t.to_dict()["results"] >= 1     # live progress, same binding
    svc.drain()
    svc.stop()
    assert t.state == FINISHED


def test_concurrent_submits_race_the_background_pump(world):
    """Regression: the pump's ``_reap``/``busy`` used to iterate the live
    ``self.tenants`` dict while ``submit`` (another thread) inserted under
    the lock — a mid-iteration insert raised ``RuntimeError: dictionary
    changed size during iteration``, silently killing the pump so nothing
    ever retired and drain timed out.  Both now iterate locked snapshots;
    submitting against a hot pump must drain cleanly."""
    _, chunks, det = world
    svc = _service(chunks, det)
    svc.start(pump=True)
    try:
        tenants = [
            svc.submit(f"t{i}", _plan(max_steps=60, limit=2), key=_qkey(i))
            for i in range(12)
        ]
        svc.drain(deadline_s=60.0)
    finally:
        svc.stop()
    assert all(t.state == FINISHED for t in tenants)
    assert svc.budget.committed_s == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# Parity: multi-tenancy never perturbs a tenant's search
# ---------------------------------------------------------------------------


def _solo(chunks, det, key, *, result_limit, max_steps, cohorts=2):
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=64), key,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return run_search_scan(
            carry, chunks, detector=det, result_limit=result_limit,
            max_steps=max_steps, cohorts=cohorts,
        )


def test_two_tenant_solo_parity_at_debited_budget(world):
    """Each tenant's trajectory through the shared service — including one
    admitted mid-flight — is bit-identical to its solo ``run_search_scan``
    run at the frame budget the service debited it."""
    _, chunks, det = world
    svc = _service(chunks, det)
    a = svc.submit("a", _plan(max_steps=1500, limit=8), key=_qkey(0))
    svc.start(pump=False)
    for _ in range(3):                        # progress the pool, then join
        svc.tick(timeout=5.0)
    b = svc.submit("b", _plan(max_steps=1500, limit=8), key=_qkey(1))
    svc.drain()
    svc.stop()
    assert a.state == b.state == FINISHED
    # the late joiner was debited the frames it missed: a whole number of
    # pool rounds × cohorts off its requested 1500, the early one none
    assert a.row_obj.budget == 1500
    assert b.row_obj.budget < 1500
    assert (1500 - b.row_obj.budget) % svc.driver.cohorts == 0
    for tenant, key in ((a, _qkey(0)), (b, _qkey(1))):
        row = tenant.row_obj
        solo_out, _ = _solo(
            chunks, det, key, result_limit=8, max_steps=row.budget,
        )
        assert int(row.carry.step) == int(solo_out.step)
        assert int(row.carry.results) == int(solo_out.results)
        assert bool(jnp.all(row.carry.key == solo_out.key))
        np.testing.assert_array_equal(
            row.carry.sampler.n, solo_out.sampler.n)
        np.testing.assert_array_equal(
            row.carry.sampler.n1, solo_out.sampler.n1)
        np.testing.assert_array_equal(
            row.carry.matcher.times_seen, solo_out.matcher.times_seen)


def test_select_id_binds_tenant_predicate(world):
    """``select_id`` routes a tenant's lane to its own predicate through
    the service's ONE universe ``class_select`` — equivalent to a solo
    Q=1 run with the predicate bound directly, with no recompilation."""
    repo, chunks, _ = world
    num_classes = int(jnp.max(repo.inst_class)) + 1
    det_all = lambda key, frame: oracle_detect(repo, frame, query_class=None)
    svc = _service(
        chunks, det_all, select=class_select(repo, list(range(num_classes))),
    )
    tenants = {}
    for cls in (0, 1):
        tenants[cls] = svc.submit(
            f"cls{cls}", _plan(max_steps=1200, limit=5),
            key=_qkey(cls), select_id=cls,
        )
    _drain_sync(svc)
    for cls, tenant in tenants.items():
        assert tenant.state == FINISHED
        row = tenant.row_obj
        ref = SearchPlan(
            queries=1, result_limit=5, max_steps=row.budget, cohorts=2,
            execution=Execution(queries_axis=True),
        ).run(
            init_carry_multi(
                init_state(chunks.length), init_matcher(max_results=64),
                jnp.stack([_qkey(cls)]),
            ),
            chunks, detector=det_all, select=class_select(repo, [cls]),
        )
        assert int(row.carry.step) == ref.steps[0]
        assert int(row.carry.results) == ref.results[0]
        np.testing.assert_array_equal(
            row.carry.sampler.n, ref.carry.sampler.n[0])
        np.testing.assert_array_equal(
            row.carry.matcher.times_seen, ref.carry.matcher.times_seen[0])


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------


def test_slo_accounting(world):
    """Time-to-first-result is measured from admission; a generous SLO is
    met, an impossible one is missed, and no SLO reports None — the
    service reports attainment, it never kills a query."""
    _, chunks, det = world
    svc = _service(chunks, det)
    met = svc.submit(
        "met", _plan(limit=3, service=ServiceConfig(slo_latency_s=300.0)),
        key=_qkey(0))
    missed = svc.submit(
        "missed", _plan(limit=3, service=ServiceConfig(slo_latency_s=1e-9)),
        key=_qkey(1))
    none = svc.submit("none", _plan(limit=3), key=_qkey(2))
    _drain_sync(svc)
    for t in (met, missed, none):
        assert t.state == FINISHED
        rep = t.slo_report()
        assert rep["ttfr_s"] is not None and rep["ttfr_s"] > 0
        # wall-clock ordering: admission precedes first result, first
        # result precedes retirement
        row = t.row_obj
        assert row.admitted_s < row.first_result_s <= row.finished_s
    assert met.slo_report()["slo_met"] is True
    assert missed.slo_report()["slo_met"] is False
    assert none.slo_report()["slo_met"] is None


def test_per_tenant_stats_and_occupancy(world):
    """Per-tenant SearchStats attribute detector economics by dedup
    representative, and the service's batch occupancy follows the
    RequestBatcher ``occupancy = 1 − padding`` convention."""
    _, chunks, det = world
    svc = _service(chunks, det)
    a = svc.submit("a", _plan(limit=4), key=_qkey(0))
    b = svc.submit("b", _plan(limit=4), key=_qkey(1))
    _drain_sync(svc)
    st = svc.stats()
    d = svc.driver.stats
    assert abs(svc.occupancy + svc.padding_fraction() - 1.0) < 1e-12
    assert st["batch"]["lanes_issued"] == d["lanes_issued"] > 0
    # attributed economics sum to the pool totals: every fresh detector
    # call and cache hit belongs to exactly one tenant (its dedup rep)
    fresh = sum(t.stats.detector_invocations for t in (a, b))
    hits = sum(t.stats.cache_hits for t in (a, b))
    assert fresh == d["detector_invocations"]
    assert hits == d["cache_hits"]
    for t in (a, b):
        s = t.stats
        assert s.frames_sampled == int(t.row_obj.carry.step)
        assert s.rounds == t.row_obj.rounds > 0
        assert s.results_spilled == len(t.row_obj.log)


def test_rounds_leave_spans_and_stamps_a_profile_can_read(world, tmp_path):
    """Under the profiler the pump thread's ``exsample.issue``/``merge``/
    ``reap``/``admit`` spans and the worker's ``exsample.process`` spans
    land on their own threads, each merged round's spans share its batch
    id, and ``stats()`` reports the rounds' stamps."""
    import glob

    from jax.profiler import ProfileData

    _, chunks, det = world
    svc = _service(chunks, det)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.start(pump=True)
        svc.submit("a", _plan(max_steps=80, limit=3), key=_qkey(0))
        svc.submit("b", _plan(max_steps=80, limit=3), key=_qkey(1))
        svc.drain(deadline_s=60.0)
    finally:
        svc.stop()
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    threads = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("exsample."):
                    threads.setdefault((plane.name, i), []).append(
                        (e.name, dict(e.stats)))
    names = {t: {n for n, _ in evs} for t, evs in threads.items()}
    pump = [t for t, n in names.items() if "exsample.merge" in n]
    workers = [t for t, n in names.items() if "exsample.process" in n]
    assert len(pump) == 1 and workers and pump[0] not in workers
    assert {"exsample.issue", "exsample.merge", "exsample.reap",
            "exsample.admit"} <= names[pump[0]]

    def batches(span):
        return sorted(a["batch"] for evs in threads.values()
                      for n, a in evs if n == span)

    assert batches("exsample.merge") == batches("exsample.process")
    rounds = svc.stats()["rounds"]
    assert rounds["rounds"] == len(batches("exsample.merge")) > 0
    assert 0.0 < rounds["round_p50_s"] and 0.0 <= rounds["slot_wait_p90_s"]


# ---------------------------------------------------------------------------
# E2E: four tenants over the front onto one live driver
# ---------------------------------------------------------------------------


def test_front_e2e_four_tenants_one_live_driver():
    """The stdin-RPC front: ≥4 tenants share one live driver, admission
    rejects one plan and queues another, the drain is clean and NO result
    is lost: per tenant, ``results == ring live entries + len(ResultLog)``."""
    from repro.launch.serve_search import build_service, handle_request

    args = argparse.Namespace(
        dataset="dashcam", scale=0.02, seed=0,
        budget_s=4 * 1200 * FRAME_S + 1.0,
        cohorts=4, workers=2, max_steps=100_000, max_results=256,
        slots_per_batch=4, cache=True,
    )
    service = build_service(args)
    service.start()   # background pump: requests arrive against live work
    try:
        def submit(tid, cls, seed, *, max_steps=1200, limit=4,
                   service_cfg=None):
            plan = {
                "result_limit": limit, "max_steps": max_steps, "cohorts": 4,
                "execution": {"queries_axis": True},
            }
            if service_cfg:
                plan["execution"]["service"] = service_cfg
            return handle_request(service, {
                "op": "submit", "tenant": tid, "class": cls,
                "seed": seed, "plan": plan,
            })

        live = [submit(f"t{i}", cls=i % service.num_classes, seed=i)
                for i in range(4)]
        assert all(r["ok"] and r["state"] == RUNNING for r in live)
        # 5th plan exceeds the REMAINING budget → queued for capacity
        queued = submit("t4", cls=0, seed=4,
                        service_cfg={"queue_on_reject": True})
        assert queued["ok"] and queued["state"] == QUEUED
        # 6th exceeds the TOTAL budget → rejected by admission
        rejected = submit("t5", cls=1, seed=5, max_steps=500_000)
        assert rejected["ok"] and rejected["state"] == REJECTED
        assert "budget" in rejected["reason"]
        # malformed plan surfaces a typed field error, not a crash
        bad = handle_request(service, {
            "op": "submit", "tenant": "bad", "class": 0,
            "plan": {"max_step": 5}})
        assert not bad["ok"] and bad["field"] == "max_step"

        resp = handle_request(service, {"op": "drain", "deadline_s": 300})
        assert resp["ok"]
    finally:
        service.stop()

    tenants = resp["tenants"]
    finished = [t for t in tenants.values() if t["state"] == FINISHED]
    assert len(finished) == 5                 # 4 live + the queued one
    assert tenants["t5"]["state"] == REJECTED
    assert "bad" not in tenants
    # zero result loss, per tenant: distinct results == live ring entries
    # + host-spilled entries
    for tid in ("t0", "t1", "t2", "t3", "t4"):
        row = service.tenants[tid].row_obj
        ring_live = int((np.asarray(row.carry.matcher.times_seen) > 0).sum())
        assert int(row.carry.results) == ring_live + len(row.log)
        assert int(row.carry.results) >= 1
        # every tenant retired for a legitimate reason: its result limit
        # or its (debited) frame budget — never dropped mid-flight
        assert (int(row.carry.results) >= 4
                or int(row.carry.step) >= row.budget)
    # budget ledger closed: nothing committed, spends settled
    assert resp["budget"]["committed_s"] == pytest.approx(0.0)
    assert resp["budget"]["spent_s"] > 0
    # every slot freed for reuse; the pool never grew past concurrency
    assert len(service.driver.rows) <= 4
    assert all(r.vacant for r in service.driver.rows)
    # unknown op is a clean protocol error
    assert not handle_request(service, {"op": "nope"})["ok"]


def test_pump_failure_fails_drain(world):
    """Regression: an exception in the background pump (here a worker
    failure re-raised by the driver's scheduler tick) used to kill the
    pump thread in silence.  ``drain()`` must raise it, not time out or
    report the tenants as drained; the front must answer ``ok: false`` to
    every later submit and stats request instead of admitting tenants
    onto a dead pump; and a restart clears the old failure."""
    from repro.launch.serve_search import handle_request
    from repro.serve.service import PumpFailure

    _, chunks, det = world
    calls = []

    def boom(frame):
        calls.append(frame)
        if len(calls) == 1:
            raise RuntimeError("detector down")
        return frame

    def failing(key, frame):
        frame = jax.pure_callback(
            boom, jax.ShapeDtypeStruct((), jnp.int32), frame,
            vmap_method="sequential",
        )
        return det(key, frame)

    svc = _service(chunks, failing)
    svc.submit("a", _plan(max_steps=600, limit=3), key=_qkey(0))
    svc.start(pump=True)
    try:
        with pytest.raises(PumpFailure, match="service pump failed"):
            svc.drain(deadline_s=60.0)
        resp = handle_request(svc, {
            "op": "submit", "tenant": "b", "class": 0,
            "plan": _plan(max_steps=600, limit=3).to_dict(),
        })
        assert not resp["ok"] and "pump failed" in resp["error"]
        assert "b" not in svc.tenants
        resp = handle_request(svc, {"op": "stats"})
        assert not resp["ok"] and "detector down" in resp["error"]
    finally:
        svc.stop()
    # a new pump starts clean: the old error is not raised again
    svc.start(pump=True)
    try:
        assert handle_request(svc, {"op": "stats"})["ok"]
    finally:
        svc.stop()
