"""Multi-tenant search service: admission + SLO scheduling (DESIGN.md §12).

The batch planner (DESIGN.md §10) answers "run these Q queries"; a video
repository in production answers a different question: queries ARRIVE, at
any time, from different tenants, and the operator grants a finite
GPU-time budget.  :class:`SearchService` is the persistent layer between
the two — it accepts declarative :class:`~repro.core.plan.SearchPlan`\\ s
(JSON over the thin ``repro.launch.serve_search`` front) and admits them
onto free Q-axis slots of ONE long-running
:class:`~repro.core.runtime.AsyncMultiSearchDriver`:

* **Admission control** prices each plan BEFORE it runs
  (:func:`~repro.sim.costmodel.plan_projected_cost` under the operator's
  :class:`~repro.sim.costmodel.CostRates`) and debits a
  :class:`~repro.sim.costmodel.CostBudget`.  A plan whose projection
  exceeds the remaining headroom is rejected — or, with
  ``ServiceConfig.queue_on_reject``, parked in a priority queue until a
  retirement frees capacity.  Projections are upper bounds, so the ledger
  is race-free: unspent cost is credited back when the tenant retires.
* **Slot reuse**: a finished tenant's row is harvested
  (:func:`~repro.core.executor.tenant_stats_from_row`) and its slot
  ``vacate``\\ d; the next admission reuses it, so the pool's device
  footprint tracks CONCURRENCY, not tenant count.
* **SLO tracking**: each tenant's time-to-first-result is measured from
  admission against its ``ServiceConfig.slo_latency_s``.  The service
  reports attainment; it never kills a query for missing an SLO.
* **Fair detector-batch sharing**: tenants share the driver's deduplicated
  detector pass and :class:`~repro.serve.batcher.DetectionCache`; batch
  occupancy is accounted with the same ``occupancy = 1 − padding``
  convention as :class:`~repro.serve.batcher.RequestBatcher`, and detector
  economics are attributed per tenant by dedup representative.

Parity contract (tests/test_service.py): the driver's at-most-one-slot
invariant is untouched, so each admitted tenant's result stream is
bit-identical to its own solo ``run_search_scan`` run at its debited
frame budget — multi-tenancy changes WHICH detector invocations happen
(sharing), never the values any tenant consumes.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Optional

import jax

import numpy as np

from repro.core.executor import SearchStats, tenant_stats_from_row
from repro.core.plan import PlanError, SearchPlan, ServiceConfig
from repro.core.runtime import AsyncMultiSearchDriver, round_summary
from repro.sim.costmodel import (
    CostBudget,
    CostRates,
    plan_projected_cost,
    sampling_cost,
)

QUEUED = "queued"
RUNNING = "running"
FINISHED = "finished"
REJECTED = "rejected"


class PumpFailure(RuntimeError):
    """The background pump died (a worker or scheduler exception); the
    service admits, reports and drains nothing until it is restarted."""


@dataclasses.dataclass
class Tenant:
    """One submitted plan's lifecycle record (QUEUED → RUNNING → FINISHED,
    or REJECTED at admission)."""

    tenant_id: str
    plan: SearchPlan
    key: jax.Array
    select_id: Optional[int]
    service: ServiceConfig
    projected_s: float
    seq: int                         # FIFO tiebreak within a priority level
    state: str = QUEUED
    reason: str = ""                 # rejection reason (REJECTED only)
    row: Optional[int] = None        # driver slot index while RUNNING
    row_obj: object = None           # this tenant's _QueryRow, bound at
    #   admission.  The binding is by OBJECT, not slot index: ``admit``
    #   installs a fresh row per tenant and ``vacate`` returns that same
    #   object, so the reference stays valid (and reports live SLO/result
    #   state) even after the slot index is reused by a later tenant.
    actual_s: float = 0.0            # settled realized cost
    submitted_s: float = 0.0
    n1_init: object = None           # sampler n1 at admission (f64[M]) —
    #   includes any injected index prior, so _reap records only the
    #   DELTA this tenant actually observed (priors never re-recorded)

    # ---- reporting ---------------------------------------------------------

    @property
    def stats(self) -> Optional[SearchStats]:
        if self.row_obj is None:
            return None
        return tenant_stats_from_row(self.row_obj)

    def slo_report(self) -> dict:
        """Time-to-first-result against this tenant's SLO.  ``ttfr_s`` is
        None until a first result merges; ``slo_met`` is None when no SLO
        was declared (slo_latency_s == 0).  The row is bound at admission,
        so attainment is visible while the tenant is still RUNNING — the
        driver stamps ``first_result_s`` at the merge, not at reap."""
        row = self.row_obj
        ttfr = None
        if row is not None and row.first_result_s:
            ttfr = row.first_result_s - row.admitted_s
        slo = self.service.slo_latency_s
        if slo <= 0:
            met = None                     # no SLO declared
        elif ttfr is not None:
            met = ttfr <= slo
        elif self.state in (QUEUED, RUNNING) and (
            row is None or time.monotonic() - row.admitted_s <= slo
        ):
            met = None                     # undetermined: window still open
        else:
            met = False                    # no first result inside the window
        return {
            "slo_latency_s": slo,
            "ttfr_s": ttfr,
            "slo_met": met,
        }

    def to_dict(self) -> dict:
        d = {
            "tenant": self.tenant_id,
            "state": self.state,
            "projected_s": self.projected_s,
            "priority": self.service.priority,
        }
        if self.state == REJECTED:
            d["reason"] = self.reason
        if self.row_obj is not None:
            row = self.row_obj
            st = self.stats
            d.update(
                results=int(row.carry.results),
                steps=int(row.carry.step),
                spilled=len(row.log),
                detector_invocations=st.detector_invocations,
                cache_hits=st.cache_hits,
                index_hits=st.index_hits,
                warm_rounds_saved=st.warm_rounds_saved,
                actual_s=self.actual_s,
                **self.slo_report(),
            )
        if self.state == FINISHED:
            # per-tenant economics: what admission reserved vs what the
            # tenant really cost once settled (credit = headroom returned)
            d["projected_vs_settled"] = {
                "projected_s": self.projected_s,
                "settled_s": self.actual_s,
                "credited_s": self.projected_s - self.actual_s,
            }
        return d


class SearchService:
    """Persistent multi-tenant front over one elastic slot driver.

    The service owns the driver (constructed around a vacated prototype
    row, so the pool starts empty), the cost ledger and the admission
    queue.  ``submit`` is thread-safe; the pump — either the background
    thread ``start(pump=True)`` spawns or explicit ``tick()`` calls —
    merges rounds, harvests finished tenants and admits queued ones as
    capacity frees.
    """

    def __init__(
        self,
        carry_proto,
        chunks,
        detector,
        *,
        select=None,
        budget_s: float = float("inf"),
        rates: CostRates = CostRates(),
        cohorts: int = 4,
        num_workers: int = 2,
        max_steps: int = 100_000,
        cache_frames: int = 0,
        slots_per_batch: int = 4,
        index=None,
    ):
        """``carry_proto`` is a leading-[1] multi-query carry
        (``init_carry_multi``) fixing the pool's sampler/matcher geometry;
        its single row is vacated immediately and never runs.  ``index``
        is a shared :class:`~repro.index.store.RepositoryIndex`: ONE
        instance serves every tenant — the driver's device cache warms
        from it at construction, retiring tenants publish their
        detections and per-chunk evidence back, and warm-start priors
        inject at admission (keyed by the tenant's ``select_id``)."""
        self.rates = rates
        self.budget = CostBudget(total_s=budget_s)
        self.index = index
        self.total_frames = int(chunks.total_frames)
        self.driver = AsyncMultiSearchDriver(
            carry_proto, chunks, detector,
            cohorts=cohorts, num_workers=num_workers,
            result_limits=1, max_steps=max_steps, select=select,
            cache_frames=cache_frames, slots_per_batch=slots_per_batch,
            index=index,
        )
        self.driver.vacate(0)
        self.tenants: dict[str, Tenant] = {}
        self._queue: list[Tenant] = []
        self._seq = itertools.count()
        self._lock = threading.Lock()
        self._stop_evt = threading.Event()
        self._pump: Optional[threading.Thread] = None
        self._pump_error: Optional[Exception] = None

    # ---- lifecycle ---------------------------------------------------------

    def start(self, pump: bool = True) -> None:
        self.driver.start()
        if pump and self._pump is None:
            self._stop_evt.clear()
            self._pump_error = None
            self._pump = threading.Thread(target=self._pump_loop, daemon=True)
            self._pump.start()

    def stop(self) -> None:
        if self._pump is not None:
            self._stop_evt.set()
            self._pump.join(timeout=10.0)
            self._pump = None
        self.driver.stop()

    def _pump_loop(self) -> None:
        try:
            while not self._stop_evt.is_set():
                self.tick(timeout=0.05)
        except Exception as e:  # noqa: BLE001 — re-raised by _check_pump()
            self._pump_error = e

    def _check_pump(self) -> None:
        """Raise the background pump's failure, if it died.  Called by
        ``submit``, ``drain`` and ``stats``, so no caller is told a tenant
        is running on a service that no longer runs anything."""
        if self._pump_error is not None:
            raise PumpFailure(
                f"service pump failed: {self._pump_error!r}"
            ) from self._pump_error

    # ---- admission ---------------------------------------------------------

    def submit(
        self,
        tenant_id: str,
        plan: SearchPlan,
        *,
        key: Optional[jax.Array] = None,
        seed: int = 0,
        select_id: Optional[int] = None,
    ) -> Tenant:
        """Price ``plan``, then admit / queue / reject it.  One tenant =
        one Q-axis row, so service plans are single-query; ``select_id``
        binds the tenant's predicate (e.g. its query class) through the
        driver's ``select`` hook without recompiling anything."""
        self._check_pump()
        plan.resolve()   # typed PlanErrors surface before any state change
        if plan.queries != 1:
            raise PlanError(
                f"service plans are single-query (one tenant = one Q-axis "
                f"slot); got queries={plan.queries} — submit one plan per "
                "query", field="queries")
        spec = plan.execution.index
        if spec is not None:
            if self.index is None and spec.prior_weight > 0:
                raise PlanError(
                    "plan requests index warm-start (prior_weight > 0) but "
                    "the service was constructed without a shared "
                    "RepositoryIndex", field="index")
            if (
                self.index is not None
                and spec.detector_version != self.index.detector_version
            ):
                raise PlanError(
                    f"plan declares index.detector_version="
                    f"{spec.detector_version!r} but the service index holds "
                    f"{self.index.detector_version!r} — a version mismatch "
                    "must be a clean miss, not a silent replay",
                    field="detector_version")
        svc = plan.execution.service or ServiceConfig()
        projected = plan_projected_cost(
            plan, self.rates, index=self.index,
            total_frames=self.total_frames,
        ).total_s
        tenant = Tenant(
            tenant_id=tenant_id,
            plan=plan,
            key=key if key is not None else jax.random.PRNGKey(seed),
            select_id=select_id,
            service=svc,
            projected_s=projected,
            seq=next(self._seq),
            submitted_s=time.monotonic(),
        )
        with self._lock:
            existing = self.tenants.get(tenant_id)
            if existing is not None and existing.state not in (
                REJECTED, FINISHED,
            ):
                raise PlanError(
                    f"tenant {tenant_id!r} already submitted", field="tenant")
            # a terminal record is replaced: a rejected tenant may resubmit
            # a smaller plan under the same id
            self.tenants[tenant_id] = tenant
            if projected > self._never_fit_bound():
                tenant.state = REJECTED
                tenant.reason = self._never_fit_reason(projected)
            elif self.budget.debit(projected):
                self._admit(tenant)
            elif svc.queue_on_reject:
                tenant.state = QUEUED
                self._queue.append(tenant)
            else:
                tenant.state = REJECTED
                tenant.reason = (
                    f"projected cost {projected:.1f}s exceeds remaining "
                    f"budget {self.budget.remaining_s:.1f}s "
                    "(set service.queue_on_reject to wait for capacity)")
        return tenant

    def _never_fit_bound(self) -> float:
        """The most headroom this budget can EVER offer again: ``total −
        spent``.  ``spent_s`` is never credited back, so the bound is
        monotonically non-increasing — a projection above it can never be
        admitted and queueing it would deadlock the drain.  Caller holds
        the lock."""
        return self.budget.total_s - self.budget.spent_s

    def _never_fit_reason(self, projected: float) -> str:
        return (
            f"projected cost {projected:.1f}s can never fit: it exceeds "
            f"the total budget {self.budget.total_s:.1f}s minus settled "
            f"spend {self.budget.spent_s:.1f}s")

    def _admit(self, tenant: Tenant) -> None:
        """Install an already-debited tenant onto the driver (caller holds
        the service lock; lock order is service → driver, never back).

        Warm start: when the shared index carries priors and the tenant's
        plan sets ``prior_weight > 0`` (or the index has a default), the
        fresh row's zeroed sampler is warmed through
        :meth:`~repro.index.priors.ChunkPriors.warm_sampler` under the
        tenant's ``select_id`` as the class key.  The warmed ``n1`` is
        stashed on the tenant so ``_reap`` records only the delta."""
        sampler_init = None
        warm_rounds_saved = 0
        if self.index is not None:
            spec = tenant.plan.execution.index
            w = (
                spec.prior_weight if spec is not None
                else self.index.prior_weight
            )
            if w > 0:
                s0 = self.driver.rows[0].carry.sampler
                fresh = dataclasses.replace(
                    s0,
                    n1=jax.numpy.zeros_like(s0.n1),
                    n=jax.numpy.zeros_like(s0.n),
                )
                warmed, equiv = self.index.priors.warm_sampler(
                    fresh, tenant.select_id, w
                )
                if equiv:
                    sampler_init = warmed
                    warm_rounds_saved = int(equiv) // max(
                        self.driver.cohorts, 1
                    )
        tenant.row = self.driver.admit(
            tenant.key,
            result_limit=int(tenant.plan.result_limit),
            base_max_steps=tenant.plan.max_steps,
            select_id=tenant.select_id,
            sampler_init=sampler_init,
            warm_rounds_saved=warm_rounds_saved,
        )
        tenant.row_obj = self.driver.rows[tenant.row]
        if self.index is not None:
            tenant.n1_init = np.asarray(
                tenant.row_obj.carry.sampler.n1, np.float64
            )
        tenant.state = RUNNING

    def _admit_queued(self) -> None:
        """Admit parked plans in (priority, FIFO) order.  Strictly: the
        head blocks the tail, so a large high-priority plan is never
        starved by small late arrivals slipping past it.  A head whose
        projection no longer fits ``total − spent`` (earlier tenants'
        settled spend shrank the ceiling since it was parked) is rejected
        rather than left to block the queue — and the drain — forever."""
        with self._lock:
            self._queue.sort(key=lambda t: (-t.service.priority, t.seq))
            while self._queue:
                head = self._queue[0]
                if self.budget.debit(head.projected_s):
                    self._queue.pop(0)
                    self._admit(head)
                    continue
                if head.projected_s > self._never_fit_bound():
                    self._queue.pop(0)
                    head.state = REJECTED
                    head.reason = self._never_fit_reason(head.projected_s)
                    continue
                break

    # ---- pump --------------------------------------------------------------

    def tick(self, timeout: float = 0.05) -> bool:
        """One service heartbeat: merge at most one driver batch, harvest
        retired tenants, admit queued plans into freed capacity."""
        merged = self.driver.service_tick(timeout=timeout)
        with jax.profiler.TraceAnnotation("exsample.reap"):
            self._reap()
        with jax.profiler.TraceAnnotation("exsample.admit"):
            self._admit_queued()
        return merged

    def _reap(self) -> None:
        """Harvest tenants whose row retired: vacate the slot for reuse
        and settle the budget reservation against the realized sampling
        cost.  Iterates a snapshot taken under the lock — ``submit`` (any
        thread) inserts into ``self.tenants`` concurrently, and a live
        dict iteration here would RuntimeError and kill the pump thread."""
        with self._lock:
            running = [
                t for t in self.tenants.values() if t.state == RUNNING
            ]
        reaped = 0
        for tenant in running:
            row = tenant.row_obj          # bound at admission, never moves
            if row.active or row.inflight or row.vacant:
                continue
            self.driver.vacate(tenant.row)
            tenant.actual_s = sampling_cost(
                int(row.carry.step), self.rates
            ).total_s
            with self._lock:
                self.budget.settle(tenant.projected_s, tenant.actual_s)
                tenant.state = FINISHED
                if self.index is not None and not self.index.read_only:
                    # delta against the warmed admission state, so the
                    # injected prior is never re-recorded as evidence
                    n1 = np.asarray(row.carry.sampler.n1, np.float64)
                    n = np.asarray(row.carry.sampler.n, np.float64)
                    base = (
                        tenant.n1_init
                        if tenant.n1_init is not None
                        else np.zeros_like(n1)
                    )
                    self.index.priors.record(
                        tenant.select_id, n1 - base, n
                    )
            reaped += 1
        if reaped and self.index is not None and not self.index.read_only:
            with self._lock:
                self.index.publish_cache(self.driver.cache)
                if self.index.path is not None:
                    self.index.save()

    def drain(self, deadline_s: float = 120.0) -> None:
        """Block until every queued/running tenant finishes.  With the
        background pump running this polls; without it, it ticks.  A pump
        or worker failure is raised here, never returned as a drain."""
        t0 = time.monotonic()
        while self.busy():
            self._check_pump()
            if time.monotonic() - t0 > deadline_s:
                with self._lock:
                    unfinished = sum(
                        t.state in (QUEUED, RUNNING)
                        for t in self.tenants.values()
                    )
                raise TimeoutError(
                    f"drain exceeded {deadline_s}s with "
                    f"{unfinished} tenants unfinished")
            if self._pump is not None:
                time.sleep(0.01)
            else:
                self.tick()

    def busy(self) -> bool:
        with self._lock:
            return any(
                t.state in (QUEUED, RUNNING)
                for t in self.tenants.values()
            )

    def evict_terminal(self) -> int:
        """Drop FINISHED/REJECTED tenant records so a persistent service
        doesn't accumulate them without bound; returns the count evicted.
        Harvest ``stats()`` first — eviction discards the records."""
        with self._lock:
            dead = [
                tid for tid, t in self.tenants.items()
                if t.state in (FINISHED, REJECTED)
            ]
            for tid in dead:
                del self.tenants[tid]
            return len(dead)

    # ---- reporting ---------------------------------------------------------

    def padding_fraction(self) -> float:
        """RequestBatcher-convention padding over the driver's slot lanes
        (0.0 before any batch has been issued)."""
        d = self.driver.stats
        total = d["lanes_issued"] + d["lanes_padded"]
        return d["lanes_padded"] / total if total else 0.0

    @property
    def occupancy(self) -> float:
        """``1 − padding_fraction()`` — consistent by construction, like
        :attr:`repro.serve.batcher.RequestBatcher.occupancy`."""
        return 1.0 - self.padding_fraction()

    def stats(self) -> dict:
        self._check_pump()
        with self._lock:
            return {
                "tenants": {
                    tid: t.to_dict() for tid, t in self.tenants.items()
                },
                "budget": {
                    "total_s": self.budget.total_s,
                    "committed_s": self.budget.committed_s,
                    "spent_s": self.budget.spent_s,
                    "remaining_s": self.budget.remaining_s,
                },
                "batch": {
                    "occupancy": self.occupancy,
                    "padding_fraction": self.padding_fraction(),
                    "lanes_issued": self.driver.stats["lanes_issued"],
                    "lanes_padded": self.driver.stats["lanes_padded"],
                },
                "driver": dict(self.driver.stats),
                "rounds": round_summary(self.driver.recent_rounds()),
                "index": (
                    dict(self.index.stats, entries=len(self.index))
                    if self.index is not None else None
                ),
            }
