"""SearchPlan lowering + execution (DESIGN.md §10).

``lower(plan)`` resolves a declarative :class:`~repro.core.plan.SearchPlan`
to ONE driver (host | scan | multi | multi_sharded | async_multi) and
``LoweredPlan.run`` executes it, returning a structured
:class:`SearchResult` — per-query step/results/trace plus uniform
:class:`SearchStats` (detector invocations, cache hit rate, matcher merge
high-water / overflow, async scheduling counters) instead of the raw carry
tuples and ad-hoc stats dicts the legacy ``run_search_*`` entry points
returned.  A single-query plan that resolves onto a Q-axis lowering (a
mesh or async plan without ``queries_axis``) runs it at Q = 1: ``run``
stacks its carry to ``[1]`` on the way in and un-stacks it on the way out.

The module also owns the mesh lowering, ``run_search_multi_sharded`` — the
§9 leading-[Q] multi-query carry in the §8 ``shard_map`` loop, so Q
queries AND M-sharded Thompson statistics share one deduplicated (and
per-shard cached) detector pass per round across the mesh.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import thompson
from repro.core.chunks import ChunkIndex, randomplus_frame
from repro.core.exsample import (
    DetectorFn,
    ExSampleCarry,
    SelectFn,
    _host_search,
    _multi_search,
    _scan_search,
    detect_needed,
    stack_carries,
)
from repro.core.matcher import MatcherState, match_and_update, merge_matcher
from repro.core.plan import PlanError, SearchPlan
from repro.core.state import SamplerState, decrement_homes


@dataclasses.dataclass(frozen=True)
class SearchStats:
    """Uniform per-run accounting, populated by every lowering (fields a
    lowering cannot observe stay at their zero defaults):

    * ``detector_invocations`` / ``cache_hits`` — detector economics: the
      Q-axis lowerings count unique, uncached frames actually detected;
      ``host`` and ``scan`` pay one invocation per sampled frame.
    * ``detector_lanes`` — lanes the detector evaluated on the device.
      The Q-axis lowerings detect only the unique, uncached, live frames,
      compacted into chunks of ``detector_chunk(B)`` lanes (B = Q × C a
      round, Q × C / shards a shard, ``slots_per_batch`` × C a slot
      batch: about B/8, a multiple of 8, B itself below 8), so a round
      counts its invocations rounded up to whole chunks, summed over
      shards and over every processed slot batch; one per cohort slot on
      ``host`` and ``scan``.  ``detector_invocations / detector_lanes``
      is how full the chunks ran.
    * ``rounds`` — synchronized choose→detect rounds (Q-axis lowerings).
    * ``frames_sampled`` — Σ per-query steps (what sequential runs pay).
    * ``merge_high_water`` / ``merge_overflow`` — matcher ring pressure
      from ``merge_matcher_checked`` semantics: the largest number of
      insertions folded in a single merge window, and whether any window
      reached ring capacity (mesh syncs, async merges).
    * ``merges`` / ``reissues`` / ``duplicate_drops`` — async scheduler
      counters (DESIGN.md §5/§11).
    * ``results_spilled`` — ring-evicted results drained to the host
      :class:`~repro.core.matcher.ResultLog` at merge boundaries (the
      async lowerings' spill contract, DESIGN.md §11).
    * ``matcher_inserted`` / ``matcher_capacity`` — final ring totals.
    * ``index_hits`` / ``persisted_detections`` / ``warm_rounds_saved`` —
      repository-index economics (DESIGN.md §13): cache hits served by
      the index preload (detector calls a PAST search paid for — a subset
      of ``cache_hits``), fresh detections persisted into the index at
      the end of the run, and the rounds of cold-start exploration the
      Thompson warm-start priors replaced.
    """

    detector_invocations: int = 0
    cache_hits: int = 0
    detector_lanes: int = 0
    rounds: int = 0
    frames_sampled: int = 0
    merge_high_water: int = 0
    merge_overflow: bool = False
    merges: int = 0
    reissues: int = 0
    duplicate_drops: int = 0
    results_spilled: int = 0
    matcher_inserted: int = 0
    matcher_capacity: int = 0
    index_hits: int = 0
    persisted_detections: int = 0
    warm_rounds_saved: int = 0

    @property
    def cache_hit_rate(self) -> float:
        """Hits over cache lookups (hits + fresh detector invocations)."""
        total = self.cache_hits + self.detector_invocations
        return self.cache_hits / total if total else 0.0

    @property
    def amortization(self) -> float:
        """Frames sampled per detector invocation — the Q-axis sharing win."""
        return self.frames_sampled / max(self.detector_invocations, 1)


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Structured outcome of ``SearchPlan.run``: the final carry plus
    per-query counters/traces and uniform :class:`SearchStats`."""

    carry: ExSampleCarry
    steps: tuple
    results: tuple
    traces: list
    stats: SearchStats
    plan: SearchPlan
    kind: str

    @property
    def num_queries(self) -> int:
        return len(self.steps)

    @property
    def trace(self):
        """Single-query convenience view of ``traces``."""
        return self.traces[0]


def lower(plan: SearchPlan) -> "LoweredPlan":
    """Validate ``plan`` and bind it to one driver (DESIGN.md §10)."""
    kind, method = plan.resolve()
    return LoweredPlan(plan=plan, kind=kind, method=method)


def tenant_stats_from_row(row) -> SearchStats:
    """Uniform per-tenant accounting for the serving path (DESIGN.md §12):
    package one Q-axis row (an ``AsyncMultiSearchDriver`` ``_QueryRow``,
    live or vacated) into the same :class:`SearchStats` every batch
    lowering returns, so a tenant's view of its own query reads identically
    to a solo run's stats.  Detector economics are attributed by dedup
    representative — frames a tenant's lane shared with another tenant's
    batch slot ride for free and appear in neither counter."""
    return SearchStats(
        detector_invocations=int(row.fresh_calls),
        cache_hits=int(row.cache_hits),
        rounds=int(row.rounds),
        frames_sampled=int(np.asarray(row.carry.step)),
        results_spilled=len(row.log),
        index_hits=int(getattr(row, "index_hits", 0)),
        warm_rounds_saved=int(getattr(row, "warm_rounds_saved", 0)),
        **_matcher_totals(row.carry),
    )


def _matcher_totals(carry: ExSampleCarry) -> dict:
    return dict(
        matcher_inserted=int(np.asarray(carry.matcher.total_inserted).sum()),
        matcher_capacity=int(carry.matcher.times_seen.shape[-1]),
    )


@dataclasses.dataclass(frozen=True)
class LoweredPlan:
    """A validated plan bound to one lowering ``kind``; ``run()`` executes
    the compiled driver and packages the :class:`SearchResult`."""

    plan: SearchPlan
    kind: str
    method: str

    @property
    def q_axis(self) -> bool:
        """True when ``run`` takes and returns a leading-[Q] carry: the plan
        asks for the Q axis (``queries > 1`` or ``queries_axis``).  Without
        it a plan takes a single-query carry, also where it resolved onto
        a Q-axis lowering (a mesh or async plan), which then runs at Q = 1."""
        return self.plan.queries > 1 or self.plan.execution.queries_axis

    def run(
        self,
        carry: ExSampleCarry,
        chunks: ChunkIndex,
        *,
        detector: DetectorFn,
        select: SelectFn | None = None,
        mesh=None,
        index=None,
    ) -> SearchResult:
        p, ex = self.plan, self.plan.execution
        q_axis = self.q_axis
        ndim = jnp.ndim(carry.step)
        if q_axis and ndim != 1:
            raise PlanError(
                f"the {self.kind!r} lowering needs a leading-[Q] carry "
                "(init_carry_multi / stack_carries); got a single-query "
                "carry", field="queries")
        if q_axis and int(carry.step.shape[0]) != p.queries:
            raise PlanError(
                f"carry has {int(carry.step.shape[0])} queries but the plan "
                f"declares queries={p.queries}", field="queries")
        if not q_axis and ndim != 0:
            raise PlanError(
                f"the plan is single-query ({self.kind!r} lowering) but the "
                "carry has a leading axis; set queries/queries_axis on the "
                "plan", field="queries")
        if select is not None and not q_axis:
            raise PlanError(
                "select predicates ride on the shared Q-axis detector pass; "
                "this plan takes a single-query carry (set "
                "queries_axis=True, valid at queries=1)", field="queries")
        # the one lift: a single-query plan on a Q-axis lowering runs it at
        # Q = 1 and hands back a single-query carry
        lift = not q_axis and self.kind not in ("host", "scan")
        if lift:
            carry = stack_carries([carry])
        cache = ex.cache
        if cache == -1:
            cache = chunks.total_frames
        if cache and self.kind == "multi_sharded":
            # hash-sharded placement (DESIGN.md §14) needs the capacity to
            # divide over the mesh; pad BEFORE the index warm so the warm
            # fill and the device layout agree on one modulus
            cache += (-cache) % ex.shards
        if isinstance(p.result_limit, tuple):
            limits = p.result_limit
        else:
            limits = (p.result_limit,) * p.queries
        limit0 = int(limits[0])

        # ---- repository index (DESIGN.md §13): open / version-check /
        # Thompson warm-start / device-cache preload --------------------
        spec = ex.index
        if index is None and spec is not None:
            from repro.index.store import RepositoryIndex

            index = RepositoryIndex.open(spec)
        elif (
            index is not None and spec is not None
            and spec.detector_version != index.detector_version
        ):
            raise PlanError(
                f"plan declares index.detector_version="
                f"{spec.detector_version!r} but the live index holds "
                f"{index.detector_version!r} — a version mismatch must be "
                "a clean miss, not a silent replay", field="detector_version")
        prior_weight = (
            spec.prior_weight if spec is not None
            else (index.prior_weight if index is not None else 0.0)
        )
        warm_rounds_saved = 0
        if index is not None and prior_weight > 0:
            warmed, equiv = index.priors.warm_sampler(
                carry.sampler, None, prior_weight
            )
            if equiv:
                carry = dataclasses.replace(carry, sampler=warmed)
                warm_rounds_saved = int(equiv) // max(p.cohorts, 1)
        if index is not None:
            # evidence base AFTER the warm boost, so recorded deltas never
            # re-count injected priors as fresh evidence
            n1_base = np.asarray(carry.sampler.n1, np.float64)
            n_base = np.asarray(carry.sampler.n, np.float64)
        warm_cache = warm_tag = None
        if index is not None and cache and self.kind in (
            "multi", "multi_sharded"
        ):
            struct = jax.eval_shape(
                detector, jax.random.PRNGKey(0), jnp.zeros((), jnp.int32)
            )
            warm_cache, _warm = index.warm(struct, cache)
            warm_tag = warm_cache.tag

        def finish(out, traces, stats, final_cache=None, index_hits=0):
            """Index write-back tail shared by every lowering branch."""
            if index is not None:
                persisted = 0
                if not index.read_only:
                    persisted = index.publish_cache(final_cache)
                    index.priors.record(
                        None,
                        np.asarray(out.sampler.n1, np.float64) - n1_base,
                        np.asarray(out.sampler.n, np.float64) - n_base,
                    )
                    if index.path is not None:
                        index.save()
                stats = dataclasses.replace(
                    stats,
                    index_hits=int(index_hits),
                    persisted_detections=int(persisted),
                    warm_rounds_saved=warm_rounds_saved,
                )
            if lift:
                out = jax.tree.map(lambda x: x[0], out)
            return self._package(out, traces, stats)

        if self.kind in ("host", "scan"):
            fn = _host_search if self.kind == "host" else _scan_search
            out, trace = fn(
                carry, chunks, detector=detector, result_limit=limit0,
                max_steps=p.max_steps, cohorts=p.cohorts, method=self.method,
                trace_every=p.trace_every,
            )
            step = int(out.step)
            stats = SearchStats(
                detector_invocations=step, detector_lanes=step,
                frames_sampled=step, **_matcher_totals(out),
            )
            return finish(out, [trace], stats)

        if self.kind == "async_multi":
            from repro.core.runtime import AsyncMultiSearchDriver

            driver = AsyncMultiSearchDriver(
                carry, chunks, detector, cohorts=p.cohorts,
                num_workers=ex.async_workers,
                result_limits=[int(v) for v in limits],
                max_steps=p.max_steps, method=self.method, select=select,
                cache_frames=cache or 0, trace_every=p.trace_every,
                index=index,
            )
            out = driver.run()
            stats = SearchStats(
                detector_invocations=int(driver.stats["detector_invocations"]),
                cache_hits=int(driver.stats["cache_hits"]),
                detector_lanes=int(driver.stats["detector_lanes"]),
                rounds=int(driver.stats["rounds"]),
                frames_sampled=int(np.asarray(out.step).sum()),
                merge_high_water=int(driver.stats["merge_high_water"]),
                merges=int(driver.stats["merges"]),
                reissues=int(driver.stats["reissues"]),
                duplicate_drops=int(driver.stats["duplicate_drops"]),
                results_spilled=int(driver.stats["spilled"]),
                **_matcher_totals(out),
            )
            return finish(
                out, driver.traces, stats, final_cache=driver.cache,
                index_hits=int(driver.stats.get("index_hits", 0)),
            )

        if mesh is None:
            if ex.axis != "data":
                raise PlanError(
                    f"axis={ex.axis!r}: only a 'data' mesh can be built "
                    "automatically — pass mesh= with the named axis",
                    field="axis")
            from repro.launch.mesh import make_data_mesh

            mesh = make_data_mesh(ex.shards)
        else:
            shape = dict(mesh.shape)
            if shape.get(ex.axis) != ex.shards:
                raise PlanError(
                    f"mesh axes {shape} do not provide the plan's "
                    f"{ex.shards} {ex.axis!r} shards — the validated "
                    "cohorts/shards geometry must match what executes",
                    field="shards")

        limits_arr = jnp.asarray([int(v) for v in limits], jnp.int32)
        if self.kind == "multi":
            out, traces, ms = _multi_search(
                carry, chunks, detector=detector, result_limits=limits_arr,
                max_steps=p.max_steps, cohorts=p.cohorts, method=self.method,
                trace_every=p.trace_every, select=select,
                cache_frames=cache or 0,
                cache=warm_cache, warm_tag=warm_tag,
            )
        else:  # multi_sharded — the composed lowering
            out, traces, ms = run_search_multi_sharded(
                carry, chunks, mesh=mesh, detector=detector, select=select,
                result_limits=limits_arr, max_steps=p.max_steps,
                cohorts=p.cohorts, sync_every=ex.sync_every, axis=ex.axis,
                cache_frames=cache or 0,
                cache=warm_cache, warm_tag=warm_tag,
            )
        stats = SearchStats(
            detector_invocations=ms["detector_invocations"],
            cache_hits=ms["cache_hits"],
            detector_lanes=ms["detector_lanes"],
            rounds=ms["rounds"],
            frames_sampled=ms["frames_sampled"],
            merge_high_water=ms.get("merge_high_water", 0),
            merge_overflow=ms.get("merge_overflow", False),
            merges=ms.get("merges", 0),
            **_matcher_totals(out),
        )
        return finish(
            out, traces, stats, final_cache=ms.get("final_cache"),
            index_hits=int(ms.get("index_hits", 0)),
        )

    def _package(self, out, traces, stats) -> SearchResult:
        with jax.profiler.TraceAnnotation("exsample.readback"):
            steps = tuple(
                int(s) for s in np.atleast_1d(np.asarray(out.step))
            )
            results = tuple(
                int(r) for r in np.atleast_1d(np.asarray(out.results))
            )
        return SearchResult(
            carry=out, steps=steps, results=results, traces=traces,
            stats=stats, plan=self.plan, kind=self.kind,
        )


# ---------------------------------------------------------------------------
# Composed lowering: Q-query carry × M-sharded statistics (DESIGN.md §10)
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis", "detector", "select", "cohorts", "sync_every",
        "max_steps", "alpha0", "beta0", "empty",
    ),
)
def _search_multi_sharded_device(
    keys: jax.Array,         # key[Q]
    step0: jax.Array,        # i32[Q]
    results0: jax.Array,     # i32[Q]
    n1: jax.Array,           # f32[Q, M] — sharded over the last axis
    n: jax.Array,            # f32[Q, M] — sharded
    frames: jax.Array,       # i32[Q, M] — sharded
    matcher: MatcherState,   # leaves [Q, ...] — replicated
    chunks: ChunkIndex,      # replicated
    result_limits: jax.Array,  # i32[Q]
    cache,                   # DetectionCache or None — hash-sharded global
    #   layout (shard_cache_layout): leading axes split over the mesh so
    #   each shard holds the 1/S of one logical cache homed on it
    warm_tag,                # i32[cap] index-preload tag snapshot
    #   (direct-mapped layout, replicated), or None
    window_limit: jax.Array,  # i32[] — max sync windows THIS call executes
    #   (INT32_MAX = run to completion; a finite limit returns a fully
    #   resumable state at a sync boundary, the elastic drain point)
    *,
    mesh,
    axis: str,
    detector: DetectorFn,
    select: SelectFn | None,
    cohorts: int,
    sync_every: int,
    max_steps: int,
    alpha0: float,
    beta0: float,
    empty=None,
):
    """Mesh-resident multi-query loop: the §9 Q-axis round (per-query
    Thompson choice, cross-query dedup + detection cache, per-query
    scatter-back) composed with the §8 merge schedule (full-width per-query
    delta buffers, one psum per sync, per-query matcher folds with the
    exact k−1 duplicate-d₁ add-back).

    Layout: every statistic of the §9 carry gains the §8 sharding — chunk
    stats ``[Q, M]`` sharded over ``axis``, per-(query, shard) matcher
    replicas of a shared ``[Q]`` snapshot, one full-width ``[Q, M]`` delta
    buffer per shard.  Per round the replicated
    ``local_cohort_winners_batched`` choice hands shard s cohorts
    ``[s·C/S, (s+1)·C/S)`` of EVERY query, whose Q·C/S frames dedup — and
    miss-check the HASH-SHARDED :class:`DetectionCache` (frame f homed on
    shard ``f % S``, DESIGN.md §14; lookups and inserts route over
    ``all_to_all``) — into one detector batch of the needed lanes only
    (``exsample.detect_needed``).  With no ``cache`` and
    ``empty = (layout, slots)`` each shard builds its own empty part of
    the cache (``slots`` of them) inside the program, so no device ever
    holds the whole logical cache.  Per-query liveness is evaluated at
    sync boundaries (the §8
    overshoot caveat, per query); a finished query freezes exactly like the
    §9 masking contract (key/step/sampler gated, slots leave the dedup).

    Parity contract (tests/test_plan_parity.py): with a deterministic
    detector, query q's trajectory — (step, results), trace, sampler
    statistics, final key — is bit-identical to its own single-query mesh
    plan (this loop at Q = 1) on the same mesh with the same key, at ANY
    Q: cross-query dedup and caching change WHICH detector invocations
    happen, never the values a query consumes.
    """
    from repro.core.distributed import local_cohort_winners_batched
    from repro.serve.batcher import (
        dedup_first_index,
        empty_cache,
        sharded_cache_insert,
        sharded_cache_lookup,
    )
    from jax.sharding import PartitionSpec as P

    q_n = step0.shape[0]
    num_shards = mesh.shape[axis]
    has_cache = cache is not None or empty is not None
    m = n1.shape[-1]
    local_m = m // num_shards
    per_shard = cohorts // num_shards
    b = q_n * per_shard
    per_sync = cohorts * sync_every
    cap = min(max_steps // max(per_sync, 1) + 3, 4096)
    cap_r = matcher.times_seen.shape[-1]

    def shard_fn(keys, step0, results0, n1_l, n_l, frames_l, matcher0,
                 chks, rlimits, cache0, wtag, wlimit):
        shard_id = jax.lax.axis_index(axis)
        if cache0 is None and empty is not None:
            with jax.named_scope("cache_init"):
                cache0 = empty_cache(*empty, shards=num_shards)
        fdt = n_l.dtype
        qi = jnp.arange(q_n, dtype=jnp.int32)
        my_slice = lambda full: jax.lax.dynamic_slice(
            full, (0, shard_id * local_m), (q_n, local_m)
        )

        def live_mask(step, results, n_loc):
            exh_l = jnp.all(
                n_loc >= frames_l.astype(fdt), axis=-1
            ).astype(jnp.int32)                                  # [Q]
            with jax.named_scope("collective"):
                exhausted = jax.lax.psum(exh_l, axis) == num_shards
            return (results < rlimits) & (step < max_steps) & ~exhausted

        def one_round(base_n1, base_n, active, rstate):
            keys, delta_n1, delta_n, foreign, matcher, cache, lstep, lres, \
                lcalls, lhits, lihits, llanes = rstate
            ks = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
            key_next, k_choice, k_det = ks[:, 0], ks[:, 1], ks[:, 2]
            # per-query view: authoritative slice + own pending deltas (the
            # §8 staleness model, replicated per query)
            view = SamplerState(
                n1=base_n1 + my_slice(delta_n1),
                n=base_n + my_slice(delta_n),
                frames=frames_l,
                alpha0=alpha0,
                beta0=beta0,
            )
            with jax.named_scope("choose"):
                a_l, b_l = thompson.gamma_params(view)
                c_ids, c_scores, c_n = local_cohort_winners_batched(
                    k_choice, a_l, b_l, view.exhausted(), view.n,
                    axis=axis, cohorts=cohorts,
                )                                                # [Q, C]
            # §8 within-window random+ rank dedup, per query: occurrence
            # index within the round plus replicated foreign-pick counts
            live_c = jnp.isfinite(c_scores) & active[:, None]    # [Q, C]
            owner = c_ids // local_m                             # [Q, C]
            pshard = jnp.arange(cohorts, dtype=jnp.int32) // per_shard
            same_before = jnp.tril(
                c_ids[:, :, None] == c_ids[:, None, :], -1
            )                                                    # [Q, C, C]
            occ = jnp.sum(same_before & live_c[:, None, :], axis=-1)
            fgather = jnp.take_along_axis(foreign, c_ids, axis=-1)
            ranks = (
                c_n + fgather.astype(fdt) + occ.astype(fdt)
            ).astype(jnp.int32)                                  # [Q, C]
            foreign = foreign.at[qi[:, None], c_ids].add(
                ((pshard[None, :] != owner) & live_c).astype(jnp.int32)
            )

            # ---- this shard's slots: cohorts [s·C/S, (s+1)·C/S) of every
            # query, deduped + cache-checked into ONE detector batch.  The
            # full [Q, C] frame matrix is computed replicated — winner ids
            # and ranks are replicated, so every shard knows which frames
            # every OTHER shard processes this round, which is what makes
            # the hash-sharded cache routing below collective-cheap ----
            fids_all = randomplus_frame(chks, c_ids, ranks)      # [Q, C]
            g0 = shard_id * per_shard
            slc = lambda a: jax.lax.dynamic_slice(
                a, (0, g0), (q_n, per_shard)
            )
            cids_s, live_s, fids_s = slc(c_ids), slc(live_c), slc(fids_all)
            gidx = g0 + jnp.arange(per_shard, dtype=jnp.int32)
            det_keys = jax.vmap(
                lambda kq: jax.vmap(
                    lambda g: jax.random.fold_in(kq, g)
                )(gidx)
            )(k_det)                                             # [Q, C/S]
            flat_frames = fids_s.reshape(b)
            flat_live = live_s.reshape(b)
            det_keys_flat = det_keys.reshape((b,) + det_keys.shape[2:])
            first_idx = dedup_first_index(flat_frames, flat_live)
            is_rep = (first_idx == jnp.arange(b, dtype=jnp.int32)) & flat_live
            if has_cache:
                # Hash-sharded cache routing (DESIGN.md §14): frame f lives
                # ONLY on shard f % S.  Requests are free — the replicated
                # [Q, C] frame matrix lets every home shard compute every
                # requester's probes locally — so one round costs two
                # all_to_alls out (hit flags + packed rows, rows =
                # requesters) and two back in (routed fresh inserts).  Each
                # shard stores and scans 1/S of one logical cache; only the
                # [b] rows a shard consumes are unpacked.
                layout = cache.layout
                req = jnp.where(live_c, fids_all, -1)            # [Q, C]
                req = req.reshape(q_n, num_shards, per_shard)
                req = req.transpose(1, 0, 2).reshape(num_shards, b)
                r_hit, r_rows = sharded_cache_lookup(
                    cache, req, shard_id, num_shards
                )                                                # [S, b]
                with jax.named_scope("collective"):
                    a_hit = jax.lax.all_to_all(r_hit, axis, 0, 0)
                    a_rows = jax.lax.all_to_all(r_rows, axis, 0, 0)
                # row h of a_* is home shard h's answer for MY b slots
                home = jnp.where(
                    flat_frames >= 0, flat_frames % num_shards, 0
                )
                bi = jnp.arange(b, dtype=jnp.int32)
                hit = a_hit[home, bi]
                need = is_rep & ~hit
                # the detector waits on the hit flags only; the rows'
                # all_to_all is free to overlap it
                fresh, lanes = detect_needed(
                    detector, det_keys_flat, flat_frames, need
                )
                with jax.named_scope("dedup_cache"):
                    cached = layout.unpack(a_rows[home, bi])
                    expand = lambda mk, x: mk.reshape(
                        mk.shape + (1,) * (x.ndim - 1)
                    )
                    resolved = jax.tree.map(
                        lambda cv, fv: jnp.where(expand(hit, fv), cv, fv),
                        cached, fresh,
                    )
                    fresh_rows = layout.pack(fresh)              # [b, W]
                # route fresh detections to their home shards; flattening
                # the received rows requester-major reproduces the exact
                # u-major batch order the replica design's gathered insert
                # used, so within-batch slot collisions pick the same
                # winner and the logical cache stays bit-identical
                dest = jnp.arange(num_shards, dtype=jnp.int32)[:, None]
                ins_frames = jnp.where(
                    (home[None, :] == dest) & need[None, :],
                    flat_frames[None, :], -1,
                )                                                # [S, b]
                ins_rows = jnp.broadcast_to(
                    fresh_rows[None], (num_shards,) + fresh_rows.shape
                )
                with jax.named_scope("collective"):
                    g_frames = jax.lax.all_to_all(
                        ins_frames, axis, 0, 0
                    ).reshape(-1)
                    g_rows = jax.lax.all_to_all(
                        ins_rows, axis, 0, 0
                    ).reshape(-1, layout.width)
                cache = sharded_cache_insert(
                    cache, g_frames, g_rows, g_frames >= 0,
                    shard_id, num_shards,
                )
            else:
                hit = jnp.zeros((b,), bool)
                need = is_rep
                resolved, lanes = detect_needed(
                    detector, det_keys_flat, flat_frames, need
                )
            dets_flat = jax.tree.map(lambda x: x[first_idx], resolved)
            llanes = llanes + lanes
            lcalls = lcalls + jnp.sum(need).astype(jnp.int32)
            lhits = lhits + jnp.sum(is_rep & hit).astype(jnp.int32)
            if wtag is not None:
                # index hits: cache hits whose slot still tags the frame
                # the repository-index preload installed (DESIGN.md §13)
                wslot = flat_frames % wtag.shape[0]
                lihits = lihits + jnp.sum(
                    is_rep & hit & (wtag[wslot] == flat_frames)
                ).astype(jnp.int32)
            dets_q = jax.tree.map(
                lambda x: x.reshape((q_n, per_shard) + x.shape[1:]),
                dets_flat,
            )

            # ---- per-query sequential fold over its own slots (vmapped
            # over Q; mirrors the §8 proc loop per query) ----
            def fold_query(q, dn1_q, dn_q, matcher_q, dets_c, cids_q,
                           fids_q, live_q, lstep_q, lres_q):
                def bodyj(j, st):
                    dn1_q, dn_q, matcher_q, lstep_q, lres_q = st
                    d = jax.tree.map(lambda x: x[j], dets_c)
                    live = live_q[j]
                    valid = d.valid & live
                    if select is not None:
                        valid = valid & select(q, d)
                    mres = match_and_update(
                        matcher_q, d.boxes, d.feats, valid,
                        chks.video_id[cids_q[j]], fids_q[j], cids_q[j],
                    )
                    d1_local = mres.d1 - mres.cross_chunk
                    upd = live.astype(dn1_q.dtype)
                    with jax.named_scope("update"):
                        dn1_q = dn1_q.at[cids_q[j]].add(
                            (mres.d0 - d1_local).astype(dn1_q.dtype) * upd
                        )
                        dn_q = dn_q.at[cids_q[j]].add(upd)
                        dn1_q = decrement_homes(dn1_q, mres.cross_home)
                    return (
                        dn1_q, dn_q, mres.new_state,
                        lstep_q + live.astype(jnp.int32),
                        lres_q + mres.d0,
                    )

                return jax.lax.fori_loop(
                    0, per_shard, bodyj,
                    (dn1_q, dn_q, matcher_q, lstep_q, lres_q),
                )

            delta_n1, delta_n, matcher, lstep, lres = jax.vmap(fold_query)(
                qi, delta_n1, delta_n, matcher, dets_q, cids_s, fids_s,
                live_s, lstep, lres,
            )
            keys = jnp.where(
                active.reshape((q_n,) + (1,) * (keys.ndim - 1)),
                key_next, keys,
            )
            return (keys, delta_n1, delta_n, foreign, matcher, cache,
                    lstep, lres, lcalls, lhits, lihits, llanes)

        def body(st):
            (keys, n1_l, n_l, matcher, snap, cache, step, results, buf, tn,
             wcalls, whits, wihits, wlanes, hw, ov, windows, _cont) = st
            active = live_mask(step, results, n_l)               # [Q]
            rst = (
                keys,
                jnp.zeros((q_n, m), n1_l.dtype),
                jnp.zeros((q_n, m), fdt),
                jnp.zeros((q_n, m), jnp.int32),
                matcher,
                cache,
                jnp.zeros((q_n,), jnp.int32),
                jnp.zeros((q_n,), jnp.int32),
                wcalls,
                whits,
                wihits,
                wlanes,
            )
            keys, dn1, dn, _foreign, matcher, cache, lstep, lres, wcalls, \
                whits, wihits, wlanes = jax.lax.fori_loop(
                    0, sync_every, lambda r, s: one_round(n1_l, n_l, active, s),
                    rst,
                )
            # ---- sampler sync: one [Q, M] psum (exact, additive) ----
            with jax.named_scope("collective"):
                n1_l = n1_l + my_slice(jax.lax.psum(dn1, axis))
                n_l = n_l + my_slice(jax.lax.psum(dn, axis))
                # ---- matcher sync: per-query §8 fold + exact k−1
                # add-back of cross-shard duplicate d₁ decrements ----
                stacked = jax.tree.map(
                    lambda x: jax.lax.all_gather(x, axis), matcher
                )                                                # [S, Q, ..]
            same_e = (stacked.video == snap.video[None]) & (
                stacked.frame == snap.frame[None]
            )
            trans = (
                same_e
                & (snap.times_seen[None] == 1)
                & (stacked.times_seen >= 2)
            )                                                    # [S, Q, R]
            k = jnp.sum(trans, axis=0)                           # [Q, R]
            over = jnp.maximum(k - 1, 0).astype(n1_l.dtype)
            corr = jnp.zeros((q_n, m), n1_l.dtype).at[
                qi[:, None], jnp.where(k > 0, snap.chunk, 0)
            ].add(jnp.where(k > 0, over, jnp.zeros((), n1_l.dtype)))
            n1_l = n1_l + my_slice(corr)
            merged = jax.lax.fori_loop(
                1,
                num_shards,
                lambda s, dst: jax.vmap(merge_matcher)(
                    dst, jax.tree.map(lambda x: x[s], stacked), snap
                ),
                jax.tree.map(lambda x: x[0], stacked),
            )
            # ---- ring-pressure accounting (merge_matcher_checked
            # semantics, replicated): insertions per shard per window ----
            inserted = stacked.total_inserted - snap.total_inserted[None]
            hw = jnp.maximum(hw, jnp.max(inserted))
            ov = ov | jnp.any(inserted >= cap_r)
            # ---- counters / per-query trace / continue flag ----
            with jax.named_scope("collective"):
                step = step + jax.lax.psum(lstep, axis)
                results = results + jax.lax.psum(lres, axis)
            entry = jnp.stack([step, results], axis=-1)          # [Q, 2]
            idx = jnp.where(active, tn, cap)
            buf = jax.vmap(lambda bq, i, e: bq.at[i].set(e, mode="drop"))(
                buf, idx, entry
            )
            tn = jnp.minimum(tn + active.astype(jnp.int32), cap)
            cont = jnp.any(live_mask(step, results, n_l)) & (
                windows + 1 < wlimit
            )
            return (keys, n1_l, n_l, merged, merged, cache, step, results,
                    buf, tn, wcalls, whits, wihits, wlanes, hw, ov,
                    windows + 1, cont)

        cont0 = jnp.any(live_mask(step0, results0, n_l)) & (wlimit > 0)
        init = (
            keys, n1_l, n_l, matcher0, matcher0, cache0, step0, results0,
            jnp.zeros((q_n, cap, 2), jnp.int32),
            jnp.zeros((q_n,), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), bool),
            jnp.zeros((), jnp.int32), cont0,
        )
        (keys, n1_l, n_l, matcher, _snap, cache_f, step, results, buf, tn,
         wcalls, whits, wihits, wlanes, hw, ov, windows,
         _c) = jax.lax.while_loop(
            lambda st: st[-1], body, init
        )
        # final per-query checkpoint only where the trace would otherwise
        # miss the end state (mirrors the §8 tail, vmapped over Q)
        idx = jnp.where(
            (tn == 0) | (tn >= cap), jnp.minimum(tn, cap - 1), cap
        )
        buf = jax.vmap(lambda bq, i, e: bq.at[i].set(e, mode="drop"))(
            buf, idx, jnp.stack([step, results], axis=-1)
        )
        tn = jnp.clip(tn, 1, cap)
        with jax.named_scope("collective"):
            calls = jax.lax.psum(wcalls, axis)
            hits = jax.lax.psum(whits, axis)
            ihits = jax.lax.psum(wihits, axis)
            lanes = jax.lax.psum(wlanes, axis)
        outs = (n1_l, n_l, matcher, keys, step, results, buf, tn, calls,
                hits, ihits, hw, ov, windows, lanes)
        if cache_f is not None:
            # each shard returns only its 1/S of the hash-sharded logical
            # cache; concatenating over the sharded out-spec gives the
            # global shard-major layout, which stays split over the mesh
            outs = outs + (cache_f,)
        return outs

    sh1, sh2, rep = P(axis), P(None, axis), P()
    out_specs = (
        sh2, sh2, rep, rep, rep, rep, rep, rep, rep, rep, rep, rep, rep,
        rep, rep,
    )
    cache_spec = rep if cache is None else sh1
    if has_cache:
        out_specs = out_specs + (sh1,)
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(rep, rep, rep, sh2, sh2, sh2, rep, rep, rep, cache_spec,
                  rep, rep),
        out_specs=out_specs,
        check_vma=False,
    )(keys, step0, results0, n1, n, frames, matcher, chunks, result_limits,
      cache, warm_tag, window_limit)


def _place_cache(cache, mesh, axis: str):
    """``cache`` in the hash-sharded layout of ``mesh``'s ``axis``, each
    shard's part placed on its own device.  A cache already in that layout
    (a resumed window's ``final_cache``) is returned as it is; any other is
    brought to the host, permuted there and handed out shard by shard, so
    no device ever holds the whole of it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.serve.batcher import host_direct_mapped, shard_cache_layout

    num_shards = mesh.shape[axis]
    if cache.shards == num_shards:
        return cache
    host = shard_cache_layout(host_direct_mapped(cache), num_shards)
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree.map(
        lambda x: jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]
        ),
        host,
    )


def run_search_multi_sharded(
    carries: ExSampleCarry,
    chunks: ChunkIndex,
    *,
    mesh,
    detector: DetectorFn,
    result_limits,
    max_steps: int,
    cohorts: int | None = None,
    sync_every: int = 1,
    axis: str = "data",
    select: SelectFn | None = None,
    cache_frames: int = 0,
    cache=None,
    warm_tag=None,
    window_limit: int | None = None,
):
    """Q concurrent queries × an M-sharded mesh, one deduplicated detector
    pass per round per shard (DESIGN.md §10) — the composed lowering behind
    ``SearchPlan`` plans with ``queries_axis`` + ``shards > 1``.

    ``carries`` is a stacked ``ExSampleCarry`` (leading [Q] axis,
    ``init_carry_multi`` / ``stack_carries``).  ``cohorts`` is each query's
    GLOBAL per-round batch (default: one frame per shard) and must divide
    over the mesh; chunk statistics are padded to the shard count with
    exhausted dummies and trimmed on the way out.  Returns
    ``(carries', traces, stats)``: one trace entry per sync window per
    query (plus a final one where the window trace would miss the end
    state) and §9-style sharing stats.

    ``cache`` overrides internal cache construction (a repository-index
    preload, DESIGN.md §13); ``warm_tag`` — the preload's tag snapshot —
    splits ``index_hits`` out of ``cache_hits``.  A cache given in the
    direct-mapped layout (a host copy) is permuted on the host and placed
    shard by shard; one already in this mesh's layout goes in as it is.
    Without one, each shard builds its own empty part inside the program.
    Whenever a cache is in play its final state rides back in
    ``stats["final_cache"]``, in the hash-sharded layout, split over the
    mesh (``DetectionCache.shards``); ``serve.batcher.host_direct_mapped``
    gives the direct-mapped view where a consumer needs it.

    ``window_limit`` caps how many sync windows THIS call executes
    (default: unbounded).  A capped call returns at a sync boundary with a
    fully resumable state — carry + ``stats["final_cache"]`` feed straight
    back in — which is the drain point the elastic runner
    (:class:`repro.core.runtime.ElasticShardedRunner`) uses to reshard
    onto a shrunken mesh between calls.
    """
    num_shards = mesh.shape[axis]
    if cohorts is None:
        cohorts = num_shards
    if cohorts < num_shards or cohorts % num_shards:
        raise ValueError(
            f"cohorts={cohorts} must be a positive multiple of the "
            f"{num_shards} '{axis}' shards"
        )
    if sync_every < 1:
        raise ValueError(f"sync_every={sync_every} must be >= 1")
    from repro.core.distributed import pad_chunks

    q_n = int(carries.step.shape[0])
    m0 = int(carries.sampler.n1.shape[-1])
    padded = pad_chunks(carries.sampler, num_shards)
    n1, n, frames = padded.n1, padded.n, padded.frames

    empty = None
    if cache is None and cache_frames:
        from repro.serve.batcher import RowLayout

        # the hash-sharded placement needs capacity % shards == 0 to be a
        # pure transposition of the direct-mapped slot map; padding the
        # capacity up never loses entries (it only splits collision sets)
        cache_frames += (-cache_frames) % num_shards
        struct = jax.eval_shape(
            detector, jax.random.PRNGKey(0), jnp.zeros((), jnp.int32)
        )
        empty = (RowLayout.of(struct), cache_frames // num_shards)
    elif cache is not None:
        cache = _place_cache(cache, mesh, axis)

    outs = _search_multi_sharded_device(
        carries.key,
        carries.step,
        carries.results,
        n1,
        n,
        frames,
        carries.matcher,
        chunks,
        jnp.broadcast_to(
            jnp.asarray(result_limits, jnp.int32), (q_n,)
        ),
        cache,
        warm_tag,
        jnp.asarray(
            np.iinfo(np.int32).max if window_limit is None
            else int(window_limit),
            jnp.int32,
        ),
        mesh=mesh,
        axis=axis,
        detector=detector,
        select=select,
        cohorts=cohorts,
        sync_every=sync_every,
        max_steps=max_steps,
        alpha0=carries.sampler.alpha0,
        beta0=carries.sampler.beta0,
        empty=empty,
    )
    (n1_out, n_out, matcher, keys, step, results, buf, tn, calls, hits,
     ihits, hw, ov, windows, lanes) = outs[:15]
    final_cache = outs[15] if len(outs) > 15 else None
    out = ExSampleCarry(
        sampler=dataclasses.replace(
            carries.sampler,
            n1=n1_out[:, :m0],
            n=n_out[:, :m0],
            frames=carries.sampler.frames,
        ),
        matcher=matcher,
        key=keys,
        step=step,
        results=results,
    )
    buf_host = np.asarray(buf)  # the single device→host sync
    tn_host = np.asarray(tn)
    traces = [
        [(int(s), int(r)) for s, r in buf_host[q][: int(tn_host[q])]]
        for q in range(q_n)
    ]
    stats = {
        "detector_invocations": int(calls),
        "cache_hits": int(hits),
        "index_hits": int(ihits),
        "detector_lanes": int(lanes),
        "rounds": int(windows) * sync_every,
        "frames_sampled": int(np.asarray(out.step).sum()),
        "merge_high_water": int(hw),
        "merge_overflow": bool(ov),
        "merges": int(windows),
        "final_cache": final_cache,
    }
    return out, traces, stats
