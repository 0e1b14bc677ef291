"""Production search driver: ExSample distinct-object query end-to-end.

Wires together: simulated repository (or any FrameStore), a detector
(oracle or noisy), the ExSample core behind ONE ``SearchPlan`` (DESIGN.md
§10), the cost model and the checkpoint manager — the full Algorithm 1
deployment loop with resumable state.

  PYTHONPATH=src python -m repro.launch.search --limit 50 --cohorts 16
  PYTHONPATH=src python -m repro.launch.search \\
      --plan '{"result_limit": 50, "max_steps": 50000, "cohorts": 16}'
  PYTHONPATH=src python -m repro.launch.search \\
      --plan '{"queries": 4, "result_limit": 20, "max_steps": 50000,
               "cohorts": 8, "execution": {"queries_axis": true,
               "shards": 8, "cache": -1}}'

``--plan`` takes a JSON ``SearchPlan.to_dict()`` document (or ``@file``)
and is the canonical path: the planner validates option compatibility and
lowers to one driver — host loop, scanned, Q-batched, the Q×shards mesh
loop or the async slot runtime; mesh and async plans run their Q-axis
lowering at any Q, so a single query is Q = 1.  The legacy flag
combinations (``--mesh/--sync-every``, ``--queries/--cache-frames``,
``--driver``) still work but are deprecated: they are translated into
the equivalent plan and a ``DeprecationWarning`` is emitted.  When the
plan needs more devices than the host exposes,
``main()`` re-execs into a child with virtual CPU devices if
``JAX_PLATFORMS=cpu`` is set, and fails otherwise
(``launch.mesh.ensure_host_devices``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings

import jax
import jax.numpy as jnp

from repro.configs.exsample_paper import bdd, dashcam
from repro.core import (
    Execution,
    SearchPlan,
    init_carry,
    init_carry_multi,
    init_matcher,
    init_state,
)
from repro.core.baselines import FrameSchedule, run_schedule
from repro.launch.compile_cache import enable_compile_cache
from repro.sim import generate
from repro.sim.costmodel import CostRates, sampling_cost
from repro.sim.oracle import class_select, noisy_detect, oracle_detect
from repro.train.checkpoint import CheckpointManager


def build_plan(args) -> SearchPlan:
    """``--plan`` JSON (inline or ``@file``) or the deprecated legacy flag
    translation — both end in one validated :class:`SearchPlan`."""
    if args.plan:
        text = args.plan
        if text.startswith("@"):
            with open(text[1:]) as f:
                text = f.read()
        return SearchPlan.from_dict(json.loads(text))

    legacy = []
    if args.mesh > 1 or args.sync_every != 1:
        legacy.append("--mesh/--sync-every")
    if args.queries:
        legacy.append("--queries/--cache-frames")
    if args.driver != "scan":
        legacy.append("--driver")
    if legacy:
        warnings.warn(
            f"{', '.join(legacy)} are deprecated: pass the equivalent "
            "--plan '<json>' (SearchPlan.to_dict schema, DESIGN.md §10)",
            DeprecationWarning,
            stacklevel=2,
        )

    shards = args.mesh if args.mesh > 1 else 1
    # the legacy CLI silently ignored --sync-every without --mesh; keep
    # that contract rather than letting the planner reject the combination
    sync_every = args.sync_every if shards > 1 else 1
    if args.sync_every != 1 and shards == 1:
        print(f"--sync-every {args.sync_every} ignored without --mesh "
              "(merge schedule is a mesh-lowering option)")
    cohorts = args.cohorts
    if shards > 1 and cohorts % shards:
        cohorts = cohorts - cohorts % shards or shards
        print(f"--cohorts {args.cohorts} → {cohorts} "
              f"(must be a multiple of --mesh {shards})")
    if args.queries:
        cache = args.cache_frames if args.cache_frames != 0 else None
        return SearchPlan(
            queries=len(args.queries), result_limit=args.limit,
            max_steps=args.max_steps, cohorts=cohorts, trace_every=256,
            execution=Execution(
                queries_axis=True, shards=shards,
                sync_every=sync_every, cache=cache,
            ),
        )
    strategy = "host" if (args.driver == "host" and shards == 1) else "auto"
    if args.driver == "host" and shards > 1:
        print(f"--driver host ignored: --mesh {shards} selects the sharded "
              "lowering (DESIGN.md §8)")
    return SearchPlan(
        result_limit=args.limit, max_steps=args.max_steps, cohorts=cohorts,
        trace_every=256,
        execution=Execution(
            strategy=strategy, shards=shards, sync_every=sync_every,
        ),
    )


def _print_result(res, args, wall: float) -> None:
    rates = CostRates()
    st = res.stats
    if res.num_queries > 1:
        for q in range(res.num_queries):
            print(f"  query {q}: {res.results[q]} results / "
                  f"{res.steps[q]:,} frames")
    cost = sampling_cost(st.detector_invocations, rates)
    line = (f"ExSample[{res.kind}]: {sum(res.results)} results / "
            f"{st.frames_sampled:,} frames sampled / "
            f"{st.detector_invocations:,} detector invocations")
    if st.cache_hits or res.num_queries > 1:
        line += (f" ({st.cache_hits:,} cache hits, "
                 f"hit rate {st.cache_hit_rate:.2f}, "
                 f"{st.amortization:.2f}x amortization)")
    print(line + f" / est. {cost.total_s:.0f} gpu·s "
          f"(driver wall {wall:.1f}s)")
    if st.merges:
        print(f"  merges: {st.merges} windows, ring high-water "
              f"{st.merge_high_water}/{st.matcher_capacity}"
              + (f", {st.results_spilled} results spilled to host log"
                 if st.results_spilled else "")
              + (" OVERFLOW" if st.merge_overflow else ""))


def _run_elastic_smoke(plan, carry, chunks, det, select, args) -> None:
    """--kill-worker path: drive the plan through ElasticShardedRunner on a
    synthetic boundary clock, silencing the listed workers after
    ``--kill-after-windows`` windows; the monitor's dead verdict lands two
    boundaries later and the search finishes on the shrunken mesh."""
    import numpy as np

    from repro.core.runtime import ElasticShardedRunner
    from repro.distributed.fault_tolerance import HeartbeatMonitor

    ex = plan.execution
    cache = ex.cache if ex.cache is not None else 0
    if cache == -1:
        cache = chunks.total_frames
    t = [0.0]

    def clock():
        t[0] += 100.0
        return t[0]

    runner = ElasticShardedRunner(
        carry, chunks, detector=det, result_limits=plan.result_limit,
        max_steps=plan.max_steps, num_shards=ex.shards,
        cohorts=plan.cohorts, sync_every=ex.sync_every, select=select,
        cache_frames=cache,
        monitor=HeartbeatMonitor(suspect_after_s=50.0, dead_after_s=150.0),
        clock=clock, sync_windows=1,
    )
    t0 = time.time()
    windows = 0
    while True:
        alive = runner.step()
        windows += 1
        if windows == args.kill_after_windows:
            for w in args.kill_worker:
                print(f"elastic: worker {w} silenced after window {windows}")
                runner.kill_worker(w)
        if not alive:
            break
    wall = time.time() - t0
    out, stats = runner.carry, runner.stats
    for ev in stats["reshard_events"]:
        print(f"elastic: reshard @window {ev['window']}: "
              f"{ev['from_shards']} -> {ev['to_shards']} shards "
              f"(dead={ev['dead']})")
    results = np.asarray(out.results).tolist()
    print(f"elastic: finished on {runner.num_shards} shards: "
          f"{sum(results)} results / "
          f"{int(np.asarray(out.step).sum()):,} frames sampled / "
          f"{stats['detector_invocations']:,} detector invocations "
          f"({stats['cache_hits']:,} cache hits) "
          f"(driver wall {wall:.1f}s)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", default="",
                    help="SearchPlan JSON (or @file) — the canonical path "
                         "(DESIGN.md §10); overrides the deprecated "
                         "driver-shaping flags below")
    ap.add_argument("--dataset", default="dashcam", choices=["dashcam", "bdd"])
    ap.add_argument("--scale", type=float, default=0.2)
    ap.add_argument("--query-class", type=int, default=0)
    ap.add_argument("--limit", type=int, default=50)
    ap.add_argument("--cohorts", type=int, default=16)
    ap.add_argument("--max-steps", type=int, default=50_000)
    ap.add_argument("--detector", default="oracle", choices=["oracle", "noisy"])
    ap.add_argument("--driver", default="scan", choices=["scan", "host"],
                    help="[deprecated: use --plan] scan = device-resident "
                         "driver; host = per-step reference loop")
    ap.add_argument("--mesh", type=int, default=1,
                    help="[deprecated: use --plan] N>1 shards the search "
                         "over an N-way data mesh (DESIGN.md §8); under "
                         "JAX_PLATFORMS=cpu virtual host devices are forced "
                         "automatically")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="[deprecated: use --plan] rounds between "
                         "sampler/matcher merges on the mesh lowerings")
    ap.add_argument("--queries", type=int, nargs="+", default=None,
                    metavar="CLASS",
                    help="[deprecated: use --plan] one concurrent search per "
                         "listed query class through the Q-axis lowering "
                         "(DESIGN.md §9); with --plan, lists the per-query "
                         "classes (default 0..Q-1)")
    ap.add_argument("--cache-frames", type=int, default=-1,
                    help="[deprecated: use --plan] detection-cache capacity "
                         "for --queries (-1 = one slot per repository "
                         "frame, 0 = off)")
    ap.add_argument("--kill-worker", type=int, action="append", default=[],
                    metavar="W",
                    help="elastic-shrink smoke (multi-sharded plans only): "
                         "silence worker W mid-run and recover on the "
                         "survivors via ElasticShardedRunner (repeatable)")
    ap.add_argument("--kill-after-windows", type=int, default=2,
                    help="sync windows to run before the --kill-worker "
                         "workers go silent")
    ap.add_argument("--baseline", action="store_true",
                    help="also run random+ for comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    plan = build_plan(args)
    lowered = plan.lower()   # validate BEFORE re-exec / data generation

    if plan.execution.shards > 1:
        from repro.launch.mesh import ensure_host_devices

        ensure_host_devices(
            plan.execution.shards,
            argv=[sys.executable, "-m", "repro.launch.search"] + sys.argv[1:],
        )

    enable_compile_cache()
    setup = (dashcam if args.dataset == "dashcam" else bdd)(
        seed=args.seed, scale=args.scale
    )
    repo, chunks = generate(setup.repo)
    print(f"{args.dataset}: {chunks.total_frames:,} frames / "
          f"{chunks.num_chunks} chunks / {repo.num_instances} instances")
    print(f"plan: lowering={lowered.kind} method={lowered.method} "
          f"{json.dumps(plan.to_dict())}")

    q_n = plan.queries
    multi = lowered.q_axis
    select = None
    if multi:
        classes = args.queries if args.queries else list(range(q_n))
        if len(classes) != q_n:
            raise SystemExit(
                f"--queries lists {len(classes)} classes for a "
                f"{q_n}-query plan")
        if args.detector == "oracle":
            det = lambda key, frame: oracle_detect(
                repo, frame, query_class=None)
        else:
            det = lambda key, frame: noisy_detect(
                key, repo, frame, query_class=None)
        select = class_select(repo, classes)
        keys = jnp.stack([
            jax.random.fold_in(jax.random.PRNGKey(args.seed), q)
            for q in range(q_n)
        ])
        carry = init_carry_multi(
            init_state(chunks.length), init_matcher(max_results=8192), keys
        )
    else:
        if args.detector == "oracle":
            det = lambda key, frame: oracle_detect(
                repo, frame, query_class=args.query_class)
        else:
            det = lambda key, frame: noisy_detect(
                key, repo, frame, query_class=args.query_class)
        carry = init_carry(
            init_state(chunks.length), init_matcher(max_results=8192),
            jax.random.PRNGKey(args.seed),
        )

    if args.kill_worker:
        if not (multi and lowered.kind == "multi_sharded"):
            raise SystemExit(
                "--kill-worker needs a queries_axis + shards>1 plan "
                f"(multi_sharded lowering, got {lowered.kind})")
        _run_elastic_smoke(plan, carry, chunks, det, select, args)
        return

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    t0 = time.time()
    res = lowered.run(carry, chunks, detector=det, select=select)
    wall = time.time() - t0
    _print_result(res, args, wall)
    if mgr:
        mgr.save(res.stats.frames_sampled, res.carry,
                 extra={"plan": plan.to_dict()})
        print(f"state checkpointed to {args.ckpt_dir}")
    if args.baseline and not multi:
        base = init_carry(
            init_state(chunks.length), init_matcher(max_results=8192),
            jax.random.PRNGKey(args.seed),
        )
        rp, _ = run_schedule(
            base, chunks,
            FrameSchedule.randomplus(chunks.total_frames, plan.max_steps),
            detector=det, result_limit=res.plan.result_limit
            if isinstance(res.plan.result_limit, int) else args.limit,
        )
        ex_steps = max(res.stats.frames_sampled, 1)
        print(f"random+: {int(rp.results)} results / {int(rp.step):,} frames "
              f"→ savings {int(rp.step) / ex_steps:.2f}x")


if __name__ == "__main__":
    main()
