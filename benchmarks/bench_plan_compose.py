"""Composed Q×shards lowering: detector invocations per result (DESIGN.md §10).

The acceptance comparison for the ``SearchPlan`` composition the legacy API
could not express: Q = 8 overlapping dashcam queries (two predicates × four
users) on an 8-way data mesh, THREE arms at identical per-query keys and
budgets:

  * **sequential-sharded** — the legacy-API ceiling: one 8-way
    ``strategy='sharded'`` plan per query, run one after another; every
    sampled frame pays a detector invocation.
  * **composed** — ONE ``queries_axis × shards`` plan: all 8 queries inside
    the §8 mesh loop, sharing per-shard deduplicated + cached detector
    passes.  With the oracle detector each query's trajectory is
    bit-identical to its own sequential-sharded run (the §10 parity
    contract), so the invocation ratio is exactly the amortization factor.
  * **single-device multi** — the §9 Q-batched driver, for the result-count
    cross-check (different PRNG path, so statistical agreement only).

Gates: composed per-query results == sequential-sharded per-query results
(bit parity); ≥ 2x fewer detector invocations per result than
sequential-sharded; per-query result counts within 15% (or one sync
window) of the single-device multi driver.

Needs 8 devices.  Under ``JAX_PLATFORMS=cpu`` the parent re-execs a child
with 8 virtual CPU devices (same pattern as bench_sharded); on an
accelerator host it runs in this process and needs 8 devices there.
"""
from __future__ import annotations

import os
import subprocess
import sys

from repro.launch.mesh import virtual_device_env, virtual_devices_allowed

Q_CLASSES = (0, 0, 0, 0, 1, 1, 1, 1)   # two predicates × four users
SHARDS = 8


def _child(quick: bool) -> None:
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs.exsample_paper import dashcam
    from repro.core import (
        Execution,
        SearchPlan,
        init_carry,
        init_carry_multi,
        init_matcher,
        init_state,
    )
    from repro.sim import generate
    from repro.sim.oracle import class_select, filter_class, oracle_detect

    scale = 0.02 if quick else 0.05
    limit = 12 if quick else 25
    budget = 1_024 if quick else 2_048
    cohorts, sync_every = SHARDS, 1
    setup = dashcam(seed=0, scale=scale)
    repo, chunks = generate(setup.repo)
    q_n = len(Q_CLASSES)

    det_all = lambda key, frame: oracle_detect(repo, frame, query_class=None)
    select = class_select(repo, Q_CLASSES)

    def class_det(c):
        return lambda key, frame: filter_class(repo, det_all(key, frame), c)

    keys = [jax.random.fold_in(jax.random.PRNGKey(0), q) for q in range(q_n)]
    fresh = lambda k: init_carry(
        init_state(chunks.length), init_matcher(max_results=4096), k
    )
    fresh_multi = lambda: init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=4096),
        jnp.stack(keys),
    )

    # ---- arm 1: sequential-sharded (one 8-way plan per query) ----
    seq_plan = lambda: SearchPlan(
        result_limit=limit, max_steps=budget, cohorts=cohorts,
        execution=Execution(shards=SHARDS, sync_every=sync_every),
    )
    seq_steps, seq_results, seq_wall = [], [], 0.0
    for q in range(q_n):
        t0 = time.perf_counter()
        res = seq_plan().run(
            fresh(keys[q]), chunks, detector=class_det(Q_CLASSES[q])
        )
        seq_wall += time.perf_counter() - t0
        seq_steps.append(res.steps[0])
        seq_results.append(res.results[0])

    # ---- arm 2: composed Q×shards (ONE plan) ----
    t0 = time.perf_counter()
    comp = SearchPlan(
        queries=q_n, result_limit=limit, max_steps=budget, cohorts=cohorts,
        execution=Execution(
            queries_axis=True, shards=SHARDS, sync_every=sync_every,
            cache=-1,
        ),
    ).run(fresh_multi(), chunks, detector=det_all, select=select)
    comp_wall = time.perf_counter() - t0
    assert comp.kind == "multi_sharded"

    # ---- arm 3: single-device multi (result-count cross-check) ----
    multi = SearchPlan(
        queries=q_n, result_limit=limit, max_steps=budget, cohorts=cohorts,
        method="wilson_hilferty",
        execution=Execution(queries_axis=True, cache=-1),
    ).run(fresh_multi(), chunks, detector=det_all, select=select)

    seq_inv = sum(seq_steps)          # one invocation per sampled frame
    comp_inv = comp.stats.detector_invocations
    seq_per_result = seq_inv / max(sum(seq_results), 1)
    comp_per_result = comp_inv / max(sum(comp.results), 1)
    ratio = seq_per_result / max(comp_per_result, 1e-9)

    print("arm,queries,results,frames_sampled,detector_invocations,"
          "det_per_result,wall_s")
    print(f"sequential_sharded,{q_n},{sum(seq_results)},{seq_inv},"
          f"{seq_inv},{seq_per_result:.2f},{seq_wall:.1f}")
    print(f"composed,{q_n},{sum(comp.results)},"
          f"{comp.stats.frames_sampled},{comp_inv},{comp_per_result:.2f},"
          f"{comp_wall:.1f}")
    print(f"multi_1dev,{q_n},{sum(multi.results)},"
          f"{multi.stats.frames_sampled},"
          f"{multi.stats.detector_invocations},"
          f"{multi.stats.detector_invocations / max(sum(multi.results), 1):.2f},-")
    print(f"amortization,{q_n},cache_hits={comp.stats.cache_hits},"
          f"hit_rate={comp.stats.cache_hit_rate:.2f},"
          f"merge_high_water={comp.stats.merge_high_water},"
          f"ratio={ratio:.2f}x,{'OK' if ratio >= 2.0 else 'FAIL'}")

    # composed ≡ sequential-sharded per query (oracle detector, §10 parity)
    assert list(comp.results) == seq_results, (list(comp.results), seq_results)
    assert list(comp.steps) == seq_steps, (list(comp.steps), seq_steps)
    # the headline gate: ≥2x fewer detector invocations per result
    assert ratio >= 2.0, f"amortization {ratio:.2f}x below the 2x gate"
    # per-query result counts match the single-device multi driver within
    # one sync window / 15% (different PRNG stream => statistical gate)
    window = cohorts * sync_every
    for q in range(q_n):
        c, m = comp.results[q], multi.results[q]
        assert abs(c - m) <= max(window, 0.15 * max(c, m)), (q, c, m)
    print("plan_compose_parity,OK")


def main(quick: bool = False) -> None:
    if not virtual_devices_allowed():
        _child(quick)   # on the host's own devices; needs SHARDS of them
        return
    env = virtual_device_env(SHARDS)
    args = [sys.executable, os.path.abspath(__file__), "--child"]
    if quick:
        args.append("--quick")
    r = subprocess.run(args, env=env, capture_output=True, text=True,
                       timeout=3_600)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stdout.write(r.stderr[-3000:])
        raise RuntimeError("bench_plan_compose child failed")


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child("--quick" in sys.argv)
    else:
        main(quick="--quick" in sys.argv)
