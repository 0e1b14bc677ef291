"""On-chip smoke run of the ExSample search engine at the paper's scale.

  python chip_smoke.py               # phases A, B, C on one TPU chip
  python chip_smoke.py --four-chips  # the Q×S composed lowering on 4 chips

Drives the main path through the entry points a user calls — a
``SearchPlan`` lowered by ``core/executor.py`` to its driver, and the
multi-tenant ``SearchService`` behind ``launch/serve_search.handle_request``
— on the paper's §4 repositories (``configs/exsample_paper``, scale 1.0).
The detector is the seeded oracle of ``sim/oracle.py``.

  * Phase A — one query on dashcam (1.08 M frames, 22 chunks) through the
    scanned lowering with exact Gamma Thompson draws, checked against the
    plain reference: the ``strategy: "host"`` loop on the same seed must
    give the same step, results and sampler statistics.
  * Phase B — 8 queries, one per class, on the Q axis over BDD (1.2 M
    frames, 1000 chunks) with a one-slot-per-frame detection cache on the
    device and the Pallas Thompson kernel; the compiled search program
    must contain the kernel, and the kernel must agree with the jnp
    Wilson–Hilferty argmax on the same normals.
  * Phase C — the service: 4 tenants submitted and drained over dashcam.
  * ``--four-chips`` — 8 queries × 4 shards through the composed
    ``multi_sharded`` lowering against 8 single-query mesh plans on the
    same mesh, each the same lowering at Q = 1: per query, step and
    results must be bit-identical, since sharing never changes a query's
    values (DESIGN.md §10), and nothing else runs.

Exits non-zero, printing no result line, when JAX finds no TPU (there is
no CPU fallback) or when any check fails.  Wall and compile seconds and
peak device memory are printed as bring-up observations, not benchmark
numbers.  The last line of a passing run is one JSON object naming the
device.  The persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
SCALE = 1.0   # the paper's §4 repositories at full size
# top-two relative margin below which the kernel and the XLA argmax may
# pick different chunks by float rounding alone
KERNEL_TOL = 1e-5
# Tracing is left out: a jit traced inside another reports its own trace
# duration inside its caller's, so summing trace events counts twice.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Clock:
    """Lowering + XLA compile seconds and persistent-cache hits and writes,
    from JAX's own monitoring events, so each phase can split compilation
    out of its wall time.  JAX reports a cache miss only when it writes
    the new entry, and it writes none for a program compiled in under
    ``jax_persistent_cache_min_compile_time_secs``, so such programs show
    as neither."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self):
        return time.perf_counter(), self.compile_s, self.cache_hits, \
            self.cache_writes


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"  check passed: {what}", flush=True)


def _carry(chunks, key, max_results=8192):
    from repro.core import init_carry, init_matcher, init_state

    return init_carry(
        init_state(chunks.length), init_matcher(max_results=max_results), key
    )


def _carry_multi(chunks, keys, max_results=8192):
    from repro.core import init_carry_multi, init_matcher, init_state

    return init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=max_results), keys
    )


def _stopped(results, steps, limit, budget) -> bool:
    """The driver's own stop rule: result limit or frame budget."""
    return results >= limit or steps >= budget


def phase_a() -> None:
    """Single query, scanned lowering, against the host reference loop."""
    import jax
    import numpy as np

    from repro.configs.exsample_paper import dashcam
    from repro.core import Execution, SearchPlan
    from repro.sim import generate
    from repro.sim.oracle import oracle_detect

    repo, chunks = generate(dashcam(seed=SEED, scale=SCALE).repo)
    print(f"  dashcam(scale={SCALE}): {chunks.total_frames:,} frames / "
          f"{chunks.num_chunks} chunks / {repo.num_instances} instances")
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    key = jax.random.PRNGKey(SEED)

    plan = SearchPlan(result_limit=50, max_steps=50_000, cohorts=50)
    check(plan.resolve() == ("scan", "exact"),
          f"plan lowers to the scan driver with exact Gamma draws "
          f"{plan.resolve()}")
    res = plan.run(_carry(chunks, key), chunks, detector=det)
    print(f"  scan: {res.results[0]} results / {res.steps[0]:,} frames")
    check(res.results[0] > 0
          and _stopped(res.results[0], res.steps[0], 50, 50_000),
          "the query found results and reached its result limit or its "
          "frame budget")

    # the same plan at a 2,000-frame budget, and again with a limit it
    # cannot reach so that the comparison spans all 40 rounds
    for limit in (50, 10**9):
        ref = {}
        for strategy in ("auto", "host"):
            r = SearchPlan(
                result_limit=limit, max_steps=2_000, cohorts=50,
                execution=Execution(strategy=strategy),
            ).run(_carry(chunks, key), chunks, detector=det)
            ref[r.kind] = r
        scan, host = ref["scan"], ref["host"]
        diverged = [
            name for name, a, b in (
                ("step", scan.carry.step, host.carry.step),
                ("results", scan.carry.results, host.carry.results),
                ("sampler.n", scan.carry.sampler.n, host.carry.sampler.n),
                ("sampler.n1", scan.carry.sampler.n1, host.carry.sampler.n1),
            )
            if not np.array_equal(np.asarray(a), np.asarray(b))
        ]
        print(f"  reference at 2,000 frames, result limit {limit}: scan "
              f"{scan.steps[0]} steps / {scan.results[0]} results, host "
              f"{host.steps[0]} steps / {host.results[0]} results; "
              f"diverged: {diverged or 'none'}")
        check(not diverged,
              "scan lowering == host reference loop (step, results, "
              "sampler.n, sampler.n1)")


def phase_b() -> None:
    """8 queries on the Q axis with the Pallas kernel and a full cache."""
    import jax
    import jax.numpy as jnp

    from repro.configs.exsample_paper import bdd
    from repro.core import Execution, SearchPlan, exsample
    from repro.sim import generate
    from repro.sim.oracle import class_select, oracle_detect

    setup = bdd(seed=SEED, scale=SCALE)
    repo, chunks = generate(setup.repo)
    print(f"  bdd(scale={SCALE}): {chunks.total_frames:,} frames / "
          f"{chunks.num_chunks} chunks / {repo.num_instances} instances")
    q_n, cohorts, limit, budget = setup.num_classes, 50, 30, 60_000
    classes = list(range(q_n))
    det = lambda key, frame: oracle_detect(repo, frame, query_class=None)
    select = class_select(repo, classes)
    keys = jnp.stack([
        jax.random.fold_in(jax.random.PRNGKey(SEED), q) for q in classes
    ])
    plan = SearchPlan(
        queries=q_n, result_limit=limit, max_steps=budget, cohorts=cohorts,
        method="pallas",
        execution=Execution(queries_axis=True, cache=-1),
    )
    check(plan.resolve() == ("multi", "pallas"),
          f"plan lowers to the Q-axis driver with the Pallas kernel "
          f"{plan.resolve()}")
    # keep the arguments the executor hands the Q-axis search program, to
    # read back below the very program that ran
    calls = []
    program = exsample._search_multi_device

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return program(*args, **kwargs)

    exsample._search_multi_device = spy
    try:
        res = plan.run(
            _carry_multi(chunks, keys), chunks, detector=det, select=select
        )
    finally:
        exsample._search_multi_device = program
    for q in classes:
        print(f"  query {q}: {res.results[q]} results / "
              f"{res.steps[q]:,} frames")
    st = res.stats
    print(f"  {st.detector_invocations:,} detector invocations / "
          f"{st.cache_hits:,} cache hits / {st.rounds} rounds")
    check(all(r > 0 and _stopped(r, s, limit, budget)
              for r, s in zip(res.results, res.steps)),
          "every query found results and reached its limit or its budget")

    # kernel [Q, C, M] vs the jnp Wilson–Hilferty argmax on the same
    # normals, on the statistics the search ended with
    _kernel_vs_reference(res.carry.sampler, cohorts)

    # the search program that ran, lowered from the executor's own
    # arguments, holds the kernel
    check(len(calls) == 1, f"the plan ran one Q-axis search program "
                           f"({len(calls)} calls)")
    args, kwargs = calls[0]
    hlo = program.lower(*args, **kwargs).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the compiled Q-axis search program contains the Pallas kernel "
          "(tpu_custom_call)")


def _kernel_vs_reference(state, cohorts: int) -> None:
    """The batched Pallas kernel against ``draw_scores_wilson_hilferty``'s
    argmax on the same normals, for leading-[Q] sampler statistics.  Rows
    whose top-two relative margin is within ``KERNEL_TOL`` may differ by
    float rounding between the kernel and the XLA program, so only rows
    decided by more than ``KERNEL_TOL`` must agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import thompson
    from repro.kernels.thompson.kernel import thompson_choose_batched

    q_n, m = state.n1.shape
    keys = jnp.stack([
        jax.random.fold_in(jax.random.PRNGKey(SEED + 1), q)
        for q in range(q_n)
    ])
    alpha, beta = thompson.gamma_params(state)
    alpha = jnp.where(state.exhausted(), -1.0, alpha)
    z = jax.vmap(
        lambda k: jax.random.normal(k, (cohorts, m), jnp.float32)
    )(keys)
    kidx, _ = thompson_choose_batched(alpha, beta, z)
    scores = np.asarray(jax.vmap(
        lambda k, s: thompson.draw_scores_wilson_hilferty(
            k, s, cohorts=cohorts)
    )(keys, state))
    top2 = np.sort(scores, axis=-1)[..., -2:]
    with np.errstate(invalid="ignore", divide="ignore"):
        margin = (top2[..., 1] - top2[..., 0]) / np.abs(top2[..., 1])
    decided = np.isfinite(top2[..., 1]) & (margin > KERNEL_TOL)
    agree = np.asarray(kidx) == scores.argmax(-1)
    print(f"  kernel vs jnp argmax at {tuple(z.shape)}: "
          f"{int(decided.sum())} of {decided.size} rows decided by a "
          f"top-two margin above {KERNEL_TOL:g}; {int(agree.sum())} rows "
          f"agree")
    check(bool(decided.mean() > 0.9),
          "over 90% of rows have a decided argmax")
    check(bool(np.all(agree | ~decided)),
          f"kernel argmax == jnp argmax wherever the top-two margin "
          f"exceeds {KERNEL_TOL:g}")


def phase_c() -> None:
    """The service: 4 tenants through handle_request, then drain."""
    from repro.launch.serve_search import (
        build_parser,
        build_service,
        handle_request,
    )

    args = build_parser().parse_args(
        ["--dataset", "dashcam", "--scale", str(SCALE), "--seed", str(SEED)]
    )
    service = build_service(args)
    service.start()
    try:
        for c in range(4):
            resp = handle_request(service, {
                "op": "submit", "tenant": f"t{c}", "class": c, "seed": c,
                "plan": {"result_limit": 20, "max_steps": 20_000,
                         "cohorts": args.cohorts,
                         "execution": {"queries_axis": True}},
            })
            check(resp.get("ok") and resp.get("state") == "running",
                  f"tenant t{c} admitted ({resp.get('state')})")
        resp = handle_request(service, {"op": "drain", "deadline_s": 900})
    finally:
        service.stop()
    check(resp.get("ok"), f"drain answered ok ({resp.get('error')})")
    for tid, t in sorted(resp["tenants"].items()):
        print(f"  tenant {tid}: {t['state']} — {t.get('results')} results / "
              f"{t.get('steps'):,} frames / "
              f"{t.get('detector_invocations'):,} fresh detections")
    check(all(t["state"] == "finished" and t["results"] > 0
              for t in resp["tenants"].values()),
          "every tenant finished with results")
    b = service.budget
    check(abs(b.committed_s) < 1e-6 and b.spent_s > 0,
          f"budget ledger settled (committed {b.committed_s}, "
          f"spent {b.spent_s})")


def phase_four_chips() -> None:
    """Q=8 × S=4 composed lowering vs 8 single-query mesh plans (the same
    lowering at Q = 1) on one mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.exsample_paper import dashcam
    from repro.core import Execution, SearchPlan, executor
    from repro.launch.mesh import make_data_mesh
    from repro.sim import generate
    from repro.sim.oracle import class_select, filter_class, oracle_detect

    shards, q_n, cohorts, limit, budget = 4, 8, 48, 50, 50_000
    setup = dashcam(seed=SEED, scale=SCALE)
    repo, chunks = generate(setup.repo)
    print(f"  dashcam(scale={SCALE}): {chunks.total_frames:,} frames / "
          f"{chunks.num_chunks} chunks / {repo.num_instances} instances")
    mesh = make_data_mesh(shards)
    devs = list(mesh.devices.flat)
    check(len({d.id for d in devs}) == shards,
          f"the mesh spans {shards} distinct devices "
          f"{[d.id for d in devs]}")
    det = lambda key, frame: oracle_detect(repo, frame, query_class=None)
    classes = list(range(q_n))
    keys = jnp.stack([
        jax.random.fold_in(jax.random.PRNGKey(SEED), q) for q in classes
    ])
    # the [Q, M] statistics as the composed program leaves them, before the
    # executor trims the shard padding off (the trimmed copy is replicated)
    resident = []
    program = executor._search_multi_sharded_device

    def spy(*args, **kwargs):
        outs = program(*args, **kwargs)
        resident.append(outs[0])
        return outs

    executor._search_multi_sharded_device = spy
    try:
        res = SearchPlan(
            queries=q_n, result_limit=limit, max_steps=budget,
            cohorts=cohorts,
            execution=Execution(shards=shards, sync_every=1, cache=-1),
        ).run(
            _carry_multi(chunks, keys), chunks, detector=det,
            select=class_select(repo, classes), mesh=mesh,
        )
    finally:
        executor._search_multi_sharded_device = program
    check(res.kind == "multi_sharded", f"composed lowering ({res.kind})")
    n1 = resident[-1]
    pieces = {s.device.id: s.data.shape for s in n1.addressable_shards}
    check(set(pieces) == {d.id for d in devs}
          and all(p[-1] * shards == n1.shape[-1] for p in pieces.values()),
          f"the [Q, M] sampler statistics {tuple(n1.shape)} sit split over "
          f"all {shards} devices: {pieces}")
    st = res.stats
    print(f"  composed: {sum(res.results)} results / {st.frames_sampled:,} "
          f"frames / {st.detector_invocations:,} detector invocations")
    same = True
    for q in classes:
        solo_det = lambda key, frame, c=q: filter_class(
            repo, det(key, frame), c)
        solo = SearchPlan(
            result_limit=limit, max_steps=budget, cohorts=cohorts,
            execution=Execution(shards=shards, sync_every=1),
        ).run(_carry(chunks, keys[q]), chunks, detector=solo_det, mesh=mesh)
        stats_same = all(
            np.array_equal(np.asarray(a), np.asarray(b[q]))
            for a, b in ((solo.carry.sampler.n, res.carry.sampler.n),
                         (solo.carry.sampler.n1, res.carry.sampler.n1))
        )
        q_same = (solo.steps[0], solo.results[0]) == (
            res.steps[q], res.results[q])
        same &= q_same
        print(f"  query {q}: composed {res.steps[q]} steps / "
              f"{res.results[q]} results, alone {solo.steps[0]} "
              f"steps / {solo.results[0]} results; sampler statistics "
              f"{'equal' if stats_same else 'DIFFER'}")
    check(same, "per query, composed step and results are bit-identical "
                "to the query's own mesh plan")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip composed-vs-single-query "
                         "mesh phase")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = Clock()
    phases = (
        [("four-chips", phase_four_chips)] if args.four_chips
        else [("A", phase_a), ("B", phase_b), ("C", phase_c)]
    )
    failed = []
    for name, fn in phases:
        print(f"phase {name}: {fn.__doc__}", flush=True)
        t0, c0, h0, w0 = clock.snapshot()
        try:
            fn()
            verdict = "PASS"
        except Exception:  # noqa: BLE001 — report and go on to the next phase
            traceback.print_exc()
            failed.append(name)
            verdict = "FAIL"
        t1, c1, h1, w1 = clock.snapshot()
        peak = dev.memory_stats().get("peak_bytes_in_use")
        print(f"phase {name}: {verdict} — wall {t1 - t0:.1f} s, of which "
              f"lowering + compile {c1 - c0:.1f} s; persistent cache "
              f"{h1 - h0} hits / "
              f"{w1 - w0} writes; peak_bytes_in_use {peak:,}", flush=True)
    if failed:
        print(f"chip_smoke: phases failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
