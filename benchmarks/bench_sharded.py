"""Sharded driver throughput + parity (paper §3.7.1 distributed, DESIGN.md §8).

Measures steps/sec of ``run_search_sharded`` at 1/2/4/8 simulated host
devices against the single-device ``run_search_scan`` baseline, and checks
the acceptance parity: at 8 shards the sharded driver must find the same
result count (±5%) as the scanned driver for the same query and frame
budget on the dashcam config.

Under ``JAX_PLATFORMS=cpu`` each device count needs its own
``--xla_force_host_platform_device_count`` flag, which must be set before
the first jax import — so the parent re-execs this file once per arm and
relays each arm's CSV rows when that arm finishes (child output is
captured, not streamed live).  On an accelerator host every arm runs in
this process on the devices the host has; an arm wider than the host
fails.  On a CPU host the simulated shards CONTEND for the same cores, so steps/sec
here isolates framework/collective overhead, not speedup; the speedup
story needs real devices where detector compute dominates and shards run
concurrently (the async model of bench_batched prices that).
"""
from __future__ import annotations

import os
import subprocess
import sys

from repro.launch.mesh import virtual_device_env, virtual_devices_allowed

DEVICE_COUNTS = (1, 2, 4, 8)


def _child(shards: int, steps: int, parity: bool) -> None:
    import time

    import jax

    from repro.core import (
        Execution,
        SearchPlan,
        init_carry,
        init_matcher,
        init_state,
    )
    from repro.launch.mesh import make_data_mesh
    from repro.sim import RepoSpec, generate
    from repro.sim.oracle import oracle_detect

    cohorts, sync_every = 8, 1
    videos, chunk_frames, m_chunks = 10, 64, 1_000
    spec = RepoSpec(
        video_lengths=[m_chunks * chunk_frames // videos] * videos,
        num_instances=64,
        chunk_frames=chunk_frames,
        seed=0,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    fresh = lambda: init_carry(
        init_state(chunks.length), init_matcher(max_results=512),
        jax.random.PRNGKey(0),
    )
    never = 10**9  # unreachable result limit: measure steady-state rate
    mesh = make_data_mesh(shards)
    scan_plan = SearchPlan(
        result_limit=never, max_steps=steps, cohorts=cohorts,
        method="wilson_hilferty",
    )
    sharded_plan = SearchPlan(
        result_limit=never, max_steps=steps, cohorts=cohorts,
        execution=Execution(shards=shards, sync_every=sync_every)
        if shards > 1 else Execution(strategy="sharded",
                                     sync_every=sync_every),
    )

    def timed(run):
        run()  # compile + warm (max_steps is static, reuse the executable)
        t0 = time.perf_counter()
        res = run()
        return res.steps[0] / (time.perf_counter() - t0)

    if shards == 1:
        rate = timed(lambda: scan_plan.run(fresh(), chunks, detector=det))
        print(f"scanned,1,{cohorts},-,{rate:.0f}", flush=True)
    rate = timed(lambda: sharded_plan.run(
        fresh(), chunks, detector=det, mesh=mesh))
    print(f"sharded,{shards},{cohorts},{sync_every},{rate:.0f}", flush=True)

    if parity and shards == max(DEVICE_COUNTS):
        from repro.configs.exsample_paper import dashcam

        setup = dashcam(seed=0, scale=0.05)
        repo, chunks = generate(setup.repo)
        det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
        fresh = lambda: init_carry(
            init_state(chunks.length), init_matcher(max_results=8192),
            jax.random.PRNGKey(0),
        )
        budget = 2_048
        scan = SearchPlan(
            result_limit=never, max_steps=budget, cohorts=cohorts,
            method="wilson_hilferty",
        ).run(fresh(), chunks, detector=det)
        sh = SearchPlan(
            result_limit=never, max_steps=budget, cohorts=cohorts,
            execution=Execution(shards=shards, sync_every=sync_every),
        ).run(fresh(), chunks, detector=det, mesh=mesh)
        ratio = sh.results[0] / max(scan.results[0], 1)
        ok = "OK" if abs(ratio - 1.0) <= 0.05 else "FAIL"
        print(
            f"parity_dashcam,{shards},scan={scan.results[0]},"
            f"sharded={sh.results[0]},ratio={ratio:.3f},{ok}",
            flush=True,
        )
        assert ok == "OK", f"8-way parity off by {ratio:.3f}x"


def main(quick: bool = False) -> None:
    steps = 256 if quick else 1_024
    print("driver,shards,global_cohorts,sync_every,steps_per_sec")
    if not virtual_devices_allowed():
        for n in DEVICE_COUNTS:
            _child(n, steps, parity=not quick)
        return
    for n in DEVICE_COUNTS:
        env = virtual_device_env(n)
        args = [sys.executable, os.path.abspath(__file__),
                "--child", str(n), "--steps", str(steps)]
        if not quick:
            args.append("--parity")
        r = subprocess.run(args, env=env, capture_output=True, text=True,
                           timeout=1_800)
        sys.stdout.write(r.stdout)
        if r.returncode != 0:
            sys.stdout.write(r.stderr[-2000:])
            raise RuntimeError(f"bench_sharded child (shards={n}) failed")


if __name__ == "__main__":
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        _child(
            int(sys.argv[i + 1]),
            int(sys.argv[sys.argv.index("--steps") + 1]),
            "--parity" in sys.argv,
        )
    else:
        main(quick="--quick" in sys.argv)
