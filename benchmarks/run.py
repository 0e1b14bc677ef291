"""Benchmark harness entry: one section per paper table/figure.

  bench_bias         -- paper 3.3.2 / Fig. 2 (estimator + Poisson validation)
  bench_savings      -- paper Figs. 3-4 (frames-processed savings vs random+)
  bench_batched      -- paper 3.7.1 (cohort batching) + straggler model
  bench_sharded      -- sharded driver steps/sec at 1/2/4/8 shards + parity
  bench_multiquery   -- Q=8 shared detector pass vs sequential (DESIGN.md §9)
  bench_async_compose -- Q=8 × 4 async workers elastic slot pool vs
                        sequential single-query async (DESIGN.md §11)
  bench_plan_compose -- Q=8 × 8-shard composed lowering vs sequential-sharded
                        and single-device multi (DESIGN.md §10)
  bench_service      -- multi-tenant service: 2 admission waves × 4 tenants
                        on one live driver, budget ledger + slot reuse
                        (DESIGN.md §12)
  bench_index_reuse  -- persistent repository index: identical query cold
                        vs warm + second tenant over a warm service, ≥5×
                        fewer detector invocations (DESIGN.md §13)
  bench_overhead     -- paper Fig. 6 (phase breakdown; surrogate fixed costs)
  bench_kernels      -- kernel reference microbenchmarks (CSV)
  bench_roofline     -- Roofline table from dry-run artifacts

Each section *declares* the ``Execution`` capabilities it exercises
(DESIGN.md §10); sections that need a mesh the host cannot provide are
SKIPPED with a logged reason — never silently.  Under
``JAX_PLATFORMS=cpu`` the subprocess-based sections (``forces_devices``)
re-exec children with virtual CPU devices and run anywhere; on an
accelerator they run in-process on the devices the host has.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Callable, Optional

from repro.core.plan import Execution
from repro.launch.mesh import virtual_devices_allowed


@dataclasses.dataclass(frozen=True)
class BenchSpec:
    """A registered benchmark section and its execution requirements."""

    name: str
    run: Callable[[bool], None]        # run(quick)
    execution: Optional[Execution] = None  # capabilities it exercises
    forces_devices: bool = False       # spawns children with forced devices


def should_skip(spec: BenchSpec, available_devices: int) -> str | None:
    """Reason this section cannot run on this host, or None to run it.

    A section declaring a mesh (``execution.shards > 1``) needs that many
    in-process devices unless it forces its own virtual CPU devices, which
    it may only do under ``JAX_PLATFORMS=cpu``.
    """
    if spec.execution is None:
        return None
    forces = spec.forces_devices and virtual_devices_allowed()
    if spec.execution.shards > available_devices and not forces:
        return (
            f"needs a {spec.execution.shards}-way "
            f"'{spec.execution.axis}' mesh but the host exposes "
            f"{available_devices} device(s); run on more devices, or set "
            "JAX_PLATFORMS=cpu for virtual CPU devices"
        )
    if spec.execution.async_workers > 0 and not _threads_available():
        return (
            f"needs {spec.execution.async_workers} async worker thread(s) "
            "but this host cannot start threads"
        )
    return None


def _threads_available() -> bool:
    """Probe that worker threads can actually start on this host (some
    sandboxed/restricted runtimes refuse thread creation)."""
    import threading

    try:
        t = threading.Thread(target=lambda: None, daemon=True)
        t.start()
        t.join(timeout=5.0)
        return not t.is_alive()
    except RuntimeError:
        return False


def _sections() -> list[BenchSpec]:
    from benchmarks import (
        bench_async_compose,
        bench_batched,
        bench_bias,
        bench_chunking,
        bench_index_reuse,
        bench_kernels,
        bench_multiquery,
        bench_overhead,
        bench_plan_compose,
        bench_roofline,
        bench_savings,
        bench_service,
        bench_sharded,
    )

    return [
        BenchSpec("bias_validation(fig2)", lambda quick: bench_bias.main()),
        BenchSpec("savings(fig3-4)",
                  lambda quick: bench_savings.main(quick=quick)),
        BenchSpec("chunking(sec3.5)", lambda quick: bench_chunking.main()),
        BenchSpec("batched(sec3.7.1)", lambda quick: bench_batched.main()),
        BenchSpec("sharded(sec3.7.1)",
                  lambda quick: bench_sharded.main(quick=quick),
                  execution=Execution(shards=8), forces_devices=True),
        BenchSpec("multiquery(sec9)",
                  lambda quick: bench_multiquery.main(quick=quick),
                  execution=Execution(queries_axis=True, cache=-1)),
        BenchSpec("async_compose(sec11)",
                  lambda quick: bench_async_compose.main(quick=quick),
                  execution=Execution(queries_axis=True, async_workers=4,
                                      cache=-1)),
        BenchSpec("plan_compose(sec10)",
                  lambda quick: bench_plan_compose.main(quick=quick),
                  execution=Execution(queries_axis=True, shards=8, cache=-1),
                  forces_devices=True),
        BenchSpec("service(sec12)",
                  lambda quick: bench_service.main(quick=quick),
                  execution=Execution(queries_axis=True, async_workers=4,
                                      cache=-1)),
        BenchSpec("index_reuse(sec13)",
                  lambda quick: bench_index_reuse.main(quick=quick),
                  execution=Execution(queries_axis=True, async_workers=2,
                                      cache=-1)),
        BenchSpec("overhead(fig6)", lambda quick: bench_overhead.main()),
        BenchSpec("kernels", lambda quick: bench_kernels.main()),
        BenchSpec("roofline", lambda quick: bench_roofline.main()),
    ]


SECTIONS = _sections()


def main() -> None:
    import jax

    quick = "--quick" in sys.argv
    available = len(jax.devices())
    for spec in SECTIONS:
        reason = should_skip(spec, available)
        if reason is not None:
            print(f"\n===== {spec.name} ===== SKIPPED: {reason}", flush=True)
            continue
        print(f"\n===== {spec.name} =====", flush=True)
        t0 = time.time()
        spec.run(quick)
        print(f"[{spec.name} done in {time.time() - t0:.1f}s]", flush=True)


if __name__ == "__main__":
    main()
