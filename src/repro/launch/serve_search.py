"""Search service front: JSON requests over stdin → one live driver.

Boots a :class:`~repro.serve.service.SearchService` around a simulated
repository and serves line-delimited JSON requests on stdin (the thin-RPC
transport every orchestration layer can speak — a real deployment would
mount :func:`handle_request` behind HTTP; the protocol is the same dict in,
dict out):

  {"op": "submit", "tenant": "a", "class": 0, "seed": 1,
   "plan": {"result_limit": 10, "max_steps": 4000, "cohorts": 4,
            "execution": {"queries_axis": true,
                          "service": {"slo_latency_s": 30.0}}}}
  {"op": "stats"}
  {"op": "drain"}

One JSON response per request line on stdout.  EOF implies ``drain`` —
the front never exits with admitted work unfinished.  Example:

  printf '%s\\n' '{"op": "submit", ...}' '{"op": "stats"}' | \\
      PYTHONPATH=src python -m repro.launch.serve_search --budget-s 500

Tenants bind their predicate by query CLASS: the service holds ONE
class-agnostic detector and one ``class_select`` over the repository's
whole class universe, and a tenant's ``class`` rides the driver's
``select_id`` routing — admission never recompiles anything
(DESIGN.md §12).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.exsample_paper import bdd, dashcam
from repro.core import init_carry_multi, init_matcher, init_state
from repro.core.plan import PlanError, SearchPlan
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.service import PumpFailure, SearchService
from repro.sim import generate
from repro.sim.costmodel import CostRates
from repro.sim.oracle import class_select, oracle_detect


def build_service(args) -> SearchService:
    """World + class-agnostic detector + universe ``class_select`` + an
    empty-pool service under the CLI's cost budget."""
    setup = (dashcam if args.dataset == "dashcam" else bdd)(
        seed=args.seed, scale=args.scale
    )
    repo, chunks = generate(setup.repo)
    num_classes = int(jnp.max(repo.inst_class)) + 1
    detector = lambda key, frame: oracle_detect(
        repo, frame, query_class=None
    )
    select = class_select(repo, list(range(num_classes)))
    proto = init_carry_multi(
        init_state(chunks.length),
        init_matcher(max_results=args.max_results),
        jnp.stack([jax.random.PRNGKey(0)]),
    )
    index = None
    index_path = getattr(args, "index", None)
    if index_path:
        from repro.index.store import RepositoryIndex

        index = RepositoryIndex(
            index_path,
            detector_version=getattr(args, "detector_version", "v0"),
            prior_weight=getattr(args, "prior_weight", 0.0),
        )
    service = SearchService(
        proto, chunks, detector,
        select=select,
        budget_s=args.budget_s,
        rates=CostRates(),
        cohorts=args.cohorts,
        num_workers=args.workers,
        max_steps=args.max_steps,
        cache_frames=chunks.total_frames if args.cache else 0,
        slots_per_batch=args.slots_per_batch,
        index=index,
    )
    service.num_classes = num_classes
    print(
        f"service: {args.dataset} {chunks.total_frames:,} frames / "
        f"{num_classes} classes / budget {args.budget_s:.0f}s / "
        f"cohorts {args.cohorts} x {args.workers} workers",
        file=sys.stderr,
    )
    return service


def handle_request(service: SearchService, obj: dict) -> dict:
    """One request dict → one response dict (transport-agnostic; the
    stdin loop and the tests both call this)."""
    op = obj.get("op")
    try:
        if op == "submit":
            plan = SearchPlan.from_dict(obj["plan"])
            tenant = service.submit(
                str(obj["tenant"]),
                plan,
                seed=int(obj.get("seed", 0)),
                select_id=(
                    int(obj["class"]) if obj.get("class") is not None
                    else None
                ),
            )
            return {"ok": True, **tenant.to_dict()}
        if op == "stats":
            return {"ok": True, **service.stats()}
        if op == "drain":
            service.drain(deadline_s=float(obj.get("deadline_s", 120.0)))
            return {"ok": True, **service.stats()}
        return {"ok": False, "error": f"unknown op {op!r} "
                                      "(submit | stats | drain)"}
    except PlanError as e:
        return {"ok": False, "error": str(e), "field": e.field}
    except (KeyError, ValueError, TimeoutError, PumpFailure) as e:
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}


def _print_tenant_summary(service: SearchService) -> None:
    for tid, t in service.stats()["tenants"].items():
        line = f"  tenant {tid}: {t['state']}"
        if "results" in t:
            line += (
                f" — {t['results']} results / {t['steps']:,} frames / "
                f"{t['detector_invocations']:,} fresh detections "
                f"({t['cache_hits']:,} cache hits)"
            )
            if t.get("ttfr_s") is not None:
                met = t.get("slo_met")
                line += f", first result {t['ttfr_s']:.2f}s" + (
                    "" if met is None else f" (SLO {'met' if met else 'MISSED'})"
                )
        elif t["state"] == "rejected":
            line += f" — {t['reason']}"
        print(line, file=sys.stderr)


def build_parser(ap: Optional[argparse.ArgumentParser] = None
                 ) -> argparse.ArgumentParser:
    """The service's CLI surface, reusable by other transports (the HTTP
    front extends this same parser with its bind address)."""
    if ap is None:
        ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="dashcam", choices=["dashcam", "bdd"])
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-s", type=float, default=float("inf"),
                    help="total priced GPU-time budget the admission "
                         "controller enforces (CostRates pricing)")
    ap.add_argument("--cohorts", type=int, default=4)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--max-steps", type=int, default=100_000,
                    help="pool-level frame-budget ceiling")
    ap.add_argument("--max-results", type=int, default=512)
    ap.add_argument("--slots-per-batch", type=int, default=4)
    ap.add_argument("--cache", action="store_true", default=True)
    ap.add_argument("--no-cache", dest="cache", action="store_false")
    ap.add_argument("--index", default=None,
                    help="directory for the persistent RepositoryIndex "
                         "(DESIGN.md §13); loaded if a snapshot exists, "
                         "saved at every tenant retirement")
    ap.add_argument("--detector-version", default="v0",
                    help="detector version key — a mismatch against a "
                         "snapshot is a clean miss")
    ap.add_argument("--prior-weight", dest="prior_weight", type=float,
                    default=0.0,
                    help="default Thompson warm-start weight for tenants "
                         "whose plans don't set execution.index")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()

    service = build_service(args)
    service.start()
    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            resp = handle_request(service, json.loads(line))
            print(json.dumps(resp), flush=True)
        if service.busy():
            service.drain()   # EOF implies drain: no admitted work is lost
    finally:
        service.stop()
    _print_tenant_summary(service)
    print("service: clean drain", file=sys.stderr)


if __name__ == "__main__":
    main()
