"""The correctness control, run on the chip at a cell's own size.

  python3 bench/control.py --workload bdd.q8 --seeds 101 102 103

The control is the plain reference put in the program's place with its
Thompson scores computed in bfloat16, the precision below the float32 the
configurations state.  For each seed it takes as many of the cell's
queries as a run compares (same repository, keys and classes as the
cell's window would give them), replays each with the float32 reference
and with the control, and applies the run's own check to the control:
``queries_differing`` has to come out above its limit of 0.  The
benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_pairs(cfg: dict, mix: dict, seed: int):
    """[reference.Query] of the cell's first ``compare`` queries, as the
    window would draw them for ``seed`` (for the service: ``compare``
    tenants of a window that long)."""
    import jax
    import numpy as np

    from bench import load, reference

    plan, m, s = mix["plan"], cfg["matcher"], cfg["sampler"]
    k, ncls = mix["compare"], int(cfg["repository"]["num_classes"])
    if mix["mode"] == "service":
        classes, tseeds = load.tenant_set(k, ncls, mix["zipf_s"], seed)
        keys = [np.asarray(jax.random.PRNGKey(int(t))) for t in tseeds]
        cohorts, method, all_classes = mix["service"]["cohorts"], "exact", True
        shards = sync_every = 1
    else:
        root = jax.random.PRNGKey(load.POOL_KEY)
        classes = [i % ncls for i in range(k)]
        keys = [np.asarray(jax.random.fold_in(
            root, load.pool_index(seed, mix["pool_per_class"], ncls, i))) for i in range(k)]
        cohorts = plan["cohorts"]
        ex = plan.get("execution", {})
        shards, sync_every = ex.get("shards", 1), ex.get("sync_every", 1)
        method = plan.get("method", "auto")
        if method == "auto":   # the mesh path is Wilson-Hilferty only (section 8)
            method = "exact" if shards == 1 else "wilson_hilferty"
        all_classes = mix["detector"] == "all_classes"
    return [
        reference.Query(
            key=keys[i], query_class=int(classes[i]), cohorts=cohorts,
            result_limit=int(plan["result_limit"]), max_steps=int(plan["max_steps"]),
            method=method, all_classes=all_classes,
            max_dets=cfg["detector"]["max_dets"], iou_thresh=m["iou_thresh"],
            time_gate=m["time_gate"], alpha0=s["alpha0"], beta0=s["beta0"],
            shards=shards, sync_every=sync_every)
        for i in range(k)
    ]


def control_check(arrays, queries) -> dict:
    """The run's check with the bfloat16 control as the program."""
    from bench import reference

    differing = undecided = 0
    for q in queries:
        ref = reference.replay(arrays, q)
        if ref.ambiguous:
            undecided += 1
            continue
        ctl = reference.replay(arrays, q, precision="bfloat16")
        prog = {"step": ctl.step, "results": ctl.results, "n": ctl.n, "n1": ctl.n1}
        differing += bool(reference.differences(prog, ref))
    return {"differing": differing, "undecided": undecided, "compared": len(queries)}


def main() -> int:
    from bench import harness
    from bench.data import repository

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, cfg, mix = harness.lookup(spec, args.workload)
    try:
        harness.devices(cell["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    for seed in args.seeds:
        arrays = repository.generate(cfg["repository"])
        r = control_check(arrays, control_pairs(cfg, mix, seed))
        verdict = "not correct" if r["differing"] > 0 else "CORRECT (control not caught)"
        print(f"{args.workload} seed {seed}: control differs on {r['differing']} of "
              f"{r['compared'] - r['undecided']} decided queries "
              f"({r['undecided']} undecided) -> {verdict}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
