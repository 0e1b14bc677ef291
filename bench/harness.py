"""The benchmark harness: one run of one cell, as ``bench/run.py`` asks.

Everything that belongs to one configuration, traffic mix or metric is
found by name: the cell's entry in ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``) and traffic
(``bench/traffic/<traffic>.json``); the configuration's ``detector``
section names its detector (``bench/detectors/<kind>.py``, the contract
in ``bench/detectors/__init__.py``); each metric is computed by
``bench/metrics/<metric>.py``, a module with ``value(ctx)`` that returns a
number or None (nothing to read, and the metric is left out of the line).
A run with ``--trace 0`` reports the cell's end-to-end metrics, one with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window, the load's counters and the program's spans.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_SECONDS = 4.0   # the traced slice: the window's last seconds
CHECK_TRIPS = 4       # detector chunks a module's ``check`` compares


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def lookup(spec: dict, workload: str, root: str = ROOT):
    """(cell, configuration, traffic mix) of ``workload`` by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(root, configs[cell["config"]]["file"])
    mix = load_json(root, "bench", "traffic", cell["traffic"] + ".json")
    return cell, cfg, mix


def metric_names(spec: dict, cell: dict, trace: bool) -> list[str]:
    """The metrics this cell reports: end-to-end ones without ``--trace``,
    per-layer ones with it.  A metric without ``workloads`` belongs to
    every cell (per-layer: every cell that reports its ``moves``)."""
    name = cell["name"]

    def mine(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m["name"] for m in spec["end_to_end"] if mine(m) in (True, None)]
    if not trace:
        return e2e
    return [m["name"] for m in spec["per_layer"]
            if mine(m) or (mine(m) is None and m["moves"] in e2e)]


def metric_module(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def units(spec: dict) -> dict:
    return {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}


def devices(chips: int, allow_cpu: bool = False):
    import jax

    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX reports {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Tracer:
    """Profiles the last ``length`` seconds of the window: the load calls
    ``poll`` with the seconds elapsed, between units of its work, and the
    harness calls ``stop`` once the window has closed, so that writing the
    trace never stalls the window.  The traced slice is marked by the
    ``bench.window`` span."""

    def __init__(self, log_dir: str, seconds: float, length: float):
        self.log_dir, self.start_at = log_dir, max(seconds - length, 0.0)
        self.span = None

    def poll(self, elapsed: float) -> None:
        import jax

        if self.span is None and elapsed >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()

    def stop(self) -> None:
        import jax

        if self.span is not None:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()


def compare(arrays, pairs, root: str = ROOT) -> dict:
    """Replay each sampled query and compare it with what the program gave."""
    from bench import reference

    rows, differing, undecided = [], 0, 0
    det = reference.detector(arrays, pairs[0][0], root) if pairs else None
    for q, prog in pairs:
        ref = reference.replay(arrays, q, det=det)
        if ref.ambiguous:
            undecided += 1
            rows.append(f"class {q.query_class}: undecided ({ref.ambiguous})")
            continue
        diff = reference.differences(prog, ref)
        differing += bool(diff)
        rows.append(f"class {q.query_class}: program {prog['step']} frames / "
                    f"{prog['results']} results, reference {ref.step} / "
                    f"{ref.results}" + (f"; DIFFER in {', '.join(diff)}" if diff else ""))
    return {"rows": rows, "differing": differing, "undecided": undecided,
            "compared": len(pairs)}


def check_frames(arrays, pairs, width: int, seed: int):
    """int32 ``[k, width]``, k at most ``CHECK_TRIPS``: frames the compared
    queries sampled (each chunk's first n in random+ order), drawn from
    the seed; where they do not fill the last row, repeated."""
    import numpy as np

    from bench import reference

    seen = set()
    for _, prog in pairs:
        for c in np.flatnonzero(prog["n"]):
            seen.update(reference.randomplus_frame(arrays, int(c), k)
                        for k in range(int(prog["n"][c])))
    k = min(CHECK_TRIPS, -(-len(seen) // width))
    rng = np.random.default_rng([seed, 5])
    take = rng.choice(sorted(seen), size=min(len(seen), k * width), replace=False)
    return np.resize(np.asarray(take, np.int32), (k, width))


def run(argv=None, *, t_start: float | None = None, allow_cpu: bool = False,
        root: str = ROOT, out=sys.stdout, err=sys.stderr) -> int:
    """One run; returns the exit code.  ``allow_cpu`` lets a test drive a
    whole run on the CPU, and ``root`` points it at a benchmark tree of its
    own; the command sets neither."""
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    spec = load_json(root, "BENCHMARK.json")
    cell, cfg, mix = lookup(spec, args.workload, root)
    try:
        devs = devices(cell["chips"], allow_cpu)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=err)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from bench import clock, load, trace
    from bench.data import repository

    if not allow_cpu:
        print(f"compile cache: {enable_compile_cache()}", file=err, flush=True)
    clk = clock.Clock()
    arrays = repository.generate(cfg["repository"])
    driver = load.LOADS[mix["mode"]](cfg, mix, arrays, args.seed, root=root)
    used = devs[: cell["chips"]]
    tdir = None
    try:
        driver.setup()
        setup_s = time.monotonic() - t_start
        c0 = clk.snapshot()
        print(f"set-up {setup_s:.3f} s: {c0['lowerings']} lowerings, "
              f"{c0['compiles']} compiles ({c0['compile_s']:.3f} s), "
              f"{c0['cache_hits']} cache hits, {c0['cache_writes']} cache writes",
              file=err, flush=True)
        tracer = None
        if args.trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            tracer = Tracer(tdir, args.seconds, TRACE_SECONDS)
        try:
            driver.window(args.seconds, tick=tracer.poll if tracer else None)
        finally:
            if tracer:
                t_stop = time.monotonic()
                tracer.stop()
                print(f"trace written in {time.monotonic() - t_stop:.1f} s",
                      file=err, flush=True)
        c1 = clk.snapshot()
        print(f"inside the window: {c1['lowerings'] - c0['lowerings']} lowerings, "
              f"{c1['compiles'] - c0['compiles']} compiles", file=err, flush=True)
        if mix["mode"] == "service":
            driver.drain(deadline_s=mix["drain_s"])
        counters = driver.counters()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
        pairs = driver.sample(mix["compare"])
    finally:
        driver.close()
    t_ref = time.monotonic()
    verdict = compare(arrays, pairs, root)
    frames_bad = None
    if hasattr(driver.det_mod, "check"):
        frames = check_frames(arrays, pairs, driver.detector_width, args.seed)
        frames_bad = int(driver.det_mod.check(
            cfg["detector"], driver.inst, arrays, driver.check_detector(), frames))
        print(f"detector check: {frames.size} frames", file=err)
    ref_s = time.monotonic() - t_ref
    for row in verdict["rows"]:
        print(f"  {row}", file=err)
    red = None
    if args.trace:
        t_read = time.monotonic()
        red = trace.reduce(trace.extract(trace.xplane_file(tdir)))
        print(f"trace read in {time.monotonic() - t_read:.1f} s", file=err)
        shutil.rmtree(tdir, ignore_errors=True)

    peaks = load_json(root, "bench", "peaks.json")["devices"].get(devs[0].device_kind)
    if peaks is None and not allow_cpu:
        raise KeyError(f"no peaks for device kind {devs[0].device_kind!r} "
                       "in bench/peaks.json")
    ctx = {"counters": counters, "setup_s": setup_s, "trace": red, "peaks": peaks,
           "peak_bytes": peak, "cell": cell, "config": cfg, "mix": mix,
           "num_chunks": arrays.num_chunks}
    metrics = {}
    unit = units(spec)
    for name in metric_names(spec, cell, bool(args.trace)):
        v = metric_module(name, root).value(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": unit[name]}
    limit_undecided = verdict["compared"] // 2
    checks = {
        "queries_differing": {"value": verdict["differing"], "limit": 0},
        "queries_undecided": {"value": verdict["undecided"], "limit": limit_undecided},
        "queries_unfinished": {"value": counters["failed"], "limit": 0},
    }
    if frames_bad is not None:
        checks["frames_differing"] = {"value": frames_bad, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and bool(pairs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": counters["attempted"],
              "failed": counters["failed"], "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    print(f"window {counters['window_s']:.3f} s, {counters['queries_done']} queries "
          f"finished of {counters['attempted']}; reference replay {ref_s:.1f} s "
          f"for {verdict['compared']} queries", file=err)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0
