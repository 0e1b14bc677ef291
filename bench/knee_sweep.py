"""One-off sweep for the service cell's arrival rate (run on the chip).

  python3 bench/knee_sweep.py --workload dashcam.service --seed 1 \\
      --seconds 20 --rates 1 2 4 6 8

Offers each rate in turn to one warmed-up service, for ``--seconds`` of
open-loop arrivals, and drains before the next.  A rate is sustained when
every tenant finishes within the drain and the last third of the
arrivals waits no longer than the first third (median latency within
1.5x): no growing backlog.  The knee is the highest rate below which
every rate offered was sustained; the cell offers about four fifths of
it.  Prints one line per rate and the knee last.
"""
import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from bench import harness, load
    from bench.data import repository
    from bench.stats import percentile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="dashcam.service")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, cfg, mix = harness.lookup(spec, args.workload)
    try:
        harness.devices(cell["chips"])
    except harness.NoChip as e:
        print(f"knee_sweep: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.enable_compile_cache()
    drv = load.ServiceLoad(cfg, mix, repository.generate(cfg["repository"]),
                           args.seed)
    knee = None
    try:
        drv.setup()
        for k, rate in enumerate(args.rates):
            drv.tenants = []
            t0 = time.monotonic()
            drv.window(args.seconds, rate=rate, prefix=f"r{k}t")
            drv.drain(deadline_s=mix["drain_s"])
            c = drv.counters()
            lat = c["latency_s"]
            third = len(lat) // 3
            first = statistics.median(lat[:third]) if third else 0.0
            last = statistics.median(lat[-third:]) if third else 0.0
            ok = c["failed"] == 0 and last <= 1.5 * max(first, 1e-9)
            print(f"rate {rate:g}/s: {c['attempted']} tenants, {c['failed']} unfinished, "
                  f"median latency {first:.3f} s first third, {last:.3f} s last third, "
                  f"p50 {percentile(lat, 50)} s, p90 {percentile(lat, 90)} s, "
                  f"{time.monotonic() - t0:.1f} s wall -> "
                  f"{'sustained' if ok else 'backlog grows'}", flush=True)
            if ok and knee == (args.rates[k - 1] if k else None):
                knee = rate
    finally:
        drv.close()
    print(f"knee {knee}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
