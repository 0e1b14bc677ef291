"""One batch of the tiny four-shard cell through ``BatchLoad``, in a
process of its own that sees four CPU devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python3 bench/tests/mesh_run.py <sync_every> <out.pkl>

Pickles ``{"arrays", "pairs"}``: the repository and the sampled
(reference query, program outcome) pairs that the run's check compares.
"""
import json
import os
import pickle
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import load  # noqa: E402
from bench.data import repository  # noqa: E402


def main() -> int:
    sync_every, out = int(sys.argv[1]), sys.argv[2]
    fx = os.path.join(HERE, "fixtures")
    cfg = json.load(open(os.path.join(fx, "tiny_config.json")))
    mix = json.load(open(os.path.join(fx, "tiny_traffic_s4.json")))
    mix["plan"]["execution"]["sync_every"] = sync_every
    arrays = repository.generate(cfg["repository"])
    batch = load.BatchLoad(cfg, mix, arrays, 2147483659)
    batch.window(0.0)                # exactly one batch, compiled inside
    pairs = batch.sample(mix["compare"])
    with open(out, "wb") as f:
        pickle.dump({"arrays": arrays, "pairs": pairs}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
