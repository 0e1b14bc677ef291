"""CPU tests of the benchmark harness (not part of the repository's tier-1
suite, which collects ``tests/`` only):

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


@pytest.fixture
def tiny_root(tmp_path):
    """A benchmark checkout of its own holding one extra cell, ``tiny.q4``,
    made only of new files (a configuration, a traffic mix, a per-layer
    metric) and new entries in its BENCHMARK.json."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    shutil.copy(os.path.join(FIXTURES, "tiny_config.json"), root / "bench/configs/tiny.json")
    shutil.copy(os.path.join(FIXTURES, "tiny_traffic.json"), root / "bench/traffic/tiny_q4.json")
    shutil.copy(os.path.join(FIXTURES, "tiny_metric.py"),
                root / "bench/metrics/results_per_query.py")
    spec["configs"].append({"name": "tiny", "source": "https://arxiv.org/abs/2005.09141",
                            "file": "bench/configs/tiny.json", "reduced": ["videos"],
                            "why": "test fixture"})
    spec["workloads"].append({"name": "tiny.q4", "config": "tiny", "traffic": "tiny_q4",
                              "chips": 1, "why": "test fixture"})
    spec["end_to_end"][0]["workloads"].append("tiny.q4")
    spec["per_layer"].append({"name": "results_per_query", "unit": "results",
                              "better": "higher", "source": "program_counter",
                              "layer": "matcher", "moves": "queries_per_s",
                              "workloads": ["tiny.q4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def run_cell(root, workload="tiny.q4", seed=2147483659, seconds=1, trace=0):
    """(exit code, result dict or None, stderr) of one CPU run."""
    import io

    from bench import harness

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(["--workload", workload, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)],
                     allow_cpu=True, root=root, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
