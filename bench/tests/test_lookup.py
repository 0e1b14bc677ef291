"""Cells, configurations, traffic mixes and metrics are found by name."""
import pytest
from conftest import run_cell

from bench import harness


def test_every_cell_resolves_to_its_files_and_metrics():
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for cell in spec["workloads"]:
        got, cfg, mix = harness.lookup(spec, cell["name"])
        assert got is cell and mix["mode"] in ("batch", "service")
        assert cfg["repository"]["num_classes"] == 8
        for trace in (0, 1):
            names = harness.metric_names(spec, cell, bool(trace))
            assert names, (cell["name"], trace)
            for name in names:
                assert callable(harness.metric_module(name).value)
        assert "setup_s" in harness.metric_names(spec, cell, False)
    with pytest.raises(KeyError):
        harness.lookup(spec, "no.such_cell")


def test_metric_selection_follows_workloads_and_moves():
    spec = {"end_to_end": [{"name": "a", "workloads": ["x"]}, {"name": "s"}],
            "per_layer": [{"name": "p", "moves": "a"}, {"name": "q", "moves": "a",
                                                      "workloads": ["y"]}]}
    assert harness.metric_names(spec, {"name": "x"}, False) == ["a", "s"]
    assert harness.metric_names(spec, {"name": "x"}, True) == ["p"]
    assert harness.metric_names(spec, {"name": "y"}, True) == ["q"]


def test_a_cell_added_from_files_alone_runs(tiny_root):
    """A new configuration, traffic mix and per-layer metric, each a new
    file, plus new entries in BENCHMARK.json (the ``tiny_root`` fixture),
    make a cell the harness runs without an edit to any file it has."""
    for trace, want in ((0, {"queries_per_s", "setup_s"}),
                        (1, {"results_per_query"})):
        rc, res, err = run_cell(tiny_root, trace=trace)
        assert rc == 0, err
        assert res["correct"] is True, err
        assert want <= set(res["metrics"]), res["metrics"]
        assert list(res)[-1] == "checks"
        assert err.strip().splitlines()[-1].startswith("check ")
