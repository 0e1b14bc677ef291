"""Metric arithmetic: rates over the window, percentiles, roofline work."""
import pytest

from bench import harness, stats


def test_percentile_nearest_rank():
    assert stats.percentile(list(range(1, 11)), 90) == 9
    assert stats.percentile(list(range(100, 0, -1)), 90) == 90
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.percentile([], 90) is None


def test_queries_per_s_is_all_work_over_all_window_time():
    m = harness.metric_module("queries_per_s")
    assert m.value({"counters": {"queries_done": 96, "window_s": 12.0}}) == 8.0


def test_latency_percentiles_read_their_own_samples():
    c = {"latency_s": [float(i) for i in range(1, 101)],
         "admission_wait_s": [0.1] * 99 + [5.0], "arrival_lag_s": []}
    ctx = {"counters": c}
    assert harness.metric_module("query_p50_s").value(ctx) == 50.0
    assert harness.metric_module("admission_wait_p90_s").value(ctx) == 0.1
    assert harness.metric_module("arrival_lag_p90_s").value(ctx) is None


def test_counter_ratios():
    c = {"frames_sampled": 1200, "results": 300, "cache_hits": 30,
         "detector_invocations": 90}
    assert harness.metric_module("frames_per_result").value({"counters": c}) == 4.0
    assert harness.metric_module("cache_hit_share").value({"counters": c}) == 25.0


def test_thompson_work_and_roofline_share():
    # [Q, C, M] = [8, 50, 1000]: alpha and beta read once, winners written
    assert stats.thompson_bytes(8, 50, 1000) == 4 * (2 * 8 * 1000 + 8 * 50)
    assert stats.thompson_flops(8, 50, 1000) == 12 * 8 * 50 * 1000
    peaks = harness.load_json(harness.ROOT, "bench", "peaks.json")["devices"]["TPU v5 lite"]
    least = stats.thompson_bytes(8, 50, 1000) / peaks["hbm_bytes_per_s"]
    red = {"ops_s": {"thompson_choose.1": 40 * least * 2}, "ops_n": {"thompson_choose.1": 40},
           "devices": 1}
    ctx = {"trace": red, "peaks": peaks, "num_chunks": 1000,
           "mix": {"plan": {"queries": 8, "cohorts": 50}}}
    mod = harness.metric_module("thompson_roofline")
    red["ops_s"] = {mod.KERNEL_NAME: 40 * least * 2}
    red["ops_n"] = {mod.KERNEL_NAME: 40}
    assert mod.value(ctx) == pytest.approx(50.0)
    red["ops_n"] = {}
    assert mod.value(ctx) is None   # nothing to read: no number, never 0
