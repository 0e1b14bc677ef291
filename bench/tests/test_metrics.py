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


def test_collective_exposed_share_counts_only_what_nothing_overlaps():
    from bench import trace

    ms = 1e6
    # op names as a v5e 2x2 trace of the mesh path gives them
    gather = "%all-gather.70 = f32[4,8,48]{2,1,0:T(8,128)S(1)} all-gather(f32[1,8,48] %x)"
    a2a = "%all_to_all.210 = pred[4,1,96]{2,1,0} all-to-all(pred[4,1,96] %y), channel_id=1"
    loop = "%while.2 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
    ev = {
        "devices": {
            # overlapped by a fusion: counts nothing
            "/device:TPU:0": [["fusion.1", 0, 4 * ms], [gather, 1 * ms, 3 * ms]],
            # alone for 2 ms; the loop around it is not another op
            "/device:TPU:1": [[loop, 0, 10 * ms], [a2a, 2 * ms, 4 * ms]],
            # half overlapped: 1 ms alone
            "/device:TPU:2": [["fusion.2", 0, 3 * ms], [gather, 2 * ms, 4 * ms]],
            # no collective here
            "/device:TPU:3": [["fusion.3", 0, 5 * ms]],
        },
        "spans": [["bench.window", 0, 10 * ms]],
    }
    mod = harness.metric_module("collective_exposed_share")
    red = trace.reduce(ev)
    # (0 + 2 + 1 + 0) ms over 4 devices, of a 10 ms window
    assert mod.value({"trace": red}) == pytest.approx(100.0 * 0.75 / 10)
    ev["devices"]["/device:TPU:1"] = [[loop, 0, 10 * ms]]
    ev["devices"]["/device:TPU:2"] = [["fusion.2", 0, 3 * ms]]
    assert mod.value({"trace": trace.reduce(ev)}) == 0.0      # all overlapped
    # a device whose ops are named only by their region says nothing
    ev["devices"]["/device:TPU:1"] = [[a2a, 2 * ms, 4 * ms], ["region.730", 5 * ms, 6 * ms]]
    assert mod.value({"trace": trace.reduce(ev)}) == 0.0
    ev["devices"]["/device:TPU:1"] = [[a2a, 2 * ms, 4 * ms]]
    assert mod.value({"trace": trace.reduce(ev)}) == pytest.approx(100.0 * 0.5 / 10)
    del ev["devices"]["/device:TPU:0"]
    ev["devices"]["/device:TPU:1"] = [[loop, 0, 10 * ms]]
    assert mod.value({"trace": trace.reduce(ev)}) is None     # nothing to read
    assert mod.value({"trace": None}) is None
