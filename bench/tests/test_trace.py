"""Trace reduction: busy and idle time, op times, gap attribution."""
import os

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "cpu_trace.xplane.pb")


def test_cpu_trace_fixture_reduces_consistently():
    # recorded on the CPU: three jitted calls under bench.search, each
    # followed by a 4 ms sleep under bench.wait, inside bench.window
    ev = trace.extract(FIXTURE, device_plane=r"^/host:CPU$",
                       op_line=r"^tf_XLAPjRtCpuClient")
    names = [s[0] for s in ev["spans"]]
    assert names.count("bench.search") == 3 and names.count("bench.wait") == 3
    assert names.count("bench.window") == 1
    red = trace.reduce(ev)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert red["idle_gaps"][0][0] == "bench.wait"       # the sleeps
    assert red["idle_gaps"][0][1] > 0.012               # three sleeps of 4 ms
    assert trace.op_count(red, r"^dot_general") == 6    # two matmuls a call
    assert trace.op_seconds(red, r"^dot_general") > 0


def _ev():
    ms = 1e6
    return {
        "devices": {
            "/device:TPU:0": [["fusion.1", 0 * ms, 2 * ms], ["fusion.2", 1 * ms, 3 * ms],
                              ["custom-call.7", 6 * ms, 7 * ms]],
            "/device:TPU:1": [["fusion.1", 0 * ms, 5 * ms]],
        },
        "spans": [["bench.window", 0, 10 * ms], ["bench.init", 2.5 * ms, 6.5 * ms],
                  ["bench.search", 3 * ms, 4 * ms]],
    }


def test_union_busy_and_idle_share_average_over_devices():
    red = trace.reduce(_ev())
    # device 0 busy [0, 3] and [6, 7]: 4 ms; device 1 busy [0, 5]: 5 ms
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.0045)
    assert trace.idle_percent(red) == pytest.approx(55.0)


def test_gaps_go_to_the_innermost_covering_span():
    red = dict(trace.reduce(_ev())["idle_gaps"])
    # device 0 gaps: [3, 6] (middle 4.5: bench.init), [7, 10] (no span);
    # device 1 gap [5, 10] (middle 7.5: no span); averaged over 2 devices
    assert red["bench.init"] == pytest.approx(0.0015)
    assert red["no span"] == pytest.approx(0.004)
    ev = _ev()
    ev["devices"]["/device:TPU:0"] = [["fusion.1", 0, 3.2e6], ["fusion.3", 3.8e6, 10e6]]
    red = dict(trace.reduce(ev)["idle_gaps"])
    assert red["bench.search"] == pytest.approx(0.0003)   # [3.2, 3.8] ms


def test_op_times_are_clipped_to_the_window():
    ev = _ev()
    ev["spans"][0] = ["bench.window", 1e6, 10e6]
    red = trace.reduce(ev)
    assert red["ops_s"]["fusion.1"] == pytest.approx(0.001 + 0.004)
    assert trace.op_seconds(red, r"custom-call") == pytest.approx(0.0005)
    assert trace.op_count(red, r"custom-call") == pytest.approx(0.5)
