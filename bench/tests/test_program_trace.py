"""Program spans, stage scopes and round stamps read from a trace."""
import os

import pytest

from bench import program_trace as pt
from bench import trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
CPU = {"device_plane": r"^/host:CPU$", "op_line": r"^tf_XLAPjRtCpuClient"}


def _spans(ev, prefix="exsample."):
    return [s for s in ev["spans"] if s[0].startswith(prefix)]


def test_cpu_trace_with_program_spans_on_two_threads():
    # recorded on the CPU: a pump thread issues, merges and reaps three
    # rounds (sleeps inside the spans, no device work), a worker thread
    # processes each (one jitted call, then a sleep), the main thread waits
    # in bench.wait spans, all inside bench.window
    ev = pt.extract(os.path.join(FIXTURES, "cpu_program_trace.xplane.pb"), **CPU)
    spans = _spans(ev)
    threads = {}
    for name, _, _, thread, _ in spans:
        threads.setdefault(thread, set()).add(name)
    assert sorted(map(sorted, threads.values())) == [
        ["exsample.issue", "exsample.merge", "exsample.reap"], ["exsample.process"]]
    for span in ("exsample.issue", "exsample.merge", "exsample.process"):
        assert [a for n, _, _, _, a in spans if n == span] == [
            {"batch": b, "lanes": 2} for b in range(3)]
    red = pt.reduce(ev)
    base = trace.reduce(trace.extract(
        os.path.join(FIXTURES, "cpu_program_trace.xplane.pb"), **CPU))
    assert red["window_s"] == pytest.approx(base["window_s"])
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(base["window_s"] - base["busy_s"], rel=1e-9)
    # every gap lies inside some round span: none goes to the generator
    assert {k for k, _ in red["idle_gaps"]} <= {
        "exsample.issue", "exsample.merge", "exsample.process", "exsample.reap"}
    # the pump's spans never overlap, so its busy time is their sum
    pump = [e - s for n, s, e, _, _ in spans if n != "exsample.process"]
    assert red["pump_busy_s"] == pytest.approx(sum(pump) * 1e-9)
    assert red["pump_busy_s"] > 3 * (0.003 + 0.002)
    m = pt.metrics(red, {})
    assert m["pump_busy_share"] == pytest.approx(100 * red["pump_busy_s"] / red["window_s"])
    assert "stage_share.update" not in m     # a CPU trace carries no scope


def test_v5e_trace_names_every_stage(tmp_path):
    # recorded on one v5e: a Q=2 multi-query plan over a small repository
    # (6 rounds, a one-slot-per-frame cache of 18,000 frames) under bench.window
    # and bench.search, trimmed to the XLA ops line, the programs' HloProtos
    # and the python thread
    import gzip

    path = tmp_path / "v5e.xplane.pb"
    with gzip.open(os.path.join(FIXTURES, "v5e_multi_trace.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    ev = pt.extract(str(path))
    ops = sorted(x[:3] for v in ev["devices"].values() for x in v)
    assert ops == sorted(x for v in trace.extract(str(path))["devices"].values()
                         for x in v)
    # fusions whose own path names no stage take the stage their fused
    # instructions carry (the HloProto fallback), loops aside
    assert any(x[3] in pt.STAGES for v in ev["devices"].values() for x in v
               if not trace.CONTAINER.search(x[0]))
    red = pt.reduce(ev)
    assert set(red["stages_s"]) == set(pt.STAGES)
    base = trace.reduce(trace.extract(str(path)))
    ops_s = sum(v for k, v in base["ops_s"].items())
    assert sum(red["stages_s"].values()) + red["unscoped_s"] == pytest.approx(ops_s)
    # the unscoped ops are the cache's layout copies at the program's edge
    assert red["unscoped_s"] < 0.25 * ops_s
    assert {n for t in red["threads"].values() for n in t} == {
        "exsample.prepare", "exsample.dispatch", "exsample.readback"}


def test_a_fusion_without_metadata_takes_its_instructions_stage():
    hlo = pt._messages()[1]()
    body = hlo.hlo_module.computations.add(id=2)
    body.instructions.add(name="reshape.1").metadata.op_name = "jit(f)/while/update/neg"
    body.instructions.add(name="transpose.2").metadata.op_name = "jit(f)/while/update/neg"
    body.instructions.add(name="scatter.3")
    body.instructions.add(name="gather.4").metadata.op_name = "jit(f)/while/match/gather"
    entry = hlo.hlo_module.computations.add(id=1)
    entry.instructions.add(name="fusion.9").called_computation_ids.append(2)
    entry.instructions.add(name="copy.5")
    assert pt._fusion_stages(hlo.SerializeToString()) == {"fusion.9": "update"}


def test_trace_without_program_spans_reduces_as_before():
    path = os.path.join(FIXTURES, "cpu_trace.xplane.pb")
    ev = pt.extract(path, **CPU)
    assert not _spans(ev)
    old = trace.reduce(trace.extract(path, **CPU))
    red = pt.reduce(ev)
    assert red["idle_gaps"] == old["idle_gaps"]
    assert red["pump_busy_s"] == 0.0 and pt.metrics(red, {}) == {}
    ops = sorted(x[:3] for v in ev["devices"].values() for x in v)
    assert ops == sorted(x for v in trace.extract(path, **CPU)["devices"].values()
                         for x in v)


def test_gaps_go_to_the_innermost_program_span_on_any_thread():
    ms = 1e6
    ev = {
        "devices": {"/device:TPU:0": [["fusion.1", 0, 2 * ms, "jit(f)/update/add"],
                                      ["fusion.2", 6 * ms, 7 * ms, ""]]},
        "spans": [["bench.window", 0, 10 * ms, "main", {}],
                  ["bench.wait", 1 * ms, 10 * ms, "main", {}],
                  ["exsample.process", 1 * ms, 6 * ms, "worker", {}],
                  ["exsample.merge", 3 * ms, 5 * ms, "pump", {}]],
    }
    red = dict(pt.reduce(ev)["idle_gaps"])
    # gap [2, 6] (middle 4: merge, the shorter of two program spans),
    # gap [7, 10] (middle 8.5: no program span, so the bench.* rule)
    assert red == {"exsample.merge": pytest.approx(0.004),
                   "bench.wait": pytest.approx(0.003)}


def test_stage_of_takes_the_innermost_stage_scope():
    assert pt.stage_of("jit(f)/jit(main)/while/body/choose/argmax") == "choose"
    assert pt.stage_of("jit(f)/dedup_cache/jit(g)/dedup_cache/eq") == "dedup_cache"
    assert pt.stage_of("jit(f)/detect/match/select_n") == "match"
    assert pt.stage_of("jit(f)/choose_chunks/add") == ""
    assert pt.stage_of("") == ""


def test_metrics_from_stages_counters_and_stamps():
    red = {"window_s": 4.0, "stages_s": {"update": 2.0, "match": 1.0},
           "unscoped_s": 0.2, "pump_busy_s": 0.0, "threads": {}}
    rounds = [(0.0, 0.1, 0.3, 0.5), (1.0, 1.0, 1.2, 1.3), (2.0, 2.2, 2.4, 2.6)]
    m = pt.metrics(red, {"detector_invocations": 30, "detector_lanes": 400}, rounds)
    assert m["stage_share.update"] == 50.0 and m["stage_share.match"] == 25.0
    assert m["stage_share.detect"] == 0.0 and m["unscoped_share"] == pytest.approx(5.0)
    assert m["detector_lane_use"] == 7.5
    assert m["round_p50_s"] == pytest.approx(0.5)      # 0.5, 0.3, 0.6
    assert m["slot_wait_p90_s"] == pytest.approx(0.4)  # 0.3, 0.1, 0.4
    assert "pump_busy_share" not in m
    assert pt.metrics(dict(red, stages_s={}), {"detector_lanes": 0}) == {}


def test_round_harvest_keeps_each_round_once_and_only_the_window():
    class Driver:
        def __init__(self):
            self.rounds = []

        def recent_rounds(self):
            return self.rounds[-2:]     # a history of two rounds

    class Load:
        window_end, run_s = 10.0, 8.0

    load = Load()
    load.service = type("S", (), {"driver": Driver()})()
    h = pt.RoundHarvest(load)
    for t in (1.0, 3.0, 5.0, 11.0):
        load.service.driver.rounds.append((t - 0.5, t - 0.4, t - 0.2, t))
        h.poll(force=True)
    assert [r[3] for r in h.rounds] == [1.0, 3.0, 5.0, 11.0]
    assert [r[3] for r in h.in_window()] == [3.0, 5.0]
    assert pt.RoundHarvest(object()).poll(force=True) is None   # no service


def test_runs_a_cell_and_reads_the_program(tiny_root, capsys):
    # the tiny batch cell on the CPU: no TPU plane, so no stage shares,
    # but the lanes counter and the host spans are read
    import json

    rc = pt.run(["--workload", "tiny.q4", "--seed", "2147483661", "--seconds", "1"],
                allow_cpu=True, root=tiny_root)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert 0 < out["metrics"]["detector_lane_use"] <= 100
    assert out["end_to_end"]["queries_per_s"] > 0
    assert not out["stages_s"] and out["window_s"] > 0
    names = {n for t in out["threads"].values() for n in t}
    assert {"exsample.prepare", "exsample.dispatch", "exsample.readback"} <= names


def test_runs_a_service_cell_and_reads_its_rounds(tiny_root, capsys):
    # a tiny open loop of tenants on the CPU: the pump's spans and the
    # rounds' stamps are read, nothing of a device that is not there
    import json

    mix = {"mode": "service", "rate_per_s": 3.0, "zipf_s": 1.0,
           "plan": {"result_limit": 4, "max_steps": 400, "cohorts": 4,
                    "execution": {"queries_axis": True}},
           "service": {"cohorts": 4, "workers": 2, "slots_per_batch": 4,
                       "max_steps": 1000, "cache": True, "warmup_tenants": 2},
           "max_results": 256, "compare": 4, "drain_s": 60}
    with open(os.path.join(tiny_root, "bench", "traffic", "tiny_svc.json"), "w") as f:
        json.dump(mix, f)
    spec_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny.svc", "config": "tiny", "traffic": "tiny_svc",
                              "chips": 1, "why": "test fixture"})
    next(m for m in spec["end_to_end"] if m["name"] == "query_p50_s")["workloads"].append(
        "tiny.svc")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    rc = pt.run(["--workload", "tiny.svc", "--seed", "2147483663", "--seconds", "2"],
                allow_cpu=True, root=tiny_root)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = out["metrics"]
    assert rc == 0 and m["round_p50_s"] > 0 and m["slot_wait_p90_s"] >= 0
    assert out["end_to_end"]["query_p50_s"] > 0
    assert 0 < m["pump_busy_share"] <= 100
    assert "detector_lane_use" not in m and not out["stages_s"]
