"""The reference of the section 8 merge schedule holds the mesh-resident
search exactly; the single-shard reference is the one recorded before it
existed; and the check rejects the faults a mesh run can have."""
import functools
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from bench import control, harness, reference
from bench.data import repository

HERE = os.path.dirname(os.path.abspath(__file__))
MESH_RUN = os.path.join(HERE, "mesh_run.py")
RUN_LIMIT_S = 300   # each program run in its own process


@functools.lru_cache(maxsize=None)
def _program(sync_every: int):
    """(arrays, pairs) of one batch of the tiny four-shard cell."""
    import tempfile

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip())
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "pairs.pkl")
        r = subprocess.run([sys.executable, MESH_RUN, str(sync_every), out], env=env,
                           timeout=RUN_LIMIT_S, capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-4000:]
        with open(out, "rb") as f:
            got = pickle.load(f)
    return got["arrays"], got["pairs"]


def test_single_shard_replay_is_the_recorded_one():
    with open(os.path.join(HERE, "fixtures", "dashcam_0.02_seed0.json")) as f:
        a = repository.generate(json.load(f)["repository"])
    with open(os.path.join(HERE, "fixtures", "replay_dashcam_0.02_seed0.json")) as f:
        cases = json.load(f)["cases"]
    for c in cases:
        q = reference.Query(key=np.asarray(c["key"], np.uint32), query_class=c["query_class"],
                            cohorts=c["cohorts"], result_limit=c["result_limit"],
                            max_steps=c["max_steps"], method=c["method"],
                            all_classes=c["all_classes"], shards=1)
        o = reference.replay(a, q)
        assert (o.step, o.results, o.ambiguous) == (c["step"], c["results"], c["ambiguous"])
        assert o.n.tolist() == c["n"] and o.n1.tolist() == c["n1"]


def test_only_a_near_tie_of_different_inputs_is_undecided():
    q = reference.Query(key=np.zeros(2, np.uint32), query_class=0, cohorts=2, result_limit=1,
                        max_steps=1, method="wilson_hilferty", all_classes=True)
    n1, n = np.zeros(4, np.int64), np.array([0, 0, 3, 0])
    draws = np.array([[0.5, 2.0, 2.0, 2.0], [1.0, 0.0, 0.2, 0.1]], np.float32)
    frames = np.full(4, 100)
    s = reference._scores(q, n1, n, frames, draws, "float32")
    choice, live = np.argmax(s, axis=1), np.ones(2, bool)
    # row 0: chunks 1 and 3 tie exactly on the same statistics and draw
    assert not reference._rival(q, n1, n, draws[:1], s[:1], choice[:1], live[:1])
    # the same draw on another chunk's statistics: a near tie is undecided
    n1b = np.array([0, 0, 0, 1])
    s_b = s.copy()
    s_b[0, 3] = s_b[0, 1] * (1 - 1e-6)
    assert reference._rival(q, n1b, n, draws[:1], s_b[:1], choice[:1], live[:1])
    # a cohort that is not live is not looked at
    assert not reference._rival(q, n1b, n, draws[:1], s_b[:1], choice[:1], ~live[:1])


@pytest.mark.parametrize("sync_every", [1, 2])
def test_every_decided_query_of_a_mesh_run_agrees(sync_every):
    arrays, pairs = _program(sync_every)
    assert all(q.shards == 4 and q.sync_every == sync_every for q, _ in pairs)
    v = harness.compare(arrays, pairs)
    assert v["compared"] == 8 and v["undecided"] <= 4, v["rows"]
    assert v["differing"] == 0, v["rows"]


def _no_add_back(monkeypatch):
    monkeypatch.setattr(reference, "_add_back",
                        lambda snap, mems, size: np.zeros(size, np.int64))


def _unsharded_draws(monkeypatch):
    def draws(key, alpha, *, cohorts, shards):
        return reference._draws(key, alpha, cohorts=cohorts, method="normal")

    monkeypatch.setattr(reference, "_draws_sharded", draws)


@pytest.mark.parametrize("plant", [_no_add_back, _unsharded_draws],
                         ids=["no_add_back", "unsharded_draws"])
def test_a_planted_mesh_fault_is_caught(plant, monkeypatch):
    """The reference with the fault planted, put in the program's place,
    comes out not correct against the sound reference."""
    arrays, pairs = _program(1)
    sound = [reference.replay(arrays, q) for q, _ in pairs]
    plant(monkeypatch)
    differing = decided = 0
    for (q, _), ref in zip(pairs, sound):
        if ref.ambiguous:
            continue
        decided += 1
        o = reference.replay(arrays, q)
        prog = {"step": o.step, "results": o.results, "n": o.n, "n1": o.n1}
        differing += bool(reference.differences(prog, ref))
    assert decided >= 4 and differing > 0


def test_bfloat16_control_of_a_mesh_run_is_not_correct():
    arrays, pairs = _program(1)
    r = control.control_check(arrays, [q for q, _ in pairs])
    assert r["compared"] - r["undecided"] >= 4 and r["differing"] > 0, r


def test_mesh_control_pairs_carry_the_geometry():
    with open(os.path.join(HERE, "fixtures", "tiny_config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "fixtures", "tiny_traffic_s4.json")) as f:
        mix = json.load(f)
    ex = mix["plan"]["execution"]
    for q in control.control_pairs(cfg, mix, 2147483659):
        assert (q.shards, q.sync_every) == (ex["shards"], ex["sync_every"])
        assert q.method == "wilson_hilferty"   # what "auto" resolves to on a mesh
