"""The comparison that decides ``correct`` rejects the control and the
faults a cell can have, and passes the program as it is."""
import dataclasses
import json
import os

import jax
import pytest
from conftest import run_cell

from bench import control
from bench.data import repository


def _tiny(tiny_root):
    cfg = json.load(open(os.path.join(tiny_root, "bench/configs/tiny.json")))
    mix = json.load(open(os.path.join(tiny_root, "bench/traffic/tiny_q4.json")))
    return cfg, mix


def test_bfloat16_control_is_not_correct(tiny_root):
    cfg, mix = _tiny(tiny_root)
    mix = dict(mix, compare=8)
    for seed in (1, 2, 3):
        arrays = repository.generate(cfg["repository"])
        r = control.control_check(arrays, control.control_pairs(cfg, mix, seed))
        decided = r["compared"] - r["undecided"]
        assert decided >= 4 and r["differing"] > 0, r   # limit 0: not correct


def test_the_program_as_it_is_is_correct(tiny_root):
    rc, res, err = run_cell(tiny_root)
    assert rc == 0 and res["correct"] is True, err


def _broken_run(monkeypatch, break_result):
    from repro.core import executor

    orig = executor.LoweredPlan.run

    def run(self, carry, chunks, **kw):
        return break_result(orig(self, carry, chunks, **kw), carry)

    monkeypatch.setattr(executor.LoweredPlan, "run", run)


def test_a_search_that_returns_its_state_unchanged_is_caught(tiny_root, monkeypatch):
    def unchanged(res, carry):
        q = len(res.steps)
        return dataclasses.replace(res, carry=carry, steps=(0,) * q, results=(0,) * q)

    _broken_run(monkeypatch, unchanged)
    rc, res, err = run_cell(tiny_root)
    assert rc == 0 and res["correct"] is False, err


def test_half_of_the_batch_left_out_is_caught(tiny_root, monkeypatch):
    def half(res, carry):
        h = len(res.steps) // 2
        kept = jax.tree.map(lambda new, old: new.at[h:].set(old[h:]), res.carry, carry)
        return dataclasses.replace(
            res, carry=kept, steps=res.steps[:h] + (0,) * (len(res.steps) - h),
            results=res.results[:h] + (0,) * (len(res.steps) - h))

    _broken_run(monkeypatch, half)
    rc, res, err = run_cell(tiny_root)
    assert rc == 0 and res["correct"] is False, err


def test_an_answer_altered_where_it_is_produced_is_caught(tiny_root, monkeypatch):
    from repro.core import exsample

    orig = exsample.match_and_update

    def miscount(*args, **kw):
        m = orig(*args, **kw)
        return m._replace(d0=m.d0 + (m.d0 > 0).astype(m.d0.dtype))

    monkeypatch.setattr(exsample, "match_and_update", miscount)
    jax.clear_caches()
    try:
        rc, res, err = run_cell(tiny_root)
    finally:
        jax.clear_caches()
    assert rc == 0 and res["correct"] is False, err


@pytest.mark.parametrize("name", ["dashcam.scan", "bdd.q8", "dashcam.service"])
def test_control_pairs_follow_each_cell(name):
    from bench import harness

    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, cfg, mix = harness.lookup(spec, name)
    qs = control.control_pairs(cfg, mix, 5)
    assert len(qs) == mix["compare"]
    if mix["mode"] == "batch":
        assert [q.query_class for q in qs[:8]] == list(range(8))
