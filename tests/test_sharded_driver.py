"""Single-query mesh search (``Execution(strategy="sharded")``, DESIGN.md §8).

A single-query mesh plan runs the Q × shards mesh loop at Q = 1: the
executor lifts its carry to ``[1]`` and hands back a single-query one.
Three layers of coverage:

  * a 1-way mesh runs on the single tier-1 test device, so the whole
    shard_map loop (choice, delta sync, matcher fold, trace) is exercised
    in-process on every run;
  * a 2-way in-process test runs whenever the host exposes ≥2 devices —
    the CI multi-device leg sets ``--xla_force_host_platform_device_count``
    so sharded collectives are exercised on every push;
  * the subprocess suite forces 8 host devices and checks statistical
    parity with the single-device scanned driver at a fixed frame budget,
    for both per-round (`sync_every=1`) and eventually-consistent
    (`sync_every=4`) merge schedules.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    Execution,
    SearchPlan,
    init_carry,
    init_matcher,
    init_state,
)
from repro.launch.mesh import make_data_mesh
from repro.sim import RepoSpec, generate
from repro.sim.oracle import oracle_detect


def _sharded(carry, chunks, *, mesh, detector, result_limit, max_steps,
             cohorts, sync_every):
    """One query on ``mesh`` through a single-query sharded plan; returns
    ``(carry', trace)``."""
    res = SearchPlan(
        result_limit=result_limit, max_steps=max_steps, cohorts=cohorts,
        execution=Execution(
            strategy="sharded", shards=mesh.shape["data"],
            sync_every=sync_every,
        ),
    ).run(carry, chunks, detector=detector, mesh=mesh)
    assert res.kind == "multi_sharded"
    return res.carry, res.trace


def _world(seed=3):
    spec = RepoSpec(
        video_lengths=[5_000] * 2, num_instances=80, chunk_frames=1_000,
        locality=4.0, seed=seed,
    )
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    return chunks, det


def _consistent(out):
    """Invariants every sharded run must satisfy after the final sync."""
    assert int(out.step) == int(jnp.sum(out.sampler.n)), "n/step diverged"
    occupied = int(jnp.sum(out.matcher.times_seen > 0))
    assert occupied == int(out.results), (occupied, int(out.results))


def test_sharded_single_shard_in_process():
    chunks, det = _world()
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=512),
        jax.random.PRNGKey(0),
    )
    out, trace = _sharded(
        carry, chunks, mesh=make_data_mesh(1), detector=det,
        result_limit=10, max_steps=500, cohorts=2, sync_every=2,
    )
    assert int(out.results) >= 10
    _consistent(out)
    assert trace[-1] == (int(out.step), int(out.results))
    # padding trimmed back to the true chunk count
    assert out.sampler.num_chunks == chunks.num_chunks


def test_sharded_rejects_indivisible_cohorts():
    chunks, det = _world()
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=64),
        jax.random.PRNGKey(0),
    )
    with pytest.raises(ValueError, match="cohorts"):
        _sharded(
            carry, chunks, mesh=make_data_mesh(1), detector=det,
            result_limit=1, max_steps=8, cohorts=0, sync_every=1,
        )
    with pytest.raises(ValueError, match="sync_every"):
        _sharded(
            carry, chunks, mesh=make_data_mesh(1), detector=det,
            result_limit=1, max_steps=8, cohorts=1, sync_every=0,
        )


@pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 host devices (CI multi-device leg)"
)
def test_sharded_two_way_in_process():
    chunks, det = _world()
    carry = init_carry(
        init_state(chunks.length), init_matcher(max_results=512),
        jax.random.PRNGKey(0),
    )
    out, _ = _sharded(
        carry, chunks, mesh=make_data_mesh(2), detector=det,
        result_limit=15, max_steps=600, cohorts=4, sync_every=1,
    )
    assert int(out.results) >= 15
    _consistent(out)


PARITY_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.core import (Execution, SearchPlan, init_carry, init_matcher,
                            init_state)
    from repro.launch.mesh import make_data_mesh
    from repro.sim import RepoSpec, generate
    from repro.sim.oracle import oracle_detect

    spec = RepoSpec(video_lengths=[10_000] * 4, num_instances=150,
                    chunk_frames=1_000, locality=4.0, seed=5)
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    fresh = lambda: init_carry(init_state(chunks.length),
                               init_matcher(max_results=2048),
                               jax.random.PRNGKey(0))
    budget = 1024
    scan = SearchPlan(result_limit=10**9, max_steps=budget, cohorts=8,
                      method="wilson_hilferty").run(
        fresh(), chunks, detector=det).carry
    assert int(scan.step) == budget
    for shards, sync_every in ((2, 1), (8, 1), (8, 4)):
        res = SearchPlan(
            result_limit=10**9, max_steps=budget, cohorts=8,
            execution=Execution(shards=shards, sync_every=sync_every),
        ).run(fresh(), chunks, detector=det, mesh=make_data_mesh(shards))
        out, trace = res.carry, res.trace
        assert int(out.step) == budget, (shards, sync_every, int(out.step))
        assert int(out.step) == int(jnp.sum(out.sampler.n))
        occ = int(jnp.sum(out.matcher.times_seen > 0))
        assert occ == int(out.results), (occ, int(out.results))
        ratio = int(out.results) / int(scan.results)
        # same frame budget => statistically matching result count within
        # the documented +-5% gate; the merge schedule only adds posterior
        # staleness (DESIGN.md Sec 8)
        assert abs(ratio - 1.0) <= 0.05, (shards, sync_every, ratio)
        assert trace[-1] == (int(out.step), int(out.results))
        print(f"parity ok shards={shards} sync={sync_every} ratio={ratio:.3f}")
    print("ALL_OK")
    """
)


@pytest.mark.slow
def test_sharded_parity_multidevice():
    env = dict(os.environ)
    # the device-count flag only affects the CPU platform — pin it, or a
    # GPU host ignores the flag and make_data_mesh(8) fails spuriously
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", PARITY_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert "ALL_OK" in r.stdout, r.stdout[-3000:] + "\n" + r.stderr[-3000:]
