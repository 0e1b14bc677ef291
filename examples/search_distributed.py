"""Distributed ExSample: mesh-sharded and Q×shards-composed search plans.

Runs the §8 mesh-resident lowering for real on an 8-device mesh (under
``JAX_PLATFORMS=cpu`` this script re-execs itself with the XLA
device-count flag; on an accelerator host it needs 8 devices): one
``SearchPlan`` with ``Execution(shards=8)`` places chunk statistics over
the ``data`` axis, every round each shard processes its slice of the
globally-consistent Thompson cohort, and per-shard matcher states merge
every ``sync_every`` rounds — the whole search is ONE device call with a
single host sync at the end.  A single-device plan of the same query
shows the sharded statistics land on the same answer, and a composed
``queries_axis × shards`` plan (DESIGN.md §10) runs four concurrent
queries through the same mesh while sharing one deduplicated + cached
detector pass per round per shard.

  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/search_distributed.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import ensure_host_devices

ensure_host_devices(8)

from repro.core import (
    Execution,
    SearchPlan,
    init_carry,
    init_carry_multi,
    init_matcher,
    init_state,
)
from repro.sim import RepoSpec, generate
from repro.sim.oracle import oracle_detect


def main():
    spec = RepoSpec(video_lengths=[20_000] * 4, num_instances=200,
                    chunk_frames=2_000, locality=4.0, seed=1)
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    fresh = lambda k: init_carry(
        init_state(chunks.length), init_matcher(max_results=1024), k,
    )

    shards, sync_every, limit, budget = 8, 4, 120, 4_000
    t0 = time.time()
    sharded = SearchPlan(
        result_limit=limit, max_steps=budget, cohorts=shards,
        execution=Execution(shards=shards, sync_every=sync_every),
    ).run(fresh(jax.random.PRNGKey(0)), chunks, detector=det)
    wall = time.time() - t0
    st = sharded.stats
    print(f"sharded({shards}x, sync_every={sync_every}): "
          f"{sharded.results[0]} distinct results in {sharded.steps[0]} "
          f"frames / {st.merges} merges (ring high-water "
          f"{st.merge_high_water}) ({wall:.1f}s incl. compile)")
    n = np.asarray(sharded.carry.sampler.n)
    top = np.argsort(-n)[:5]
    print("most-sampled chunks:", top.tolist(),
          "samples:", n[top].astype(int).tolist())

    scan = SearchPlan(
        result_limit=limit, max_steps=budget, cohorts=shards,
        method="wilson_hilferty",
    ).run(fresh(jax.random.PRNGKey(0)), chunks, detector=det)
    print(f"single-device scan: {scan.results[0]} results "
          f"in {scan.steps[0]} frames")
    sn = np.asarray(scan.carry.sampler.n)
    overlap = len(set(top.tolist()) & set(np.argsort(-sn)[:5].tolist()))
    print(f"top-5 hot-chunk overlap with scan: {overlap}/5")

    # ---- composed lowering: 4 concurrent queries × the same 8-way mesh,
    # one deduplicated + cached detector pass per round per shard ----
    q_n = 4
    keys = jnp.stack([
        jax.random.fold_in(jax.random.PRNGKey(0), q) for q in range(q_n)
    ])
    carries = init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=1024), keys,
    )
    t0 = time.time()
    comp = SearchPlan(
        queries=q_n, result_limit=limit // q_n, max_steps=budget,
        cohorts=shards,
        execution=Execution(queries_axis=True, shards=shards,
                            sync_every=sync_every, cache=-1),
    ).run(carries, chunks, detector=det)
    wall = time.time() - t0
    st = comp.stats
    print(f"composed({q_n} queries x {shards} shards): "
          f"{sum(comp.results)} results / {st.frames_sampled} frames "
          f"sampled / {st.detector_invocations} detector invocations "
          f"({st.amortization:.2f}x amortization, cache hit rate "
          f"{st.cache_hit_rate:.2f}) ({wall:.1f}s incl. compile)")


if __name__ == "__main__":
    main()
