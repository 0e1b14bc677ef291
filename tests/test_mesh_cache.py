"""The hash-sharded detection cache of the composed Q × shards lowering on a
four-device mesh (DESIGN.md §14), run in one subprocess with four host
devices: each shard builds and keeps its own part of the cache, the cache
changes which detector calls happen and never what a query consumes, a
warm repository index is placed shard by shard, and a windowed elastic run
resumes from the sharded cache exactly.  Each test reads its part of the
subprocess's report."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import json, re
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import init_carry_multi, init_matcher, init_state
    from repro.core.executor import (
        _place_cache, _search_multi_sharded_device, run_search_multi_sharded)
    from repro.core.distributed import pad_chunks
    from repro.core.runtime import ElasticShardedRunner
    from repro.index.store import RepositoryIndex
    from repro.launch.mesh import make_data_mesh
    from repro.serve.batcher import RowLayout, host_direct_mapped
    from repro.sim import RepoSpec, generate
    from repro.sim.oracle import oracle_detect

    spec = RepoSpec(video_lengths=[3_000] * 4, num_instances=120,
                    chunk_frames=500, locality=4.0, seed=3)
    repo, chunks = generate(spec)
    det = lambda key, frame: oracle_detect(repo, frame, query_class=0)
    q_n, cohorts, limit, budget = 3, 8, 40, 480
    cap = int(chunks.total_frames)          # 12,000 frames: divides by 4
    keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(1), q)
                      for q in range(q_n)])
    fresh = lambda: init_carry_multi(
        init_state(chunks.length), init_matcher(max_results=2048), keys)
    mesh = make_data_mesh(4)
    kw = dict(mesh=mesh, detector=det, result_limits=limit,
              max_steps=budget, cohorts=cohorts)
    struct = jax.eval_shape(det, jax.random.PRNGKey(0),
                            jnp.zeros((), jnp.int32))
    layout = RowLayout.of(struct)
    report = {}

    def same_carry(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    def same_cache(a, b):
        a, b = host_direct_mapped(a), host_direct_mapped(b)
        return (np.array_equal(a.tag, b.tag)
                and np.array_equal(a.store, b.store))

    def shard_rows(arr):
        return sorted((s.device.id, list(s.data.shape))
                      for s in arr.addressable_shards)

    # ---- cold: the cache built shard by shard inside the program --------
    before = {id(a) for a in jax.live_arrays()}   # the repository's own
    out_c, tr_c, st_c = run_search_multi_sharded(
        fresh(), chunks, cache_frames=cap, **kw)
    fc = st_c["final_cache"]
    full = [list(a.shape) for a in jax.live_arrays() if id(a) not in before
            for s in a.addressable_shards if s.data.shape[:1] == (cap,)]
    report["build"] = {
        "shards": fc.shards, "capacity": fc.capacity,
        "tag": shard_rows(fc.tag), "store": shard_rows(fc.store),
        "width": layout.width, "full_on_one_device": full,
    }

    # ---- the cache is transparent; its content is the detector's -------
    out_0, tr_0, st_0 = run_search_multi_sharded(fresh(), chunks, **kw)
    host = host_direct_mapped(fc)
    occ = np.flatnonzero(host.tag >= 0)
    got = layout.unpack(host.store[occ])
    want = jax.tree.map(np.asarray, jax.vmap(det)(
        jax.random.split(jax.random.PRNGKey(0), len(occ)),
        jnp.asarray(host.tag[occ])))
    report["transparent"] = {
        "carry": same_carry(out_c, out_0), "traces": tr_c == tr_0,
        "frames": [st_c["frames_sampled"], st_0["frames_sampled"]],
        "calls": [st_c["detector_invocations"], st_c["cache_hits"],
                  st_0["detector_invocations"]],
        "occupied": int(len(occ)),
        "slots_match_frames": bool(np.all(host.tag[occ] % cap == occ)),
        # the boxes are box + t * drift, which one compiled program may
        # contract into a fused multiply-add and another not: a rounding
        "rows_are_detections": bool(
            all(np.array_equal(a, b) for a, b in
                zip(got[1:], want[1:]))
            and np.allclose(got.boxes, want.boxes, rtol=0, atol=1e-6)),
    }

    # ---- a warm index: placed shard by shard, every hit an index hit ----
    index = RepositoryIndex()
    index.publish_cache(fc)
    warm, _ = index.warm(struct, cap)
    placed = _place_cache(warm, mesh, "data")
    out_w, tr_w, st_w = run_search_multi_sharded(
        fresh(), chunks, cache=warm, warm_tag=warm.tag, **kw)
    report["warm"] = {
        "placed": shard_rows(placed.store), "placed_shards": placed.shards,
        "carry": same_carry(out_w, out_c), "traces": tr_w == tr_c,
        "calls": [st_w["detector_invocations"], st_w["cache_hits"],
                  st_w["index_hits"]],
        "cache": same_cache(st_w["final_cache"], fc),
    }

    # ---- a windowed elastic run resumes from the sharded cache ----------
    t = [0.0]
    def clock():
        t[0] += 1.0
        return t[0]
    runner = ElasticShardedRunner(
        fresh(), chunks, detector=det, result_limits=limit,
        max_steps=budget, num_shards=4, cohorts=cohorts, cache_frames=cap,
        clock=clock, sync_windows=2)
    out_e, tr_e, st_e = runner.run()
    report["elastic"] = {
        "windows": st_e["merges"],
        "carry": same_carry(out_e, out_c), "traces": tr_e == tr_c,
        "counts": [[st_e[k], st_c[k]] for k in (
            "detector_invocations", "cache_hits", "rounds")],
        "cache": same_cache(st_e["final_cache"], fc),
        "shards": st_e["final_cache"].shards,
    }

    # ---- the compacted detector batch against the full batch -----------
    from repro.core import exsample, executor
    from repro.sim.oracle import noisy_detect

    needs = []
    compacted = exsample.detect_needed

    def spy(detector, keys_, frames, need):
        jax.debug.callback(lambda n: needs.append(int(n)), jnp.sum(need))
        return compacted(detector, keys_, frames, need)

    def full_batch(detector, keys_, frames, need):
        return jax.vmap(detector)(keys_, frames), jnp.int32(frames.shape[0])

    noisy = lambda key, frame: noisy_detect(key, repo, frame, query_class=0)

    def both(base, qs, cohorts_, cache=None):
        ks = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(1), q)
                        for q in range(qs)])
        runs = []
        for fn in (spy, full_batch):
            executor.detect_needed = fn
            runs.append(run_search_multi_sharded(
                init_carry_multi(init_state(chunks.length),
                                 init_matcher(max_results=2048), ks),
                chunks, mesh=mesh, detector=lambda k, f: base(k, f),
                result_limits=limit, max_steps=budget, cohorts=cohorts_,
                cache_frames=0 if cache is not None else cap, cache=cache))
            jax.effects_barrier()
        executor.detect_needed = compacted
        (out, tr, st), (ref, tr_r, st_r) = runs
        return {
            "carry": same_carry(out, ref), "traces": tr == tr_r,
            "cache": same_cache(st["final_cache"], st_r["final_cache"]),
            "counts": [[st[k], st_r[k]] for k in (
                "detector_invocations", "cache_hits", "rounds")],
            "lanes": [st["detector_lanes"], st_r["detector_lanes"]],
            "needs": list(needs), "batch": qs * cohorts_ // 4,
        }

    report["compaction"] = {}
    for name, base, qs, cohorts_, cache in (
        ("no_needed_lane", det, q_n, cohorts, fc),
        ("narrow_batch", det, q_n, cohorts, None),
        ("every_lane_needed", det, 1, 64, None),
        ("padded_chunk", det, q_n, 16, None),
        ("key_dependent", noisy, q_n, cohorts, None),
    ):
        del needs[:]
        report["compaction"][name] = both(base, qs, cohorts_, cache)

    # ---- the program's collective and cache_init scopes ----------------
    c = fresh()
    padded = pad_chunks(c.sampler, 4)
    text = _search_multi_sharded_device.lower(
        c.key, c.step, c.results, padded.n1, padded.n, padded.frames,
        c.matcher, chunks, jnp.full((q_n,), limit, jnp.int32), None, None,
        jnp.asarray(2**31 - 1, jnp.int32), mesh=mesh, axis="data",
        detector=det, select=None, cohorts=cohorts, sync_every=1,
        max_steps=budget, alpha0=c.sampler.alpha0, beta0=c.sampler.beta0,
        empty=(layout, cap // 4),
    ).as_text(debug_info=True)
    names = re.findall(r'loc[(]"([^"]*)"', text)
    report["scopes"] = {
        s: sorted({n.rsplit("/", 1)[-1] for n in names
                   if n.startswith(f"{s}/") or f"/{s}/" in n})
        for s in ("collective", "cache_init")
    }
    print("REPORT " + json.dumps(report))
    """
)


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600, env=env,
    )
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("REPORT ")]
    assert r.returncode == 0 and lines, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(lines[-1][len("REPORT "):])


def test_sharded_build_keeps_a_quarter_of_the_cache_on_each_device(report):
    b = report["build"]
    quarter = b["capacity"] // 4
    assert b["shards"] == 4 and b["capacity"] == 12_000
    assert b["tag"] == [[d, [quarter]] for d in range(4)]
    assert b["store"] == [[d, [quarter, b["width"]]] for d in range(4)]
    assert b["width"] % 128 == 0
    assert b["full_on_one_device"] == []


def test_cache_changes_detector_calls_never_what_a_query_consumes(report):
    t = report["transparent"]
    assert t["carry"] and t["traces"]
    assert t["frames"][0] == t["frames"][1] > 0
    calls, hits, calls_uncached = t["calls"]
    assert calls + hits == calls_uncached and hits > 0
    # each occupied slot is its frame's direct-mapped slot and holds the
    # detector's own output for that frame, bit for bit
    assert 0 < t["occupied"] <= calls
    assert t["slots_match_frames"] and t["rows_are_detections"]


def test_warm_index_is_placed_shard_by_shard_and_replays_exactly(report):
    w = report["warm"]
    assert w["placed_shards"] == 4
    assert [rows[1][0] for rows in w["placed"]] == [3_000] * 4
    assert w["carry"] and w["traces"] and w["cache"]
    calls, hits, index_hits = w["calls"]
    assert calls == 0 and hits == index_hits > 0


def test_elastic_windowed_resume_on_four_shards_matches_one_call(report):
    e = report["elastic"]
    assert e["windows"] >= 4       # at least two slices of two windows
    assert e["carry"] and e["traces"] and e["cache"]
    assert all(a == b for a, b in e["counts"]), e["counts"]
    assert e["shards"] == 4


def test_mesh_program_names_its_collectives_and_cache_build(report):
    s = report["scopes"]
    assert {"all_gather", "all_to_all", "psum"} <= set(s["collective"])
    assert "broadcast_in_dim" in s["cache_init"]


# case: lanes a chunk for the shard's batch of Q · C / 4 lanes
CHUNK = {
    "no_needed_lane": 6, "narrow_batch": 6, "every_lane_needed": 8,
    "padded_chunk": 8, "key_dependent": 6,
}


@pytest.mark.parametrize("case", list(CHUNK))
def test_compacted_detect_on_four_shards_matches_full_batch(report, case):
    """Each shard detects only its round's unique, uncached, live lanes, in
    chunks, and every value a query consumes is the full batch's, bit for
    bit: final carry, traces, the sharded final cache and the detector
    counters.  The lanes the detector evaluated are the needed lanes
    rounded up to whole chunks, summed over rounds and shards: none where
    a warm cache serves every round, the shard's 6 lanes in one chunk
    below 8, two full chunks of 8 where a single query needs all 16, a
    padded second chunk where a shard of 12 needs more than 8."""
    c = report["compaction"][case]
    width = CHUNK[case]
    assert c["carry"] and c["traces"] and c["cache"]
    assert all(a == b for a, b in c["counts"]), c["counts"]
    calls, hits, rounds = (a for a, _ in c["counts"])
    needs = c["needs"]
    assert len(needs) == 4 * rounds > 0 and sum(needs) == calls
    lanes, full_lanes = c["lanes"]
    assert lanes == sum(-(-n // width) * width for n in needs)
    assert full_lanes == 4 * rounds * c["batch"]
    if case == "no_needed_lane":
        assert lanes == 0 and hits > 0
    elif case == "every_lane_needed":
        assert set(needs) == {16}
    elif case == "padded_chunk":
        assert any(width < n < 2 * width for n in needs)
