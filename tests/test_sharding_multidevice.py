"""Multi-device behaviours, run in a subprocess with 8 host devices.

Covers: distributed Thompson choice, delta merging, compressed cross-pod
all-reduce, and a tiny-mesh lower+compile of a train cell — the unit-scale
version of the production dry-run.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_test_mesh
    from repro.core.state import init_state, apply_update
    from repro.core.distributed import (
        distributed_choose, merge_deltas, pad_chunks, shard_sampler_state)

    mesh = make_test_mesh((4, 2), ("data", "model"))

    # --- distributed Thompson choice matches rich-chunk expectation -------
    s = init_state(jnp.full((16,), 100, jnp.int32))
    for _ in range(12):
        s = apply_update(s, 5, 1, 0)          # chunk 5 is rich
    for c in (0, 1, 2, 3):
        for _ in range(12):
            s = apply_update(s, c, 0, 0)
    s = pad_chunks(s, 4)
    picks = []
    for i in range(50):
        c = distributed_choose(jax.random.PRNGKey(i), s, mesh=mesh, cohorts=4)
        picks += list(np.asarray(c))
    frac = (np.asarray(picks) == 5).mean()
    assert frac > 0.5, frac
    print("choose ok", frac)

    # --- delta merge == sum over workers ------------------------------------
    base = init_state(jnp.full((16,), 100, jnp.int32))
    d1 = jnp.zeros((4, 16)).at[:, 3].set(2.0)     # 4 workers, same chunk
    dn = jnp.zeros((4, 16)).at[:, 3].set(1.0)
    merged = merge_deltas(base, d1, dn)
    assert float(merged.n1[3]) == 8.0, merged.n1
    assert float(merged.n[3]) == 4.0
    print("merge ok")

    # --- tiny-mesh train cell lower+compile --------------------------------
    import dataclasses
    from repro.configs import ARCHS, scale_down
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.launch.specs import build_cell
    from repro.distributed.sharding import ShardingRules, use_rules

    cfg = scale_down(ARCHS["qwen2.5-32b"], layers=2, d_model=64, heads=4,
                     kv_heads=2, d_ff=128, vocab=256)
    shape = ShapeConfig("tiny_train", 64, 8, "train")
    run = RunConfig(param_dtype="float32", unroll=True, block_q=32, block_kv=32,
                    causal_block_skip=False, sequence_parallel=False,
                    remat=True, microbatches=2)
    cell = build_cell(cfg, shape, mesh, run=run)
    with mesh, use_rules(ShardingRules.for_mesh(mesh)):
        compiled = jax.jit(cell.step_fn, in_shardings=cell.in_shardings) \\
            .lower(*cell.args).compile()
    print("tiny dryrun ok", compiled.memory_analysis().temp_size_in_bytes)

    # --- compressed cross-pod allreduce ------------------------------------
    mesh3 = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    from repro.distributed.compression import (
        make_cross_pod_allreduce, init_error_feedback)
    grads = {"w": jnp.arange(32.0).reshape(4, 8) / 31.0}
    ef = init_error_feedback(grads)
    fn = make_cross_pod_allreduce(mesh3, compress=True)
    out, ef2 = fn(grads, ef)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(grads["w"]),
                               atol=2e-2)
    print("compressed allreduce ok")
    print("ALL_OK")
    """
)


@pytest.mark.slow
def test_multidevice_suite():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert "ALL_OK" in r.stdout, r.stdout[-3000:] + "\n" + r.stderr[-3000:]
