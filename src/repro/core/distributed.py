"""Distributed ExSample runtime (paper §3.7.1 extended to multi-pod).

The paper observes all sampler updates are additive/commutative and sketches
an asynchronous distributed execution.  This module realizes it on a JAX
mesh:

  * chunk statistics are sharded over the ``data`` axis (and replicated over
    ``model`` / ``pod``) — each data shard owns M/|data| chunks;
  * cohort selection runs under ``shard_map``: every shard Thompson-samples
    its local chunks, then the *global* top cohort indices are recovered with
    an all-gather of per-shard (score, index) winners — collective volume is
    O(cohorts × |data|) scalars, negligible next to detector compute;
  * workers accumulate *delta* statistics locally and merge them with a
    `psum` every ``sync_every`` rounds ("eventual-consistency Thompson") —
    staleness only widens the posterior noise, which Thompson tolerates; the
    merge schedule is the straggler-mitigation lever: a late worker's delta
    joins whenever it lands, nobody barriers inside a round.

These functions are written against an abstract mesh so the same code runs
on the 2-device test mesh and the 512-chip production mesh.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.state import SamplerState
from repro.core.thompson import gamma_params, wilson_hilferty


def shard_sampler_state(state: SamplerState, mesh: Mesh, axis: str = "data"):
    """Place chunk-stat arrays sharded over ``axis`` (M must divide evenly;
    pad_chunks() handles ragged M)."""
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(
        lambda x: jax.device_put(x, sh) if x.ndim == 1 else x, state
    )


def pad_chunks(state: SamplerState, multiple: int) -> SamplerState:
    """Pad chunk arrays to a multiple of the shard count with exhausted
    dummy chunks (frames=0 ⇒ never selected).  Pads the LAST axis, so the
    same helper serves ``distributed_choose`` ([M] stats) and the mesh
    search loop ([Q, M] stats) — one fill-value contract for both."""
    m = state.n1.shape[-1]
    pad = (-m) % multiple
    if pad == 0:
        return state
    import dataclasses as _dc

    f = lambda x, fill: jnp.concatenate(
        [x, jnp.full(x.shape[:-1] + (pad,), fill, x.dtype)], axis=-1
    )
    return _dc.replace(
        state,
        n1=f(state.n1, 0),
        n=f(state.n, 1),       # n>0, frames=0 ⇒ exhausted
        frames=f(state.frames, 0),
    )


def local_cohort_winners_batched(
    keys: jax.Array,         # key[Q] — one PRNG key per query
    alpha_l: jax.Array,      # f32[Q, local_m] — this shard's slice, per query
    beta_l: jax.Array,       # f32[Q, local_m]
    exhausted_l: jax.Array,  # bool[Q, local_m]
    n_l: jax.Array,          # f32[Q, local_m]
    *,
    axis: str,
    cohorts: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-shard body of the globally-consistent Thompson choice, for Q
    queries at once — called INSIDE ``shard_map`` (by ``distributed_choose``
    at Q = 1 and by the mesh search loop, which cannot nest another
    shard_map).

    Every shard draws WH-approximate gamma scores for its local chunks
    (keys decorrelated per shard by ``fold_in(key, shard_id)``) and reduces
    to its per-cohort local winner; the (score, global index, winner's n)
    triples are all-gathered — ONE pass of collectives carrying
    ``[S, Q, C]`` — and the global argmax is computed redundantly on all
    shards (deterministic).  Row q depends only on ``keys[q]`` and row q
    of the statistics, which is what makes a query's mesh trajectory
    independent of the other queries in the batch.  Returns replicated
    (i32[Q, cohorts] global chunk ids, f32[Q, cohorts] winning scores —
    −inf iff every chunk everywhere is exhausted, f32[Q, cohorts] the
    owning shard's sample count for each winner — the random+ rank base).
    """
    local_m = alpha_l.shape[-1]
    shard_id = jax.lax.axis_index(axis)
    k = jax.vmap(lambda kk: jax.random.fold_in(kk, shard_id))(keys)
    z = jax.vmap(
        lambda kk: jax.random.normal(kk, (cohorts, local_m), alpha_l.dtype)
    )(k)                                                        # [Q, C, lm]
    scores = wilson_hilferty(alpha_l[:, None, :], z) / beta_l[:, None, :]
    scores = jnp.where(exhausted_l[:, None, :], -jnp.inf, scores)
    local_best = jnp.argmax(scores, axis=-1)                    # [Q, C]
    local_score = jnp.take_along_axis(
        scores, local_best[..., None], axis=-1
    )[..., 0]                                                   # [Q, C]
    global_idx = shard_id * local_m + local_best
    local_n = jnp.take_along_axis(n_l, local_best, axis=-1)
    with jax.named_scope("collective"):
        all_scores = jax.lax.all_gather(local_score, axis)      # [S, Q, C]
        all_idx = jax.lax.all_gather(global_idx, axis)
        all_n = jax.lax.all_gather(local_n, axis)
    win = jnp.argmax(all_scores, axis=0)                        # [Q, C]
    pick = lambda a: jnp.take_along_axis(a, win[None], axis=0)[0]
    return (
        pick(all_idx).astype(jnp.int32),
        pick(all_scores),
        pick(all_n),
    )


@partial(jax.jit, static_argnames=("cohorts", "axis", "mesh"))
def distributed_choose(
    key: jax.Array,
    state: SamplerState,
    *,
    mesh: Mesh,
    cohorts: int,
    axis: str = "data",
) -> jax.Array:
    """Globally-consistent batched Thompson choice over sharded stats
    (the standalone shard_map wrapper around
    ``local_cohort_winners_batched`` at Q = 1).  Returns replicated
    i32[cohorts] of *global* chunk ids.
    """
    num_shards = mesh.shape[axis]
    m = state.num_chunks
    assert m % num_shards == 0, "call pad_chunks() first"

    alpha, beta = gamma_params(state)
    exhausted = state.exhausted()

    def local_choice(key, alpha_l, beta_l, exhausted_l, n_l):
        idx, _, _ = local_cohort_winners_batched(
            key[None], alpha_l[None], beta_l[None], exhausted_l[None],
            n_l[None], axis=axis, cohorts=cohorts,
        )
        return idx[0]

    specs = P(axis)
    choice = jax.shard_map(
        local_choice,
        mesh=mesh,
        in_specs=(P(), specs, specs, specs, specs),
        out_specs=P(),
        check_vma=False,
    )(key, alpha, beta, exhausted, state.n)
    return choice


@jax.jit
def merge_deltas(
    state: SamplerState, delta_n1: jax.Array, delta_n: jax.Array
) -> SamplerState:
    """Merge per-worker delta statistics into the state.

    ``delta_*`` are stacked per-worker updates ``[W, M]`` (or a single
    ``[M]`` delta).  Additivity makes the merge exact regardless of
    interleaving — the §3.7.1 argument.  On a multi-controller deployment
    the identical reduction is one ``psum`` over the ``data`` axis of each
    process's local delta buffer (shard_map with replicated specs); in the
    single-controller runtime the workers' buffers arrive stacked, so the
    merge is a plain sum over the worker axis — same semantics, no
    collective theater.
    """
    import dataclasses as _dc

    d1 = jnp.atleast_2d(delta_n1).sum(axis=0)
    dn = jnp.atleast_2d(delta_n).sum(axis=0)
    return _dc.replace(state, n1=state.n1 + d1, n=state.n + dn)
