"""Per-chunk sampler statistics for ExSample (paper §3, Algorithm 1).

The sampler state is a dense, fixed-shape pytree so that every update is
jittable and shardable.  Per chunk j we track:

  * ``n1[j]``    — N¹_j: number of results seen *exactly once globally* whose
                   single sighting happened in chunk j (paper §3.4).
  * ``n[j]``     — number of frames sampled from chunk j so far.
  * ``frames[j]``— number of frames chunk j contains (for exhaustion masking).

All updates are additive and therefore commutative + associative, which is
the paper's §3.7.1 justification for batched/asynchronous execution; the
distributed runtime (``repro.core.distributed``) relies on exactly this.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

# Paper §3.3.1: Gamma prior smoothing constants.  "We used alpha0 = .1 and
# beta0 = 1 in practice, though we did not observe a strong dependence."
DEFAULT_ALPHA0: float = 0.1
DEFAULT_BETA0: float = 1.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SamplerState:
    """Dense ExSample statistics over M chunks."""

    n1: jax.Array          # f32[M]  — N¹ per chunk
    n: jax.Array           # f32[M]  — samples drawn per chunk
    frames: jax.Array      # i32[M]  — frames available per chunk
    alpha0: float = dataclasses.field(metadata=dict(static=True), default=DEFAULT_ALPHA0)
    beta0: float = dataclasses.field(metadata=dict(static=True), default=DEFAULT_BETA0)

    @property
    def num_chunks(self) -> int:
        return self.n1.shape[0]

    def exhausted(self) -> jax.Array:
        """bool[M] — True where every frame of the chunk has been sampled."""
        return self.n >= self.frames.astype(self.n.dtype)


def init_state(
    frames_per_chunk: jax.Array | Any,
    *,
    alpha0: float = DEFAULT_ALPHA0,
    beta0: float = DEFAULT_BETA0,
    dtype: jnp.dtype = jnp.float32,
) -> SamplerState:
    """Fresh state: all-zero statistics (Algorithm 1 lines 2-3)."""
    frames = jnp.asarray(frames_per_chunk, dtype=jnp.int32)
    zeros = jnp.zeros(frames.shape, dtype=dtype)
    return SamplerState(n1=zeros, n=zeros, frames=frames, alpha0=alpha0, beta0=beta0)


def apply_update(
    state: SamplerState,
    chunk_idx: jax.Array,
    d0: jax.Array,
    d1: jax.Array,
    *,
    samples: jax.Array | int = 1,
) -> SamplerState:
    """Algorithm 1 lines 13-14 for one (possibly batched) observation.

    Args:
      chunk_idx: i32[] or i32[B] — chunk(s) the frame(s) were drawn from.
      d0: number of detections that matched *no* previous result.
      d1: number of detections whose result now has exactly one prior match
          (i.e. results transitioning from seen-once to seen-twice).
      samples: frames consumed per entry (normally 1).

    ``N¹[j*] += |d0| - |d1|``; ``n[j*] += 1``.  Batched form uses
    scatter-add so colliding chunk indices accumulate, preserving
    commutativity.
    """
    with jax.named_scope("update"):
        chunk_idx = jnp.atleast_1d(jnp.asarray(chunk_idx))
        d0 = jnp.broadcast_to(jnp.asarray(d0, state.n1.dtype), chunk_idx.shape)
        d1 = jnp.broadcast_to(jnp.asarray(d1, state.n1.dtype), chunk_idx.shape)
        samples = jnp.broadcast_to(
            jnp.asarray(samples, state.n.dtype), chunk_idx.shape
        )
        n1 = state.n1.at[chunk_idx].add(d0 - d1)
        n = state.n.at[chunk_idx].add(samples)
    return dataclasses.replace(state, n1=n1, n=n)


def decrement_homes(n1: jax.Array, home_chunk: jax.Array) -> jax.Array:
    """``n1[home_chunk[d]] -= 1`` for every lane d; a lane whose home is -1
    changes nothing.  A one-hot compare-and-sum over [D, M] rather than a
    scatter: the TPU runs a scatter's updates one by one, and N¹ holds
    whole numbers, so the sum gives the scatter's result bit for bit."""
    home_chunk = jnp.atleast_1d(jnp.asarray(home_chunk))
    hit = home_chunk[:, None] == jnp.arange(n1.shape[-1])
    return n1 - jnp.sum(hit, axis=0).astype(n1.dtype)


def apply_cross_chunk_decrement(
    state: SamplerState, home_chunk: jax.Array
) -> SamplerState:
    """§3.4: a result first seen in chunk ``home_chunk`` was re-found in a
    *different* chunk — its contribution leaves N¹ of the home chunk
    (home -1: no such result)."""
    with jax.named_scope("update"):
        n1 = decrement_homes(state.n1, home_chunk)
    return dataclasses.replace(state, n1=n1)


def merge_states(a: SamplerState, b: SamplerState) -> SamplerState:
    """Merge two independently-updated replicas of the *same* initial state.

    Because all updates are additive, merged = init + (a - init) + (b - init)
    and init is zero, so the statistics simply add.  Used by the async /
    multi-pod runtime and by elastic resharding.
    """
    if a.num_chunks != b.num_chunks:
        raise ValueError(
            f"cannot merge states over {a.num_chunks} vs {b.num_chunks} chunks"
        )
    return dataclasses.replace(a, n1=a.n1 + b.n1, n=a.n + b.n)


def point_estimate(state: SamplerState) -> jax.Array:
    """Eq. 7 point estimate N¹_j / n_j with the prior-smoothed form used for
    decision making: (N¹+α₀)/(n+β₀).  Exhausted chunks score -inf."""
    est = (state.n1 + state.alpha0) / (state.n + state.beta0)
    return jnp.where(state.exhausted(), -jnp.inf, est)
