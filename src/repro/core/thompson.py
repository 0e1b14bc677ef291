"""Thompson sampling over Gamma beliefs (paper §3.3.1, Eq. 9-10).

Three interchangeable samplers:

  * ``draw_scores``           — exact Gamma draws via ``jax.random.gamma``.
  * ``draw_scores_wilson_hilferty`` — branch-free Wilson-Hilferty cube-normal
    approximation, the transform used inside the Pallas kernel
    (``repro.kernels.thompson``).  See DESIGN.md §3 for why rejection
    sampling (Marsaglia-Tsang) is replaced on TPU.
  * ``method="pallas"`` in ``choose_chunks`` — the fused VMEM-resident
    kernel (``repro.kernels.thompson.ops.choose``): same WH transform and
    the same ``gamma_params`` clamping, with exhaustion encoded as an
    ``alpha < 0`` sentinel (DESIGN.md §3).  Bit-identical chunk choices to
    ``"wilson_hilferty"`` for the same key.

``choose_chunks`` implements the batched-cohort selection of §3.7.1: B
independent Thompson draws per chunk yield B chunk indices, biased toward
promising chunks but diversified by the posterior noise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.state import SamplerState


def gamma_params(state: SamplerState) -> tuple[jax.Array, jax.Array]:
    """(α, β) of Eq. 10:  α = N¹_j + α₀,  β = n_j + β₀."""
    alpha = state.n1 + state.alpha0
    beta = state.n + state.beta0
    # N¹ can transiently dip below 0 only through cross-chunk decrements of
    # results later re-found; clamp so the belief stays a valid Gamma.
    return jnp.maximum(alpha, state.alpha0 * 0.5), beta


def draw_scores(key: jax.Array, state: SamplerState, *, cohorts: int = 1) -> jax.Array:
    """Exact Thompson draws.  Returns f32[cohorts, M]."""
    alpha, beta = gamma_params(state)
    draws = jax.random.gamma(key, alpha[None, :].repeat(cohorts, axis=0))
    scores = draws / beta[None, :]
    return jnp.where(state.exhausted()[None, :], -jnp.inf, scores)


def wilson_hilferty(alpha: jax.Array, z: jax.Array) -> jax.Array:
    """Wilson-Hilferty: if X ~ Γ(α, 1) then (X/α)^(1/3) ≈ N(1 − 1/(9α), 1/(9α)).

    Inverting:  X ≈ α · (1 − 1/(9α) + z/(3√α))³, clamped at 0.  Branch-free,
    uses only mul/add/rsqrt — VPU friendly.  Relative quantile error < 1e-2
    for α ≥ 0.3 and the sampler only consumes *ordinal* information.
    """
    c = 1.0 - 1.0 / (9.0 * alpha) + z / (3.0 * jnp.sqrt(alpha))
    return alpha * jnp.maximum(c, 0.0) ** 3


def draw_scores_wilson_hilferty(
    key: jax.Array, state: SamplerState, *, cohorts: int = 1
) -> jax.Array:
    """Approximate Thompson draws via the WH transform.  f32[cohorts, M]."""
    alpha, beta = gamma_params(state)
    z = jax.random.normal(key, (cohorts, alpha.shape[0]), dtype=alpha.dtype)
    scores = wilson_hilferty(alpha[None, :], z) / beta[None, :]
    return jnp.where(state.exhausted()[None, :], -jnp.inf, scores)


@partial(jax.jit, static_argnames=("cohorts", "method"))
def choose_chunks(
    key: jax.Array,
    state: SamplerState,
    *,
    cohorts: int = 1,
    method: str = "exact",
) -> jax.Array:
    """Algorithm 1 lines 5-8, batched (§3.7.1).  Returns i32[cohorts]."""
    with jax.named_scope("choose"):
        if method == "exact":
            scores = draw_scores(key, state, cohorts=cohorts)
        elif method == "wilson_hilferty":
            scores = draw_scores_wilson_hilferty(key, state, cohorts=cohorts)
        elif method == "pallas":
            # deferred import: kernels.thompson.ref imports this module
            from repro.kernels.thompson.ops import choose

            alpha, beta = gamma_params(state)  # already clamped ≥ alpha0/2 > 0
            alpha = jnp.where(state.exhausted(), -1.0, alpha)
            z = jax.random.normal(key, (cohorts, alpha.shape[0]), dtype=alpha.dtype)
            idx, _ = choose(alpha, beta, z)
            return idx
        else:
            raise ValueError(f"unknown Thompson method: {method!r}")
        return jnp.argmax(scores, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cohorts", "method"))
def choose_chunks_batched(
    keys: jax.Array,
    state: SamplerState,
    *,
    cohorts: int = 1,
    method: str = "exact",
) -> jax.Array:
    """Leading-[Q] batched ``choose_chunks`` for the multi-query driver
    (DESIGN.md §9): per-query keys ``keys[Q]`` and per-query statistics
    (every ``state`` leaf carries a leading [Q] axis) decided in ONE
    batched call.  Returns i32[Q, cohorts].

    Contract: row q is bit-identical to ``choose_chunks(keys[q],
    state_q, cohorts, method)`` — ``vmap`` of the PRNG + score path is
    per-lane exact, which is what makes the Q=1 multi-query parity test
    meaningful.  The pallas path stays ONE kernel launch (per-query alpha
    rows, grid [Q·C, M-blocks]) rather than Q serial kernel calls.
    """
    with jax.named_scope("choose"):
        if method in ("exact", "wilson_hilferty"):
            f = partial(choose_chunks, cohorts=cohorts, method=method)
            return jax.vmap(f)(keys, state)
        if method == "pallas":
            from repro.kernels.thompson.ops import choose_batched

            alpha, beta = gamma_params(state)            # [Q, M], pre-clamped
            alpha = jnp.where(state.exhausted(), -1.0, alpha)
            m = alpha.shape[-1]
            z = jax.vmap(
                lambda k: jax.random.normal(k, (cohorts, m), dtype=alpha.dtype)
            )(keys)
            idx, _ = choose_batched(alpha, beta, z)
            return idx
        raise ValueError(f"unknown Thompson method: {method!r}")


def greedy_chunks(state: SamplerState, *, cohorts: int = 1) -> jax.Array:
    """Greedy baseline: always argmax of the point estimate (no posterior
    noise).  The paper shows this underperforms Thompson because it cannot
    diversify; kept as a benchmark arm."""
    from repro.core.state import point_estimate

    idx = jnp.argmax(point_estimate(state)).astype(jnp.int32)
    return jnp.broadcast_to(idx, (cohorts,))


def expected_regret_proxy(state: SamplerState, true_r: jax.Array) -> jax.Array:
    """Diagnostic: gap between the value of the chosen chunk distribution and
    the best chunk, under ground-truth per-chunk new-result rates ``true_r``
    (available in simulation only)."""
    alpha, beta = gamma_params(state)
    mean_scores = alpha / beta
    chosen = jnp.argmax(mean_scores)
    return jnp.max(true_r) - true_r[chosen]
