"""Clamping-contract parity: kernels/thompson (ref + interpret-mode
kernel) ≡ core.thompson.draw_scores_wilson_hilferty (DESIGN.md §3).

``gamma_params`` owns the statistical clamp (α floored at α₀/2 when N¹
dips below zero through §3.4 cross-chunk decrements); the kernel's
internal ``max(α, 1e-6)`` is numeric safety that must never bind for a
live chunk.  These tests lock both halves of that contract in.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import thompson
from repro.core.state import init_state
from repro.kernels.thompson.kernel import thompson_choose
from repro.kernels.thompson.ref import thompson_ref


def _tricky_state(m=130, frames=50, seed=0):
    """State exercising every clamping branch: negative N¹ (cross-chunk
    decrements), zero stats, rich chunks, and exhausted chunks."""
    rng = np.random.default_rng(seed)
    s = init_state(jnp.full((m,), frames, jnp.int32))
    n1 = rng.integers(-3, 8, m).astype(np.float32)   # negatives ⇒ α clamp
    n = rng.integers(0, frames, m).astype(np.float32)
    n[::17] = frames                                  # some exhausted
    return dataclasses.replace(s, n1=jnp.asarray(n1), n=jnp.asarray(n))


def _sentinel_params(state):
    alpha, beta = thompson.gamma_params(state)
    return jnp.where(state.exhausted(), -1.0, alpha), beta


def test_gamma_params_clamps_negative_n1_at_half_alpha0():
    s = _tricky_state()
    alpha, _ = thompson.gamma_params(s)
    assert float(jnp.min(alpha)) == pytest.approx(s.alpha0 * 0.5)
    assert bool(jnp.all(alpha > 0))  # live α always beats the 1e-6 floor


def test_ref_matches_draw_scores_wilson_hilferty():
    s = _tricky_state()
    key = jax.random.PRNGKey(42)
    cohorts = 9
    scores = thompson.draw_scores_wilson_hilferty(key, s, cohorts=cohorts)
    expected_idx = jnp.argmax(scores, axis=-1).astype(jnp.int32)

    alpha, beta = _sentinel_params(s)
    z = jax.random.normal(key, (cohorts, alpha.shape[0]), dtype=alpha.dtype)
    idx, val = thompson_ref(alpha, beta, z)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(expected_idx))
    # scores (not just argmax) agree exactly on live chunks — the kernel's
    # 1e-6 clamp never bound
    np.testing.assert_array_equal(
        np.asarray(val),
        np.asarray(jnp.max(scores, axis=-1)),
    )


@pytest.mark.parametrize("m,bm", [(130, 64), (64, 64), (300, 128)])
def test_interpret_kernel_matches_ref_on_tricky_states(m, bm):
    s = _tricky_state(m=m, seed=m)
    alpha, beta = _sentinel_params(s)
    z = jax.random.normal(jax.random.PRNGKey(m), (4, m))
    kidx, kval = thompson_choose(alpha, beta, z, block_m=bm, interpret=True)
    ridx, rval = thompson_ref(alpha, beta, z)
    np.testing.assert_array_equal(np.asarray(kidx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(kval), np.asarray(rval), rtol=1e-6)


@pytest.mark.parametrize("m,bm", [(130, 64), (300, 128)])
def test_interpret_batched_kernel_matches_per_query_kernel(m, bm):
    """Multi-query grid (DESIGN.md §9): one (Q, M-blocks) launch must give,
    for every query row, what the plain reference ``thompson_ref`` gives on
    that query's own statistics and normals — no row may read another
    query's statistics, and each row must be right on its own."""
    from repro.kernels.thompson.kernel import thompson_choose_batched

    q_n, cohorts = 3, 4
    alphas, betas, zs = [], [], []
    for q in range(q_n):
        s = _tricky_state(m=m, seed=m + q)
        a, b = _sentinel_params(s)
        alphas.append(a)
        betas.append(b)
        zs.append(jax.random.normal(jax.random.PRNGKey(100 + q), (cohorts, m)))
    bidx, bval = thompson_choose_batched(
        jnp.stack(alphas), jnp.stack(betas), jnp.stack(zs),
        block_m=bm, interpret=True,
    )
    for q in range(q_n):
        ridx, rval = thompson_ref(alphas[q], betas[q], zs[q])
        np.testing.assert_array_equal(np.asarray(bidx[q]), np.asarray(ridx))
        np.testing.assert_allclose(
            np.asarray(bval[q]), np.asarray(rval), rtol=1e-6
        )


def test_choose_chunks_pallas_equals_wilson_hilferty():
    """method="pallas" must be bit-identical in its chunk choices to
    method="wilson_hilferty" under the same key."""
    s = _tricky_state(m=257, seed=5)
    for k in range(4):
        key = jax.random.PRNGKey(k)
        wh = thompson.choose_chunks(key, s, cohorts=16, method="wilson_hilferty")
        pal = thompson.choose_chunks(key, s, cohorts=16, method="pallas")
        np.testing.assert_array_equal(np.asarray(wh), np.asarray(pal))


def test_pallas_never_picks_exhausted_chunks():
    s = init_state(jnp.full((8,), 4, jnp.int32))
    n = jnp.full((8,), 4.0).at[6].set(0.0)  # only chunk 6 live
    s = dataclasses.replace(s, n=n)
    for k in range(10):
        c = thompson.choose_chunks(
            jax.random.PRNGKey(k), s, cohorts=4, method="pallas"
        )
        assert bool(jnp.all(c == 6)), c
