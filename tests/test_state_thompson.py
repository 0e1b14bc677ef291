"""Sampler-state algebra + Thompson sampling behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.state import (
    SamplerState,
    apply_cross_chunk_decrement,
    apply_update,
    init_state,
    merge_states,
    point_estimate,
)
from repro.core import thompson


def _state(m=8, frames=1000):
    return init_state(jnp.full((m,), frames, jnp.int32))


@settings(max_examples=30, deadline=None)
@given(
    updates=st.lists(
        st.tuples(st.integers(0, 7), st.integers(0, 5), st.integers(0, 3)),
        min_size=1,
        max_size=30,
    ),
    seed=st.integers(0, 100),
)
def test_updates_commute(updates, seed):
    """§3.7.1: additive updates are order-independent."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(updates))
    s1 = _state()
    for c, d0, d1 in updates:
        s1 = apply_update(s1, c, d0, d1)
    s2 = _state()
    for i in perm:
        c, d0, d1 = updates[i]
        s2 = apply_update(s2, c, d0, d1)
    assert jnp.allclose(s1.n1, s2.n1)
    assert jnp.allclose(s1.n, s2.n)


def test_merge_equals_sequential():
    """Async merge (psum of deltas) == sequential application."""
    a, b = _state(), _state()
    a = apply_update(a, 1, 3, 1)
    b = apply_update(b, 2, 2, 0)
    merged = merge_states(a, b)
    seq = apply_update(apply_update(_state(), 1, 3, 1), 2, 2, 0)
    assert jnp.allclose(merged.n1, seq.n1)
    assert jnp.allclose(merged.n, seq.n)


def test_cross_chunk_decrement():
    s = apply_update(_state(), 0, 2, 0)
    s = apply_cross_chunk_decrement(s, jnp.array([0]))
    assert float(s.n1[0]) == 1.0
    # one lane per detection: -1 lanes carry no home, a repeated home
    # loses one a lane
    s = apply_update(s, 1, 3, 0)
    s = apply_cross_chunk_decrement(s, jnp.array([1, -1, 1, -1]))
    assert s.n1[:2].tolist() == [1.0, 1.0]
    assert float(jnp.sum(s.n1)) == 2.0


def test_exhausted_chunks_never_chosen():
    s = _state(m=4, frames=2)
    s = dataclasses.replace(s, n=jnp.array([2.0, 2.0, 2.0, 0.0]))
    for i in range(20):
        c = thompson.choose_chunks(jax.random.PRNGKey(i), s, cohorts=4)
        assert jnp.all(c == 3)


def test_point_estimate_prefers_productive_chunk():
    s = _state(m=3)
    s = apply_update(s, 0, 5, 0)    # 5 fresh results
    s = apply_update(s, 1, 0, 0)    # nothing
    est = point_estimate(s)
    assert int(jnp.argmax(est)) == 0


def test_thompson_concentrates_but_explores():
    """A rich chunk wins most draws; an UNSAMPLED chunk retains nonzero
    selection probability through the Γ(α₀, β₀) prior (Eq. 10) — heavily
    sampled barren chunks are effectively retired."""
    s = _state(m=4)
    for _ in range(20):
        s = apply_update(s, 0, 1, 0)            # chunk 0: rich
    for c in (1, 2):
        for _ in range(20):
            s = apply_update(s, c, 0, 0)        # 1,2: barren, well-sampled
    # chunk 3: never sampled — prior Γ(0.1, 1) has a fat right tail
    picks = np.asarray(
        thompson.choose_chunks(jax.random.PRNGKey(0), s, cohorts=2000)
    )
    counts = np.bincount(picks, minlength=4)
    assert counts[0] / 2000 > 0.6
    assert counts[3] > 0                         # prior keeps exploring
    assert counts[3] > counts[1] + counts[2]     # unexplored ≻ known-barren


def test_wilson_hilferty_ordinal_agreement():
    """WH approximation agrees with exact Gamma on argmax distribution."""
    s = _state(m=6)
    s = apply_update(s, 2, 4, 0)
    s = apply_update(s, 5, 1, 0)
    exact = np.asarray(
        thompson.choose_chunks(jax.random.PRNGKey(1), s, cohorts=2000, method="exact")
    )
    wh = np.asarray(
        thompson.choose_chunks(
            jax.random.PRNGKey(2), s, cohorts=2000, method="wilson_hilferty"
        )
    )
    pe = np.bincount(exact, minlength=6) / len(exact)
    pw = np.bincount(wh, minlength=6) / len(wh)
    assert np.abs(pe - pw).max() < 0.08


def test_wh_transform_moments():
    """WH draws match Gamma mean/variance within tolerance for α ≥ 1."""
    key = jax.random.PRNGKey(0)
    alpha = jnp.float32(4.0)
    z = jax.random.normal(key, (200_000,))
    x = thompson.wilson_hilferty(alpha, z)
    assert abs(float(jnp.mean(x)) - 4.0) < 0.05
    assert abs(float(jnp.var(x)) - 4.0) < 0.2
