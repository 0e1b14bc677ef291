"""Every seed offers the same work, in another order."""
import numpy as np

from bench import load


def test_batch_queries_are_a_fixed_pool_in_seed_order():
    pool, ncls = 4, 8
    take = lambda seed, n: [load.pool_index(seed, pool, ncls, i) for i in range(n)]
    a, b = take(11, 3 * pool * ncls), take(12, 3 * pool * ncls)
    assert a == take(11, 3 * pool * ncls)          # same seed, same order
    assert a != b
    one_pass = pool * ncls
    for s in (a, b):                                # every pass holds the pool once
        for p in range(3):
            assert sorted(s[p * one_pass:(p + 1) * one_pass]) == list(range(one_pass))
    # query i keeps its class i mod ncls
    assert all(idx // pool == i % ncls for i, idx in enumerate(a))


def test_tenants_and_gaps_are_one_sequence_rotated_by_the_seed():
    c1, k1 = load.tenant_set(40, 8, 1.0, 1)
    c2, k2 = load.tenant_set(40, 8, 1.0, 2)
    assert sorted(zip(c1, k1)) == sorted(zip(c2, k2))
    assert list(c1) != list(c2)
    counts = np.bincount(c1, minlength=8)
    assert counts[0] == max(counts) and counts.sum() == 40   # Zipf: class 0 most
    # one sequence, rotated: each tenant keeps the gap before it
    r1, r2 = load._rotation(40, 1), load._rotation(40, 2)
    assert r1 != r2
    assert list(np.roll(c1, r1)) == list(np.roll(c2, r2))
    g1 = np.diff(np.concatenate([[0], load.arrival_offsets(40, 50.0, 1)]))
    g2 = np.diff(np.concatenate([[0], load.arrival_offsets(40, 50.0, 2)]))
    assert not np.allclose(g1, g2)
    assert np.allclose(np.roll(g1, r1), np.roll(g2, r2))
    assert load.arrival_offsets(40, 50.0, 1)[-1] < 50.0
