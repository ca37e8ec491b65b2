"""The copied generators are deterministic per seed, and a seed changes
the order of the work, not its amount."""
import json

import numpy as np
import pytest

from bench import corpus, manifest
from bench.traffic import loop

BIG = 2**31 + 12345
GEO = {"n_special": 6, "n_patches": 64, "dim": 16, "bands": 8}


@pytest.mark.parametrize("seed", [0, BIG])
def test_pages_are_a_function_of_seed_and_batch(seed):
    t = corpus.topics(seed, 4, 16)
    a = np.asarray(corpus.page_batch(GEO, seed, 3, t, 8))
    b = np.asarray(corpus.page_batch(GEO, seed, 3, t, 8))
    assert a.shape == (8, 70, 16) and np.array_equal(a, b)
    assert not np.array_equal(a, np.asarray(
        corpus.page_batch(GEO, seed, 4, t, 8)))
    np.testing.assert_allclose(np.linalg.norm(a, axis=-1), 1.0, atol=1e-5)


def test_seeds_past_32_bits_do_not_collide():
    t = corpus.topics(1, 4, 16)
    a = corpus.page_batch(GEO, 5, 0, t, 4)
    b = corpus.page_batch(GEO, 5 + 2**32, 0, t, 4)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_query_pool_same_lengths_other_order():
    t = corpus.topics(7, 8, 16)
    q1, l1 = corpus.query_pool(7, t, 100, 4, 32)
    q2, l2 = corpus.query_pool(7, t, 100, 4, 32)
    q3, l3 = corpus.query_pool(8, t, 100, 4, 32)
    assert np.array_equal(q1, q2) and np.array_equal(l1, l2)
    assert np.array_equal(np.sort(l1), np.sort(l3))
    assert not np.array_equal(l1, l3)
    assert l1.min() == 4 and l1.max() == 32
    # zero padding past each query's length, unit tokens before it
    for q, n in zip(q1, l1):
        assert not q[n:].any()
        np.testing.assert_allclose(np.linalg.norm(q[:n], axis=1), 1,
                                   atol=1e-5)


def test_arrivals_fixed_count_inside_the_window():
    a = loop.arrivals(700.0, 20.0, BIG)
    b = loop.arrivals(700.0, 20.0, BIG + 1)
    assert len(a) == len(b) == 14000
    assert np.all(np.diff(a) > 0) and 0 < a[0] and a[-1] < 20.0
    assert np.array_equal(a, loop.arrivals(700.0, 20.0, BIG))
    gaps = np.diff(a)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.05)
