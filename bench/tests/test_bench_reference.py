"""The plain AVSS reference against hand-worked cases: the MTMC code words
give the ideal distance, the thresholds rise, the best candidate follows
the paper's rule, and the reordered float32 sum is a sum."""

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax.numpy as jnp  # noqa: E402

from bench.reference import avss as ref  # noqa: E402


def test_code_words_give_the_ideal_distance():
    cl = 4
    values = jnp.arange(3 * cl + 1, dtype=jnp.int32)[:, None]    # every level
    w = ref.words(values, cl)[:, 0, :]                           # (13, cl)
    for q in range(ref.CELL_STATES):
        got = np.abs(q - np.asarray(w)).sum(-1)
        assert list(got) == [abs(cl * q - v) for v in range(3 * cl + 1)]


def test_thresholds_rise_and_count():
    mcam = {"string_len": 24, "rho": 8.0, "n_thresholds": 8}
    th = ref.thresholds(mcam)
    assert th.shape == (8,) and np.all(np.diff(th) > 0)
    assert th[-1] < 1.0                    # below a string with no mismatch


def test_best_candidate_by_votes_then_distance_then_order():
    votes = jnp.array([[3.0, 5.0, 5.0, 1.0],
                       [2.0, 2.0, 2.0, 2.0],
                       [-jnp.inf, 0.0, 0.0, 0.0]])
    dist = jnp.array([[0.0, 9.0, 7.0, 1.0],
                      [4.0, 3.0, 3.0, 5.0],
                      [0.0, 2.0, 1.0, 1.0]])
    assert list(np.asarray(ref.best(votes, dist))) == [2, 1, 2]


def test_pairwise_sum_is_a_sum():
    x = jnp.arange(2 * 24, dtype=jnp.float32).reshape(2, 24)
    assert np.array_equal(np.asarray(ref._pairwise_sum(x)),
                          np.asarray(x.sum(-1)))
