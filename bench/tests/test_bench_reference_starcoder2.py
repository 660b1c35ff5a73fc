"""The plain StarCoder2 / kNN-LM reference against hand-worked cases: the
blocked windowed attention is the naive masked softmax, the rotary
embedding is a rotation, the mixture puts the vote softmax on the stored
tokens, and the KL of a distribution with itself is zero."""

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.reference import starcoder2 as ref  # noqa: E402


def naive_attention(q, k, v, window):
    S, H, D = q.shape
    G = H // k.shape[1]
    out = np.zeros_like(q)
    for i in range(S):
        for h in range(H):
            js = [j for j in range(i + 1) if not window or j > i - window]
            s = np.array([q[i, h] @ k[j, h // G] for j in js]) / np.sqrt(D)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, h] = sum(pj * v[j, h // G] for pj, j in zip(p, js))
    return out


def test_blocked_window_attention_is_the_masked_softmax():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(16, 4, 8)).astype(np.float32)
    k = rng.normal(size=(16, 2, 8)).astype(np.float32)
    v = rng.normal(size=(16, 2, 8)).astype(np.float32)
    for window in (0, 5, 16):
        got = ref._attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             window, 4)
        np.testing.assert_allclose(np.asarray(got),
                                   naive_attention(q, k, v, window),
                                   rtol=1e-5, atol=1e-5)


def test_rope_is_a_rotation_and_identity_at_position_zero():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 8))
    y = ref._rope(x, jnp.arange(3), 10000.0)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x[0]))
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


def test_mixture_puts_the_vote_softmax_on_the_stored_tokens():
    lm = jnp.log(jnp.full((1, 4), 0.25))
    votes = jnp.array([[10.0, 0.0, 5.0]])
    labels = jnp.array([[2, 2, -1]])                 # the last is invalid
    got = np.exp(np.asarray(ref.knn_mixture(lm, votes, labels, 0.5, 10.0)))
    # both valid candidates vote for token 2: p_kNN puts all its mass there
    np.testing.assert_allclose(got[0], [0.125, 0.125, 0.625, 0.125],
                               rtol=1e-6)
    votes = jnp.array([[10.0, 0.0]])
    labels = jnp.array([[1, 3]])
    w = np.exp(1.0) / (np.exp(1.0) + 1.0)
    got = np.exp(np.asarray(ref.knn_mixture(jnp.log(jnp.full((1, 4), 0.25)),
                                            votes, labels, 0.5, 10.0)))
    np.testing.assert_allclose(got[0], [0.125, 0.125 + 0.5 * w, 0.125,
                                        0.125 + 0.5 * (1 - w)], rtol=1e-6)


def test_kl_of_a_distribution_with_itself_is_zero():
    lp = jax.nn.log_softmax(jax.random.normal(jax.random.PRNGKey(1), (3, 9)))
    assert float(np.abs(np.asarray(ref.kl(lp, lp))).max()) < 1e-7
    flat = jnp.log(jnp.full((1, 2), 0.5))
    peaked = jnp.log(jnp.array([[0.9, 0.1]]))
    np.testing.assert_allclose(float(ref.kl(flat, peaked)[0]),
                               0.5 * np.log(0.5 / 0.9)
                               + 0.5 * np.log(0.5 / 0.1), rtol=1e-6)
