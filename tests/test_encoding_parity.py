"""Encoding coverage through the ENGINE paths (satellite of ISSUE 5).

The baseline encodings (B4E, B4WE, SRE) were configurable but effectively
untested beyond the raw encode/decode rules: this file runs each of them
through `engine.search` across the ref/mxu/fused backends and a sharded
store, asserting bit-parity, plus the paper-Table-1 `levels`/`words`
accounting. (Separate from tests/test_encodings.py, which module-skips
without hypothesis.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.avss import SearchConfig
from repro.core.encodings import CELL_STATES, make_encoding
from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
from repro.launch.mesh import make_mesh

# (name, cl, paper-Table-1 levels, words per dimension)
TABLE1 = [
    ("mtmc", 8, 3 * 8 + 1, 8),
    ("mtmc", 32, 97, 32),
    ("b4e", 3, CELL_STATES**3, 3),
    ("b4we", 2, CELL_STATES**2, (CELL_STATES**2 - 1) // 3),
    ("sre", 4, CELL_STATES, 4),
]


@pytest.mark.parametrize("name,cl,levels,words", TABLE1)
def test_levels_and_words_match_paper_table1(name, cl, levels, words):
    enc = make_encoding(name, cl)
    assert enc.levels == levels
    assert enc.length == words
    # every code word must fit one MLC cell
    v = jnp.arange(enc.levels)
    codes = np.asarray(enc.encode(v))
    assert codes.min() >= 0 and codes.max() <= CELL_STATES - 1
    # encode/decode round-trips every representable level
    np.testing.assert_array_equal(np.asarray(enc.decode(jnp.asarray(codes))),
                                  np.asarray(v))


ENGINE_ENCODINGS = [("mtmc", 8), ("b4e", 3), ("b4we", 2), ("sre", 4)]


def _store_and_queries(name, cl, n=48, d=16, b=5):
    cfg = SearchConfig(name, cl=cl, mode="avss", use_kernel="ref")
    sv = jax.random.randint(jax.random.PRNGKey(0), (n, d), 0,
                            cfg.enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(1), (b, d), 0, 4)
    labels = jnp.arange(n, dtype=jnp.int32) % 7
    return cfg, MemoryStore.from_quantized(sv, labels, cfg), qv


@pytest.mark.slow  # kernel-backend compile matrix: full tier
@pytest.mark.parametrize("name,cl", ENGINE_ENCODINGS)
@pytest.mark.parametrize("mode", ["two_phase", "ideal"])
def test_engine_backends_bit_identical_per_encoding(name, cl, mode):
    """ref / mxu / fused backends agree bitwise for every encoding, in
    both serving modes (votes, distances, candidate order, labels)."""
    cfg, store, qv = _store_and_queries(name, cl)
    req = SearchRequest(mode=mode, k=12)
    ref = RetrievalEngine(cfg, backend="ref").search(store, qv, req)
    for backend in ("mxu", "fused"):
        got = RetrievalEngine(cfg, backend=backend).search(store, qv, req)
        for field in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, field)),
                np.asarray(getattr(got, field)),
                err_msg=f"{name}/{mode}/{backend}/{field}")


@pytest.mark.slow  # shard_map + fused-kernel compile matrix: full tier
@pytest.mark.parametrize("name,cl", ENGINE_ENCODINGS)
def test_sharded_store_bit_identical_per_encoding(name, cl):
    """A sharded store (ragged split included: 48 rows never divide a
    5-shard... here 1-dev mesh keeps the fast tier fast; the multi-device
    subprocess sweep lives in tests/test_engine.py) serves every encoding
    bit-identically to the unsharded search, ref and fused shortlists."""
    cfg, store, qv = _store_and_queries(name, cl)
    mesh = make_mesh((1,), ("data",))
    sharded = store.shard(mesh)
    for backend in ("ref", "fused"):
        req = SearchRequest(mode="two_phase", k=12, backend=backend)
        want = RetrievalEngine(cfg, backend="ref").search(store, qv, req)
        got = RetrievalEngine(cfg).search(sharded, qv, req)
        for field in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(
                np.asarray(getattr(want, field)),
                np.asarray(getattr(got, field)),
                err_msg=f"{name}/sharded/{backend}/{field}")


@pytest.mark.parametrize("name,cl", ENGINE_ENCODINGS)
def test_episode_votes_parity_per_encoding(name, cl):
    """The train/serve parity contract holds for the baseline encodings
    too (their identity-STE path still forwards the exact hard encode)."""
    cfg = SearchConfig(name, cl=cl, mode="avss", use_kernel="ref")
    eng = RetrievalEngine(cfg)
    s = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(2), (10, 12)))
    q = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(3), (4, 12)))
    ep = eng.episode_votes(q, s, noisy=False)
    from repro.core.memory import MemoryConfig
    mcfg = MemoryConfig(capacity=10, dim=12, search=cfg)
    store = MemoryStore.create(mcfg).calibrate(
        jnp.concatenate([s.ravel(), q.ravel()])).write(
            s, jnp.arange(10, dtype=jnp.int32))
    res = eng.search(store, q, SearchRequest(mode="full", noisy=False))
    np.testing.assert_array_equal(np.asarray(ep["votes"]),
                                  np.asarray(res.votes))
    np.testing.assert_array_equal(np.asarray(ep["dist"]),
                                  np.asarray(res.dist))
