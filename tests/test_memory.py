"""MCAM external memory module: write/search/predict + distributed search."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import memory as mem
from repro.core.avss import SearchConfig
from repro.core.memory import MemoryConfig
from repro.launch.mesh import make_mesh

# Legacy-API suite: the deprecation shims legitimately fire here, so the
# suite-wide promotion to errors (tests/conftest.py) is scoped back.
pytestmark = pytest.mark.filterwarnings(
    "default:repro\\.core\\.memory:DeprecationWarning")


def _toy_memory(n_classes=6, per_class=8, dim=24, key=0):
    cfg = MemoryConfig(capacity=128, dim=dim,
                       search=SearchConfig(encoding="mtmc", cl=8,
                                           mode="avss", use_kernel="ref"))
    centers = jax.random.normal(jax.random.PRNGKey(key), (n_classes, dim)) * 2
    ks = jax.random.split(jax.random.PRNGKey(key + 1), n_classes)
    vecs, labels = [], []
    for c in range(n_classes):
        vecs.append(centers[c] + 0.2 * jax.random.normal(ks[c],
                                                         (per_class, dim)))
        labels += [c] * per_class
    vecs = jnp.concatenate(vecs)
    labels = jnp.asarray(labels, jnp.int32)
    state = mem.init_memory(cfg)
    state = mem.calibrate(state, vecs, cfg)
    state = mem.write(state, vecs, labels, cfg)
    return cfg, state, centers


@pytest.mark.slow
def test_write_and_1nn_predict():
    cfg, state, centers = _toy_memory()
    queries = centers + 0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                                centers.shape)
    res = mem.search(state, queries, cfg)
    pred = mem.predict(res)
    np.testing.assert_array_equal(np.asarray(pred), np.arange(6))


@pytest.mark.slow
def test_two_phase_predict_matches():
    cfg, state, centers = _toy_memory()
    queries = centers + 0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                                centers.shape)
    res = mem.search(state, queries, cfg, two_phase=True, k=16)
    pred = mem.predict(res)
    np.testing.assert_array_equal(np.asarray(pred), np.arange(6))


@pytest.mark.slow
def test_unwritten_slots_masked():
    cfg, state, _ = _toy_memory(per_class=2)  # 12 of 128 slots used
    q = jax.random.normal(jax.random.PRNGKey(3), (4, cfg.dim))
    res = mem.search(state, q, cfg)
    votes = np.asarray(res["votes"])
    assert np.isneginf(votes[:, int(state["size"]):]).all()


def test_ring_buffer_overwrite():
    cfg = MemoryConfig(capacity=16, dim=8,
                       search=SearchConfig(encoding="mtmc", cl=4,
                                           mode="avss", use_kernel="ref"))
    state = mem.init_memory(cfg)
    v1 = jnp.ones((16, 8))
    state = mem.calibrate(state, v1, cfg)
    state = mem.write(state, v1, jnp.zeros((16,), jnp.int32), cfg)
    v2 = -jnp.ones((8, 8))
    state = mem.write(state, v2, jnp.ones((8,), jnp.int32), cfg)
    labels = np.asarray(state["labels"])
    assert (labels[:8] == 1).all() and (labels[8:] == 0).all()


@pytest.mark.slow
def test_distributed_search_matches_local():
    cfg, state, centers = _toy_memory(dim=24)
    mesh = make_mesh((1, 1), ("data", "model"))
    sstate = mem.shard_state(state, mesh, ("data", "model"))
    q = centers + 0.05 * jax.random.normal(jax.random.PRNGKey(5),
                                           centers.shape)
    with mesh:
        res = mem.distributed_search(sstate, q, cfg, mesh, k=8)
    # top-1 label should match the local exact ideal-distance search
    pred = np.asarray(res["labels"])[:, 0]
    np.testing.assert_array_equal(pred, np.arange(6))
