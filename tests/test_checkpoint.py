"""Checkpointing: roundtrip, atomicity, async, GC, elastic restore."""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.launch.mesh import make_mesh


def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {"params": {"w": jax.random.normal(k, (16, 8)),
                       "b": jnp.arange(8, dtype=jnp.float32)},
            "opt": {"m": jnp.ones((16, 8), jnp.bfloat16)},
            "step": jnp.int32(7)}


def _assert_tree_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        assert la.dtype == lb.dtype


def test_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 7, t)
    out = restore(str(tmp_path), jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t))
    _assert_tree_equal(t, out)


def test_async_and_latest(tmp_path):
    t = _tree()
    th = save(str(tmp_path), 3, t, blocking=False)
    assert th is None or isinstance(th, threading.Thread)
    if th:
        th.join()
    save(str(tmp_path), 9, t)
    assert latest_step(str(tmp_path)) == 9


def test_atomicity_no_partial_dirs(tmp_path):
    save(str(tmp_path), 1, _tree())
    entries = os.listdir(tmp_path)
    assert all(not e.startswith(".tmp") for e in entries)
    # a directory without manifest is ignored
    os.makedirs(tmp_path / "step_0000000099")
    assert latest_step(str(tmp_path)) == 1


def test_gc_keeps_newest(tmp_path):
    for s in (1, 2, 3, 4, 5):
        save(str(tmp_path), s, _tree(), keep=2)
    from repro.checkpoint.ckpt import all_steps
    assert all_steps(str(tmp_path)) == [4, 5]


def test_elastic_restore_onto_sharding(tmp_path):
    """Save unsharded, restore onto a mesh sharding (elastic restart)."""
    t = _tree()
    save(str(tmp_path), 5, t)
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    shardings = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, P()), t)
    out = restore(str(tmp_path), jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t),
        shardings=shardings)
    _assert_tree_equal(t, out)
    assert out["params"]["w"].sharding == NamedSharding(mesh, P())


def test_manager_flow(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=2, keep=2)
    t = _tree()
    for step in range(1, 6):
        mgr.maybe_save(step, t)
    mgr.wait()
    assert mgr.latest_step() == 4  # steps 2 and 4 saved
    out = mgr.restore(jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t))
    _assert_tree_equal(t, out)


def test_missing_leaf_raises(tmp_path):
    save(str(tmp_path), 1, {"a": jnp.ones((3,))})
    with pytest.raises(KeyError):
        restore(str(tmp_path), {"b": jax.ShapeDtypeStruct((3,), jnp.float32)})


# ---------------------------------------------------------------------------
# MemoryStore persistence (ISSUE 5 satellite): a trained-and-written store
# round-trips through checkpoint/ckpt.py bit-identically, so a separate
# serving process can restore and search it.
# ---------------------------------------------------------------------------


def _programmed_store():
    from repro.core.avss import SearchConfig
    from repro.core.memory import MemoryConfig
    from repro.engine import MemoryStore
    cfg = MemoryConfig(capacity=12, dim=6,
                       search=SearchConfig("mtmc", cl=4, mode="avss",
                                           use_kernel="ref"))
    vecs = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(0), (9, 6)))
    labels = jnp.arange(9, dtype=jnp.int32) % 3
    store = MemoryStore.create(cfg).calibrate(vecs).write(vecs, labels)
    return cfg, vecs, store


def test_memory_store_save_restore_bit_parity(tmp_path):
    """Every persisted field (values/labels/proj/s_grid/lo/hi/size) round-
    trips exactly, the restored store is marked calibrated, and searches on
    it are bit-identical to the writer's store."""
    from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
    cfg, vecs, store = _programmed_store()
    store.save(str(tmp_path), step=5)
    restored = MemoryStore.restore(str(tmp_path), cfg)
    for field in ("values", "proj", "s_grid", "labels", "size", "lo", "hi"):
        a, b = getattr(store, field), getattr(restored, field)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=field)
        assert a.dtype == b.dtype, field
    assert restored.calibrated and int(restored.size) == 9
    # a float query exercises the restored calibration range end-to-end
    eng = RetrievalEngine(cfg.search)
    req = SearchRequest(mode="two_phase", k=6)
    want = eng.search(store, vecs[:4], req)
    got = eng.search(restored, vecs[:4], req)
    for field in ("votes", "dist", "indices", "labels"):
        np.testing.assert_array_equal(np.asarray(getattr(want, field)),
                                      np.asarray(getattr(got, field)),
                                      err_msg=field)


def test_memory_store_restore_is_calibrated_and_writable(tmp_path):
    """A restored store IS calibrated (the persisted range is the
    calibration): writing more supports to it works without re-calibrating,
    and the ring position continues from the persisted size."""
    from repro.engine import MemoryStore
    cfg, vecs, store = _programmed_store()
    store.save(str(tmp_path))
    restored = MemoryStore.restore(str(tmp_path), cfg)
    more = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(1), (2, 6)))
    grown = restored.write(more, jnp.array([5, 6], jnp.int32))
    assert int(grown.size) == 11
    np.testing.assert_array_equal(np.asarray(grown.labels[9:11]), [5, 6])
