"""Fast-tier `deprecations` check (CI): the legacy `memory.search` /
`memory.distributed_search` shims emit a DeprecationWarning EXACTLY once
per process per function, and return bit-identical results to the unified
`RetrievalEngine.search(store, queries, SearchRequest)` API.

The shim calls are jitted (eager shard_map retraces per op and costs ~10s
per call on this suite's CI budget); the warning fires at TRACE time, so
the repeat call uses a different query batch size to force a retrace --
an unguarded shim would warn again there.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import memory as mem
from repro.core.avss import SearchConfig
from repro.core.memory import MemoryConfig
from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
from repro.launch.mesh import make_mesh


@pytest.fixture()
def toy():
    cfg = MemoryConfig(capacity=16, dim=8,
                       search=SearchConfig("mtmc", cl=4, mode="avss",
                                           use_kernel="ref"))
    vecs = jax.random.normal(jax.random.PRNGKey(0), (12, cfg.dim))
    labs = jnp.arange(12, dtype=jnp.int32) % 4
    state = mem.init_memory(cfg)
    state = mem.calibrate(state, vecs, cfg)
    state = mem.write(state, vecs, labs, cfg)
    q = vecs[:3] + 0.02
    return cfg, state, q


def _deprecations(records):
    return [w for w in records if issubclass(w.category, DeprecationWarning)
            and "repro.core.memory" in str(w.message)]


def test_search_shim_warns_once_and_is_bit_identical(toy):
    cfg, state, q = toy
    mem._WARNED.discard("search")
    f_full = jax.jit(lambda s, qq: mem.search(s, qq, cfg))
    f_tp = jax.jit(lambda s, qq: mem.search(s, qq, cfg, two_phase=True,
                                            k=4))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old_full = f_full(state, q)
        old_tp = f_tp(state, q)
        f_tp(state, q[:2])              # retrace: must NOT warn again
    assert len(_deprecations(rec)) == 1, [str(w.message) for w in rec]

    eng = RetrievalEngine(cfg.search)
    store = MemoryStore.from_state(state, cfg)
    new_full = jax.jit(lambda st, qq: eng.search(
        st, qq, SearchRequest(mode="full")))(store, q)
    new_tp = jax.jit(lambda st, qq: eng.search(
        st, qq, SearchRequest(mode="two_phase", k=4)))(store, q)
    for key in ("votes", "dist", "labels"):
        np.testing.assert_array_equal(np.asarray(old_full[key]),
                                      np.asarray(getattr(new_full, key)),
                                      err_msg=f"full/{key}")
    for key in ("votes", "dist", "indices", "labels"):
        np.testing.assert_array_equal(np.asarray(old_tp[key]),
                                      np.asarray(getattr(new_tp, key)),
                                      err_msg=f"two_phase/{key}")
    # predict agrees across the result types too
    np.testing.assert_array_equal(np.asarray(mem.predict(old_tp)),
                                  np.asarray(new_tp.predict()))


def test_distributed_shim_warns_once_and_is_bit_identical(toy):
    cfg, state, q = toy
    mesh = make_mesh((1,), ("data",))
    mem._WARNED.discard("distributed_search")
    f_old = jax.jit(lambda s, qq: mem.distributed_search(
        s, qq, cfg, mesh, axes=("data",), k=4))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        with mesh:
            old = f_old(state, q)
            f_old(state, q[:2])         # retrace: must NOT warn again
    assert len(_deprecations(rec)) == 1, [str(w.message) for w in rec]

    eng = RetrievalEngine(cfg.search)
    sstore = MemoryStore.from_state(state, cfg).shard(mesh, ("data",))
    with mesh:
        new = jax.jit(lambda st, qq: eng.search(
            st, qq, SearchRequest(mode="two_phase", k=4)))(sstore, q)
    for key in ("votes", "dist", "indices", "labels"):
        np.testing.assert_array_equal(np.asarray(old[key]),
                                      np.asarray(getattr(new, key)),
                                      err_msg=key)
