"""Host-paging suite (repro/engine/pager.py, PR 10).

The tentpole's contract, part (b): a partitioned store with
`residency="host"` keeps its row blocks in host memory; `ShardPager`
pages the router's top-nprobe shards into a small LRU working set of
device slot tables and runs the SAME jitted routed-block search the
device-resident path uses -- so every paged search is bit-identical to
`RetrievalEngine.search(device_twin, q, request)` with the same nprobe.
Steady-state paging must be clean under `jax.transfer_guard("disallow")`
(all host<->device movement is explicit `device_put` / `device_get`),
and a paged store round-trips through save/restore bit-identically.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.avss import SearchConfig
from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
from repro.engine.pager import ShardPager

N, DIM, S = 144, 12, 8


def _cfg(backend="pallas"):
    return SearchConfig("mtmc", cl=8, mode="avss", use_kernel=backend)


@pytest.fixture(scope="module")
def paged_fixture():
    """(host_store, device_twin, engine, queries): one partitioned store
    in both residencies, built from the same rows (with masked labels)."""
    cfg = _cfg()
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.integers(0, 16, (N, DIM)))
    labs = np.arange(N) % 9
    labs[labs % 4 == 3] = -1
    store = MemoryStore.from_quantized(vals, jnp.asarray(labs), cfg)
    q = jnp.asarray(rng.integers(0, 4, (5, DIM)))
    return (store.shard(n_shards=S, residency="host"),
            store.shard(n_shards=S), RetrievalEngine(cfg), q)


def _assert_equal(a, b, ctx=""):
    for f in ("votes", "dist", "indices", "labels"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{ctx}: {f}")


@pytest.mark.parametrize("mode", ["two_phase", "ideal"])
@pytest.mark.parametrize("nprobe", [1, 2, 3])
def test_paged_search_bit_identical_to_device_twin(paged_fixture, mode,
                                                   nprobe):
    host, dev, eng, q = paged_fixture
    pager = ShardPager(host, eng, slots=S)
    req = SearchRequest(mode=mode, k=10, nprobe=nprobe)
    _assert_equal(pager.search(q, req), eng.search(dev, q, req),
                  f"{mode}/nprobe={nprobe}")


def test_steady_state_is_transfer_guard_clean(paged_fixture):
    """After the warm-up call (compilation embeds LUT constants), every
    paged search -- including ones that page NEW shards in -- runs under
    jax.transfer_guard('disallow')."""
    host, dev, eng, q = paged_fixture
    pager = ShardPager(host, eng, slots=4)
    req = SearchRequest(mode="two_phase", k=8, nprobe=2)
    q1, req3 = q[:1], SearchRequest(mode="two_phase", k=8, nprobe=3)
    pager.search(q, req)                       # warm-up: compile both
    pager.search(q1, req3)                     # (batch, request) combos
    # evict everything the warm-ups left resident, so the guarded
    # searches below must page their shards back in
    pager.ensure([s for s in range(S) if s not in pager.resident()][:4])
    before = pager.pages_in
    with jax.transfer_guard("disallow"):
        res = pager.search(q, req)
        res2 = pager.search(q1, req3)
    assert pager.pages_in > before             # paging DID happen guarded
    _assert_equal(res, eng.search(dev, q, req), "guarded")
    res2.votes.block_until_ready()


def test_lru_eviction_and_warm_hits(paged_fixture):
    """2 slots, single-query working sets: shards page in and out through
    eviction with per-search parity, repeats are warm hits (no paging),
    and residency never exceeds the slot count."""
    host, dev, eng, _ = paged_fixture
    rng = np.random.default_rng(1)
    pager = ShardPager(host, eng, slots=2, prefetch=False)
    req = SearchRequest(mode="two_phase", k=6, nprobe=1)
    queries = [jnp.asarray(rng.integers(0, 4, (1, DIM))) for _ in range(8)]
    seen = set()
    for q1 in queries:
        _assert_equal(pager.search(q1, req), eng.search(dev, q1, req))
        assert len(pager.resident()) <= 2
        seen.update(pager.resident())
    assert len(seen) > 2, "fixture never exercised eviction"
    before = pager.pages_in
    pager.search(queries[-1], req)             # warm hit
    assert pager.pages_in == before


def test_prefetch_stages_a_spare_shard(paged_fixture):
    """With head-room, the (nprobe+1)-th-best shard is staged after the
    search, and consuming it later costs no host->device block copy at
    ensure() time beyond the install."""
    host, _, eng, q = paged_fixture
    pager = ShardPager(host, eng, slots=4, prefetch=True)
    pager.search(q[:1], SearchRequest(mode="ideal", k=6, nprobe=2))
    assert len(pager._staged) == 1             # double-buffer in flight
    staged = next(iter(pager._staged))
    assert staged not in pager.resident()
    pager.ensure([staged])                     # consume the staged copy
    assert staged in pager.resident() and not pager._staged


def test_batch_union_exceeding_slots_raises(paged_fixture):
    host, _, eng, q = paged_fixture
    pager = ShardPager(host, eng, slots=2)
    with pytest.raises(ValueError, match="device slots"):
        pager.search(q, SearchRequest(mode="ideal", k=6, nprobe=2))


def test_constructor_validation(paged_fixture):
    host, dev, eng, _ = paged_fixture
    with pytest.raises(ValueError, match="slots"):
        ShardPager(host, eng, slots=S + 1)
    unpartitioned = host._unpad()
    with pytest.raises(ValueError, match="partitioned"):
        ShardPager(unpartitioned, eng)


def test_nprobe_required_and_bounded(paged_fixture):
    host, _, eng, q = paged_fixture
    pager = ShardPager(host, eng, slots=4)
    with pytest.raises(ValueError, match="nprobe"):
        pager.search(q, SearchRequest(mode="ideal", k=4))     # no nprobe
    with pytest.raises(ValueError, match="nprobe"):
        pager.search(q, SearchRequest(mode="ideal", k=4, nprobe=S + 1))


def test_paged_store_save_restore_bit_identical(paged_fixture):
    """save() -> restore() -> re-shard(residency='host') reproduces every
    leaf (sketch included -- rebuilt deterministically) and every paged
    search result bit-for-bit."""
    host, _, eng, q = paged_fixture
    with tempfile.TemporaryDirectory() as td:
        host.save(td, 0)
        back = MemoryStore.restore(td, host.cfg).shard(
            n_shards=S, residency="host")
    for f in ("values", "proj", "s_grid", "labels", "sketch_sums",
              "sketch_counts", "lo", "hi", "size"):
        np.testing.assert_array_equal(np.asarray(getattr(host, f)),
                                      np.asarray(getattr(back, f)),
                                      err_msg=f)
    assert back.residency == "host" and back.n_shards == S
    req = SearchRequest(mode="two_phase", k=10, nprobe=2)
    _assert_equal(ShardPager(host, eng, slots=S).search(q, req),
                  ShardPager(back, eng, slots=S).search(q, req))
