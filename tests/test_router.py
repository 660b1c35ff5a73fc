"""Hierarchical routing suite (repro/engine/router.py, PR 10).

The tentpole's contract, part (a): `SearchRequest.nprobe=p` on a
partitioned store scores the write-time per-shard sketch with one small
matmul and dispatches phase 1/2 to the top-p shards only, and the result
is BIT-IDENTICAL to the exhaustive search restricted to the visited
shards -- same SHORTLIST_MASK_PENALTY, same (distance, index) lex merge,
two-phase votes keyed on the same GLOBAL (query, row) noise coordinates.
`nprobe=None` (and `nprobe >= n_shards`) must reproduce today's
exhaustive sharded search byte-for-byte.

The fixture is deliberately tie-heavy (every row repeated 9x across the
shard boundary) so only an exact (distance, global index) lexicographic
merge over the visited blocks can pass, and it carries masked label -1
rows that land inside the top-k.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.avss import SearchConfig
from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
from repro.engine import router as router_lib

N_SHARDS = 8
ROWS = 72          # 9 rows/shard
DIM = 20
K = 12


def _cfg(backend="ref"):
    return SearchConfig("mtmc", cl=8, mode="avss", use_kernel=backend)


@pytest.fixture(scope="module")
def routed_fixture():
    """(store_by_backend, queries): the 72-row tie-heavy partitioned store
    on each backend config, plus 5 pre-quantized queries."""
    rng = np.random.default_rng(0)
    base = rng.integers(0, 16, (8, DIM))
    vals = jnp.asarray(np.concatenate([base] * 9))           # ties galore
    labs = np.arange(ROWS) % 9
    labs[labs % 4 == 0] = -1                                 # masked rows
    labs = jnp.asarray(labs)
    q = jnp.asarray(rng.integers(0, 4, (5, DIM)))
    stores = {}
    for backend in ("ref", "pallas"):
        stores[backend] = MemoryStore.from_quantized(
            vals, labs, _cfg(backend)).shard(n_shards=N_SHARDS)
    return stores, q


def _leaves(res):
    return {f: np.asarray(getattr(res, f))
            for f in ("votes", "dist", "indices", "labels")}


@pytest.mark.parametrize("backend", ["ref", "pallas", "chunked"])
@pytest.mark.parametrize("mode", ["two_phase", "ideal"])
@pytest.mark.parametrize("nprobe", [1, 3, 5])
def test_routed_bit_identical_to_restricted_brute_force(
        routed_fixture, monkeypatch, backend, mode, nprobe):
    """Routed == exhaustive search filtered to the visited shards, per
    query, on every leaf -- including two-phase votes (global noise
    coordinates) and distances (exact integers, so equality is exact).
    `chunked`: the routed batch is mapped 2 queries at a time (the
    phase-1 budget cut to a (2, nprobe * rows) block)."""
    stores, q = routed_fixture
    store = stores["pallas" if backend == "chunked" else backend]
    eng = RetrievalEngine(store.cfg.search)

    # the reference: the FULL search ranked over all rows, then filtered
    # to the rows of the router's visited shards
    full = eng.search(store, q, SearchRequest(mode=mode, k=store.capacity))
    rows = store.capacity // N_SHARDS
    if backend == "chunked":
        from repro.engine import sharded
        monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES",
                            2 * nprobe * rows * 4)
    routed = eng.search(store, q, SearchRequest(mode=mode, k=K,
                                                nprobe=nprobe))
    scores = router_lib.route_scores(
        store.quantize_queries(q), store.sketch_sums, store.sketch_counts,
        store.cfg.search.enc)
    sids = np.asarray(router_lib.top_shards(scores, nprobe))
    got, ref = _leaves(routed), _leaves(full)
    for b in range(q.shape[0]):
        shard_of_row = ref["indices"][b] // rows
        keep = np.isin(shard_of_row, sids[b])
        for f in ("dist", "indices", "labels", "votes"):
            np.testing.assert_array_equal(
                got[f][b], ref[f][b][keep][:K],
                err_msg=f"{backend}/{mode}/nprobe={nprobe}: {f}[{b}]")


@pytest.mark.parametrize("mode", ["two_phase", "ideal"])
def test_nprobe_none_and_all_shards_byte_identical(routed_fixture, mode):
    """nprobe=None, nprobe=n_shards and nprobe>n_shards are the SAME
    exhaustive program -- byte-identical results."""
    stores, q = routed_fixture
    store = stores["pallas"]
    eng = RetrievalEngine(store.cfg.search)
    base = eng.search(store, q, SearchRequest(mode=mode, k=K))
    for p in (N_SHARDS, N_SHARDS + 3):
        alt = eng.search(store, q, SearchRequest(mode=mode, k=K, nprobe=p))
        for f, v in _leaves(base).items():
            np.testing.assert_array_equal(v, _leaves(alt)[f], err_msg=f)


def test_nprobe_on_unpartitioned_store_is_exhaustive(routed_fixture):
    """n_shards=1: any nprobe >= 1 is the plain unsharded search."""
    _, q = routed_fixture
    rng = np.random.default_rng(3)
    store = MemoryStore.from_quantized(
        jnp.asarray(rng.integers(0, 16, (24, DIM))),
        jnp.asarray(rng.integers(0, 5, (24,))), _cfg("pallas"))
    eng = RetrievalEngine(store.cfg.search)
    a = eng.search(store, q, SearchRequest(mode="two_phase", k=6))
    b = eng.search(store, q, SearchRequest(mode="two_phase", k=6, nprobe=1))
    for f, v in _leaves(a).items():
        np.testing.assert_array_equal(v, _leaves(b)[f], err_msg=f)


def test_router_prefers_the_matching_shard():
    """A query equal to one shard's class centroid routes there first."""
    cfg = _cfg("ref")
    # shard 0: rows near level 2; shard 1: rows near level 13
    vals = jnp.asarray([[2] * DIM] * 4 + [[13] * DIM] * 4)
    labs = jnp.asarray([0] * 4 + [1] * 4)
    store = MemoryStore.from_quantized(vals, labs, cfg).shard(n_shards=2)
    scores = router_lib.route_scores(
        jnp.asarray([[0] * DIM, [3] * DIM]),   # low words vs high words
        store.sketch_sums, store.sketch_counts, cfg.enc)
    sids = np.asarray(router_lib.top_shards(scores, 1))
    assert sids[0, 0] == 0 and sids[1, 0] == 1
    # ...and nprobe=1 retrieval then hits the right class
    eng = RetrievalEngine(cfg)
    res = eng.search(store, jnp.asarray([[0] * DIM, [3] * DIM]),
                     SearchRequest(mode="ideal", k=2, nprobe=1))
    assert np.asarray(res.predict()).tolist() == [0, 1]


def test_sketch_tracks_scatter_writes_through_wraparound():
    """The write-path sketch (incremental S=1 delta) equals a from-scratch
    rebuild after ring writes that overwrite and wrap."""
    cfg = _cfg("ref")
    rng = np.random.default_rng(1)
    from repro.core.memory import MemoryConfig
    mc = MemoryConfig(capacity=12, dim=DIM, search=cfg)
    sample = jnp.asarray(rng.normal(size=(8, DIM)), jnp.float32)
    store = MemoryStore.create(mc).calibrate(sample)
    for n in (5, 5, 7):                       # 17 rows > capacity: wraps
        v = jnp.asarray(rng.normal(size=(n, DIM)), jnp.float32)
        lab = jnp.asarray(rng.integers(-1, 6, (n,)))
        store = store.write(v, lab)
        want_s, want_c = router_lib.build_sketch(store.values, store.labels,
                                                 1)
        np.testing.assert_array_equal(np.asarray(store.sketch_sums),
                                      np.asarray(want_s))
        np.testing.assert_array_equal(np.asarray(store.sketch_counts),
                                      np.asarray(want_c))


def test_sketch_tracks_writes_on_partitioned_store():
    """Writes on a logically partitioned store rebuild the per-shard
    sketch exactly (full-rebuild path)."""
    rng = np.random.default_rng(2)
    from repro.core.memory import MemoryConfig
    mc = MemoryConfig(capacity=32, dim=DIM, search=_cfg("ref"))
    sample = jnp.asarray(rng.normal(size=(16, DIM)), jnp.float32)
    store = (MemoryStore.create(mc).calibrate(sample)
             .write(sample, jnp.asarray(rng.integers(0, 6, (16,))))
             .shard(n_shards=N_SHARDS))
    store = store.write(
        jnp.asarray(rng.normal(size=(6, DIM)), jnp.float32),
        jnp.asarray(rng.integers(0, 6, (6,))))
    want_s, want_c = router_lib.build_sketch(
        store.values, store.labels, N_SHARDS)
    np.testing.assert_array_equal(np.asarray(store.sketch_sums),
                                  np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(store.sketch_counts),
                                  np.asarray(want_c))


def test_request_validation():
    with pytest.raises(ValueError, match="nprobe routes the shortlist"):
        SearchRequest(mode="full", nprobe=2)
    with pytest.raises(ValueError, match="nprobe must be >= 1"):
        SearchRequest(mode="ideal", nprobe=0)


def test_host_residency_must_go_through_the_pager(routed_fixture):
    stores, q = routed_fixture
    host = stores["ref"]._unpad().shard(n_shards=4, residency="host")
    eng = RetrievalEngine(host.cfg.search)
    with pytest.raises(ValueError, match="ShardPager"):
        eng.search(host, q, SearchRequest(mode="ideal", k=4, nprobe=2))


def test_empty_shard_never_outranks_real_rows():
    """A shard of pure label -1 padding carries the mask penalty in the
    sketch and is routed LAST."""
    cfg = _cfg("ref")
    rng = np.random.default_rng(4)
    vals = jnp.asarray(rng.integers(0, 16, (12, DIM)))
    labs = jnp.asarray([3] * 6 + [-1] * 6)    # shard 1 is all masked
    store = MemoryStore.from_quantized(vals, labs, cfg).shard(n_shards=2)
    scores = router_lib.route_scores(
        jnp.asarray(rng.integers(0, 4, (3, DIM))),
        store.sketch_sums, store.sketch_counts, cfg.enc)
    sids = np.asarray(router_lib.top_shards(scores, 1))
    assert (sids == 0).all()
