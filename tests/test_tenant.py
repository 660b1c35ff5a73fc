"""Multi-tenant serving parity suite (repro/engine/tenant.py, PR 9).

The tentpole's contract: `RetrievalEngine.search_tenants` over a stacked
`TenantStore` is BIT-IDENTICAL per tenant to solo `engine.search` over
each tenant's own store -- on every mode x backend route, phase 1 in one
block or in query chunks, including the noisy paths (whose counter-hash
noise is keyed on the query's rank WITHIN its tenant group, not its batch
position) -- and
ONE jitted search program serves any tenant count (one cache entry per
distinct batch shape, none per tenant, none per write).

Fixture geometry (mirrors the shard-parity tests): 5 tenants with ragged
capacities, one tenant left EMPTY (create + calibrate, no writes; its
queries must predict the -1 sentinel), one tie-heavy tenant (duplicated
rows force (distance, index) lexicographic rank to carry the parity),
and masked label -1 rows placed to land inside the top-k.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.avss import SearchConfig
from repro.core.memory import MemoryConfig
from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest,
                          TenantStore, tenant_query_rank)

CAPS = (12, 7, 16, 5, 9)
EMPTY = 3        # tenant created+calibrated but never written
TIE_HEAVY = 2    # tenant whose rows repeat 4x (lexicographic tie-break)
DIM = 20
K = 4


def _cfg():
    return SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")


@pytest.fixture(scope="module")
def tenant_fixture():
    """(stores, tstore, queries, tenant_ids): the 5-tenant stack above
    plus an interleaved query batch hitting every tenant (with repeats,
    so the per-tenant noise rank differs from the batch position)."""
    cfg = _cfg()
    rng = np.random.default_rng(0)
    stores = []
    for i, c in enumerate(CAPS):
        if i == EMPTY:
            mc = MemoryConfig(capacity=c, dim=DIM, search=cfg)
            sample = jnp.asarray(rng.normal(size=(8, DIM)), jnp.float32)
            stores.append(MemoryStore.create(mc).calibrate(sample))
            continue
        v = rng.integers(0, cfg.enc.levels, size=(c, DIM))
        if i == TIE_HEAVY:
            v = np.concatenate([v[:4]] * 4)[:c]
        lab = rng.integers(0, 5, size=(c,))
        lab[::4] = -1            # masked rows inside the top-k
        stores.append(MemoryStore.from_quantized(
            jnp.asarray(v), jnp.asarray(lab), cfg))
    tstore = TenantStore.stack(stores)
    tenant_ids = jnp.array([0, 2, 1, 0, 2, 4, 2, 3, 0, 1], jnp.int32)
    queries = jnp.asarray(rng.integers(0, 4, size=(10, DIM)), jnp.int32)
    return stores, tstore, queries, tenant_ids


def _assert_rows_equal(batched, solo, sel, width, mode):
    """Per-tenant rows of the coalesced result == the solo result, and on
    the full mode the pad columns beyond the tenant's capacity are fully
    masked (-inf votes)."""
    for leaf in ("votes", "dist", "indices", "labels"):
        b = getattr(batched, leaf)
        if b is None:           # full mode has no indices/labels
            assert getattr(solo, leaf) is None
            continue
        np.testing.assert_array_equal(
            np.asarray(b[sel][:, :width]), np.asarray(getattr(solo, leaf)),
            err_msg=f"{mode}: {leaf}")
    if mode == "full" and batched.votes.shape[1] > width:
        assert bool((batched.votes[sel][:, width:] == -jnp.inf).all())


@pytest.mark.parametrize("mode,backend,chunked", [
    *[(m, b, False) for b in ("ref", "pallas")
      for m in ("full", "two_phase", "ideal")],
    ("two_phase", "pallas", True), ("ideal", "pallas", True)])
def test_search_tenants_bit_parity(tenant_fixture, monkeypatch, mode,
                                   backend, chunked):
    stores, tstore, queries, tenant_ids = tenant_fixture
    eng = RetrievalEngine(_cfg())
    req = SearchRequest(mode=mode, k=K, backend=backend)
    if chunked:
        # phase-1 budget cut to 3 queries' (3, Np) block: the tenant batch
        # of 10 is mapped in chunks of 3 (the last one ragged)
        from repro.engine import sharded
        monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES",
                            3 * tstore.n_pad * 4)
    res = eng.search_tenants(tstore, queries, tenant_ids, req)
    tids = np.asarray(tenant_ids)
    for t in range(len(CAPS)):
        sel = np.where(tids == t)[0]
        if not len(sel):
            continue
        solo = eng.search(stores[t], queries[jnp.asarray(sel)], req)
        width = CAPS[t] if mode == "full" else min(K, CAPS[t])
        _assert_rows_equal(res, solo, sel, width, mode)


def test_empty_tenant_predicts_sentinel(tenant_fixture):
    _, tstore, queries, tenant_ids = tenant_fixture
    eng = RetrievalEngine(_cfg())
    res = eng.search_tenants(tstore, queries, tenant_ids,
                             SearchRequest(mode="two_phase", k=K))
    preds = np.asarray(res.predict())
    empty = np.asarray(tenant_ids) == EMPTY
    assert (preds[empty] == -1).all()
    assert (preds[~empty] >= -1).all()      # others may still abstain


def test_k_beyond_tenant_capacity_pads_masked(tenant_fixture):
    """k larger than the smallest tenant's capacity: the extra shortlist
    columns must be masked pads (-inf votes, label -1) -- never rows
    leaked from another tenant."""
    stores, tstore, queries, tenant_ids = tenant_fixture
    eng = RetrievalEngine(_cfg())
    k = min(CAPS) + 2
    res = eng.search_tenants(tstore, queries, tenant_ids,
                             SearchRequest(mode="two_phase", k=k))
    tids = np.asarray(tenant_ids)
    for t in (np.argmin(CAPS), EMPTY):
        sel = np.where(tids == t)[0]
        over = res.labels[sel][:, CAPS[t]:] if t != EMPTY else \
            res.labels[sel]
        assert bool((over == -1).all())
        votes_over = res.votes[sel][:, CAPS[t]:] if t != EMPTY else \
            res.votes[sel]
        assert bool((votes_over == -jnp.inf).all())


def test_noiseless_parity(tenant_fixture):
    """noisy=False route (no counter hash at all) stays bit-identical."""
    stores, tstore, queries, tenant_ids = tenant_fixture
    eng = RetrievalEngine(_cfg())
    req = SearchRequest(mode="two_phase", k=K, noisy=False)
    res = eng.search_tenants(tstore, queries, tenant_ids, req)
    tids = np.asarray(tenant_ids)
    for t in range(len(CAPS)):
        sel = np.where(tids == t)[0]
        solo = eng.search(stores[t], queries[jnp.asarray(sel)], req)
        _assert_rows_equal(res, solo, sel, min(K, CAPS[t]), "two_phase")


def test_tenant_query_rank():
    ranks = tenant_query_rank(jnp.array([0, 2, 1, 0, 2, 4, 2, 3, 0, 1]))
    assert ranks.tolist() == [0, 0, 0, 1, 1, 0, 2, 0, 2, 1]
    assert ranks.dtype == jnp.uint32


def test_stack_round_trip(tenant_fixture):
    stores, tstore, _, _ = tenant_fixture
    assert tstore.n_tenants == len(CAPS)
    assert tstore.n_pad == max(CAPS)
    assert tstore.capacities == CAPS
    for i, s in enumerate(stores):
        t = tstore.tenant(i)
        for leaf in ("values", "proj", "s_grid", "labels", "size", "lo",
                     "hi"):
            np.testing.assert_array_equal(np.asarray(getattr(t, leaf)),
                                          np.asarray(getattr(s, leaf)),
                                          err_msg=f"tenant {i}: {leaf}")
        assert t.cfg == s.cfg and t.calibrated == s.calibrated


def test_stack_rejects_mismatched_stores():
    cfg = _cfg()
    other = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="ref")
    a = MemoryStore.from_quantized(jnp.zeros((2, 8), jnp.int32),
                                   jnp.array([0, 1]), cfg)
    b = MemoryStore.from_quantized(jnp.zeros((2, 8), jnp.int32),
                                   jnp.array([0, 1]), other)
    c = MemoryStore.from_quantized(jnp.zeros((2, 6), jnp.int32),
                                   jnp.array([0, 1]), cfg)
    with pytest.raises(ValueError, match="at least one store"):
        TenantStore.stack([])
    with pytest.raises(ValueError, match="SearchConfig/dim"):
        TenantStore.stack([a, b])
    with pytest.raises(ValueError, match="SearchConfig/dim"):
        TenantStore.stack([a, c])


def test_write_at_matches_solo_write(tenant_fixture):
    stores, tstore, _, _ = tenant_fixture
    rng = np.random.default_rng(7)
    vecs = jnp.asarray(rng.normal(size=(3, DIM)), jnp.float32)
    labs = jnp.array([9, 8, 7])
    t2 = tstore.write_at(EMPTY, vecs, labs).tenant(EMPTY)
    solo = stores[EMPTY].write(vecs, labs)
    for leaf in ("values", "proj", "s_grid", "labels", "size"):
        np.testing.assert_array_equal(np.asarray(getattr(t2, leaf)),
                                      np.asarray(getattr(solo, leaf)),
                                      err_msg=leaf)


def test_write_at_guards(tenant_fixture):
    stores, tstore, _, _ = tenant_fixture
    vecs = jnp.zeros((2, DIM), jnp.float32)
    labs = jnp.array([1, 2])
    # never-calibrated tenant (from_quantized stores): concrete id raises
    with pytest.raises(ValueError, match="never-calibrated"):
        tstore.write_at(0, vecs, labs)
    # traced id on a partially-calibrated stack raises at trace time
    with pytest.raises(ValueError, match="never-calibrated"):
        jax.jit(lambda ts, t: ts.write_at(t, vecs, labs))(
            tstore, jnp.asarray(EMPTY, jnp.int32))
    # oversize batch on the concrete path
    calibrated = TenantStore.stack([stores[EMPTY], stores[EMPTY]])
    with pytest.raises(AssertionError, match="exceeds"):
        calibrated.write_at(0, jnp.zeros((CAPS[EMPTY] + 1, DIM)),
                            jnp.zeros((CAPS[EMPTY] + 1,), jnp.int32))


def test_single_jit_entry_per_tenant_count(tenant_fixture):
    """ONE compiled search program per batch shape: for each tenant count
    T, repeated calls with fresh stores/queries/tenant_ids of the same
    shape add exactly one cache entry -- no per-tenant or per-write
    retrace. The same mapping feeds the single_jit_entry_across_tenants
    contract invariant (analysis/registry.py)."""
    from functools import partial

    from repro.analysis import hlo_contracts as hc

    cfg = _cfg()
    eng = RetrievalEngine(cfg)
    req = SearchRequest(mode="two_phase", k=2)

    @partial(jax.jit, static_argnames=("req",))
    def f(ts, q, tids, req):
        return eng.search_tenants(ts, q, tids, req).votes

    def mk_stack(T, seed):
        r = np.random.default_rng(seed)
        return TenantStore.stack([
            MemoryStore.from_quantized(
                jnp.asarray(r.integers(0, cfg.enc.levels, size=(6, 8))),
                jnp.asarray(r.integers(0, 3, size=(6,))), cfg)
            for _ in range(T)])

    entries = {}
    for T in (1, 5, 64):
        before = f._cache_size()
        for trial in range(3):
            r = np.random.default_rng(100 * T + trial)
            ts = mk_stack(T, seed=T + trial)
            q = jnp.asarray(r.integers(0, 4, size=(4, 8)), jnp.int32)
            tids = jnp.asarray(r.integers(0, T, size=(4,)), jnp.int32)
            f(ts, q, tids, req).block_until_ready()
        entries[T] = f._cache_size() - before
    hc.assert_single_jit_entry_across_tenants(entries)
    assert entries == {1: 1, 5: 1, 64: 1}


def test_tenant_server_coalesce_and_write():
    """The launch/serve.py coalescing shell: submit -> flush returns each
    ticket's row bit-identical to the direct coalesced call, and ring
    writes through the server never retrace the search."""
    from repro.launch.serve import TenantServer

    cfg = _cfg()
    rng = np.random.default_rng(3)
    stores = []
    for t in range(3):
        mc = MemoryConfig(capacity=6, dim=DIM, search=cfg)
        emb = jnp.asarray(rng.normal(size=(6, DIM)), jnp.float32)
        stores.append(MemoryStore.create(mc).calibrate(emb).write(
            emb, jnp.asarray(rng.integers(0, 4, size=(6,)))))
    eng = RetrievalEngine(cfg)
    req = SearchRequest(mode="two_phase", k=3)
    server = TenantServer(eng, TenantStore.stack(stores), req)

    q = jnp.asarray(rng.normal(size=(4, DIM)), jnp.float32)
    tids = [1, 0, 2, 1]
    tickets = [server.submit(t, q[i]) for i, t in enumerate(tids)]
    out = server.flush()
    direct = eng.search_tenants(server.tstore, q,
                                jnp.asarray(tids, jnp.int32), req)
    for i in tickets:
        np.testing.assert_array_equal(np.asarray(out[i].labels[0]),
                                      np.asarray(direct.labels[i]))
    entries = server.cache_entries()
    server.write(0, jnp.asarray(rng.normal(size=(2, DIM)), jnp.float32),
                 jnp.array([5, 6]))
    for i, t in enumerate(tids):
        server.submit(t, q[i])
    server.flush()
    assert server.cache_entries() == entries    # write did not retrace


def _mk_server(seed=7, n_tenants=3):
    from repro.launch.serve import TenantServer
    cfg = _cfg()
    rng = np.random.default_rng(seed)
    stores = []
    for t in range(n_tenants):
        mc = MemoryConfig(capacity=6, dim=DIM, search=cfg)
        emb = jnp.asarray(rng.normal(size=(6, DIM)), jnp.float32)
        stores.append(MemoryStore.create(mc).calibrate(emb).write(
            emb, jnp.asarray(rng.integers(0, 4, size=(6,)))))
    eng = RetrievalEngine(cfg)
    req = SearchRequest(mode="two_phase", k=3)
    return (TenantServer(eng, TenantStore.stack(stores), req), eng, req,
            rng)


def test_tenant_server_flush_empty_queue():
    """flush() with nothing queued is a no-op: returns {} and never
    touches the compiled search (no zero-row batch dispatch)."""
    server, _, _, _ = _mk_server()
    before = server.cache_entries()
    assert server.flush() == {}
    assert server.flush() == {}                # idempotent
    assert server.cache_entries() == before


def test_tenant_server_interleaved_write_between_submits():
    """A ring write to a tenant BETWEEN submits to that same tenant:
    flush() serves every queued query against the POST-write store (the
    server holds one store; submit enqueues queries, not snapshots), and
    the write does not retrace the compiled search."""
    server, eng, req, rng = _mk_server(seed=8)
    q = jnp.asarray(rng.normal(size=(3, DIM)), jnp.float32)
    t0 = server.submit(1, q[0])
    server.write(1, jnp.asarray(rng.normal(size=(2, DIM)), jnp.float32),
                 jnp.array([8, 9]))
    t1 = server.submit(1, q[1])
    t2 = server.submit(0, q[2])
    entries = server.cache_entries()
    out = server.flush()
    assert sorted(out) == [t0, t1, t2]
    assert server.cache_entries() in (entries, entries + 1)  # shape only
    direct = eng.search_tenants(server.tstore, q,
                                jnp.asarray([1, 1, 0], jnp.int32), req)
    for tk, row in ((t0, 0), (t1, 1), (t2, 2)):
        for f in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out[tk], f)[0]),
                np.asarray(getattr(direct, f)[row]), err_msg=f)


def test_tenant_server_duplicate_tenant_ticket_ordering():
    """Many queries for the SAME tenant in one flush: each ticket gets
    ITS OWN query's row back (ticket == batch row), and the per-tenant
    noise rank keys on queue order -- bit-identical to the direct
    coalesced call with the same duplicate tenant_ids batch."""
    server, eng, req, rng = _mk_server(seed=9)
    q = jnp.asarray(rng.normal(size=(5, DIM)), jnp.float32)
    tids = [2, 2, 0, 2, 2]                     # duplicates, interleaved
    tickets = [server.submit(t, q[i]) for i, t in enumerate(tids)]
    assert tickets == [0, 1, 2, 3, 4]          # tickets ARE queue order
    out = server.flush()
    assert sorted(out) == tickets
    direct = eng.search_tenants(server.tstore, q,
                                jnp.asarray(tids, jnp.int32), req)
    for tk in tickets:
        for f in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out[tk], f)[0]),
                np.asarray(getattr(direct, f)[tk]), err_msg=f"{tk}:{f}")
    # identical queries to the same tenant must still get DISTINCT noise
    # ranks (queue position), hence independent result rows exist per
    # ticket rather than one shared row object
    same = [server.submit(2, q[0]) for _ in range(3)]
    out2 = server.flush()
    assert sorted(out2) == same and len(out2) == 3
