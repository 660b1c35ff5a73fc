"""Property-based multi-tenant bit-parity (repro/engine/tenant.py, PR 9).

Random sweeps over (n_tenants, ragged capacity lists, k, mode, masked
rows, tie-heavy pools, query interleavings) pin two contracts the
deterministic twins in tests/test_tenant.py pin only pointwise:

* stack -> search parity: `search_tenants` over the stacked store equals
  per-tenant solo `engine.search` row-for-row (exact, including the
  rank-keyed noise coordinates and the (distance, index) lexicographic
  order under duplicated rows);
* stack -> tenant round-trip: `stack(stores).tenant(i)` reproduces
  `stores[i]` leaf-for-leaf under ANY ragged capacity list.

Skip-clean without hypothesis (it is not in the pinned environment; the
deterministic edge-case twins live in tests/test_tenant.py).
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

import jax.numpy as jnp                                        # noqa: E402
from hypothesis import HealthCheck, example, given, settings   # noqa: E402
from hypothesis import strategies as st                        # noqa: E402

from repro.core.avss import SearchConfig                       # noqa: E402
from repro.engine import (MemoryStore, RetrievalEngine,        # noqa: E402
                          SearchRequest, TenantStore)

CFG = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
DIM = 10


def _stores(caps, masked, ties, seed):
    rng = np.random.default_rng(seed)
    out = []
    for c in caps:
        pool = rng.integers(0, CFG.enc.levels,
                            (max(1, c // 3) if ties else c, DIM))
        v = pool[rng.integers(0, pool.shape[0], c)]
        lab = rng.integers(0, 4, size=(c,))
        if masked:
            lab[rng.random(c) < 0.4] = -1
        out.append(MemoryStore.from_quantized(jnp.asarray(v),
                                              jnp.asarray(lab), CFG))
    return out


# report_multiple_bugs=False: stop at the first failing example, so the
# pinned one below fails the test at once rather than after the others
@settings(max_examples=20, deadline=None, report_multiple_bugs=False,
          suppress_health_check=list(HealthCheck))
@given(caps=st.lists(st.integers(1, 14), min_size=1, max_size=6),
       kfrac=st.floats(0.1, 1.5),
       mode=st.sampled_from(["full", "two_phase", "ideal"]),
       backend=st.sampled_from(["ref", "pallas"]),
       masked=st.booleans(), ties=st.booleans(),
       seed=st.integers(0, 2 ** 16))
# the known tenant-parity defect (ROADMAP B1): tenant 1's only row is
# masked, and the stacked search returns the pad row's phase-1 `dist` in
# its place. Pinned so the test fails on it at once, without a shrink.
@example(caps=[2, 1], kfrac=1.0, mode="two_phase", backend="ref",
         masked=True, ties=False, seed=63)
# maximally ragged + tie-heavy + masked, k beyond the smallest capacity
@example(caps=[1, 14, 3], kfrac=1.5, mode="two_phase", backend="pallas",
         masked=True, ties=True, seed=7)
# single tenant degenerate case through the full (noisy dense) route
@example(caps=[5], kfrac=0.5, mode="full", backend="ref", masked=False,
         ties=False, seed=3)
def test_stack_search_parity_property(caps, kfrac, mode, backend, masked,
                                      ties, seed):
    rng = np.random.default_rng(seed)
    stores = _stores(caps, masked, ties, seed)
    tstore = TenantStore.stack(stores)
    k = max(1, round(kfrac * min(caps)))
    eng = RetrievalEngine(CFG)
    req = SearchRequest(mode=mode, k=k, backend=backend)

    b = int(rng.integers(1, 7))
    tids = rng.integers(0, len(caps), size=(b,))
    queries = jnp.asarray(rng.integers(0, 4, size=(b, DIM)), jnp.int32)
    res = eng.search_tenants(tstore, queries, jnp.asarray(tids, jnp.int32),
                             req)
    for t in range(len(caps)):
        sel = np.where(tids == t)[0]
        if not len(sel):
            continue
        solo = eng.search(stores[t], queries[jnp.asarray(sel)], req)
        width = caps[t] if mode == "full" else min(k, caps[t])
        for leaf in ("votes", "dist", "indices", "labels"):
            bres = getattr(res, leaf)
            if bres is None:
                assert getattr(solo, leaf) is None
                continue
            np.testing.assert_array_equal(
                np.asarray(bres[sel][:, :width]),
                np.asarray(getattr(solo, leaf)),
                err_msg=f"{mode}/{backend} tenant {t}: {leaf}")
        # columns past the tenant's own rows are masked pads, never rows
        # leaked from another tenant
        if res.votes.shape[1] > width:
            assert bool((res.votes[sel][:, width:] == -jnp.inf).all())


@settings(max_examples=20, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(caps=st.lists(st.integers(1, 20), min_size=1, max_size=8),
       masked=st.booleans(), seed=st.integers(0, 2 ** 16))
@example(caps=[20, 1, 1, 20], masked=True, seed=0)
def test_stack_tenant_round_trip_property(caps, masked, seed):
    stores = _stores(caps, masked, False, seed)
    tstore = TenantStore.stack(stores)
    assert tstore.n_pad == max(caps)
    assert tstore.capacities == tuple(caps)
    for i, s in enumerate(stores):
        t = tstore.tenant(i)
        for leaf in ("values", "proj", "s_grid", "labels", "size", "lo",
                     "hi"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t, leaf)), np.asarray(getattr(s, leaf)),
                err_msg=f"tenant {i}: {leaf}")
        assert t.cfg == s.cfg and t.calibrated == s.calibrated
        # pad rows beyond the tenant's capacity are label -1
        assert bool((tstore.labels[i, caps[i]:] == -1).all())
