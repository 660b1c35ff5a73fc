"""Phase 1 over the budget: the same dense block, ranked in query chunks.

Every shortlist site ranks its queries in one (B, N) f32 distance block
while that block fits `sharded.SHORTLIST_DENSE_MAX_BYTES`, and in chunks
of `sharded.shortlist_chunk(B, N)` queries beyond it -- the direct sites
(unsharded, per shard inside shard_map) through a `jax.lax.map` over the
padded batch, the per-query sites (routed, paged, tenant) through
`jax.lax.map(..., batch_size=c)`. Each query's result depends on that
query alone, so the chunked results are bit-identical to the unchunked
ones; these tests force the chunks by cutting the budget.

Also here: the store no longer carries a bit-packed projection
(`proj_packed`), so a state dict or checkpoint that still holds one is
read and the key ignored, and a row costs what the searches read.
"""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.avss import SearchConfig
from repro.core.mcam import MCAMConfig
from repro.core.memory import MemoryConfig
from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest,
                          TenantStore, sharded)
from repro.engine.pager import ShardPager
from repro.launch.mesh import make_mesh

ROWS, DIM, K = 72, 20, 10
SHARDS, NPROBE = 8, 3
CHUNK = 3
LEAVES = ("votes", "dist", "indices", "labels")


def _cfg():
    return SearchConfig("mtmc", cl=8, mode="avss", use_kernel="pallas")


@pytest.fixture(scope="module")
def store():
    """72 rows of 8 distinct ones (9x duplicates, so only the exact
    (distance, row) order agrees), every 4th row masked."""
    rng = np.random.default_rng(0)
    base = rng.integers(0, _cfg().enc.levels, (8, DIM))
    labels = np.arange(ROWS) % 9
    labels[::4] = -1
    return MemoryStore.from_quantized(jnp.asarray(np.tile(base, (9, 1))),
                                      jnp.asarray(labels), _cfg())


def _queries(b):
    return jnp.asarray(np.random.default_rng(b).integers(0, 4, (b, DIM)),
                       jnp.int32)


# -- the shape rule -----------------------------------------------------------


@pytest.mark.parametrize("case,batch,rows,budget,chunk", [
    ("under", 64, 1_572_864, None, 64),         # the omniglot cell
    ("at", 8, 100, 8 * 100 * 4, 8),
    ("just_over", 8, 100, 8 * 100 * 4 - 1, 7),
    ("ragged", 10, 100, 4 * 100 * 4, 4),        # chunks of 4, 4, 2
    ("one", 8, 100, 2 * 100 * 4 - 1, 1),
    ("single_query_over", 8, 100, 100 * 4 - 1, None),
])
def test_shortlist_chunk_rule(monkeypatch, case, batch, rows, budget, chunk):
    if budget is not None:
        monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES", budget)
    if chunk is None:
        with pytest.raises(ValueError, match="SHORTLIST_DENSE_MAX_BYTES"):
            sharded.shortlist_chunk(batch, rows)
        return
    assert sharded.shortlist_chunk(batch, rows) == chunk


@pytest.mark.parametrize("site", ["unsharded", "tenant"])
def test_single_query_over_budget_raises_at_trace_time(store, monkeypatch,
                                                       site):
    """One query's distance row over the budget cannot be chunked: the
    search refuses it when traced, with the rule's message."""
    monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES", ROWS * 4 - 1)
    eng = RetrievalEngine(_cfg())
    req = SearchRequest(mode="ideal", k=K)
    q = _queries(2)
    with pytest.raises(ValueError, match="SHORTLIST_DENSE_MAX_BYTES"):
        if site == "unsharded":
            jax.jit(lambda st, qq: eng.search(st, qq, req)).lower(store, q)
        else:
            ts = TenantStore.stack([store, store])
            ids = jnp.array([0, 1], jnp.int32)
            jax.jit(lambda t, qq, i: eng.search_tenants(t, qq, i, req)
                    ).lower(ts, q, ids)


# -- chunked == unchunked, at every site --------------------------------------


def _search(site, store, q, req):
    """(result, rows of the phase-1 block one query ranks) at `site`."""
    eng = RetrievalEngine(_cfg())
    if site == "unsharded":
        search = jax.jit(lambda st, qq: eng.search(st, qq, req))
        return search(store, q), ROWS
    if site == "mesh":
        mesh = make_mesh((1,), ("data",))
        with mesh:
            return jax.jit(lambda st, qq: eng.search(st, qq, req))(
                store.shard(mesh, ("data",)), q), ROWS
    routed = dataclasses.replace(req, nprobe=NPROBE)
    rows_vis = NPROBE * ROWS // SHARDS
    if site == "routed":
        return jax.jit(lambda st, qq: eng.search(st, qq, routed))(
            store.shard(n_shards=SHARDS), q), rows_vis
    if site == "paged":
        host = store.shard(n_shards=SHARDS, residency="host")
        pager = ShardPager(host, eng, slots=SHARDS, prefetch=False)
        return pager.search(q, routed), rows_vis
    # tenant: three tenants, the store itself and two ragged slices of it
    ts = TenantStore.stack([store, *(MemoryStore.from_quantized(
        store.values[:n], store.labels[:n], _cfg()) for n in (40, 65))])
    ids = jnp.asarray(np.arange(q.shape[0]) % 3, jnp.int32)
    return jax.jit(lambda t, qq, i: eng.search_tenants(t, qq, i, req))(
        ts, q, ids), ROWS


@pytest.mark.parametrize("batch", [2 * CHUNK, 2 * CHUNK - 1],
                         ids=["divisible", "ragged"])
@pytest.mark.parametrize("mode", ["ideal", "two_phase"])
@pytest.mark.parametrize("site", ["unsharded", "mesh", "routed", "paged",
                                  "tenant"])
def test_chunked_search_bit_identical(store, monkeypatch, site, mode,
                                      batch):
    q = _queries(batch)
    req = SearchRequest(mode=mode, k=K)
    whole, rows = _search(site, store, q, req)
    chunks = []
    rule = sharded.shortlist_chunk
    monkeypatch.setattr(sharded, "shortlist_chunk",
                        lambda b, n: chunks.append(rule(b, n)) or chunks[-1])
    monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES",
                        CHUNK * rows * 4)
    got, _ = _search(site, store, q, req)
    assert CHUNK in chunks, chunks          # the batch went in chunks
    for leaf in LEAVES:
        np.testing.assert_array_equal(np.asarray(getattr(whole, leaf)),
                                      np.asarray(getattr(got, leaf)),
                                      err_msg=f"{site}/{mode}: {leaf}")


# -- the store without its bit-packed projection ------------------------------


@pytest.mark.parametrize("source", ["state", "checkpoint"])
def test_restore_ignores_proj_packed(store, source):
    """A state dict or checkpoint written by an older store still carries
    `proj_packed`: it is read and that key ignored, leaf for leaf."""
    cfg = store.cfg
    state = dict(store.to_state(), proj_packed=jnp.zeros(
        (ROWS, 16), jnp.int32))
    if source == "state":
        back = MemoryStore.from_state(state, cfg)
    else:
        from repro.checkpoint import ckpt
        with tempfile.TemporaryDirectory() as td:
            ckpt.save(td, 0, state)
            back = MemoryStore.restore(td, cfg)
    assert not hasattr(back, "proj_packed")
    for leaf in ("values", "proj", "s_grid", "labels", "size", "lo", "hi",
                 "sketch_sums", "sketch_counts"):
        np.testing.assert_array_equal(np.asarray(getattr(back, leaf)),
                                      np.asarray(getattr(store, leaf)),
                                      err_msg=leaf)


# (dim, MTMC CL, bytes a row) of each benchmark cell's store: values int32,
# proj bf16 (4 LUT columns a value), s_grid int8 (strings x cells), label
@pytest.mark.parametrize("cell,dim,cl,row_bytes", [
    ("omniglot-1536K", 48, 32, 192 + 384 + 1536 + 4),
    ("starcoder2-3b-knnlm", 3072, 8, 12288 + 24576 + 24576 + 4),
])
def test_store_bytes_per_row(cell, dim, cl, row_bytes):
    cfg = MemoryConfig(capacity=256, dim=dim, search=SearchConfig(
        "mtmc", cl=cl, mode="avss", mcam=MCAMConfig(string_len=24)))
    shapes = jax.eval_shape(lambda: MemoryStore.create(cfg))
    per_row = sum(a.dtype.itemsize * int(np.prod(a.shape[1:]))
                  for a in jax.tree_util.tree_leaves(shapes)
                  if a.ndim and a.shape[0] == cfg.capacity)
    assert per_row == row_bytes, (cell, per_row)
