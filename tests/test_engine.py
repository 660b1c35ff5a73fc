"""Parity suite for the unified retrieval engine (repro/engine).

Every backend and every sharding must produce BIT-IDENTICAL results -- the
engine's contract is that backend choice is purely a performance decision.
Exactness rests on (see repro/engine docstrings): integer-valued phase-1
distances (exact in f32 under any reduction order), (distance, index)
lexicographic ranking everywhere, and counter-based noise keyed on absolute
(query, string, cell) coordinates.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_contracts as hc
from repro.core import avss as avss_lib
from repro.core.avss import SearchConfig
from repro.core.mcam import MCAMConfig
from repro.engine import RetrievalEngine, resolve_backend
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Backend resolution.
# ---------------------------------------------------------------------------


def test_resolve_backend_precedence():
    assert resolve_backend("ref", "pallas") == "ref"   # engine overrides cfg
    assert resolve_backend("auto", "ref") == "ref"     # cfg honoured on auto
    assert resolve_backend("auto", "auto") in ("pallas", "ref")
    with pytest.raises(ValueError):
        resolve_backend("cuda")


# ---------------------------------------------------------------------------
# (a) Pallas full search == reference, across odd shapes.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,N,d", [
    (5, 200, 50),   # B not a tile_b multiple, N crosses a tile_n boundary,
                    # d not a string_len multiple (50 = 2*24 + 2)
    (3, 37, 10),    # tiny everything
    pytest.param(9, 130, 24, marks=pytest.mark.slow),  # d exactly 1 string
    pytest.param(1, 16, 72, marks=pytest.mark.slow),   # 1 query, 3 strings
])
def test_full_search_pallas_matches_ref_odd_shapes(B, N, d):
    cfg = SearchConfig("mtmc", cl=8, mode="avss", mcam=MCAMConfig(),
                       use_kernel="ref")
    sv = jax.random.randint(jax.random.PRNGKey(N), (N, d), 0, cfg.enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(B), (B, d), 0, 4)
    ref = RetrievalEngine(cfg, backend="ref").full(qv, sv)
    pal = RetrievalEngine(cfg, backend="pallas").full(qv, sv)
    np.testing.assert_array_equal(np.asarray(ref["votes"]),
                                  np.asarray(pal["votes"]))
    np.testing.assert_array_equal(np.asarray(ref["dist"]),
                                  np.asarray(pal["dist"]))


@pytest.mark.slow
def test_full_search_pallas_matches_ref_svss():
    cfg = SearchConfig("mtmc", cl=4, mode="svss", mcam=MCAMConfig(),
                       use_kernel="ref")
    sv = jax.random.randint(jax.random.PRNGKey(2), (33, 30), 0,
                            cfg.enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(3), (4, 30), 0,
                            cfg.enc.levels)
    ref = RetrievalEngine(cfg, backend="ref").full(qv, sv)
    pal = RetrievalEngine(cfg, backend="pallas").full(qv, sv)
    np.testing.assert_array_equal(np.asarray(ref["votes"]),
                                  np.asarray(pal["votes"]))


# ---------------------------------------------------------------------------
# Two-phase backends agree bit-exactly; votes match the full search.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_two_phase_backends_bit_identical(quantized_store):
    cfg, qv, sv = quantized_store
    res = {b: RetrievalEngine(cfg, backend=b).two_phase(qv, sv, k=48)
           for b in ("ref", "pallas")}
    for key in ("votes", "dist", "indices"):
        np.testing.assert_array_equal(
            np.asarray(res["ref"][key]), np.asarray(res["pallas"][key]),
            err_msg=f"pallas/{key}")


@pytest.mark.slow
def test_two_phase_votes_match_full_search(quantized_store):
    cfg, qv, sv = quantized_store
    eng = RetrievalEngine(cfg, backend="ref")
    full = eng.full(qv, sv)
    tp = eng.two_phase(qv, sv, k=48)
    v_full = np.asarray(full["votes"])
    idx = np.asarray(tp["indices"])
    for b in range(qv.shape[0]):
        np.testing.assert_array_equal(np.asarray(tp["votes"])[b],
                                      v_full[b, idx[b]])


def _chunk_budget(monkeypatch, chunk: int, rows: int) -> None:
    """Cut the phase-1 budget so `chunk` queries' (chunk, rows) f32 block
    is the most that fits: the shortlist then ranks in query chunks."""
    from repro.engine import sharded
    monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES",
                        chunk * rows * 4)


def _tie_heavy_store():
    """72 rows of 8 distinct ones (9x duplicates), every 4th row masked."""
    from repro.engine import MemoryStore
    cfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="ref")
    base = jax.random.randint(jax.random.PRNGKey(0), (8, 20), 0,
                              cfg.enc.levels)
    sv = jnp.concatenate([base] * 9, axis=0)
    labels = jnp.where(jnp.arange(72) % 4 == 0, -1,
                       jnp.arange(72)).astype(jnp.int32)
    qv = jax.random.randint(jax.random.PRNGKey(1), (5, 20), 0, 4)
    return cfg, MemoryStore.from_quantized(sv, labels, cfg), qv


def test_fused_shortlist_matches_topk_tie_heavy(monkeypatch):
    """The phase-1 block ranked in query chunks (2, 2, 1 of 5 queries)
    reproduces lax.top_k over the whole dense block EXACTLY, including tie
    order, on a store built almost entirely of duplicated rows."""
    from repro.core.encodings import make_encoding
    from repro.engine.sharded import _local_shortlist
    from repro.kernels import ops as kops
    enc = make_encoding("mtmc", 8)
    base = jax.random.randint(jax.random.PRNGKey(0), (8, 20), 0, enc.levels)
    sv = jnp.concatenate([base] * 9, axis=0)               # 72 rows, 9x dups
    qv = jax.random.randint(jax.random.PRNGKey(1), (5, 20), 0, 4)
    q1h = kops.query_onehot(qv, jnp.float32)
    sp = kops.support_projection(sv, enc, jnp.float32)
    neg, idx_ref = jax.lax.top_k(-(q1h @ sp.T), 30)
    _chunk_budget(monkeypatch, 2, 72)
    dist, idx = _local_shortlist(q1h, kops.support_projection(sv, enc),
                                 None, 30)
    np.testing.assert_array_equal(np.asarray(-neg), np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(idx_ref), np.asarray(idx))


def test_shortlist_valid_mask_excludes_rows(monkeypatch):
    """Masked rows rank after every valid row (integer-exact penalty), and
    masking is bit-identical across shortlist backends, whether phase 1
    ranks the queries in one block or in chunks."""
    cfg = SearchConfig("mtmc", cl=4, mode="avss", mcam=MCAMConfig(),
                       use_kernel="ref")
    sv = jax.random.randint(jax.random.PRNGKey(0), (40, 16), 0,
                            cfg.enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 4)
    valid = (jnp.arange(40) % 3) != 0                      # 26 valid rows
    run = lambda b: RetrievalEngine(cfg, backend=b).two_phase(
        qv, sv, k=20, valid=valid)
    res = {b: run(b) for b in ("ref", "pallas")}
    _chunk_budget(monkeypatch, 2, 40)
    res.update({f"chunked-{b}": run(b) for b in ("ref", "pallas")})
    for b, got in res.items():
        for key in ("votes", "dist", "indices"):
            np.testing.assert_array_equal(
                np.asarray(res["ref"][key]), np.asarray(got[key]),
                err_msg=f"{b}/{key}")
    # k=20 <= 26 valid rows: no masked row may appear at all
    assert bool(jnp.all(valid[res["ref"]["indices"]]))


# ---------------------------------------------------------------------------
# Ideal mode: query chunks == one dense (B, N) block, bit for bit.
# ---------------------------------------------------------------------------


def test_ideal_fused_matches_dense_tie_heavy(monkeypatch):
    """The ideal serving path ranked in query chunks is bit-identical to
    one dense block -- votes, dist, indices AND labels -- on a tie-heavy
    store with masked rows inside the top-k (masked rows carry the
    integer-exact penalty in both)."""
    from repro.engine import SearchRequest
    cfg, store, qv = _tie_heavy_store()
    req = SearchRequest(mode="ideal", k=70)             # reaches masked rows
    dense = RetrievalEngine(cfg, backend="ref").search(store, qv, req)
    _chunk_budget(monkeypatch, 2, 72)
    for backend in ("ref", "pallas"):
        chunked = RetrievalEngine(cfg, backend=backend).search(store, qv,
                                                               req)
        for key in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(
                np.asarray(getattr(dense, key)),
                np.asarray(getattr(chunked, key)),
                err_msg=f"{backend}/{key}")
    # masked candidates surface as -inf votes / -1 labels
    assert np.isneginf(np.asarray(dense.votes)).any()


def test_ideal_routes_through_fused_kernel_at_large_n(monkeypatch):
    """Phase 1 follows the size of the dense block: under
    SHORTLIST_DENSE_MAX_BYTES the compiled search ranks every query in one
    block (no loop in `search_shortlist`), over it (the budget forced
    small) in query chunks (a loop), on every backend. Both are
    bit-identical to ref on a ragged (1,100-row), tie-heavy store whose
    masked rows reach the top-k, in `ideal` and in `two_phase`."""
    from repro.engine import MemoryStore, SearchRequest, sharded
    cfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="auto")
    N = 1100                    # past the old 1024-row threshold, ragged
    sv = jnp.tile(jax.random.randint(jax.random.PRNGKey(0), (100, 16), 0,
                                     cfg.enc.levels), (N // 100, 1))
    labels = jnp.where(jnp.arange(N) % 4 == 0, -1,
                       jnp.arange(N) % 17).astype(jnp.int32)
    store = MemoryStore.from_quantized(sv, labels, cfg)
    qv = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 4)
    eng = RetrievalEngine(cfg)                  # auto -> pallas (kernels)
    assert eng.resolved_backend != "ref"
    ref_eng = RetrievalEngine(cfg, backend="ref")
    budget = sharded.SHORTLIST_DENSE_MAX_BYTES
    for mode in ("ideal", "two_phase"):
        monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES", budget)
        req = SearchRequest(mode=mode, k=840)    # 825 valid rows: masked
        ref_res = ref_eng.search(store, qv, req)  # rows reach the top-k
        assert np.isneginf(np.asarray(ref_res.votes)).any(), mode
        compiled = lambda e, r=req: jax.jit(
            lambda st, q: e.search(st, q, r).votes
        ).lower(store, qv).compile().as_text()

        dense_res = eng.search(store, qv, req)
        hc.assert_phase1_one_block(compiled(eng))
        monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES",
                            3 * N * 4 - 1)
        chunked_res = eng.search(store, qv, req)
        for e in (eng, ref_eng):
            assert hc.check_phase1_one_block(compiled(e)), \
                f"{mode}: a block over the budget ranks in query chunks"
        for got in (dense_res, chunked_res):
            for key in ("votes", "dist", "indices", "labels"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ref_res, key)),
                    np.asarray(getattr(got, key)), err_msg=f"{mode}/{key}")


# ---------------------------------------------------------------------------
# Query chunks on the SHARDED paths: one phase-1 block implementation across
# unsharded/sharded x ref/pallas.
# ---------------------------------------------------------------------------


def test_shortlist_kernel_native_mask_odd_n_ties(monkeypatch):
    """The shared phase-1 block handles masked rows (the per-row penalty)
    and a row count no tile divides in query chunks: bit-identical to the
    dense penalised f32 matrix + lax.top_k, masked rows in the top-k
    included, on a tie-heavy 45-row (odd) store with a bf16 projection."""
    from repro.core.encodings import make_encoding
    from repro.engine.sharded import _local_shortlist
    from repro.kernels import ops as kops
    enc = make_encoding("mtmc", 8)
    base = jax.random.randint(jax.random.PRNGKey(0), (9, 20), 0, enc.levels)
    sv = jnp.concatenate([base] * 5, axis=0)[:45]          # 45 rows, ties
    qv = jax.random.randint(jax.random.PRNGKey(1), (5, 20), 0, 4)
    valid = (jnp.arange(45) % 3) != 0                      # 15 masked rows
    q1h = kops.query_onehot(qv, jnp.float32)
    sp32 = kops.support_projection(sv, enc, jnp.float32)
    dense = q1h @ sp32.T + jnp.where(valid, 0.0,
                                     kops.SHORTLIST_MASK_PENALTY)[None]
    neg, idx_ref = jax.lax.top_k(-dense, 40)               # masked in top-k
    sp16 = kops.support_projection(sv, enc)                # bf16 write-time
    _chunk_budget(monkeypatch, 3, 45)                      # chunks of 3, 2
    dist, idx = _local_shortlist(q1h, sp16, valid, 40)
    np.testing.assert_array_equal(np.asarray(-neg), np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(idx_ref), np.asarray(idx))
    # the penalty is integer-exact and visible on masked candidates
    assert float(dist[0, -1]) >= kops.SHORTLIST_MASK_PENALTY


def test_sharded_fused_shortlist_matches_dense_and_unsharded(monkeypatch):
    """Sharded `ideal` and `two_phase` over the phase-1 budget rank each
    shard's block in query chunks inside shard_map (a loop in the
    compiled `search_shortlist`, none under the budget) and stay
    bit-identical to the one-block path AND the unsharded ref store --
    ties and masked rows in the top-k included."""
    from repro.engine import SearchRequest, sharded
    cfg, store, qv = _tie_heavy_store()
    mesh = make_mesh((1,), ("data",))
    sstore = store.shard(mesh, ("data",))
    eng = RetrievalEngine(cfg, backend="pallas")
    budget = sharded.SHORTLIST_DENSE_MAX_BYTES
    for mode in ("ideal", "two_phase"):
        ref = RetrievalEngine(cfg, backend="ref").search(
            store, qv, SearchRequest(mode=mode, k=60))
        req = SearchRequest(mode=mode, k=60)
        for chunked, cut in ((False, budget), (True, 2 * 72 * 4)):
            monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES", cut)
            with mesh:
                got = jax.jit(lambda st, q: eng.search(st, q, req))(
                    sstore, qv)
                hlo = jax.jit(
                    lambda st, q: eng.search(st, q, req).votes
                ).lower(sstore, qv).compile().as_text()
            for key in ("votes", "dist", "indices", "labels"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ref, key)),
                    np.asarray(getattr(got, key)),
                    err_msg=f"{mode}/chunked={chunked}/{key}")
            assert bool(hc.check_phase1_one_block(hlo)) == chunked, mode
        # masked candidates did reach the merged top-k (k=60 > 54 valid)
        assert np.isneginf(np.asarray(ref.votes)).any(), mode


@pytest.mark.slow
def test_sharded_fused_8dev_ragged_bit_identical():
    """On a forced 8-device mesh with a RAGGED capacity-100 split (13-row
    local blocks, 4 pad rows), sharded `ideal` and `two_phase` over the
    phase-1 budget rank each shard's block in query chunks inside
    shard_map (a loop in the compiled `search_shortlist`) with results
    bit-identical to the one-block path and the unsharded store --
    tie-heavy rows and masked rows (70 empty slots + 4 pads) inside the
    merged top-k."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.analysis import hlo_contracts as hc
        from repro.core.avss import SearchConfig
        from repro.core.memory import MemoryConfig
        from repro.engine import (MemoryStore, RetrievalEngine,
                                  SearchRequest, sharded)

        cfg = MemoryConfig(capacity=100, dim=24,
                           search=SearchConfig("mtmc", cl=8, mode="avss",
                                               use_kernel="ref"))
        base = jax.random.normal(jax.random.PRNGKey(0), (10, 24))
        vecs = jnp.tile(base, (3, 1))                  # 30 rows, 3x dups
        labs = jnp.arange(30, dtype=jnp.int32) % 7
        store = MemoryStore.create(cfg).calibrate(vecs).write(vecs, labs)
        q = vecs[:6] + 0.02
        mesh = make_mesh((8,), ("data",))
        sstore = store.shard(mesh, ("data",))
        assert sstore.capacity == 104, sstore.capacity  # ragged: 13/shard
        eng = RetrievalEngine(cfg.search, backend="pallas")
        budget = sharded.SHORTLIST_DENSE_MAX_BYTES

        # k=50 > 30 valid rows: masked (empty + pad) rows reach the top-k;
        # k_loc = 13 == the full local block, so the merge is exhaustive
        for mode in ("ideal", "two_phase"):
            ref = RetrievalEngine(cfg.search, backend="ref").search(
                store, q, SearchRequest(mode=mode, k=50))
            assert np.isneginf(np.asarray(ref.votes)).any(), mode
            req = SearchRequest(mode=mode, k=50)
            outs = {}
            # 4 of the 6 queries' (4, 13) block fits: chunks of 4 and 2
            for tag, cut in (("dense", budget), ("chunked", 4 * 13 * 4)):
                sharded.SHORTLIST_DENSE_MAX_BYTES = cut
                with mesh:
                    f = jax.jit(lambda st, qq: eng.search(st, qq, req))
                    outs[tag] = f(sstore, q)
                    hlo = jax.jit(lambda st, qq: eng.search(
                        st, qq, req).votes).lower(sstore, q).compile(
                        ).as_text()
                assert bool(hc.check_phase1_one_block(hlo)) == (
                    tag == "chunked"), (mode, tag)
            sharded.SHORTLIST_DENSE_MAX_BYTES = budget
            for tag in ("chunked", "dense"):
                for key in ("votes", "dist", "indices", "labels"):
                    np.testing.assert_array_equal(
                        np.asarray(getattr(ref, key)),
                        np.asarray(getattr(outs[tag], key)),
                        err_msg=f"{mode}/{tag}/{key}")
        print("SHARDED-CHUNKED-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED-CHUNKED-OK" in proc.stdout


# ---------------------------------------------------------------------------
# (c) Two-phase recall@k == 1.0 vs full search on small clustered stores.
# ---------------------------------------------------------------------------


def _clustered_store(key, n_way=10, k_shot=4, n_query=2, dim=32):
    kc, ks, kq = jax.random.split(jax.random.PRNGKey(key), 3)
    centers = jax.random.normal(kc, (n_way, dim)) * 2.2
    s_lab = jnp.repeat(jnp.arange(n_way), k_shot)
    q_lab = jnp.repeat(jnp.arange(n_way), n_query)
    s = centers[s_lab] + 0.9 * jax.random.normal(ks, (len(s_lab), dim))
    q = centers[q_lab] + 0.9 * jax.random.normal(kq, (len(q_lab), dim))
    lo, hi = float(s.min()), float(s.max())
    to_int = lambda x, lv: jnp.clip(jnp.round(
        (x - lo) / (hi - lo) * (lv - 1)), 0, lv - 1).astype(jnp.int32)
    return to_int(q, 4), to_int(s, 25)  # mtmc cl=8 -> 25 levels


@pytest.mark.parametrize("key", [0, 1, 2])
def test_two_phase_recall_at_k_is_one(key):
    cfg = SearchConfig("mtmc", cl=8, mode="avss", mcam=MCAMConfig(),
                       use_kernel="ref")
    qv, sv = _clustered_store(key)
    eng = RetrievalEngine(cfg, backend="ref")
    full = eng.full(qv, sv)
    full_best = np.asarray(avss_lib.best_support(full))
    tp = eng.two_phase(qv, sv, k=16)
    idx = np.asarray(tp["indices"])
    # recall@k: the full-search winner makes the shortlist for every query
    in_shortlist = [full_best[b] in idx[b] for b in range(len(full_best))]
    assert float(np.mean(in_shortlist)) == 1.0
    # and the two-phase 1-NN decision matches the full search exactly
    best = np.asarray(avss_lib.best_support(tp))
    tp_best = idx[np.arange(len(best)), best]
    recall = float((full_best == tp_best).mean())
    assert recall == 1.0, recall


# ---------------------------------------------------------------------------
# (b) Sharded two-phase == single-device two-phase, bit for bit, on a forced
# 8-device host mesh (subprocess: XLA_FLAGS must precede jax import).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_two_phase_bit_identical():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.avss import SearchConfig
        from repro.core.mcam import MCAMConfig
        from repro.engine import RetrievalEngine

        cfg = SearchConfig("mtmc", cl=8, mode="avss", mcam=MCAMConfig(),
                           use_kernel="ref")
        N, B, d = 256, 6, 48
        sv = jax.random.randint(jax.random.PRNGKey(0), (N, d), 0,
                                cfg.enc.levels)
        qv = jax.random.randint(jax.random.PRNGKey(1), (B, d), 0, 4)
        eng = RetrievalEngine(cfg, backend="ref")
        tp = eng.two_phase(qv, sv, k=48)
        for shape, axes in [((8,), ("data",)),
                            ((4, 2), ("data", "model"))]:
            mesh = make_mesh(shape, ("data", "model")[:len(shape)])
            svs = jax.device_put(sv, NamedSharding(mesh, P(axes)))
            with mesh:
                sh = eng.sharded_two_phase(qv, svs, mesh, axes=axes, k=48)
            for key in ("votes", "dist", "indices"):
                np.testing.assert_array_equal(
                    np.asarray(tp[key]), np.asarray(sh[key]),
                    err_msg=f"{shape}/{key}")
        # adversarial tie stress: every row duplicated once per shard, so
        # every distance appears 8x and the (distance, global row) ordering
        # is the ONLY thing keeping shards in agreement
        sv2 = jnp.concatenate([sv[:32]] * 8, 0)
        tp2 = eng.two_phase(qv, sv2, k=40)
        mesh = make_mesh((8,), ("data",))
        svs2 = jax.device_put(sv2, NamedSharding(mesh, P("data")))
        with mesh:
            sh2 = eng.sharded_two_phase(qv, svs2, mesh, axes=("data",),
                                        k=40)
        for key in ("votes", "dist", "indices"):
            np.testing.assert_array_equal(np.asarray(tp2[key]),
                                          np.asarray(sh2[key]), err_msg=key)

        # validity mask: parity must survive phase-1 masking too
        valid = (jnp.arange(N) % 5) != 0
        tpm = eng.two_phase(qv, sv, k=48, valid=valid)
        shm = eng.sharded_two_phase(
            qv, jax.device_put(sv, NamedSharding(mesh, P("data"))),
            mesh, axes=("data",), k=48,
            valid=jax.device_put(valid, NamedSharding(mesh, P("data"))))
        for key in ("votes", "dist", "indices"):
            np.testing.assert_array_equal(np.asarray(tpm[key]),
                                          np.asarray(shm[key]),
                                          err_msg=f"mask/{key}")

        # memory-level: distributed exact search == local two-phase search
        from repro.core import memory as mem
        from repro.core.memory import MemoryConfig
        mcfg = MemoryConfig(capacity=128, dim=24,
                            search=SearchConfig("mtmc", cl=8, mode="avss",
                                                use_kernel="ref"))
        vecs = jax.random.normal(jax.random.PRNGKey(5), (96, 24))
        labs = jnp.arange(96, dtype=jnp.int32) % 7
        state = mem.init_memory(mcfg)
        state = mem.calibrate(state, vecs, mcfg)
        state = mem.write(state, vecs, labs, mcfg)
        queries = vecs[:5] + 0.05 * jax.random.normal(
            jax.random.PRNGKey(6), (5, 24))
        local = mem.search(state, queries, mcfg, two_phase=True, k=16)
        mesh = make_mesh((8,), ("data",))
        sstate = mem.shard_state(state, mesh, ("data",))
        with mesh:
            dist = mem.distributed_search(sstate, queries, mcfg, mesh,
                                          axes=("data",), k=16)
        for key in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(np.asarray(local[key]),
                                          np.asarray(dist[key]), err_msg=key)

        # unified API: engine.search over a shard-aware MemoryStore must be
        # bit-identical to the pre-redesign two_phase/sharded_two_phase --
        # including a RAGGED (non-divisible) split: capacity 100 over 8
        # shards pads to 104 rows with label -1 pad rows
        from repro.engine import MemoryStore, SearchRequest
        rcfg = MemoryConfig(capacity=100, dim=24,
                            search=SearchConfig("mtmc", cl=8, mode="avss",
                                                use_kernel="ref"))
        rvecs = jax.random.normal(jax.random.PRNGKey(9), (100, 24))
        rlabs = jnp.arange(100, dtype=jnp.int32) % 11
        rstore = MemoryStore.create(rcfg).calibrate(rvecs).write(rvecs,
                                                                 rlabs)
        rq = rvecs[:6] + 0.03 * jax.random.normal(jax.random.PRNGKey(10),
                                                  (6, 24))
        reng = RetrievalEngine(rcfg.search)
        req = SearchRequest(mode="two_phase", k=32)
        # pre-redesign reference: raw-array two_phase + global label gather
        rqv = rstore.quantize_queries(rq)
        pre = reng.two_phase(rqv, rstore.values, k=32,
                             valid=rstore.labels >= 0)
        pre_labels = rstore.labels[pre["indices"]]
        new_local = reng.search(rstore, rq, req)
        np.testing.assert_array_equal(np.asarray(pre["votes"]),
                                      np.asarray(new_local.votes))
        np.testing.assert_array_equal(np.asarray(pre_labels),
                                      np.asarray(new_local.labels))
        mesh = make_mesh((8,), ("data",))
        rsharded = rstore.shard(mesh, ("data",))
        assert rsharded.capacity == 104, rsharded.capacity
        with mesh:
            new_sh = reng.search(rsharded, rq, req)
        for key in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(
                np.asarray(getattr(new_local, key)),
                np.asarray(getattr(new_sh, key)), err_msg=f"ragged/{key}")
        print("SHARDED-BIT-IDENTICAL")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED-BIT-IDENTICAL" in proc.stdout
