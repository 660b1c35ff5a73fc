"""Pallas kernels vs pure-jnp oracle: shape/dtype/mode sweeps, bit-exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import avss as avss_lib
from repro.core.avss import SearchConfig
from repro.core.encodings import avss_sum_lut, make_encoding
from repro.core.mcam import MCAMConfig
from repro.kernels import ops, ref
from repro.kernels.mcam_search import mcam_search_pallas


def _layouts(mode, enc, d, N, B, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    sv = jax.random.randint(k1, (N, d), 0, enc.levels)
    qmax = 4 if mode == "avss" else enc.levels
    qv = jax.random.randint(k2, (B, d), 0, qmax)
    return qv, sv


@pytest.mark.parametrize("mode", ["avss", "svss"])
@pytest.mark.parametrize("encoding,cl", [("mtmc", 4), ("mtmc", 9),
                                         ("b4e", 2), ("sre", 3)])
@pytest.mark.parametrize("d", [10, 48])
@pytest.mark.slow
def test_search_kernel_matches_ref(mode, encoding, cl, d):
    cfg = SearchConfig(encoding=encoding, cl=cl, mode=mode,
                       mcam=MCAMConfig(sigma_device=0.1, sigma_read=0.05))
    enc = cfg.enc
    qv, sv = _layouts(mode, enc, d, N=40, B=5)
    sl = cfg.mcam.string_len
    s_grid = avss_lib.layout_support(sv, enc, sl)
    q_grid = avss_lib.layout_query(qv, enc, mode, sl)
    th = jnp.asarray(cfg.mcam.thresholds())
    # kernel (padded tiles) vs oracle
    votes_k, dist_k = ops.mcam_search(q_grid, s_grid, enc.weights_array(),
                                      cfg, th)
    L = s_grid.shape[2]
    q = ops.flatten_strings(ops.broadcast_query(q_grid, L)).astype(jnp.int8)
    s = ops.flatten_strings(s_grid).astype(jnp.int8)
    w = jnp.tile(enc.weights_array(), s_grid.shape[1])
    votes_r, dist_r = ref.mcam_search_ref(q, s, w, th, cfg.mcam, noisy=True)
    np.testing.assert_allclose(np.asarray(votes_k), np.asarray(votes_r),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dist_k), np.asarray(dist_r),
                               rtol=1e-6)


@pytest.mark.parametrize("tile_b,tile_n", [(2, 16), (8, 64)])
@pytest.mark.slow
def test_kernel_tiling_invariance(tile_b, tile_n):
    """Different VMEM tilings must produce bit-identical results."""
    cfg = SearchConfig(encoding="mtmc", cl=6, mode="avss")
    enc = cfg.enc
    qv, sv = _layouts("avss", enc, 24, N=64, B=8)
    s_grid = avss_lib.layout_support(sv, enc, 24)
    q_grid = avss_lib.layout_query(qv, enc, "avss", 24)
    th = jnp.asarray(cfg.mcam.thresholds())
    L = s_grid.shape[2]
    q = ops.flatten_strings(ops.broadcast_query(q_grid, L)).astype(jnp.int8)
    s = ops.flatten_strings(s_grid).astype(jnp.int8)
    w = jnp.tile(enc.weights_array(), s_grid.shape[1])
    v1, d1 = mcam_search_pallas(q, s, w, th, cfg.mcam, tile_b=tile_b,
                                tile_n=tile_n)
    v2, d2 = ref.mcam_search_ref(q, s, w, th, cfg.mcam)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_noiseless_dist_equals_weighted_l1():
    cfg = SearchConfig(encoding="mtmc", cl=8, mode="svss", noisy=False,
                       use_kernel="ref")
    enc = cfg.enc
    qv, sv = _layouts("svss", enc, 16, N=30, B=4)
    res = avss_lib.search_quantized(qv, sv, cfg)
    expect = np.abs(np.asarray(qv)[:, None] - np.asarray(sv)[None]).sum(-1)
    np.testing.assert_allclose(np.asarray(res["dist"]), expect)


@pytest.mark.parametrize("cl", [2, 8, 32])
@pytest.mark.parametrize("d", [16, 48, 100])
def test_mxu_lut_dist_exact(cl, d):
    """The dense phase-1 distance -- the query one-hots dotted against the
    stored bf16 `proj` with f32 accumulation (engine/sharded
    ._local_shortlist, every row ranked) -- equals the reference AVSS
    distance and |cl*q - v| exactly."""
    from repro.engine.sharded import _local_shortlist
    enc = make_encoding("mtmc", cl)
    qv, sv = _layouts("avss", enc, d, N=70, B=6, seed=cl + d)
    dist, idx = _local_shortlist(ops.query_onehot(qv),
                                 ops.support_projection(sv, enc), None,
                                 sv.shape[0])
    di = np.empty(dist.shape, np.float32)
    np.put_along_axis(di, np.asarray(idx), np.asarray(dist), axis=1)
    dr = ref.avss_dist_ref(qv, sv, jnp.asarray(avss_sum_lut(enc)))
    np.testing.assert_array_equal(np.asarray(di), np.asarray(dr))
    # against direct value-space distance |cl*q - v|
    expect = np.abs(cl * np.asarray(qv)[:, None] - np.asarray(sv)[None]
                    ).sum(-1).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(di), expect)


@pytest.mark.slow
def test_two_phase_matches_full_search():
    cfg = SearchConfig(encoding="mtmc", cl=8, mode="avss", use_kernel="ref")
    enc = cfg.enc
    qv, sv = _layouts("avss", enc, 48, N=64, B=8)
    full = avss_lib.search_quantized(qv, sv, cfg)
    tp = ops.two_phase_search(qv, sv, cfg, k=64)  # k=N: full coverage
    # same noise counters => identical votes for every support
    order = np.argsort(np.asarray(tp["indices"]), axis=1)
    votes_sorted = np.take_along_axis(np.asarray(tp["votes"]), order, 1)
    np.testing.assert_allclose(votes_sorted, np.asarray(full["votes"]),
                               rtol=1e-5)


@pytest.mark.slow
def test_two_phase_winner_agreement():
    """Shortlist recall: on UNSTRUCTURED random vectors (worst case: many
    near-ties) k=64/200 already recovers the exact noisy-vote winner; the
    recall-vs-k curve is benchmarked in benchmarks/bench_kernels.py."""
    cfg = SearchConfig(encoding="mtmc", cl=8, mode="avss", use_kernel="ref")
    enc = cfg.enc
    qv, sv = _layouts("avss", enc, 48, N=200, B=8)
    full = avss_lib.search_quantized(qv, sv, cfg)
    agree = {}
    for k in (32, 64):
        tp = ops.two_phase_search(qv, sv, cfg, k=k)
        full_best = np.asarray(jnp.argmax(
            full["votes"] - 1e-6 * full["dist"], -1))
        sc = np.asarray(tp["votes"]) - 1e-6 * np.asarray(tp["dist"])
        tp_best = np.asarray(tp["indices"])[np.arange(8), sc.argmax(1)]
        agree[k] = (full_best == tp_best).mean()
    assert agree[64] >= 0.95, agree
    assert agree[32] >= 0.5, agree
    assert agree[64] >= agree[32]


def _rescore_flatten_then_gather(q_grid, s_grid, short_idx, weights, cfg,
                                 thresholds, noise_idx=None, noise_qidx=None):
    """`ops.rescore_shortlist` as it read before the gather moved ahead of
    the flatten: all N rows flattened to (N, S, sl), then gathered."""
    from repro.core import mcam as mcam_lib
    L = s_grid.shape[2]
    q = ops.flatten_strings(ops.broadcast_query(q_grid, L))
    s = ops.flatten_strings(s_grid)
    B, S, sl = q.shape
    sg = s[short_idx]
    m = jnp.abs(q[:, None].astype(jnp.int32) - sg.astype(jnp.int32))
    m = m.astype(jnp.float32)
    if noise_idx is None:
        noise_idx = short_idx
    if noise_qidx is None:
        noise_qidx = jnp.arange(B, dtype=jnp.uint32)
    string_id = (noise_idx.astype(jnp.uint32)[..., None] * jnp.uint32(S)
                 + jnp.arange(S, dtype=jnp.uint32)[None, None, :])
    b_idx = noise_qidx.astype(jnp.uint32)[:, None, None]
    mc = cfg.mcam
    dev = rd = None
    if cfg.noisy:
        cell = jnp.arange(sl, dtype=jnp.uint32)
        dev = mcam_lib.hash_normal(b_idx[..., None], string_id[..., None],
                                   cell, seed=mc.seed)
        rd = mcam_lib.hash_normal(b_idx, string_id,
                                  seed=mc.seed + ref.READ_SEED_OFFSET)
    r = mcam_lib.string_resistance(m, mc, device_noise=dev)
    cur = mcam_lib.current_from_resistance(r, sl, mc, read_noise=rd)
    v = (cur[..., None] > thresholds).sum(-1).astype(jnp.float32)
    w_flat = jnp.tile(weights.astype(jnp.float32), s_grid.shape[1])
    return (v * w_flat[None, None, :]).sum(-1)


@pytest.mark.parametrize("case", ["noisy", "noiseless", "duplicates",
                                  "tiny_store", "sharded", "noise_qidx"])
def test_rescore_shortlist_matches_flatten_then_gather(case):
    """Gathering the candidate rows of the stored (N, seg, L, sl) grid and
    flattening only them gives votes bit-identical to flattening every row
    and then gathering, in each form the callers use: noise on and off,
    repeated candidates, more candidates than rows, a shard's grid with
    shard-local rows and global noise rows, per-query noise coordinates."""
    cfg = SearchConfig(encoding="mtmc", cl=6, mode="avss",
                       noisy=case != "noiseless",
                       mcam=MCAMConfig(sigma_device=0.1, sigma_read=0.05))
    enc = cfg.enc
    N, B, K = {"tiny_store": (3, 4, 5)}.get(case, (40, 6, 8))
    qv, sv = _layouts("avss", enc, 10, N=N, B=B, seed=3)
    sl = cfg.mcam.string_len
    s_grid = avss_lib.layout_support(sv, enc, sl)
    q_grid = avss_lib.layout_query(qv, enc, "avss", sl)
    rng = np.random.default_rng(7)
    short_idx = rng.integers(0, N, (B, K))
    if case == "duplicates":
        short_idx[:, K // 2:] = short_idx[:, :K // 2]
        short_idx[1] = short_idx[1, 0]
    kw = {}
    if case == "sharded":
        # the third of four equal shards: local rows, global noise rows
        rows = N // 4
        s_grid = s_grid[2 * rows:3 * rows]
        short_idx = short_idx % rows
        kw["noise_idx"] = jnp.asarray(short_idx + 2 * rows, jnp.int32)
    if case == "noise_qidx":
        kw["noise_qidx"] = jnp.asarray([5, 0, 0, 2, 9, 1], jnp.uint32)
    short_idx = jnp.asarray(short_idx, jnp.int32)
    args = (q_grid, s_grid, short_idx, enc.weights_array(), cfg,
            jnp.asarray(cfg.mcam.thresholds()))
    new = jax.jit(ops.rescore_shortlist, static_argnums=4)(*args, **kw)
    old = jax.jit(_rescore_flatten_then_gather, static_argnums=4)(*args,
                                                                  **kw)
    assert new.shape == (B, K)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    if case == "duplicates":
        v = np.asarray(new)
        np.testing.assert_array_equal(v[:, K // 2:], v[:, :K // 2])
        assert (v[1] == v[1, 0]).all()
