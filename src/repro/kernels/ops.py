"""Jitted wrappers around the Pallas MCAM kernels (padding, layout, dispatch).

Public entry points:

  mcam_search(q_grid, s_grid, weights, cfg, thresholds)
      Exact paper-faithful search; dispatches to the fused Pallas kernel
      (VPU path) with tile padding. Semantics == kernels/ref.py.

  two_phase_search(q_values, s_values, cfg, k)
      Beyond-paper TPU pipeline: MXU shortlist (ideal distance) + exact noisy
      rescoring of the top-k candidates. Bit-identical votes to the full
      search for every support that makes the shortlist.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core import encodings as enc_lib
from repro.core import mcam as mcam_lib
from repro.core.encodings import Encoding
from repro.kernels import mcam_search as mcam_search_kernel
from repro.kernels import ref

# Integer-exact penalty added to the phase-1 distance of masked-out support
# rows (never-written slots, ragged-shard and tenant pad rows): larger than
# any real AVSS distance and exact in f32, so masked rows rank after every
# valid row and keep their relative (distance, row) order.
SHORTLIST_MASK_PENALTY = 2.0 ** 22


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flatten_strings(grid: jax.Array) -> jax.Array:
    """(X, seg, L, sl) -> (X, seg*L, sl)."""
    x, seg, L, sl = grid.shape
    return grid.reshape(x, seg * L, sl)


def broadcast_query(q_grid: jax.Array, L: int) -> jax.Array:
    """(B, seg, Lq, sl) -> (B, seg, L, sl); AVSS queries have Lq == 1."""
    if q_grid.shape[2] == L:
        return q_grid
    assert q_grid.shape[2] == 1
    return jnp.broadcast_to(q_grid, (*q_grid.shape[:2], L, q_grid.shape[3]))


def mcam_search(q_grid: jax.Array, s_grid: jax.Array, weights: jax.Array,
                cfg, thresholds: jax.Array,
                qidx: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Drop-in kernel backend for repro.core.avss.search_quantized.

    qidx: optional (B,) per-query noise coordinates (default arange(B)).
    `engine.search_tenants` passes each query's rank within its tenant
    group, so batched-across-tenants noise is bit-identical to solo calls.
    """
    L = s_grid.shape[2]
    seg = s_grid.shape[1]
    q = flatten_strings(broadcast_query(q_grid, L)).astype(jnp.int8)
    s = flatten_strings(s_grid).astype(jnp.int8)
    w_flat = jnp.tile(weights.astype(jnp.float32), seg)
    B, N = q.shape[0], s.shape[0]
    if qidx is None:
        qidx = jnp.arange(B, dtype=jnp.uint32)
    tb = min(mcam_search_tile_b(), max(B, 1))
    tn = min(mcam_search_tile_n(), max(N, 1))
    qp = _pad_to(q, 0, tb)
    sp = _pad_to(s, 0, tn)
    votes, dist = mcam_search_kernel.mcam_search_pallas(
        qp, sp, w_flat, thresholds.astype(jnp.float32), cfg.mcam,
        noisy=cfg.noisy, qidx=_pad_to(qidx.astype(jnp.uint32), 0, tb),
        tile_b=tb, tile_n=tn)
    return votes[:B, :N], dist[:B, :N]


def mcam_search_tile_b() -> int:
    return mcam_search_kernel.DEFAULT_TILE_B


def mcam_search_tile_n() -> int:
    return mcam_search_kernel.DEFAULT_TILE_N


# ---------------------------------------------------------------------------
# MXU LUT path.
# ---------------------------------------------------------------------------


def support_projection(s_values: jax.Array, enc: Encoding,
                       dtype=jnp.bfloat16) -> jax.Array:
    """(N, d) int values -> (N, 4*d) LUT projection (precompute at write time).

    bf16 is exact for integer LUT entries < 256 (always true for MTMC with
    CL <= 85); pass dtype=jnp.float32 for long weighted encodings.
    """
    lut = jnp.asarray(enc_lib.avss_sum_lut(enc))          # (4, levels)
    proj = lut.T[s_values]                                # (N, d, 4)
    return proj.reshape(s_values.shape[0], -1).astype(dtype)


def query_onehot(q_values: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """(B, d) ints in [0,4) -> (B, 4*d) one-hot."""
    oh = jax.nn.one_hot(q_values, enc_lib.CELL_STATES, dtype=dtype)
    return oh.reshape(q_values.shape[0], -1)


# ---------------------------------------------------------------------------
# Two-phase search: MXU shortlist + exact rescore.
# ---------------------------------------------------------------------------


def rescore_shortlist(q_grid: jax.Array, s_grid: jax.Array,
                      short_idx: jax.Array, weights: jax.Array,
                      cfg, thresholds: jax.Array, *,
                      noise_idx: jax.Array | None = None,
                      noise_qidx: jax.Array | None = None) -> jax.Array:
    """Exact noisy votes for per-query shortlists.

    q_grid (B, seg, Lq, sl); s_grid (N, seg, L, sl); short_idx (B, K).
    Uses GLOBAL support indices for the noise counters, so votes are
    bit-identical to the full search. When `s_grid` holds only a SHARD of
    the store, pass `noise_idx` (B, K) with the global row of each
    candidate while `short_idx` stays shard-local -- this is what makes the
    sharded two-phase search bit-identical to the single-device one.
    `noise_qidx` (B,) is the query-side twin: the noise coordinate of each
    query (default arange(B), the batch position). `engine.search_tenants`
    passes each query's rank within its tenant group, so a batch mixing
    tenants rescores bit-identically to per-tenant solo calls.

    Only the B*K candidate rows of `s_grid` are read: they are gathered
    from the grid as stored and then flattened. The order matters on the
    chip, where the stored grid keeps N as its minor dimension: flattening
    all N rows first makes XLA relayout the whole grid on every call, while
    the gather reads the candidate rows in place. Gathering and then
    flattening gives the same array as flattening and then gathering.
    Returns votes (B, K).
    """
    B, K = short_idx.shape
    _, seg, L, sl = s_grid.shape
    S = seg * L
    q = flatten_strings(broadcast_query(q_grid, L))        # (B, S, sl)
    sg = s_grid[short_idx].reshape(B, K, S, sl)            # (B, K, S, sl)
    m = jnp.abs(q[:, None].astype(jnp.int32) - sg.astype(jnp.int32))
    m = m.astype(jnp.float32)                              # (B, K, S, sl)
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
    w_flat = jnp.tile(weights.astype(jnp.float32), seg)
    return (v * w_flat[None, None, :]).sum(-1)             # (B, K)


def two_phase_search(q_values: jax.Array, s_values: jax.Array, cfg,
                     k: int = 64) -> dict[str, jax.Array]:
    """Full beyond-paper pipeline. cfg: repro.core.avss.SearchConfig (avss).

    Backwards-compatible wrapper over the unified API: raw quantized arrays
    are programmed into an anonymous MemoryStore and searched through
    RetrievalEngine.search -- results bit-identical to the historical
    RetrievalEngine.two_phase(q, s, k) call.
    """
    from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
    store = MemoryStore.from_quantized(
        s_values, jnp.zeros((s_values.shape[0],), jnp.int32), cfg)
    res = RetrievalEngine(cfg).search(
        store, q_values, SearchRequest(mode="two_phase", k=k))
    return {"votes": res.votes, "dist": res.dist, "indices": res.indices,
            "iterations": res.iterations}
