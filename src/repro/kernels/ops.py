"""Jitted wrappers around the Pallas MCAM kernels (padding, layout, dispatch).

Public entry points:

  mcam_search(q_grid, s_grid, weights, cfg, thresholds)
      Exact paper-faithful search; dispatches to the fused Pallas kernel
      (VPU path) with tile padding. Semantics == kernels/ref.py.

  avss_ideal_dist(q_values, s_values, enc)
      Ideal digital AVSS distance via the MXU LUT-matmul kernel.

  two_phase_search(q_values, s_values, cfg, k)
      Beyond-paper TPU pipeline: MXU shortlist (ideal distance) + exact noisy
      rescoring of the top-k candidates. Bit-identical votes to the full
      search for every support that makes the shortlist.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encodings as enc_lib
from repro.core import mcam as mcam_lib
from repro.core.encodings import Encoding
from repro.kernels import mcam_dist, ref
from repro.kernels import mcam_search as mcam_search_kernel


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


def projection_pack_bits(enc: Encoding, dtype=jnp.bfloat16) -> int:
    """Field width (4/8/16/32 bits) of the packed LUT projection for `enc`.

    The smallest width that holds every LUT entry AS STORED in a `dtype`
    projection (bf16 rounds entries >= 256, e.g. long weighted encodings,
    possibly up to the next power of two -- the packed words must reproduce
    the stored values bit-for-bit, not the ideal ones). 32 disables the
    shrink (1 word per int32) but keeps one code path.

    Pure host-side numpy (the LUT is a compile-time constant of the
    encoding), so it stays callable from inside jit traces."""
    lut = np.asarray(enc_lib.avss_sum_lut(enc), np.float32)
    m = float(lut.astype(np.dtype(dtype)).astype(np.float32).max())
    for bits in (4, 8, 16):
        if m < (1 << bits):
            return bits
    return 32


def pack_projection(proj: jax.Array, enc: Encoding) -> jax.Array:
    """(N, C) integer-valued LUT projection -> (N, ceil(C/wpi)) int32.

    wpi = 32 / projection_pack_bits(enc, proj.dtype) projection columns per
    int32 word: column m of the packed word holds projection columns
    {w*dp + m, w in [0, wpi)} with dp = ceil(C/wpi), i.e. the column axis is
    split into wpi CONTIGUOUS chunks so the kernel unpacks with shift/mask
    and dots each chunk against the matching contiguous query slice -- no
    in-kernel reshapes or query reordering. Materialised once at
    MemoryStore.write time (the searches jit against it as a constant);
    shrinks the fused-shortlist streamed operand up to 8x."""
    bits = projection_pack_bits(enc, proj.dtype)
    wpi = 32 // bits
    p = proj.astype(jnp.int32)
    n, c = p.shape
    dp = -(-c // wpi)
    if c != dp * wpi:
        p = jnp.pad(p, ((0, 0), (0, dp * wpi - c)))
    parts = p.reshape(n, wpi, dp)
    shifts = (jnp.arange(wpi, dtype=jnp.int32) * bits)[None, :, None]
    # fields occupy disjoint bit ranges, so the (modular) sum IS the
    # bitwise-or of the shifted fields
    return jnp.sum(parts << shifts, axis=1).astype(jnp.int32)


def query_onehot(q_values: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """(B, d) ints in [0,4) -> (B, 4*d) one-hot."""
    oh = jax.nn.one_hot(q_values, enc_lib.CELL_STATES, dtype=dtype)
    return oh.reshape(q_values.shape[0], -1)


def avss_ideal_dist(q_values: jax.Array, s_values: jax.Array, enc: Encoding,
                    dtype=jnp.bfloat16, proj: jax.Array | None = None
                    ) -> jax.Array:
    """(B, N) exact digital AVSS distances on the MXU.

    proj: optional precomputed write-time projection (MemoryStore.proj);
    it IS support_projection(s_values, enc) -- a deterministic function of
    the values -- so passing it changes nothing but when it is computed.
    """
    q1h = query_onehot(q_values, dtype)
    sp = support_projection(s_values, enc, dtype) if proj is None \
        else proj.astype(dtype)
    B, K = q1h.shape
    N = sp.shape[0]
    tm, tn, tk = 8, 512, 512
    q1h = _pad_to(_pad_to(q1h, 0, tm), 1, tk)
    sp = _pad_to(_pad_to(sp, 0, tn), 1, tk)
    out = mcam_dist.lut_dist_matmul(q1h, sp, tile_m=tm, tile_n=tn, tile_k=tk)
    return out[:B, :N]


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
    RetrievalEngine.search (MXU shortlist backend) -- results bit-identical
    to the historical RetrievalEngine.two_phase(q, s, k) call.
    """
    from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
    store = MemoryStore.from_quantized(
        s_values, jnp.zeros((s_values.shape[0],), jnp.int32), cfg)
    res = RetrievalEngine(cfg, backend="mxu").search(
        store, q_values, SearchRequest(mode="two_phase", k=k))
    return {"votes": res.votes, "dist": res.dist, "indices": res.indices,
            "iterations": res.iterations}


# The integer-exact penalty added to the phase-1 distance of masked-out
# support rows lives with the kernel that applies it natively; re-exported
# here (its historical home) for the engine and the test suite.
from repro.kernels.shortlist import SHORTLIST_MASK_PENALTY  # noqa: E402


def lut_shortlist(q_values: jax.Array, s_values: jax.Array, enc: Encoding,
                  k: int, dtype=jnp.bfloat16, valid: jax.Array | None = None,
                  proj: jax.Array | None = None,
                  packed: jax.Array | None = None,
                  pack_bits: int | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Fused shortlist: (B, k) distances + indices without materialising the
    (B, N) distance matrix in HBM (kernels/shortlist.py).

    valid: optional (N,) bool; the kernel handles invalid rows natively
    (a per-row SHORTLIST_MASK_PENALTY block stream), so they sort after
    every valid row with no caller-side mask plumbing.
    proj: optional precomputed write-time projection (MemoryStore.proj),
    bit-identical to recomputing it from s_values here.
    packed: optional bit-packed projection (MemoryStore.proj_packed, from
    `pack_projection`); when given it is streamed INSTEAD of the wide
    projection -- up to 8x less kernel HBM traffic, bit-identically.
    pack_bits: the field width `packed` was PACKED with
    (MemoryStore.pack_bits / projection_pack_bits at pack time). Pass it
    whenever the packing dtype can differ from `proj`/`dtype` here: the
    width is a property of the packed operand, and re-deriving it from a
    different dtype mis-unpacks large-LUT encodings (a bf16-rounded LUT
    entry can force 32-bit fields while the f32 projection packs to 16 --
    tests/test_analysis.py pins the b4e edge case).
    """
    from repro.kernels import shortlist as shortlist_kernel
    q1h = query_onehot(q_values, dtype)
    if packed is not None:
        bits = pack_bits if pack_bits is not None else projection_pack_bits(
            enc, proj.dtype if proj is not None else dtype)
        return shortlist_kernel.lut_shortlist_pallas(
            q1h, None, k, valid=valid, packed=packed, pack_bits=bits)
    sp = support_projection(s_values, enc, dtype) if proj is None \
        else proj.astype(dtype)
    return shortlist_kernel.lut_shortlist_pallas(q1h, sp, k, valid=valid)
