"""Pallas TPU kernel: fused MCAM string search (mismatch -> current -> votes).

Computes, for every (query, support) pair in a VMEM-tiled block, the noisy
series-resistance string currents and SA vote accumulation of the simulated
NAND MCAM -- the full inner loop of AVSS/SVSS (see kernels/ref.py for the
exact semantics contract).

Blocking: grid (B/tb, N/tn). The wrapper moves the string axis in front,

    q (S, B, sl) int32,  s (S, sl, N) int8,

so each program holds a q tile (S, tb, sl) and an s tile (S, sl, tn) in VMEM
and its fori_loop over the S strings indexes the leading, untiled axis. The
sl cells of a string are unrolled: cell c is a static lane slice of q (tb, 1)
against a static row of s (1, tn), so every per-cell quantity -- mismatch,
noise, resistance -- is one (tb, tn) f32 block (one vreg at tb=8, tn=128).
Per-query noise coordinates arrive as a (B, 1) column and the per-string
weights and SA thresholds as SMEM scalars.

Noise is the counter-based hash of repro.core.mcam: the (query, string)
prefix of each stream is folded once per string and only the cell is folded
per cell, which is integer-exact. Cells are summed in the left-fold order of
`mcam.string_resistance`, so results are bit-identical to the reference
regardless of tiling or compiler.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mcam as mcam_lib
from repro.core.mcam import MCAMConfig
from repro.kernels.ref import READ_SEED_OFFSET

DEFAULT_TILE_B = 8
DEFAULT_TILE_N = 128


def _search_kernel(w_ref, th_ref, q_ref, s_ref, qidx_ref, votes_ref,
                   dist_ref, *, cfg: MCAMConfig, noisy: bool, S: int,
                   sl: int, tile_n: int, n_th: int):
    ni = pl.program_id(1)
    # per-query noise coordinate: an explicit input rather than the tile's
    # batch position, so a caller batching queries from INDEPENDENT stores
    # (engine.search_tenants) can reproduce each query's solo coordinates
    b_abs = jax.lax.bitcast_convert_type(qidx_ref[...], jnp.uint32)  # (tb, 1)
    n_abs = ni * tile_n + jax.lax.broadcasted_iota(jnp.int32, (1, tile_n), 1)
    seeds = (cfg.seed, cfg.seed + mcam_lib.NORMAL_PAIR_OFFSET,
             cfg.seed + READ_SEED_OFFSET,
             cfg.seed + READ_SEED_OFFSET + mcam_lib.NORMAL_PAIR_OFFSET)
    h_b = [mcam_lib.hash_fold(mcam_lib.hash_init(sd), b_abs, 0)
           for sd in seeds] if noisy else []

    def body(s, carry):
        votes, dist = carry
        qs = q_ref[s]                                     # (tb, sl) int32
        ss = s_ref[s].astype(jnp.int32)                   # (sl, tn)
        # int32 wraps like uint32 under * and +, so the vector bitcast
        # gives the reference's uint32 string id (Mosaic has no scalar one)
        string_id = jax.lax.bitcast_convert_type(n_abs * S + s, jnp.uint32)
        h_bs = [mcam_lib.hash_fold(h, string_id, 1) for h in h_b]
        r = msum = None
        for c in range(sl):
            m = jnp.abs(qs[:, c:c + 1] - ss[c:c + 1, :]).astype(jnp.float32)
            m_eff = m
            if noisy:
                dev = mcam_lib.box_muller(
                    mcam_lib.hash_to_uniform(
                        mcam_lib.hash_fold(h_bs[0], c, 2)),
                    mcam_lib.hash_to_uniform(
                        mcam_lib.hash_fold(h_bs[1], c, 2)))
                m_eff = mcam_lib.noisy_mismatch(m, dev, cfg)
            e = mcam_lib.cell_resistance(m_eff, cfg)
            r = e if r is None else r + e
            msum = m if msum is None else msum + m
        rd = None
        if noisy:
            rd = mcam_lib.box_muller(mcam_lib.hash_to_uniform(h_bs[2]),
                                     mcam_lib.hash_to_uniform(h_bs[3]))
        cur = mcam_lib.current_from_resistance(r, sl, cfg, read_noise=rd)
        v = (cur > th_ref[0]).astype(jnp.float32)
        for k in range(1, n_th):
            v = v + (cur > th_ref[k]).astype(jnp.float32)
        w = w_ref[s]
        return votes + w * v, dist + w * msum

    shape = votes_ref.shape
    zeros = jnp.zeros(shape, jnp.float32)
    votes, dist = jax.lax.fori_loop(0, S, body, (zeros, zeros))
    votes_ref[...] = votes
    dist_ref[...] = dist


def mcam_search_pallas(q_strings: jax.Array, s_strings: jax.Array,
                       weights: jax.Array, thresholds: jax.Array,
                       cfg: MCAMConfig, *, noisy: bool = True,
                       qidx: jax.Array | None = None,
                       tile_b: int = DEFAULT_TILE_B,
                       tile_n: int = DEFAULT_TILE_N,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """q (B, S, sl) int8, s (N, S, sl) int8 -> votes (B, N), dist (B, N).

    B and N must be multiples of the tile sizes (ops.py pads).
    qidx: optional (B,) uint32 per-query noise coordinates; default
    arange(B) -- the historical batch-position coordinate, bit-identical
    to the pre-parameter kernel.
    """
    B, S, sl = q_strings.shape
    N = s_strings.shape[0]
    assert B % tile_b == 0 and N % tile_n == 0, (B, N, tile_b, tile_n)
    if qidx is None:
        qidx = jnp.arange(B, dtype=jnp.uint32)
    assert qidx.shape == (B,), (qidx.shape, B)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    q_t = jnp.transpose(q_strings.astype(jnp.int32), (1, 0, 2))   # (S, B, sl)
    s_t = jnp.transpose(s_strings.astype(jnp.int8), (1, 2, 0))    # (S, sl, N)
    qcol = jax.lax.bitcast_convert_type(qidx.astype(jnp.uint32),
                                        jnp.int32)[:, None]        # (B, 1)
    n_th = thresholds.shape[0]
    kernel = functools.partial(
        _search_kernel, cfg=cfg, noisy=noisy, S=S, sl=sl, tile_n=tile_n,
        n_th=n_th)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((tile_b, tile_n), lambda i, j: (i, j))
    votes, dist = pl.pallas_call(
        kernel,
        grid=(B // tile_b, N // tile_n),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((S, tile_b, sl), lambda i, j: (0, i, 0)),
            pl.BlockSpec((S, sl, tile_n), lambda i, j: (0, 0, j)),
            pl.BlockSpec((tile_b, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((B, N), jnp.float32)] * 2,
        interpret=interpret,
    )(weights.astype(jnp.float32), thresholds.astype(jnp.float32), q_t, s_t,
      qcol)
    return votes, dist
