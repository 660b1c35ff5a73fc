"""Pure-jnp oracle for the MCAM search kernels.

Semantics contract (shared bit-exactly with the Pallas kernels):

  inputs:
    q_strings : (B, S, sl) int8   query words per string (AVSS queries are
                                  pre-broadcast over the L word strings)
    s_strings : (N, S, sl) int8   stored words; S = n_seg * L strings/support
    weights   : (S,) f32          per-string accumulation weight (Eq. 2)
    thresholds: (K,) f32          SA reference currents (ascending)

  per (b, n, s):
    m        = |q - s| per cell                               (f32)
    string_id= n * S + s
    dev      = hash_normal(b, string_id, cell; seed)
    m_eff    = clip(m + sigma_device * dev, 0, 3)
    R        = sum_cell rho ** m_eff
    I        = sl / R * (1 + sigma_read * hash_normal(b, string_id; seed+RD))
    votes   += weights[s] * sum_k (I > thresholds[k])
    dist    += weights[s] * sum_cell m

  outputs: votes (B, N) f32, dist (B, N) f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import mcam as mcam_lib
from repro.core.mcam import MCAMConfig

READ_SEED_OFFSET = 0x2C1B


def mcam_search_ref(q_strings: jax.Array, s_strings: jax.Array,
                    weights: jax.Array, thresholds: jax.Array,
                    cfg: MCAMConfig, *, noisy: bool = True,
                    query_chunk: int = 8) -> tuple[jax.Array, jax.Array]:
    B, S, sl = q_strings.shape
    N = s_strings.shape[0]

    string_id = (jnp.arange(N, dtype=jnp.uint32)[:, None] * jnp.uint32(S)
                 + jnp.arange(S, dtype=jnp.uint32)[None, :])        # (N, S)
    cell = jnp.arange(sl, dtype=jnp.uint32)

    def one_query(args):
        qs, b = args                                                # (S, sl)
        m = jnp.abs(qs[None].astype(jnp.int32)
                    - s_strings.astype(jnp.int32)).astype(jnp.float32)
        dev = rd = None
        if noisy:
            dev = mcam_lib.hash_normal(
                b, string_id[..., None], cell[None, None, :], seed=cfg.seed)
            rd = mcam_lib.hash_normal(b, string_id,
                                      seed=cfg.seed + READ_SEED_OFFSET)
        r = mcam_lib.string_resistance(m, cfg, device_noise=dev)   # (N, S)
        cur = mcam_lib.current_from_resistance(r, sl, cfg, read_noise=rd)
        v = (cur[..., None] > thresholds).sum(-1).astype(jnp.float32)
        votes = (v * weights[None, :]).sum(-1)                      # (N,)
        dist = (m.sum(-1) * weights[None, :]).sum(-1)
        return votes, dist

    bidx = jnp.arange(B, dtype=jnp.uint32)
    votes, dist = jax.lax.map(one_query, (q_strings, bidx),
                              batch_size=min(query_chunk, B))
    return votes, dist


def avss_dist_ref(q_values: jax.Array, s_values: jax.Array,
                  sum_lut: jax.Array) -> jax.Array:
    """Ideal (noise-free) AVSS digital distance via the (4, levels) LUT:
    dist[b, n] = sum_d LUT[q[b, d], v[n, d]]. Oracle for the MXU kernel."""
    # (B, d, levels) rows of the LUT selected by the query word
    q_rows = sum_lut[q_values]                     # (B, d, levels)
    v_onehot = jax.nn.one_hot(s_values, sum_lut.shape[1], dtype=sum_lut.dtype)
    return jnp.einsum("bdl,ndl->bn", q_rows, v_onehot)
