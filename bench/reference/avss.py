"""Plain reference of the AVSS / MTMC search on a simulated 3D-NAND MCAM.

Written from the semantics the paper (arXiv:2409.07832, Sec. 3) and the
configuration files state, in straightforward `jax.numpy`, and imports
nothing of the program under test:

* Quantization: a sample's range is mean +- clip_std * std, clamped to the
  sample's extent; x -> round((clip(x, lo, hi) - lo) * (levels - 1) /
  (hi - lo)), clamped to [0, levels). Stored values take 3 * CL + 1
  levels, AVSS queries 4.
* MTMC code word c (of CL) of value v: x = v // CL, n = v % CL;
  word = x + 1 if c >= CL - n else x (clamped to [0, 3]). Hence
  sum_c |q - word_c(v)| = |CL * q - v| for an integer query word q: the
  ideal distance of a row is sum_d |CL * q_d - v_d|.
* Layout: a row's d dimensions are split into segments of `string_len`
  cells (zero-padded); string (seg, c) holds word c of the segment's
  dimensions, string id = row * (seg_count * CL) + seg * CL + c.
* Cell mismatch m = |q - word|; device noise perturbs it,
  m' = clip(m + sigma_device * N(b, string, cell), 0, 3); a string's
  resistance is the left fold over its cells of rho ** m'; its current is
  string_len / R * (1 + sigma_read * N'(b, string)); a string votes once
  per sense threshold its current exceeds, thresholds being the ideal
  currents of strings with s single-level mismatches, s geometrically
  spaced in [1, 1.5 * string_len]. N and N' are standard normals from a
  counter hash of the absolute coordinates (b = the query's position in
  its batch), so every row draws the same noise wherever it is searched.
* Phase 1 ranks rows by (ideal distance, row); rows never written (label
  -1) carry a 2**22 penalty. Phase 2 scores the k candidates by their
  noisy votes; a query's answer is the label of its best candidate: most
  votes, then least ideal distance, then earliest in the list.

`dtype` is the precision of every float computed (float32 as stated; the
control runs bfloat16). Integer quantities are exact in either.
`reordered` computes the votes with the same float32 meaning in another
order (rho ** m by `power`, the cells summed as a pairwise tree): what a
sound rewrite of the rescore may change, read to set the vote limit.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MASK_PENALTY = float(2 ** 22)
CELL_STATES = 4

# counter hash: a 32-bit finalizer folded over the coordinates, then the
# top 23 bits as a uniform in (0, 1); a normal is Box-Muller over two
# streams of the hash
_M1, _M2 = np.uint32(0x7FEB352D), np.uint32(0x846CA68B)
_GOLDEN = 0x9E3779B9
_SECOND_UNIFORM = 0x5BD1
_READ_NOISE = 0x2C1B


def _mix(x):
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    return x ^ (x >> 16)


def _uniform(coords, seed: int, dtype):
    h = jnp.uint32((seed * _GOLDEN + 0x85EBCA6B) & 0xFFFFFFFF)
    for k, c in enumerate(coords):
        step = np.uint32(((k + 1) * _GOLDEN) & 0xFFFFFFFF)
        h = _mix((jnp.asarray(c).astype(jnp.uint32) + step) ^ h)
    top = jax.lax.bitcast_convert_type(h >> 9, jnp.int32)
    u = (top.astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -23)
    return u.astype(dtype)


def normal(coords, seed: int, dtype=jnp.float32):
    """Standard normal at integer coordinates (broadcasting)."""
    u1 = _uniform(coords, seed, dtype)
    u2 = _uniform(coords, seed + _SECOND_UNIFORM, dtype)
    two_pi = jnp.asarray(2.0, dtype) * jnp.asarray(np.pi, dtype)
    return jnp.sqrt(jnp.asarray(-2.0, dtype) * jnp.log(u1)) \
        * jnp.cos(two_pi * u2)


def thresholds(mcam: dict) -> np.ndarray:
    """(n_thresholds,) ascending sense thresholds (float32)."""
    sl, rho, n = mcam["string_len"], mcam["rho"], mcam["n_thresholds"]
    s = np.unique(np.round(np.geomspace(1.0, 1.5 * sl, n)))
    while len(s) < n:
        s = np.unique(np.concatenate(
            [s, s[-1:] + np.arange(1, 1 + n - len(s))]))
    s = s[:n].astype(np.float64)
    return np.sort(sl / ((sl - s) + s * rho)).astype(np.float32)


def calibrate(sample: jax.Array, clip_std: float, dtype=jnp.float32):
    x = sample.astype(dtype)
    mu, sd = x.mean(), x.std() + jnp.asarray(1e-8, dtype)
    lo = jnp.maximum(mu - jnp.asarray(clip_std, dtype) * sd, x.min())
    hi = jnp.minimum(mu + jnp.asarray(clip_std, dtype) * sd,
                     x.max() + jnp.asarray(1e-8, dtype))
    return lo, hi


def quantize(x: jax.Array, levels: int, lo, hi,
             dtype=jnp.float32) -> jax.Array:
    x = x.astype(dtype)
    scale = jnp.asarray(levels - 1, dtype) / (hi - lo)
    q = jnp.round((jnp.clip(x, lo, hi) - lo) * scale)
    return jnp.clip(q, 0, levels - 1).astype(jnp.int32)


def words(values: jax.Array, cl: int) -> jax.Array:
    """(..., d) values -> (..., d, cl) MTMC code words."""
    x, n = values // cl, values % cl
    c = jnp.arange(cl, dtype=jnp.int32)
    w = x[..., None] + (c >= (cl - n)[..., None]).astype(jnp.int32)
    return jnp.clip(w, 0, CELL_STATES - 1)


def _segments(x: jax.Array, string_len: int) -> jax.Array:
    """(..., d) -> (..., seg, string_len), zero-padded."""
    d = x.shape[-1]
    seg = -(-d // string_len)
    pad = seg * string_len - d
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], seg, string_len)


def layout(values: jax.Array, cl: int, string_len: int) -> jax.Array:
    """(n, d) values -> (n, seg, cl, string_len) int8 programmed cells."""
    w = jnp.moveaxis(words(values, cl), -1, -2)          # (n, cl, d)
    w = _segments(w, string_len)                         # (n, cl, seg, sl)
    return jnp.moveaxis(w, -3, -2).astype(jnp.int8)      # (n, seg, cl, sl)


@partial(jax.jit, static_argnames=("cl", "k"))
def _block_topk(q_words, vals, ok, start, *, cl, k):
    dist = jnp.abs(cl * q_words[:, None, :] - vals[None]).sum(-1)
    dist = dist.astype(jnp.float32) + jnp.where(ok, 0.0, MASK_PENALTY)
    neg, i = jax.lax.top_k(-dist, k)
    return -neg, i.astype(jnp.int32) + start


@partial(jax.jit, static_argnames=("k",))
def _merge(d0, i0, d1, i1, *, k):
    d, i = jax.lax.sort((jnp.concatenate([d0, d1], 1),
                         jnp.concatenate([i0, i1], 1)),
                        dimension=1, num_keys=2)
    return d[:, :k], i[:, :k]


def ideal_topk(q_words: jax.Array, values: jax.Array, valid: jax.Array,
               cl: int, k: int, block: int = 32768):
    """Top-k rows by (ideal distance + mask penalty, row): dist (B, k)
    float32 and rows (B, k) int32, computed block by block."""
    n = values.shape[0]
    best = None
    for s in range(0, n, block):
        e = min(n, s + block)
        cur = _block_topk(q_words, values[s:e], valid[s:e], jnp.int32(s),
                          cl=cl, k=min(k, e - s))
        best = cur if best is None else _merge(*best, *cur, k=k)
    return best


def votes(q_words: jax.Array, cand_values: jax.Array, cand_rows: jax.Array,
          qidx: jax.Array, cl: int, mcam: dict, dtype=jnp.float32,
          reordered: bool = False) -> jax.Array:
    """Noisy MCAM votes (B, K) of candidate rows cand_values (B, K, d)
    (global rows cand_rows (B, K)) for AVSS query words (B, d)."""
    return _votes(q_words, cand_values, cand_rows, qidx, cl=cl,
                  mcam=tuple(sorted(mcam.items())), dtype=dtype,
                  reordered=reordered)


def _pairwise_sum(x):
    """Sum over the last axis as a balanced tree of pairwise adds."""
    parts = [x[..., c] for c in range(x.shape[-1])]
    while len(parts) > 1:
        parts = [parts[i] + parts[i + 1] if i + 1 < len(parts) else parts[i]
                 for i in range(0, len(parts), 2)]
    return parts[0]


@partial(jax.jit, static_argnames=("cl", "mcam", "dtype", "reordered"))
def _votes(q_words, cand_values, cand_rows, qidx, *, cl, mcam, dtype,
           reordered):
    mcam = dict(mcam)
    sl = mcam["string_len"]
    grid = layout(cand_values.reshape(-1, cand_values.shape[-1]), cl, sl)
    b, kk = cand_rows.shape
    grid = grid.reshape(b, kk, *grid.shape[1:])          # (B, K, seg, L, sl)
    seg = grid.shape[2]
    qg = _segments(q_words, sl)[:, None, :, None, :]     # (B, 1, seg, 1, sl)
    m = jnp.abs(qg - grid.astype(jnp.int32)).astype(dtype)
    strings = seg * cl
    sid = (cand_rows.astype(jnp.uint32)[:, :, None, None] * jnp.uint32(strings)
           + (jnp.arange(seg, dtype=jnp.uint32)[:, None] * cl
              + jnp.arange(cl, dtype=jnp.uint32)[None, :])[None, None])
    bq = qidx.astype(jnp.uint32)[:, None, None, None]
    cell = jnp.arange(sl, dtype=jnp.uint32)
    seed = mcam["noise_seed"]
    dev = normal((bq[..., None], sid[..., None], cell), seed, dtype)
    rd = normal((bq, sid), seed + _READ_NOISE, dtype)
    m = jnp.clip(m + jnp.asarray(mcam["sigma_device"], dtype) * dev,
                 0.0, float(CELL_STATES - 1))
    if reordered:
        total = _pairwise_sum(jnp.power(jnp.asarray(mcam["rho"], dtype), m))
    else:
        r = jnp.exp(m * jnp.asarray(np.log(mcam["rho"]), dtype))
        total = r[..., 0]
        for c in range(1, sl):                           # left fold
            total = total + r[..., c]
    cur = jnp.asarray(sl, dtype) / total
    cur = cur * (1.0 + jnp.asarray(mcam["sigma_read"], dtype) * rd)
    th = jnp.asarray(thresholds(mcam)).astype(dtype)
    v = (cur[..., None] > th).sum(-1).astype(jnp.float32)
    return v.sum((-1, -2))                               # MTMC weights 1


def search(q_words: jax.Array, values: jax.Array, labels: jax.Array,
           qidx: jax.Array, cl: int, k: int, mcam: dict,
           dtype=jnp.float32, reordered: bool = False) -> dict:
    """Two-phase search of a store (values (N, d), labels (N,), -1 empty):
    phase-1 shortlist, phase-2 noisy votes, answer labels."""
    dist, rows = ideal_topk(q_words, values, labels >= 0, cl, k)
    lab = labels[rows]
    v = votes(q_words, values[rows], rows, qidx, cl, mcam, dtype, reordered)
    v = jnp.where(lab >= 0, v, -jnp.inf)
    answer = jnp.take_along_axis(lab, best(v, dist)[:, None], 1)[:, 0]
    return {"dist": dist, "indices": rows, "votes": v, "labels": lab,
            "answer": answer}


def best(votes, dist):
    """(B,) position of each query's best candidate: most votes, then
    least ideal distance, then earliest in the list."""
    votes, dist = jnp.asarray(votes), jnp.asarray(dist)
    top = votes.max(-1, keepdims=True)
    return jnp.argmin(jnp.where(votes == top, dist, jnp.inf), axis=-1)
