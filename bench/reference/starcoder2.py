"""Plain reference of StarCoder2 and of its kNN-LM head.

Written from the published description, in straightforward `jax.numpy`
and float32 under the highest matmul precision, and imports nothing of the
program under test:

* StarCoder2 (arXiv:2402.19173; hf bigcode/starcoder2-3b `config.json`):
  every layer is x += o(attn(LN1(x))) + b_o, then
  x += W2 gelu_tanh(W1 LN2(x) + b1) + b2; LayerNorm with a bias and
  `norm_eps`; grouped-query attention (`n_heads` query heads share
  `n_kv_heads` key/value heads), biases on q, k, v and o; rotary
  embeddings over the whole head (first half / second half rotated
  together, inverse frequencies theta ** (-2i / head_dim)); a causal mask
  within a sliding window: query i sees key j iff i - window < j <= i.
  A final LayerNorm, then logits against the tied embedding.
* kNN-LM (Khandelwal et al., arXiv:1911.00172): the served distribution
  is (1 - lam) p_LM + lam p_kNN, p_kNN putting the softmax of the k
  candidates' scores / temperature on each candidate's stored next token.
  Here the datastore is the MCAM store of arXiv:2409.07832, searched by
  `bench.reference.avss` (AVSS 4-level queries, noisy two-phase votes),
  and a candidate's score is its noisy vote count.

The decoder runs one layer at a time over one sequence, attention in
blocks of queries, so it fits on the chip beside the program. Weights are
read from the program's parameter tree as stored (bfloat16) and each
layer's are cast to float32 when it runs; `weight_dtype` first rounds
them to a lower precision (the control).

Departures from the published description: the weights are random from a
seed, not the released checkpoint; the key of a datastore row is the
post-final-norm hidden state (the vector the LM head reads) where the
kNN-LM paper keys on the final layer's input to the feed-forward block;
scores are MCAM votes, not negative L2 distances.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import avss

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256                         # queries per attention block

# the decoder's shape, as the configuration file's `decoder` block states it
SHAPE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "window", "norm_eps", "rope_theta")


def _f32(a, weight_dtype):
    if weight_dtype is not None:
        a = a.astype(weight_dtype)
    return a.astype(jnp.float32)


def _layernorm(x, p, eps, weight_dtype):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(p["scale"], weight_dtype) \
        + _f32(p["nbias"], weight_dtype)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, pos, theta):
    """x (S, H, D) rotated by the angles of positions pos (S,)."""
    d = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d),
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv                # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, block):
    """Causal attention of (S, H, D) queries over (S, KV, D) keys/values,
    within `window` (0: none), one block of queries at a time."""
    S, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    span = S if not window else -(-window // block) * block + block
    span = min(span, S)
    front = span - block
    kp = jnp.pad(k, ((front, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((front, 0), (0, 0), (0, 0)))

    def one(b):
        s0 = b * block
        qb = jax.lax.dynamic_slice_in_dim(q, s0, block).reshape(
            block, KV, G, D)
        kb = jax.lax.dynamic_slice_in_dim(kp, s0, span)
        vb = jax.lax.dynamic_slice_in_dim(vp, s0, span)
        qpos = s0 + jnp.arange(block)
        kpos = s0 - front + jnp.arange(span)
        ok = (kpos[None] <= qpos[:, None]) & (kpos[None] >= 0)
        if window:
            ok &= kpos[None] > qpos[:, None] - window
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kb, precision=HIGHEST) \
            / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, vb,
                          precision=HIGHEST).reshape(block, H, D)

    return jax.lax.map(one, jnp.arange(S // block)).reshape(S, H, D)


@partial(jax.jit, static_argnames=("shape", "window", "weight_dtype",
                                   "block"))
def _layer(group, i, x, *, shape, window, weight_dtype, block):
    cfg = dict(shape)
    p = jax.tree_util.tree_map(lambda a: a[i], group)
    w = lambda a: _f32(a, weight_dtype)
    pos = jnp.arange(x.shape[0])
    h = _layernorm(x, p["norm1"], cfg["norm_eps"], weight_dtype)
    a = p["attn"]
    q = jnp.einsum("sd,dhk->shk", h, w(a["wq"]), precision=HIGHEST) \
        + w(a["bq"])
    k = jnp.einsum("sd,dhk->shk", h, w(a["wk"]), precision=HIGHEST) \
        + w(a["bk"])
    v = jnp.einsum("sd,dhk->shk", h, w(a["wv"]), precision=HIGHEST) \
        + w(a["bv"])
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    o = _attention(q, k, v, window, block)
    x = x + jnp.einsum("shk,hkd->sd", o, w(a["wo"]), precision=HIGHEST) \
        + w(a["bo"])
    h = _layernorm(x, p["norm2"], cfg["norm_eps"], weight_dtype)
    m = p["mlp"]
    u = _gelu_tanh(jnp.dot(h, w(m["w1"]), precision=HIGHEST) + w(m["mb1"]))
    return x + jnp.dot(u, w(m["w2"]), precision=HIGHEST) + w(m["mb2"])


@partial(jax.jit, static_argnames=("weight_dtype",))
def _embed(embed, tokens, *, weight_dtype):
    return _f32(embed, weight_dtype)[tokens]


@partial(jax.jit, static_argnames=("eps", "weight_dtype"))
def _final_norm(norm, x, rows, *, eps, weight_dtype):
    return _layernorm(x[rows], norm, eps, weight_dtype)


@partial(jax.jit, static_argnames=("weight_dtype",))
def _logprobs(embed, h, *, weight_dtype):
    logits = jnp.einsum("pd,vd->pv", h, _f32(embed, weight_dtype),
                        precision=HIGHEST)
    return jax.nn.log_softmax(logits, -1)


def hidden_states(params, shape: dict, tokens, positions, *,
                  window: bool = True, weight_dtype=None,
                  block: int = BLOCK) -> jax.Array:
    """(P, d_model) post-final-norm hidden states at `positions` of one
    sequence `tokens` (S,): the forward pass over tokens[0 ..
    max(positions)]. `shape`: the decoder block of the configuration
    (`SHAPE_KEYS`). `window=False` attends past the sliding window (a
    control); `weight_dtype` rounds every weight to it first (a
    control)."""
    positions = np.asarray(positions)
    n = int(positions.max()) + 1
    padded = -(-n // block) * block
    toks = np.zeros(padded, np.int32)
    toks[:n] = np.asarray(tokens)[:n]
    cfg = {k: shape[k] for k in SHAPE_KEYS}
    frozen = tuple(sorted(cfg.items()))
    x = _embed(params["embed"], jnp.asarray(toks), weight_dtype=weight_dtype)
    (group,) = params["groups"]
    for i in range(cfg["n_layers"]):
        x = _layer(group, jnp.int32(i), x, shape=frozen,
                   window=cfg["window"] if window else 0,
                   weight_dtype=weight_dtype, block=block)
    return _final_norm(params["final_norm"], x, jnp.asarray(positions),
                       eps=cfg["norm_eps"], weight_dtype=weight_dtype)


def forward(params, shape: dict, tokens, positions, *,
            weight_dtype=None, **kw) -> tuple[jax.Array, jax.Array]:
    """((P, V) log-probabilities of the token after each of `positions`,
    (P, d_model) the hidden states the LM head reads there)
    (`hidden_states`)."""
    h = hidden_states(params, shape, tokens, positions,
                      weight_dtype=weight_dtype, **kw)
    return _logprobs(params["embed"], h, weight_dtype=weight_dtype), h


def lm_logprobs(params, shape: dict, tokens, positions, **kw) -> jax.Array:
    """(P, V) log-probabilities of the token after each of `positions`
    (`forward`)."""
    return forward(params, shape, tokens, positions, **kw)[0]


def rel_err(want, got) -> jax.Array:
    """(P,) |got - want| / |want| of rows of vectors, L2."""
    want = jnp.asarray(want, jnp.float32)
    got = jnp.asarray(got, jnp.float32)
    return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want,
                                                                  axis=-1)


def kl(ref_logprobs, logprobs) -> jax.Array:
    """(P,) KL(reference || other) in nats, per position."""
    ref_logprobs = jnp.asarray(ref_logprobs, jnp.float32)
    logprobs = jnp.asarray(logprobs, jnp.float32)
    return (jnp.exp(ref_logprobs) * (ref_logprobs - logprobs)).sum(-1)


# -- the kNN-LM head ---------------------------------------------------------

def datastore(keys, store: dict, write_batch: int):
    """The datastore the configuration's store keys describe, over keys
    (N, d) float32: the quantization range from the first write batch,
    every key quantized to 3 * CL + 1 levels. Returns (values (N, d)
    int32, lo, hi)."""
    keys = jnp.asarray(keys, jnp.float32)
    lo, hi = avss.calibrate(keys[:write_batch], store["clip_std"])
    quant = jax.jit(avss.quantize, static_argnums=1)
    levels = 3 * store["cl"] + 1
    values = jnp.concatenate([quant(keys[s:s + write_batch], levels, lo, hi)
                              for s in range(0, keys.shape[0], write_batch)])
    return values, lo, hi


def knn_search(queries, values, labels, lo, hi, store: dict, k: int) -> dict:
    """The two-phase AVSS search of float32 queries (B, d): shortlist,
    noisy votes and candidate labels (`bench.reference.avss.search`)."""
    qw = avss.quantize(jnp.asarray(queries, jnp.float32), 4, lo, hi)
    qidx = jnp.arange(qw.shape[0], dtype=jnp.uint32)
    return avss.search(qw, values, labels, qidx, store["cl"], k,
                       store["mcam"])


def knn_mixture(lm_logprobs, votes, labels, lam: float, temperature: float,
                dtype=jnp.float32) -> jax.Array:
    """(B, V) log of (1 - lam) p_LM + lam p_kNN, computed in `dtype`:
    p_kNN the softmax over the valid candidates (label >= 0) of votes /
    temperature, summed onto their labels."""
    lm = jnp.asarray(lm_logprobs).astype(dtype)
    votes = jnp.asarray(votes).astype(dtype)
    labels = jnp.asarray(labels)
    valid = labels >= 0
    s = jnp.where(valid, votes / jnp.asarray(temperature, dtype), -jnp.inf)
    w = jnp.exp(s - s.max(-1, keepdims=True))
    w = jnp.where(valid, w, 0) / jnp.where(valid, w, 0).sum(-1, keepdims=True)
    onehot = labels[..., None] == jnp.arange(lm.shape[-1])       # (B, K, V)
    p_knn = (w[..., None] * onehot).sum(-2)
    lam = jnp.asarray(lam, dtype)
    return jnp.log((1 - lam) * jnp.exp(lm) + lam * p_knn)
