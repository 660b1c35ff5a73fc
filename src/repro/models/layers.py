"""Transformer building blocks: norms, RoPE/M-RoPE, GQA/MLA/SWA attention,
MLPs, embeddings -- pure functional JAX with name-based logical sharding.

Parameter shardings are resolved from leaf NAMES (single source of truth in
PARAM_LOGICAL below): any params tree built here can be mapped to
NamedShardings via `param_specs(params)` regardless of nesting or of the
extra leading layer axis introduced by scan-over-layers stacking.
"""

from __future__ import annotations

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.sharding import ParamSpec, aconstrain

# --------------------------------------------------------------------------
# Name -> logical axes registry. Arrays may carry extra LEADING axes (layer
# stacking); logical tuples are right-aligned and left-padded with None.
# --------------------------------------------------------------------------

import os as _os

# Perf iteration 1 (REPRO_OPT=1, see EXPERIMENTS.md §Perf): embeddings
# vocab-sharded ONLY. FSDP-sharding the d_model dim makes the unembed
# contraction partial-sum the full fp32 logits over the data axis (measured
# 8 GB AR + 8 GB AG per microbatch on llama3-405b); vocab-only sharding
# keeps logits tensor-sharded with no logits-sized collective at all.
# Baseline (REPRO_OPT=0): FSDP+vocab sharding, as swept for the tables.
_OPT = _os.environ.get("REPRO_OPT", "0") != "0"

_EMBED_LOGICAL = ([(r"^unembed$", (None, "tensor")),
                   (r"^embed$", ("tensor", None))] if _OPT else
                  [(r"^unembed$", ("fsdp", "tensor")),
                   (r"^embed$", ("tensor", "fsdp"))])

PARAM_LOGICAL = _EMBED_LOGICAL + [
    (r"pos_embed$", (None, "fsdp")),
    (r"w[qkv]$", ("fsdp", "tensor", None)),
    (r"b[qkv]$", ("tensor", None)),
    (r"wo$", ("tensor", None, "fsdp")),
    (r"w[13]$", ("fsdp", "tensor")),
    (r"w2$", ("tensor", "fsdp")),
    (r"wq_a$|wkv_a$", ("fsdp", None)),
    (r"wq_b$|wkv_b$", (None, "tensor", None)),
    (r"wo_mla$", ("tensor", None, "fsdp")),
    (r"router$", ("fsdp", None)),
    (r"we[13]$", ("expert", None, "fsdp")),
    (r"we2$", ("expert", "fsdp", None)),
    (r"w_gates$", ("fsdp", "tensor")),
    (r"w_ogate$", ("fsdp", "tensor", None)),
    (r"r_(z|i|f|o)$", ("tensor", None, None)),
    (r"w_(z|i|f|o)$", ("fsdp", "tensor", None)),
    (r"in_proj$", ("fsdp", "tensor")),
    (r"out_proj$", ("tensor", "fsdp")),
    (r"conv_w$", (None, "tensor")),
    (r"x_proj$", ("tensor", None)),
    (r"dt_proj$", (None, "tensor")),
    (r"a_log$", ("tensor", None)),
    (r"head_w$", ("fsdp", "tensor")),
    # everything else (norm scales, small biases, gate vectors): replicated
    (r".", ()),
]


def logical_for(name: str, ndim: int) -> tuple:
    for pat, logical in PARAM_LOGICAL:
        if re.search(pat, name):
            pad = ndim - len(logical)
            return (None,) * pad + tuple(logical)
    return (None,) * ndim


def param_specs(params) -> object:
    """Pytree of ParamSpec mirroring `params` (works on ShapeDtypeStructs)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for path, leaf in flat:
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        specs.append(ParamSpec(logical_for(name, leaf.ndim)))
    return jax.tree_util.tree_unflatten(treedef, specs)


# --------------------------------------------------------------------------
# Initializers.
# --------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale_axis=None):
    """Normal init scaled by 1/sqrt(fan_in); scale_axis is the EXPLICIT
    fan-in value (defaults to shape[0])."""
    fan_in = shape[0] if scale_axis is None else scale_axis
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


# --------------------------------------------------------------------------
# Norms.
# --------------------------------------------------------------------------


def norm_init(cfg: ModelConfig, dim=None):
    dim = dim or cfg.d_model
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["nbias"] = jnp.zeros((dim,), jnp.float32)
    return p


_OPT = int(_os.environ.get("REPRO_OPT", "0") or 0)


def apply_norm(p, x, cfg: ModelConfig):
    eps = cfg.norm_eps
    if _OPT >= 4:
        # Perf iteration 4: statistics in f32, MULTIPLY in the compute dtype
        # -- keeps the residual stream free of f32 consumers so the
        # partitioner's psum stays bf16 (see EXPERIMENTS.md §Perf).
        xf = x.astype(jnp.float32)
        if cfg.norm == "layernorm":
            mu = xf.mean(-1, keepdims=True)
            var = xf.var(-1, keepdims=True)
            inv = jax.lax.rsqrt(var + eps)
            y = (x - mu.astype(x.dtype)) * inv.astype(x.dtype) \
                * p["scale"].astype(x.dtype) + p["nbias"].astype(x.dtype)
        else:
            var = (xf * xf).mean(-1, keepdims=True)
            y = x * jax.lax.rsqrt(var + eps).astype(x.dtype) \
                * p["scale"].astype(x.dtype)
        return y
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["nbias"]
    else:
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary embeddings (+ Qwen2-VL multimodal M-RoPE).
# --------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def rope_sincos(pos: jax.Array, dim: int, theta: float):
    """pos (..., S) -> sin/cos (..., S, dim/2)."""
    ang = pos[..., None].astype(jnp.float32) * rope_freqs(dim, theta)
    return jnp.sin(ang), jnp.cos(ang)


def mrope_sincos(pos3: jax.Array, dim: int, theta: float, sections: tuple):
    """pos3 (..., S, 3) -> sin/cos (..., S, dim/2) with the dim/2 frequency
    slots split across (temporal, height, width) position streams."""
    assert sum(sections) == dim // 2, (sections, dim)
    sin, cos = rope_sincos(jnp.moveaxis(pos3, -1, 0), dim, theta)  # (3,...,S,d/2)
    idx = np.repeat(np.arange(3), np.asarray(sections))            # (d/2,)
    sel = jax.nn.one_hot(jnp.asarray(idx), 3, dtype=sin.dtype)     # (d/2, 3)
    pick = lambda t: jnp.einsum("t...f,ft->...f", t, sel)
    return pick(sin), pick(cos)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x (B, S, H, D); sin/cos (B, S, D/2) or (S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:
        sin, cos = sin[None], cos[None]
    sin, cos = sin[:, :, None, :], cos[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Blockwise (flash-style) attention: online softmax over kv chunks.
# --------------------------------------------------------------------------


def _attn_scores_mask(qpos, kpos, window):
    m = kpos[None, :] <= qpos[:, None]
    if window:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def dot_attention(q, k, v, *, qpos, kpos, window=0, chunk=0,
                  kv_valid=None, softcap=0.0):
    """Grouped-query attention with absolute-position causal/window masking.

    q (B, S, H, D); k, v (B, T, KV, D); qpos (S,), kpos (T,) absolute
    positions; kv_valid optional (B, T) bool. Returns (B, S, H, D).
    """
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    DV = v.shape[-1]                     # may differ from D (MLA)
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, KV, G, D) * scale

    def scores_of(kc, kposc, validc):
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kc).astype(jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        m = _attn_scores_mask(qpos, kposc, window)
        if validc is not None:
            m = m[None, :, :] & validc[:, None, :]
            m = m[:, None, None]
        else:
            m = m[None, None, None]
        return jnp.where(m, s, -1e30)

    if not chunk or T <= chunk:
        s = scores_of(k, kpos, kv_valid)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgst,btkd->bskgd", p, v)
        return o.reshape(B, S, H, DV)

    n_chunks = T // chunk
    assert T % chunk == 0, (T, chunk)
    ks = k.reshape(B, n_chunks, chunk, KV, D)
    vs = v.reshape(B, n_chunks, chunk, KV, DV)
    kps = kpos.reshape(n_chunks, chunk)
    valids = None if kv_valid is None else kv_valid.reshape(B, n_chunks, chunk)

    def body(carry, xs):
        m_run, l_run, acc = carry
        kc, vc, kpc, vldc = xs
        s = scores_of(kc, kpc, vldc)                      # (B,KV,G,S,c)
        m_new = jnp.maximum(m_run, s.max(-1))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_run = l_run * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (m_new, l_run, acc), None

    init = (jnp.full((B, KV, G, S), -jnp.inf, jnp.float32),
            jnp.zeros((B, KV, G, S), jnp.float32),
            jnp.zeros((B, KV, G, S, DV), jnp.float32))
    xs = (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0), kps,
          None if valids is None else jnp.moveaxis(valids, 1, 0))
    (m_run, l_run, acc), _ = jax.lax.scan(body, init, xs)
    o = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return jnp.moveaxis(o, 3, 1).reshape(B, S, H, DV).astype(q.dtype)


# --------------------------------------------------------------------------
# GQA attention layer (with SWA + decode caches).
# --------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig, dtype):
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads, hd), dtype),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads, hd), dtype),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads, hd), dtype),
        "wo": dense_init(ks[3], (cfg.n_heads, hd, cfg.d_model), dtype,
                         scale_axis=cfg.n_heads * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
    if cfg.o_bias:
        p["bo"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _rope_for(cfg: ModelConfig, pos, positions3=None):
    if cfg.rope_type == "none":
        return None
    if cfg.rope_type == "mrope":
        assert positions3 is not None
        return mrope_sincos(positions3, cfg.hd, cfg.rope_theta,
                            cfg.mrope_sections)
    return rope_sincos(pos, cfg.hd, cfg.rope_theta)


def attn_apply(p, x, cfg: ModelConfig, *, layer_window=0, cache=None,
               pos0=0, positions3=None):
    """x (B, S, D). cache None (train/prefill) or dict(k, v, kpos) for decode.
    Returns (y, new_cache)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = aconstrain(q, "batch", None, "tensor", None)
    k = aconstrain(k, "batch", None, "tensor", None)
    v = aconstrain(v, "batch", None, "tensor", None)
    qpos = pos0 + jnp.arange(S)
    sc = _rope_for(cfg, qpos, positions3)
    if sc is not None:
        q = apply_rope(q, *sc)
        k = apply_rope(k, *sc)

    if cache is None:
        y = dot_attention(q, k, v, qpos=qpos, kpos=qpos,
                          window=layer_window, chunk=cfg.attn_chunk,
                          softcap=cfg.logit_softcap)
        new_cache = {"k": k, "v": v, "kpos": qpos}
    else:
        # decode: attend over the cache as it stands, its slot for this
        # position masked, and over this step's own k/v; the caller writes
        # k/v into the slot afterwards (`attn_cache_write`), so a donated
        # cache is updated in place
        T = cache["k"].shape[1]
        slot = cache_slot(T, pos0, layer_window)
        kp = cache["kpos"]
        valid = (kp <= pos0) & (jnp.arange(T) != slot)
        if layer_window:
            valid &= kp > pos0 - layer_window
        y = _decode_attention(q, cache["k"], cache["v"], k, v, valid,
                              cfg.logit_softcap)
        new_cache = {"k": k, "v": v}
    y = aconstrain(y, "batch", None, "tensor", None)
    y = jnp.einsum("bshk,hkd->bsd", y, p["wo"])
    if cfg.o_bias:
        y = y + p["bo"]
    y = aconstrain(y, "batch", None, None)
    return y, new_cache


def cache_slot(T: int, pos0, layer_window: int):
    """Cache slot of position `pos0` in a T-slot layer cache: a ring
    (`pos0 % T`) for a windowed layer, else the position itself."""
    return (pos0 % T) if layer_window else jnp.minimum(pos0, T - 1)


def _decode_attention(q, ck, cv, k, v, valid, softcap=0.0):
    """One query position against the cache (B, T, KV, D) where `valid`
    (T,), plus the step's own key/value (B, 1, KV, D): one softmax over
    the T + 1 scores. Returns (B, 1, H, D)."""
    B, _, H, D = q.shape
    KV = ck.shape[2]
    qg = q.reshape(B, 1, KV, H // KV, D) * (1.0 / math.sqrt(D))
    s_c = jnp.einsum("bskgd,btkd->bkgst", qg, ck).astype(jnp.float32)
    s_n = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    if softcap:
        s_c = jnp.tanh(s_c / softcap) * softcap
        s_n = jnp.tanh(s_n / softcap) * softcap
    s_c = jnp.where(valid, s_c, -1e30)
    m = jnp.maximum(s_c.max(-1, keepdims=True), s_n)
    p_c, p_n = jnp.exp(s_c - m), jnp.exp(s_n - m)
    den = p_c.sum(-1, keepdims=True) + p_n
    o = (jnp.einsum("bkgst,btkd->bskgd", (p_c / den).astype(cv.dtype), cv)
         + jnp.einsum("bkgst,btkd->bskgd", (p_n / den).astype(v.dtype), v))
    return o.reshape(B, 1, H, cv.shape[-1]).astype(q.dtype)


def attn_cache_write(cache, new, pos0, layer_window: int):
    """Write one decode step's k/v into stacked layer caches: `cache` k/v
    (L, B, T, KV, D) and kpos (L, T), `new` k/v (L, B, 1, KV, D)."""
    T = cache["k"].shape[2]
    slot = cache_slot(T, pos0, layer_window)
    at = lambda a, n: jax.lax.dynamic_update_slice(
        a, n.astype(a.dtype), (0, 0, slot) + (0,) * (a.ndim - 3))
    kp = jnp.full((cache["kpos"].shape[0], 1), pos0, cache["kpos"].dtype)
    return {"k": at(cache["k"], new["k"]), "v": at(cache["v"], new["v"]),
            "kpos": jax.lax.dynamic_update_slice(cache["kpos"], kp,
                                                 (0, slot))}


def attn_cache_init(cfg: ModelConfig, batch, max_seq, layer_window, dtype):
    T = min(layer_window, max_seq) if layer_window else max_seq
    return {
        "k": jnp.zeros((batch, T, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, T, cfg.n_kv_heads, cfg.hd), dtype),
        "kpos": jnp.full((T,), jnp.iinfo(jnp.int32).max, jnp.int32),
    }


# --------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V3).
# --------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig, dtype):
    m: MLAConfig = cfg.mla
    ks = jax.random.split(key, 5)
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wq_a": dense_init(ks[0], (cfg.d_model, m.q_lora_rank), dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, cfg.n_heads, qk_dim), dtype),
        "wkv_a": dense_init(ks[2],
                            (cfg.d_model, m.kv_lora_rank + m.qk_rope_dim),
                            dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wkv_b": dense_init(ks[3], (m.kv_lora_rank, cfg.n_heads,
                                    m.qk_nope_dim + m.v_dim), dtype),
        "wo_mla": dense_init(ks[4], (cfg.n_heads, m.v_dim, cfg.d_model),
                             dtype, scale_axis=cfg.n_heads * m.v_dim),
    }


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps) * scale
    return y.astype(x.dtype)


def mla_apply(p, x, cfg: ModelConfig, *, cache=None, pos0=0):
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    cq = _rms(x @ p["wq_a"], p["q_norm"])
    q = aconstrain(jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"]),
                   "batch", None, "tensor", None)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    kv_a = x @ p["wkv_a"]
    c_kv = _rms(kv_a[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = kv_a[..., m.kv_lora_rank:]                      # (B,S,rope)
    qpos = pos0 + jnp.arange(S)
    sin, cos = rope_sincos(qpos, m.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0]
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)

    if cache is None:
        kv = aconstrain(jnp.einsum("bsr,rhn->bshn", c_kv, p["wkv_b"]),
                        "batch", None, "tensor", None)
        k_nope, v = kv[..., :m.qk_nope_dim], kv[..., m.qk_nope_dim:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, H, m.qk_rope_dim))], -1)
        qf = jnp.concatenate([q_nope, q_rope], -1)
        y = dot_attention(qf, k, v, qpos=qpos, kpos=qpos,
                          chunk=cfg.attn_chunk)
        new_cache = {"ckv": c_kv, "krope": k_rope, "kpos": qpos}
    else:
        # absorbed decode: score against the cached LATENTS directly
        T = cache["ckv"].shape[1]
        slot = jnp.minimum(pos0, T - 1)
        ckv = jax.lax.dynamic_update_slice(cache["ckv"], c_kv, (0, slot, 0))
        krp = jax.lax.dynamic_update_slice(cache["krope"], k_rope, (0, slot, 0))
        kp = jax.lax.dynamic_update_slice(
            cache["kpos"], qpos.astype(jnp.int32), (slot,))
        w_uk = p["wkv_b"][..., :m.qk_nope_dim]               # (r, h, nope)
        q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
        s = (jnp.einsum("bshr,btr->bhst", q_abs, ckv)
             + jnp.einsum("bshk,btk->bhst", q_rope, krp)).astype(jnp.float32)
        s = s * scale
        valid = (kp <= pos0)[None, None, None, :]
        s = jnp.where(valid, s, -1e30)
        prob = jax.nn.softmax(s, -1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", prob, ckv)
        w_uv = p["wkv_b"][..., m.qk_nope_dim:]               # (r, h, v)
        y = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)
        new_cache = {"ckv": ckv, "krope": krp, "kpos": kp}
    y = jnp.einsum("bshv,hvd->bsd", y, p["wo_mla"])
    y = aconstrain(y, "batch", None, None)
    return y, new_cache


def mla_cache_init(cfg: ModelConfig, batch, max_seq, dtype):
    m: MLAConfig = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_seq, m.qk_rope_dim), dtype),
        "kpos": jnp.full((max_seq,), jnp.iinfo(jnp.int32).max, jnp.int32),
    }


# --------------------------------------------------------------------------
# MLP.
# --------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def mlp_init(key, cfg: ModelConfig, dtype, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], (cfg.d_model, d_ff), dtype),
         "w2": dense_init(ks[1], (d_ff, cfg.d_model), dtype)}
    if cfg.mlp_gated:
        p["w3"] = dense_init(ks[2], (cfg.d_model, d_ff), dtype)
    if cfg.mlp_bias:
        p["mb1"] = jnp.zeros((d_ff,), dtype)
        p["mb2"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def mlp_apply(p, x, cfg: ModelConfig):
    act = _ACTS[cfg.mlp_act]
    h = aconstrain(x @ p["w1"], "batch", None, "tensor")
    if cfg.mlp_bias:
        h = h + p["mb1"]
    h = act(h)
    if cfg.mlp_gated:
        h = h * aconstrain(x @ p["w3"], "batch", None, "tensor")
    y = h @ p["w2"]
    y = aconstrain(y, "batch", None, None)
    if cfg.mlp_bias:
        y = y + p["mb2"]
    return y
