"""Model assembly: scan-over-layers transformer covering every assigned
architecture family (dense GQA, MoE, MLA, SWA hybrids, xLSTM, Mamba-parallel,
embedding-stub frontends).

Layers are partitioned into GROUPS of consecutive structurally-identical
layers (see ModelConfig.layer_groups); each group's parameters are stacked on
a leading axis and executed with lax.scan (+ optional jax.checkpoint remat),
keeping the HLO compact enough that a 126-layer 405B model compiles in
seconds on the multi-pod mesh.

Public API (all pure functions):
  init(key, cfg)                         -> params
  forward(params, cfg, batch, rules)     -> (logits, aux)        train
  loss_fn(params, cfg, batch, rules)     -> (loss, metrics)
  init_cache(cfg, batch, max_seq)        -> cache
  prefill(params, cfg, batch, max_seq, rules) -> (logits, hidden, cache)
  decode_step(params, cfg, tok, cache, pos, rules) -> (logits, cache)
  abstract_params(cfg) / shardings(cfg, mesh, rules)

`prefill` hands `decode_step` its cache in the decode layout: a sliding-
window layer keeps a ring of min(window, max_seq) slots, position p at
slot p % slots; a full-attention layer keeps max_seq slots. The decode
step reads the cache as it stands and writes the step's k/v into its slot
after the layer scan, so a donated cache is updated in place.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.analysis import hlo_contracts as hc
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.sharding import Rules, constrain

# --------------------------------------------------------------------------
# Init.
# --------------------------------------------------------------------------


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _init_layer(key, cfg: ModelConfig, layer_type: str, is_moe: bool):
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": L.norm_init(cfg)}
    if layer_type in ("attn", "swa"):
        p["attn"] = L.attn_init(ks[0], cfg, dt)
    elif layer_type == "mla":
        p["attn"] = L.mla_init(ks[0], cfg, dt)
    elif layer_type in ("hymba", "hymba_g"):
        p["attn"] = L.attn_init(ks[0], cfg, dt)
        p["mamba"] = ssm_lib.mamba_init(ks[3], cfg, dt)
    elif layer_type == "mlstm":
        p["cell"] = ssm_lib.mlstm_init(ks[0], cfg, dt)
    elif layer_type == "slstm":
        p["cell"] = ssm_lib.slstm_init(ks[0], cfg, dt)
    else:
        raise ValueError(layer_type)
    has_ffn = cfg.d_ff > 0 or is_moe
    if has_ffn and not cfg.parallel_block:
        p["norm2"] = L.norm_init(cfg)
    if is_moe:
        p["moe"] = moe_lib.moe_init(ks[1], cfg, dt)
    elif cfg.d_ff > 0:
        p["mlp"] = L.mlp_init(ks[1], cfg, dt)
    return p


def _dense_ffn_width(cfg: ModelConfig, is_moe: bool) -> int:
    if not is_moe and cfg.moe is not None and cfg.moe.dense_d_ff:
        return cfg.moe.dense_d_ff
    return cfg.d_ff


def init(key, cfg: ModelConfig):
    keys = jax.random.split(key, len(cfg.layer_groups()) + 2)
    dt = _dtype(cfg)
    params: dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        params["embed"] = (jax.random.normal(keys[0],
                                             (cfg.vocab_size, cfg.d_model))
                           * 0.02).astype(dt)
    groups = []
    for gi, (ltype, is_moe, count) in enumerate(cfg.layer_groups()):
        gcfg = _group_cfg(cfg, is_moe)
        gkeys = jax.random.split(keys[gi + 1], count)
        groups.append(jax.vmap(
            lambda k: _init_layer(k, gcfg, ltype, is_moe))(gkeys))
    params["groups"] = groups
    params["final_norm"] = L.norm_init(cfg)
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(keys[-1],
                                         (cfg.d_model, cfg.vocab_size), dt)
    return params


def _group_cfg(cfg: ModelConfig, is_moe: bool) -> ModelConfig:
    """Dense layers inside MoE models may use a wider dense FFN."""
    w = _dense_ffn_width(cfg, is_moe)
    if w != cfg.d_ff:
        import dataclasses
        return dataclasses.replace(cfg, d_ff=w)
    return cfg


# --------------------------------------------------------------------------
# Layer application (shared by train/prefill and decode).
# --------------------------------------------------------------------------


import os as _os

# Perf iteration 2 (REPRO_OPT>=2, EXPERIMENTS.md §Perf): an optimization
# barrier on each block output pins the residual-stream tensor to bf16 at
# the point where the SPMD partitioner inserts the tensor-parallel psum --
# without it XLA hoists the f32 upcast (feeding the next norm) above the
# all-reduce, doubling its bytes.
_OPT_LEVEL = int(_os.environ.get("REPRO_OPT", "0") or 0)


def _barrier(y):
    return jax.lax.optimization_barrier(y) if _OPT_LEVEL >= 2 else y


def _layer_apply(p, x, cfg: ModelConfig, ltype: str, is_moe: bool,
                 rules: Rules, *, cache=None, pos0=0, positions3=None,
                 decode: bool = False):
    """Returns (x, new_cache, aux)."""
    aux = {"load_balance": jnp.zeros((), jnp.float32),
           "z_loss": jnp.zeros((), jnp.float32)}
    h = L.apply_norm(p["norm1"], x, cfg)
    new_cache = cache
    if ltype in ("attn", "swa", "hymba", "hymba_g"):
        window = _window(cfg, ltype)
        is_hymba = ltype.startswith("hymba")
        acache = (cache["attn"] if (is_hymba and cache is not None)
                  else cache)
        y, acache = L.attn_apply(p["attn"], h, cfg, layer_window=window,
                                 cache=acache, pos0=pos0,
                                 positions3=positions3)
        if is_hymba:
            if decode:
                ym, scache = ssm_lib.mamba_apply_step(
                    p["mamba"], h, cfg, cache["ssm"])
            else:
                ym, scache = ssm_lib.mamba_apply_seq(
                    p["mamba"], h, cfg,
                    None if cache is None else cache["ssm"])
            y = 0.5 * (y + ym)
            new_cache = {"attn": acache, "ssm": scache}
        elif not is_hymba:
            new_cache = acache
    elif ltype == "mla":
        y, new_cache = L.mla_apply(p["attn"], h, cfg, cache=cache, pos0=pos0)
    elif ltype == "mlstm":
        if decode:
            y, new_cache = ssm_lib.mlstm_apply_step(p["cell"], h, cfg, cache)
        else:
            y, new_cache = ssm_lib.mlstm_apply_seq(p["cell"], h, cfg, cache)
    elif ltype == "slstm":
        if decode:
            y, new_cache = ssm_lib.slstm_apply_step(p["cell"], h, cfg, cache)
        else:
            y, new_cache = ssm_lib.slstm_apply_seq(p["cell"], h, cfg, cache)
    else:
        raise ValueError(ltype)

    if cfg.parallel_block:
        # command-r style: x + attn(norm(x)) + mlp(norm(x)), single norm
        f = _barrier(_ffn(p, h, cfg, is_moe, rules, aux))
        x = x + _barrier(y) + f
        return _decode_stream(x, rules, decode), new_cache, aux
    x = x + _barrier(y)
    if ("norm2" in p) and (is_moe or cfg.d_ff > 0):
        h2 = L.apply_norm(p["norm2"], x, cfg)
        x = x + _barrier(_ffn(p, h2, cfg, is_moe, rules, aux))
    return _decode_stream(x, rules, decode), new_cache, aux


def _window(cfg: ModelConfig, ltype: str) -> int:
    return cfg.window if ltype in ("swa", "hymba") else 0


def _decode_stream(x, rules, decode):
    """Perf iteration 5 (REPRO_OPT>=5): hidden-dim-sharded decode residual.

    With decode activations replicated over DP (iteration 3), the w2/wo
    output projections still conflict with the weights' FSDP rows and the
    partitioner gathers ~208 MB of weights per layer per token. Sharding the
    tiny (B, 1, D) residual stream on D over the FSDP axes instead makes
    every projection a local partial dot + a KB-scale activation all-reduce:
    weights never move."""
    if decode and _OPT_LEVEL >= 5:
        from repro.models.sharding import aconstrain
        x = aconstrain(x, "batch", None, "fsdp")
    return x


def _ffn(p, h, cfg: ModelConfig, is_moe: bool, rules: Rules, aux: dict):
    if is_moe:
        y, a = moe_lib.moe_apply(p["moe"], h, cfg, rules)
        aux["load_balance"] += a["load_balance"]
        aux["z_loss"] += a["z_loss"]
        return y
    if cfg.d_ff > 0:
        return L.mlp_apply(p["mlp"], h, cfg)
    return jnp.zeros_like(h)


def _scan_group(params_g, x, cfg, ltype, is_moe, rules, *, caches=None,
                pos0=0, positions3=None, decode=False,
                collect_cache=False):
    """Run `count` stacked layers with lax.scan. caches: stacked or the
    empty sentinel; new caches are collected only when requested (so train
    steps never materialise stacked KV tensors)."""
    gcfg = _group_cfg(cfg, is_moe)
    collect = collect_cache or decode

    def body(carry, xs):
        x, lb, zl = carry
        p, c = xs
        if _is_empty(c):
            c = None
        x, new_c, aux = _layer_apply(p, x, gcfg, ltype, is_moe, rules,
                                     cache=c, pos0=pos0,
                                     positions3=positions3, decode=decode)
        y = new_c if collect else jnp.zeros((0,))
        return (x, lb + aux["load_balance"], zl + aux["z_loss"]), y

    if cfg.remat and not decode:
        body = jax.checkpoint(body, prevent_cse=False)
    n = jax.tree_util.tree_leaves(params_g)[0].shape[0]
    if caches is None:
        caches = _none_tree(n)
    carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    if cfg.scan_layers:
        (x, lb, zl), new_caches = jax.lax.scan(body, carry,
                                               (params_g, caches))
        return x, new_caches, lb, zl
    # unrolled python loop (dry-run cost calibration: every layer body
    # appears in the HLO so cost_analysis counts all of them)
    ys = []
    for i in range(n):
        sl = jax.tree_util.tree_map(lambda a: a[i], (params_g, caches))
        carry, y = body(carry, sl)
        ys.append(y)
    x, lb, zl = carry
    new_caches = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a, 0), *ys) if ys else None
    return x, new_caches, lb, zl


def _none_tree(n):
    return jnp.zeros((n, 0))  # dummy scanned input when no cache exists


def _is_empty(c):
    return hasattr(c, "size") and getattr(c, "size", 1) == 0


# --------------------------------------------------------------------------
# Forward passes.
# --------------------------------------------------------------------------


def _embed_in(params, cfg: ModelConfig, batch, rules: Rules, pos0=0):
    if cfg.input_mode == "tokens":
        x = params["embed"][batch["tokens"]].astype(jnp.dtype(cfg.dtype))
    else:
        x = batch["embeddings"].astype(jnp.dtype(cfg.dtype))
    if cfg.pos_embed == "sinusoidal":
        S, D = x.shape[1], x.shape[2]
        pos = pos0 + jnp.arange(S)
        sin, cos = L.rope_sincos(pos, D, 10000.0)
        x = x + jnp.concatenate([sin, cos], -1)[None].astype(x.dtype)
    return constrain(x, rules, "batch", None, None)


def _final_hidden(params, cfg: ModelConfig, x):
    """The residual stream after the final norm: what the LM head reads."""
    return L.apply_norm(params["final_norm"], x, cfg)


def _head(params, cfg: ModelConfig, h, rules: Rules):
    """Vocab-sharded logits of post-norm hidden states, kept in the compute
    dtype: consumers must not gather the full vocab axis (loss_fn uses
    vocab-parallel CE)."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
    else:
        logits = h @ params["unembed"]
    if cfg.logit_softcap:
        logits = (jnp.tanh(logits.astype(jnp.float32) / cfg.logit_softcap)
                  * cfg.logit_softcap).astype(logits.dtype)
    return constrain(logits, rules, "batch", None, "tensor")


def _trunk(params, cfg: ModelConfig, batch, rules: Rules,
           collect_cache: bool):
    """Embedding and every layer group: (x, caches, load_balance, z_loss),
    the caches in the layout the layers produce (attention: the whole
    sequence's k/v)."""
    x = _embed_in(params, cfg, batch, rules)
    positions3 = batch.get("positions3")
    lb = zl = jnp.zeros((), jnp.float32)
    caches = []
    for params_g, (ltype, is_moe, count) in zip(params["groups"],
                                                cfg.layer_groups()):
        x, new_c, l, z = _scan_group(params_g, x, cfg, ltype, is_moe, rules,
                                     positions3=positions3,
                                     collect_cache=collect_cache)
        caches.append(new_c)
        lb, zl = lb + l, zl + z
    return x, caches, lb, zl


def forward(params, cfg: ModelConfig, batch, rules: Rules | None = None):
    """Train forward. batch: tokens (B,S) | embeddings (B,S,D)
    [+ positions3 (B,S,3) for mrope]. Returns (logits, aux). Serving
    prefills through `prefill`."""
    rules = rules or Rules(batch=(), fsdp=(), tensor=(), expert=())
    x, _, lb, zl = _trunk(params, cfg, batch, rules, False)
    logits = _head(params, cfg, _final_hidden(params, cfg, x), rules)
    return logits, {"load_balance": lb, "z_loss": zl}


def _ring(a, axis: int, S: int, T: int, fill):
    """Sequence axis `axis` of length S -> T decode slots: position p at
    slot p % T, the last T positions kept; unwritten slots hold `fill`."""
    if S >= T:
        a = jax.lax.slice_in_dim(a, S - T, S, axis=axis)
        return jnp.roll(a, (S - T) % T, axis=axis)
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, T - S)
    return jnp.pad(a, pad, constant_values=fill)


def _attn_decode_layout(c, window: int, S: int, max_seq: int):
    """Stacked prefill k/v (L, B, S, KV, D) and kpos (L, S) -> the cache
    `init_cache` lays out for `max_seq` positions."""
    T = min(window, max_seq) if window else max_seq
    if not window and S > T:
        raise ValueError(f"a prompt of {S} positions does not fit a "
                         f"{T}-slot full-attention cache")
    empty = jnp.iinfo(jnp.int32).max
    return {"k": _ring(c["k"], 2, S, T, 0), "v": _ring(c["v"], 2, S, T, 0),
            "kpos": _ring(c["kpos"].astype(jnp.int32), 1, S, T, empty)}


def prefill(params, cfg: ModelConfig, batch, max_seq: int,
            rules: Rules | None = None):
    """Serving prefill of a prompt batch (B, S), one forward pass.
    Returns (logits at the last position (B,1,V), post-final-norm hidden
    states (B,S,D), caches in the decode layout of
    `init_cache(cfg, B, max_seq)`), so decoding continues at position S.
    Attention (and hymba's attention) caches are converted; other layer
    states are the forward's own."""
    rules = rules or Rules(batch=(), fsdp=(), tensor=(), expert=())
    S = jax.tree_util.tree_leaves(batch)[0].shape[1]
    with jax.named_scope(hc.PREFILL_SCOPE):
        x, caches, _, _ = _trunk(params, cfg, batch, rules, True)
        h = _final_hidden(params, cfg, x)
        logits = _head(params, cfg, h[:, -1:], rules)
        out = []
        for c, (ltype, _, _) in zip(caches, cfg.layer_groups()):
            w = _window(cfg, ltype)
            if ltype in ("attn", "swa"):
                c = _attn_decode_layout(c, w, S, max_seq)
            elif ltype in ("hymba", "hymba_g"):
                c = {**c, "attn": _attn_decode_layout(c["attn"], w, S,
                                                      max_seq)}
            out.append(c)
    return logits, h, out


def loss_fn(params, cfg: ModelConfig, batch, rules: Rules | None = None):
    """Vocab-PARALLEL cross entropy: per-shard logsumexp + one-hot label
    contraction, so only (B, S)-sized statistics cross the tensor axis (the
    full fp32 (B, S, V) log-softmax would otherwise be all-gathered)."""
    logits, aux = forward(params, cfg, batch, rules)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)                      # (B, S)
    onehot = jax.nn.one_hot(labels, cfg.vocab_size, dtype=logits.dtype)
    label_logit = jnp.einsum("bsv,bsv->bs", lf,
                             onehot.astype(jnp.float32))
    nll = lse - label_logit
    mask = (labels >= 0).astype(jnp.float32)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_weight * aux["load_balance"] \
                    + 1e-3 * aux["z_loss"]
    return loss, {"nll": loss, "load_balance": aux["load_balance"]}


# --------------------------------------------------------------------------
# Decode.
# --------------------------------------------------------------------------


def _cache_for_layer(cfg: ModelConfig, ltype: str, batch: int, max_seq: int,
                     prefill: bool = False):
    dt = jnp.dtype(cfg.dtype)
    if ltype == "attn":
        return L.attn_cache_init(cfg, batch, max_seq, 0, dt)
    if ltype == "swa":
        return L.attn_cache_init(cfg, batch, max_seq, cfg.window, dt)
    if ltype == "mla":
        return L.mla_cache_init(cfg, batch, max_seq, dt)
    if ltype in ("hymba", "hymba_g"):
        w = cfg.window if ltype == "hymba" else 0
        return {"attn": L.attn_cache_init(cfg, batch, max_seq, w, dt),
                "ssm": ssm_lib.mamba_state_init(cfg, batch)}
    if ltype == "mlstm":
        return ssm_lib.mlstm_state_init(cfg, batch)
    if ltype == "slstm":
        return ssm_lib.slstm_state_init(cfg, batch)
    return None


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    caches = []
    for (ltype, is_moe, count) in cfg.layer_groups():
        one = _cache_for_layer(cfg, ltype, batch, max_seq)
        if one is None:
            caches.append(_none_tree(count))
        else:
            caches.append(jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (count,) + a.shape).copy(), one))
    return caches


def decode_step(params, cfg: ModelConfig, batch, caches, pos,
                rules: Rules | None = None, return_hidden: bool = False):
    """One token for every sequence. batch: tokens (B,1) | embeddings
    (B,1,D) [+ positions3 (B,1,3)]; pos: scalar int32 current position.
    Returns (logits (B,1,V), new_caches[, hidden (B,1,D)]), hidden being
    the post-final-norm state the LM head reads."""
    rules = rules or Rules(batch=(), fsdp=(), tensor=(), expert=())
    with jax.named_scope(hc.DECODER_SCOPE):
        x = _embed_in(params, cfg, batch, rules, pos0=pos)
        x = _decode_stream(x, rules, True)
        positions3 = batch.get("positions3")
        new_caches = []
        for params_g, caches_g, (ltype, is_moe, count) in zip(
                params["groups"], caches, cfg.layer_groups()):
            x, nc, _, _ = _scan_group(params_g, x, cfg, ltype, is_moe,
                                      rules, caches=caches_g, pos0=pos,
                                      positions3=positions3, decode=True)
            w = _window(cfg, ltype)
            if ltype in ("attn", "swa"):
                nc = L.attn_cache_write(caches_g, nc, pos, w)
            elif ltype in ("hymba", "hymba_g"):
                nc = {"attn": L.attn_cache_write(caches_g["attn"],
                                                 nc["attn"], pos, w),
                      "ssm": nc["ssm"]}
            new_caches.append(nc)
        h = _final_hidden(params, cfg, x)
        logits = _head(params, cfg, h, rules)
    if return_hidden:
        return logits, new_caches, h
    return logits, new_caches


# --------------------------------------------------------------------------
# Abstract params + shardings (dry-run path: no allocation).
# --------------------------------------------------------------------------


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))


from repro.models.sharding import legalize_spec as _legalize  # noqa: E402


def shardings(cfg: ModelConfig, mesh, rules: Rules):
    """NamedSharding tree for params (legalized against actual dims)."""
    aps = abstract_params(cfg)
    specs = L.param_specs(aps)

    def mk(leaf, spec):
        pspec = rules.resolve(*spec.logical)
        pspec = _legalize(pspec, leaf.shape, mesh)
        return NamedSharding(mesh, pspec)

    return jax.tree_util.tree_map(
        mk, aps, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
