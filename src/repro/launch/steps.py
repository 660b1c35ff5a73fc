"""Step builders: jitted/shardable train, prefill and serve steps + the
ShapeDtypeStruct `input_specs` used by the multi-pod dry-run.

Sharding summary (logical axes resolved by repro.models.sharding.Rules and
LEGALIZED against actual dims -- indivisible axes shift right or drop):

  params      name-based specs (layers.PARAM_LOGICAL); FSDP rows over
              ("pod","data"), tensor columns over "model", experts over
              "model" with FSDP'd expert FFN width.
  opt state   inherits the tracked param's sharding (ZeRO); 8-bit block
              states shard their block axis over the FSDP axes.
  batch       (accum, microbatch, ...) with microbatch over ("pod","data").
  kv caches   batch over DP; heads over "model" when divisible, else
              head_dim; for batch=1 long-context the DP axes legalize onto
              the sequence axis => sequence-parallel cache (SP).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import hlo_contracts as hc
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.models import transformer as tfm
from repro.models.sharding import Rules, rules_for_mesh
from repro.optim import clip_by_global_norm, make_optimizer
from repro.runtime.compression import with_error_feedback

# --------------------------------------------------------------------------
# Config adaptation per (arch x shape).
# --------------------------------------------------------------------------

# microbatch sizes for train_4k (global batch 256) chosen from the HBM model
# in DESIGN.md / EXPERIMENTS.md Sec. Dry-run
TRAIN_MICROBATCH = {
    "llama3-405b": 64,
    "deepseek-v3-671b": 64,
    "qwen1.5-110b": 128,
    "command-r-plus-104b": 128,
}

# optimizer choice at scale (moment memory -- see optim/optimizers.py)
ARCH_OPTIMIZER = {
    "llama3-405b": ("adamw", {"state_dtype": jnp.bfloat16}),
    "qwen1.5-110b": ("adamw", {"state_dtype": jnp.bfloat16}),
    "command-r-plus-104b": ("adamw", {"state_dtype": jnp.bfloat16}),
    "deepseek-v3-671b": ("adafactor", {}),
}


def decode_rules(mesh) -> Rules:
    """Perf iteration 3 (REPRO_OPT>=3, EXPERIMENTS.md §Perf): serving rules.

    Decode activations are tiny (B x 1 x D); sharding their batch over the
    data axis CONFLICTS with the FSDP row sharding of the weights on that
    same axis, so the partitioner all-gathers every layer's weights every
    token (~50 GB/step on llama3-405b). Replicating decode activations over
    DP removes the conflict (weights stay put, partial-sum ARs are KBs);
    the KV cache shards its sequence axis over ALL chips (ring-attention
    layout: softmax stats cross chips, the cache never does).
    """
    names = mesh.axis_names
    dp = ("pod", "data") if "pod" in names else ("data",)
    return Rules(batch=(), fsdp=dp, tensor=("model",), expert=("model",),
                 seq=dp + ("model",))


import os as _os

OPT_LEVEL = int(_os.environ.get("REPRO_OPT", "0") or 0)


def rules_for(mesh, shape: ShapeConfig) -> Rules:
    from repro.models.sharding import rules_for_mesh
    if shape.kind == "decode" and OPT_LEVEL >= 3:
        return decode_rules(mesh)
    return rules_for_mesh(mesh)


def adapt_config(cfg: ModelConfig, shape: ShapeConfig, dp: int) -> ModelConfig:
    """Resolve execution knobs that depend on the deployment."""
    upd = {}
    if cfg.moe is not None:
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                       else 1)
        g = max(dp, tokens // 1024)
        g = (g // dp) * dp or dp
        upd["moe"] = dataclasses.replace(cfg.moe, groups=g)
    if shape.kind != "train":
        upd["remat"] = False
    if shape.seq_len >= 16384 and cfg.attn_chunk:
        upd["attn_chunk"] = 2048
    if OPT_LEVEL >= 6 and shape.kind == "train" and shape.seq_len <= 4096:
        # Perf iteration 6: at 4k the full (S, S) score tile fits per-device
        # HBM; the online-softmax chunk scan re-reads the q block and
        # rescales the accumulator per chunk, costing extra HBM passes.
        upd["attn_chunk"] = 0
    return dataclasses.replace(cfg, **upd) if upd else cfg


def microbatch_for(cfg: ModelConfig, shape: ShapeConfig) -> int:
    if shape.kind != "train":
        return shape.global_batch
    if shape.microbatch:
        return shape.microbatch
    return TRAIN_MICROBATCH.get(cfg.name, shape.global_batch)


def optimizer_for(cfg: ModelConfig, tc: TrainConfig):
    name, kw = ARCH_OPTIMIZER.get(cfg.name, (tc.optimizer, {}))
    return make_optimizer(name, tc.learning_rate, **kw)


# --------------------------------------------------------------------------
# Sharding trees.
# --------------------------------------------------------------------------


def param_shardings(cfg: ModelConfig, mesh, rules: Rules):
    return tfm.shardings(cfg, mesh, rules)


def opt_shardings(opt_shapes, params_abs, p_shardings, mesh, rules: Rules):
    """Moments with the param's shape inherit its sharding; blocked 8-bit
    states shard dim0 over FSDP; scalars replicate."""
    by_shape = {}
    for p, s in zip(jax.tree_util.tree_leaves(params_abs),
                    jax.tree_util.tree_leaves(p_shardings)):
        by_shape[p.shape] = s

    rep = NamedSharding(mesh, P())
    fsdp = rules.resolve("fsdp")[0]

    def mk(leaf):
        if leaf.shape in by_shape and len(leaf.shape):
            return by_shape[leaf.shape]
        if leaf.ndim >= 1:
            spec = tfm._legalize(P(fsdp), leaf.shape, mesh)
            return NamedSharding(mesh, spec)
        return rep

    return jax.tree_util.tree_map(mk, opt_shapes)


_CACHE_LOGICAL = {
    "k": (None, "batch", None, "tensor", None),
    "v": (None, "batch", None, "tensor", None),
    "kpos": (),
    "ckv": (None, "batch", None, "tensor"),
    "krope": (None, "batch", None, None),
    "C": (None, "batch", "tensor", None, None),
    "n": (None, "batch", "tensor", None),
    "m": (None, "batch", None),
    "h": (None, "batch", "tensor", None),
    "c": (None, "batch", "tensor", None),
    "conv": (None, "batch", None, "tensor"),
}


def cache_shardings(cfg: ModelConfig, batch: int, max_seq: int, mesh,
                    rules: Rules):
    """KV caches: batch over DP; the model axis goes to KV heads when
    divisible, OTHERWISE to the sequence axis (sequence-parallel cache: the
    attention contraction then reduces tiny softmax stats instead of
    gathering the cache -- the ring-attention layout)."""
    import numpy as np
    tensor_size = int(np.prod([mesh.shape[a] for a in rules.tensor])) \
        if rules.tensor else 1
    seq_size = int(np.prod([mesh.shape[a] for a in rules.seq])) \
        if rules.seq else 0
    cache_abs = jax.eval_shape(lambda: tfm.init_cache(cfg, batch, max_seq))
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_abs)
    out = []
    for path, leaf in flat:
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if (name in ("k", "v", "ckv") and leaf.ndim >= 4 and seq_size
                and leaf.shape[2] % seq_size == 0):
            # serving layout: sequence sharded over every mesh axis
            logical = (None, None, "seq", None, None)[:leaf.ndim]
        elif name in ("k", "v", "ckv") and leaf.ndim >= 4:
            # (Lg, B, T, KV[, hd]) / (Lg, B, T, r)
            kv_dim = 3 if name in ("k", "v") else 3
            kv_ok = leaf.shape[kv_dim] % tensor_size == 0 \
                if name in ("k", "v") else leaf.shape[3] % tensor_size == 0
            if kv_ok:
                logical = (None, "batch", None, "tensor", None)[:leaf.ndim]
            elif leaf.shape[2] % tensor_size == 0:
                logical = (None, "batch", "tensor", None, None)[:leaf.ndim]
            else:
                logical = (None, "batch", None, None, None)[:leaf.ndim]
        else:
            logical = _CACHE_LOGICAL.get(name, ())
            logical = logical[:leaf.ndim]
            logical = (None,) * (leaf.ndim - len(logical)) + tuple(logical)
        spec = rules.resolve(*logical)
        spec = tfm._legalize(spec, leaf.shape, mesh)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out), cache_abs


# --------------------------------------------------------------------------
# Input specs (dry-run contract: weak-type-correct, shardable, no alloc).
# --------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: Rules):
    """ShapeDtypeStruct stand-ins (with shardings) for every model input of
    the step that `shape` lowers."""
    # helper building a struct with a legalized sharding for ITS shape
    def struct(shp, dtype, logical):
        spec = rules.resolve(*logical)
        spec = tfm._legalize(spec, shp, mesh)
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, spec))

    S = shape.seq_len
    if shape.kind == "train":
        mb = microbatch_for(cfg, shape)
        accum = shape.global_batch // mb
        lead = (accum, mb)
        llog = (None, "batch")
    else:
        lead = (shape.global_batch,)
        llog = ("batch",)

    batch = {}
    seq = S if shape.kind != "decode" else 1
    if cfg.input_mode == "tokens":
        batch["tokens"] = struct(lead + (seq,), jnp.int32, llog + (None,))
    else:
        batch["embeddings"] = struct(lead + (seq, cfg.d_model), jnp.bfloat16,
                                     llog + (None, None))
        if cfg.rope_type == "mrope":
            batch["positions3"] = struct(lead + (seq, 3), jnp.int32,
                                         llog + (None, None))
    if shape.kind == "train":
        batch["labels"] = struct(lead + (seq,), jnp.int32, llog + (None,))
    return batch


# --------------------------------------------------------------------------
# Steps.
# --------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, tc: TrainConfig, rules: Rules,
                    unroll_accum: bool = False):
    """(params, opt_state, batch) -> (params, opt_state, metrics).
    batch leaves carry a leading accumulation axis. unroll_accum unrolls the
    accumulation loop (dry-run cost calibration)."""
    optimizer = optimizer_for(cfg, tc)

    def train_step(params, opt_state, batch):
        accum = jax.tree_util.tree_leaves(batch)[0].shape[0]

        def micro(carry, mb):
            gsum, lsum = carry
            (loss, _), grads = jax.value_and_grad(
                tfm.loss_fn, has_aux=True)(params, cfg, mb, rules)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            return (gsum, lsum + loss), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        carry = (zeros, jnp.zeros(()))
        if unroll_accum:
            for i in range(accum):
                mb = jax.tree_util.tree_map(lambda a: a[i], batch)
                carry, _ = micro(carry, mb)
            gsum, lsum = carry
        else:
            (gsum, lsum), _ = jax.lax.scan(micro, carry, batch)
        grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
        loss = lsum / accum

        if tc.grad_compression == "int8":
            grads, ef = with_error_feedback(grads,
                                            opt_state.get("ef_residual"))
            opt_state = {**opt_state, "ef_residual": ef}

        grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
        inner = {k: v for k, v in opt_state.items() if k != "ef_residual"}
        updates, inner_new = optimizer.update(grads, inner, params)
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        # in-step anomaly guard: non-finite => keep old params (skip)
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        new_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new_params, params)
        new_inner = jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), inner_new, inner)
        out_state = {**opt_state, **new_inner}
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "applied": ok.astype(jnp.float32)}
        return new_params, out_state, metrics

    return train_step, optimizer


def make_hat_train_steps(apply_fn, hat_cfg, pre_optimizer,
                         meta_optimizer=None, *, n_way: int,
                         mesh=None, data_axes=("data",)):
    """Two-stage hardware-aware trainer steps (paper Sec. 3.3).

    Stage 1 (`pretrain_step`): controller + linear head, plain CE over the
    full training class set. Stage 2 (`meta_step`): episodic CE THROUGH the
    simulated MCAM -- `repro.core.hat.meta_loss`, whose forward is the
    engine's own differentiable episodic path
    (`RetrievalEngine.episode_votes`), so the trained controller serves
    bit-identically through `MemoryStore` + `engine.search`.

    Data parallelism follows the launch-layer idiom (same as
    `make_train_step`): the steps are jitted and the returned `place(tree)`
    helper row-shards batch/episode leaves over the mesh's `data_axes`
    (leading dim divisible by the shard count; everything else, params
    included, replicates) -- the partitioner then runs the embedding
    forward data-parallel and the episodic quantization statistics as
    global collectives, with unchanged semantics.

    apply_fn:       (backbone_params, images) -> embeddings.
    hat_cfg:        repro.core.hat.HATConfig.
    pre_optimizer / meta_optimizer: (init, update) optimizers from
                    repro.optim; meta defaults to the pretrain one.
    n_way:          episode way count (static: kept out of the traced tree).
    Returns (pretrain_step, meta_step, place).

    >>> import jax, jax.numpy as jnp
    >>> from repro.core.avss import SearchConfig
    >>> from repro.core.hat import HATConfig
    >>> from repro.launch.steps import make_hat_train_steps
    >>> from repro.optim import adamw
    >>> hat = HATConfig(search=SearchConfig("mtmc", cl=2, mode="avss",
    ...                                     use_kernel="ref"))
    >>> apply_fn = lambda p, x: jax.nn.relu(x @ p["w"])
    >>> opt = adamw(1e-2)
    >>> pre, meta, place = make_hat_train_steps(apply_fn, hat, opt, n_way=2)
    >>> params = {"backbone": {"w": jnp.eye(4)}}
    >>> ep = {"support_images": jnp.eye(4),
    ...       "support_labels": jnp.array([0, 1, 0, 1]),
    ...       "query_images": jnp.eye(4)[:2],
    ...       "query_labels": jnp.array([0, 1])}
    >>> p2, s2, loss = meta(params, opt.init(params), place(ep),
    ...                     jax.random.PRNGKey(0))
    >>> bool(jnp.isfinite(loss))
    True
    """
    from repro.core import hat as hat_lib
    if meta_optimizer is None:
        meta_optimizer = pre_optimizer

    def _apply(params, opt_state, grads, optimizer):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state

    @jax.jit
    def pretrain_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(hat_lib.pretrain_loss)(
            params, batch, apply_fn)
        params, opt_state = _apply(params, opt_state, grads, pre_optimizer)
        return params, opt_state, loss

    @jax.jit
    def meta_step(params, opt_state, ep_arrays, key):
        episode = {**ep_arrays, "n_way": n_way}      # n_way stays static
        loss, grads = jax.value_and_grad(hat_lib.meta_loss)(
            params, episode, apply_fn, hat_cfg, key)
        params, opt_state = _apply(params, opt_state, grads, meta_optimizer)
        return params, opt_state, loss

    def place(tree):
        """Row-shard batch leaves over the data axes; replicate the rest."""
        if mesh is None:
            return tree
        shards = int(np.prod([mesh.shape[a] for a in data_axes]))
        row = NamedSharding(mesh, P(data_axes))
        rep = NamedSharding(mesh, P())
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(
                jnp.asarray(x),
                row if (jnp.ndim(x) and jnp.shape(x)[0] % shards == 0)
                else rep),
            tree)

    return pretrain_step, meta_step, place


def make_prefill_step(cfg: ModelConfig, rules: Rules):
    """(params, batch) -> (last logits, caches in the decode layout for the
    batch's own length)."""
    def prefill_step(params, batch):
        S = jax.tree_util.tree_leaves(batch)[0].shape[1]
        logits, _, caches = tfm.prefill(params, cfg, batch, S, rules)
        return logits, caches
    return prefill_step


def make_prefill_into_step(cfg: ModelConfig, rules: Rules, max_seq: int):
    """Serving prefill of one prompt into a batch of decode caches:
    (params, caches, tokens (1, S), row) -> (last logits (1, 1, V),
    caches), the prompt's decode-layout cache written at batch row `row`
    of `caches` (donated: the batch's caches are updated in place).
    Position slots (`kpos`) are shared by the batch: every row's prompt
    has the same length."""
    def put(a, n, row):
        if a.shape == n.shape:
            return n.astype(a.dtype)
        return jax.lax.dynamic_update_slice(
            a, n.astype(a.dtype), (0, row) + (0,) * (a.ndim - 2))

    def prefill_into(params, caches, tokens, row):
        logits, _, one = tfm.prefill(params, cfg, {"tokens": tokens},
                                     max_seq, rules)
        caches = jax.tree_util.tree_map(lambda a, n: put(a, n, row),
                                        caches, one)
        return logits, caches
    return jax.jit(prefill_into, donate_argnums=1)


def make_serve_step(cfg: ModelConfig, rules: Rules):
    def serve_step(params, caches, batch, pos):
        logits, caches = tfm.decode_step(params, cfg, batch, caches, pos,
                                         rules)
        return logits, caches
    return serve_step


# p_kNN is the softmax of the candidates' noisy votes / this
KNN_VOTE_TEMPERATURE = 10.0


def knn_distribution(votes, labels, vocab_size: int):
    """kNN-LM p_kNN (B, V): the softmax of the candidates' votes /
    KNN_VOTE_TEMPERATURE, each candidate's weight on its label (the stored
    next token); invalid candidates (label -1) weigh nothing, and a query
    with none has an all-zero row."""
    valid = labels >= 0
    w = jax.nn.softmax(
        jnp.where(valid, votes / KNN_VOTE_TEMPERATURE, -1e30), -1)
    w = jnp.where(valid, w, 0.0)
    rows = jnp.arange(labels.shape[0])[:, None]
    return jnp.zeros((labels.shape[0], vocab_size), jnp.float32).at[
        rows, jnp.where(valid, labels, 0)].add(w)


def make_serve_step_with_mcam(cfg: ModelConfig, rules: Rules, mem_cfg,
                              lam: float = 0.3, engine=None, k: int = 32,
                              mode: str = "two_phase",
                              nprobe: int | None = None):
    """kNN-LM decode step served from the simulated NAND-CAM (Khandelwal
    et al., arXiv:1911.00172): the decoder's post-final-norm hidden state
    (the whole d_model vector the LM head reads) queries the MCAM store,
    whose rows are such states keyed to the token that followed them, and
    the served distribution is (1 - lam) p_LM + lam p_kNN.

    Returns the jitted step (params, caches, batch, pos, store) -> (out,
    caches), the caches donated (updated in place). `out` holds what the
    served token came from: `logprobs` (B, V) the served mixture's log,
    `token` (B,) its argmax (greedy decode), `lm_logprobs` (B, V) the
    decoder's own, `query` (B, d_model) float32 the vector searched, and the
    search's k candidates' store rows `indices`, `labels`, `votes` and
    `dist` (B, k).

    The store is a `repro.engine.MemoryStore` at `mem_cfg.dim == d_model`;
    its write-time layouts are arguments of the step, so no step re-runs
    `layout_support` or `support_projection`. Retrieval is
    `engine.search(store, q, SearchRequest(mode, k, nprobe))` (default
    engine: `RetrievalEngine(mem_cfg.search)`):
      'two_phase'  MXU shortlist + exact noisy vote rescore; p_kNN is the
                   softmax of the NOISY MCAM VOTES / KNN_VOTE_TEMPERATURE: the
                   served distribution reflects the simulated hardware's
                   similarity judgement, not the ideal distance.
      'ideal'      top-k by exact digital distance only (votes == -dist).
    nprobe: shards visited per query when the store is partitioned
    (`MemoryStore.shard`); nprobe < n_shards engages the phase-0 router;
    None searches every shard."""
    from repro.engine import RetrievalEngine, SearchRequest
    if mem_cfg.dim != cfg.d_model:
        raise ValueError(f"the kNN-LM store keys are d_model = "
                         f"{cfg.d_model} hidden states; the store holds "
                         f"dim {mem_cfg.dim}")
    engine = engine or RetrievalEngine(mem_cfg.search)
    request = SearchRequest(mode=mode, k=k, nprobe=nprobe)

    def serve_step(params, caches, batch, pos, store):
        logits, caches, hidden = tfm.decode_step(
            params, cfg, batch, caches, pos, rules, return_hidden=True)
        with jax.named_scope(hc.DECODER_SCOPE):
            lm = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), -1)
        with jax.named_scope(hc.KNN_SCOPE):
            q = hidden[:, 0].astype(jnp.float32)              # (B, d_model)
            res = engine.search(store, q, request)
            p_knn = knn_distribution(res.votes, res.labels, cfg.vocab_size)
            mixed = jnp.log((1 - lam) * jnp.exp(lm) + lam * p_knn + 1e-20)
            token = jnp.argmax(mixed, -1).astype(jnp.int32)
        out = {"logprobs": mixed, "token": token, "lm_logprobs": lm,
               "query": q, "indices": res.indices, "labels": res.labels,
               "votes": res.votes, "dist": res.dist}
        return out, caches

    return jax.jit(serve_step, donate_argnums=1)
