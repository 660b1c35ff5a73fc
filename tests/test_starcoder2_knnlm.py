"""StarCoder2 kNN-LM decode against the plain reference
(`bench/reference/starcoder2.py`), on seeded random weights at toy widths:
prefill into the sliding-window ring and decode through it, the ring the
prefill writes, the kNN head on the program's own queries, and a program
that ignores the window."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.reference import starcoder2 as ref  # noqa: E402
from repro.configs import load_config  # noqa: E402
from repro.launch import serve as serve_lib  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.models.sharding import Rules  # noqa: E402

RULES = Rules(batch=(), fsdp=(), tensor=(), expert=())
WINDOW, PROMPT, STEPS = 16, 24, 16


def toy(dtype="float32", **over):
    cfg = load_config("starcoder2-3b", smoke=True)
    return dataclasses.replace(cfg, window=WINDOW, dtype=dtype,
                               param_dtype=dtype, **over)


def shape(cfg):
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.hd, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "window": cfg.window,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def params_of(cfg, seed=0):
    """Seeded random weights, with biases and norms drawn too (init leaves
    them at 0 and 1, which would hide a missing term)."""
    params = tfm.init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    key = jax.random.PRNGKey(seed + 1)
    drawn = []
    for i, (path, a) in enumerate(leaves):
        if str(path[-1].key) in ("scale", "nbias", "bq", "bk", "bv", "bo",
                                 "mb1", "mb2"):
            k = jax.random.fold_in(key, i)
            a = (a + 0.1 * jax.random.normal(k, a.shape)).astype(a.dtype)
        drawn.append(a)
    return jax.tree_util.tree_unflatten(tree, drawn)


def decoder(cfg):
    return jax.jit(lambda p, b, c, pos: tfm.decode_step(p, cfg, b, c, pos))


def program_logprobs(cfg, params, tokens):
    """LM log-probs (STEPS + 1, V) of one sequence: the prefill's last
    position, then every decode step through the ring."""
    max_seq = PROMPT + STEPS
    caches = tfm.init_cache(cfg, 1, max_seq)
    prefill = steps_lib.make_prefill_into_step(cfg, RULES, max_seq)
    logits, caches = prefill(params, caches, tokens[None, :PROMPT],
                             jnp.int32(0))
    out = [logits[0, 0]]
    step = decoder(cfg)
    for i in range(STEPS):
        pos = PROMPT + i
        logits, caches = step(params, {"tokens": tokens[None, pos:pos + 1]},
                              caches, jnp.int32(pos))
        out.append(logits[0, 0])
    return jax.nn.log_softmax(jnp.stack(out).astype(jnp.float32), -1)


def tokens_of(cfg, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (PROMPT + STEPS,),
                              0, cfg.vocab_size)


# float32: the same arithmetic in another order (batched prefill and
# single-token decode matmuls, blocked reference attention) moves a
# log-prob by a few float32 ulps, and the KL's own float32 sum reads a few
# 1e-7 where the distributions agree; bfloat16: the program rounds
# activations, attention probabilities and logits to 8 bits of mantissa
# (about 0.4% each): 0.005 nats and a KL of 2e-6 here, where the
# reference with its weights rounded to float8_e4m3 reads a KL of 8e-5
@pytest.mark.parametrize("dtype,atol,max_kl", [("float32", 1e-4, 1e-5),
                                               ("bfloat16", 0.03, 1e-5)])
def test_prefill_and_decode_through_the_ring_match_the_reference(
        dtype, atol, max_kl):
    cfg = toy(dtype)
    params = params_of(cfg)
    tokens = tokens_of(cfg)
    got = program_logprobs(cfg, params, tokens)
    positions = np.arange(PROMPT - 1, PROMPT + STEPS)
    want = ref.lm_logprobs(params, shape(cfg), np.asarray(tokens), positions,
                           block=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
    assert float(ref.kl(want, got).max()) < max_kl
    if dtype == "bfloat16":
        f8 = ref.lm_logprobs(params, shape(cfg), np.asarray(tokens),
                             positions, weight_dtype=jnp.float8_e4m3fn,
                             block=8)
        assert float(ref.kl(want, f8).max()) > max_kl


def test_prefill_ring_equals_the_cache_built_step_by_step():
    cfg = toy()
    params = params_of(cfg)
    tokens = tokens_of(cfg)
    max_seq = PROMPT + STEPS
    _, _, ring = tfm.prefill(params, cfg, {"tokens": tokens[None, :PROMPT]},
                             max_seq)
    caches = tfm.init_cache(cfg, 1, max_seq)
    step = decoder(cfg)
    for pos in range(PROMPT):
        _, caches = step(params, {"tokens": tokens[None, pos:pos + 1]},
                         caches, jnp.int32(pos))
    (a,), (b,) = ring, caches
    assert a["k"].shape[2] == WINDOW                   # a ring, wrapped
    np.testing.assert_array_equal(np.asarray(a["kpos"]), np.asarray(b["kpos"]))
    assert sorted(np.asarray(a["kpos"])[0]) == list(range(PROMPT - WINDOW,
                                                          PROMPT))
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(a[name]), np.asarray(b[name]),
                                   rtol=1e-5, atol=1e-5)


def test_kv_heads_biases_and_window_are_published():
    cfg = load_config("starcoder2-3b")
    assert cfg.layer_types() == ("swa",) * 30 and cfg.window == 4096
    assert cfg.norm_eps == 1e-5 and cfg.rope_theta == 999999.4420358813
    assert cfg.qkv_bias and cfg.o_bias and cfg.mlp_bias
    p = tfm.abstract_params(cfg)["groups"][0]["attn"]
    assert p["bo"].shape == (30, 3072)


def test_a_program_that_ignores_the_window_fails_past_it():
    sound = toy()
    params = params_of(sound)
    tokens = tokens_of(sound)
    full = dataclasses.replace(sound, default_layer="attn")  # same weights
    got = program_logprobs(full, params, tokens)
    positions = np.arange(PROMPT - 1, PROMPT + STEPS)
    want = ref.lm_logprobs(params, shape(sound), np.asarray(tokens),
                           positions, block=8)
    gap = np.abs(np.asarray(got) - np.asarray(want)).max(-1)
    assert gap.min() > 1e-2                     # every position is past it
    early = ref.lm_logprobs(params, shape(sound), np.asarray(tokens),
                            np.arange(WINDOW), block=8)
    early_full = ref.lm_logprobs(params, shape(sound), np.asarray(tokens),
                                 np.arange(WINDOW), window=False, block=8)
    np.testing.assert_allclose(np.asarray(early), np.asarray(early_full),
                               atol=1e-5)


def test_knn_head_matches_the_reference_on_the_programs_queries():
    cfg = toy()
    params = params_of(cfg)
    rows, chunk, wb, k, batch = 512, 128, 64, 8, 4
    mem_cfg = serve_lib.datastore_config(cfg, rows)
    stream = jax.random.randint(jax.random.PRNGKey(5), (rows + 1,), 0,
                                cfg.vocab_size)
    keys = serve_lib.datastore_keys(params, cfg, RULES, stream, rows, chunk)
    store = serve_lib.build_datastore(mem_cfg, keys, stream[1:rows + 1], wb)
    step = steps_lib.make_serve_step_with_mcam(cfg, RULES, mem_cfg, k=k)
    caches = tfm.init_cache(cfg, batch, 8)
    tok = jax.random.randint(jax.random.PRNGKey(6), (batch, 1), 0,
                             cfg.vocab_size)
    outs = []
    for pos in range(3):
        out, caches = step(params, caches, {"tokens": tok}, jnp.int32(pos),
                           store)
        outs.append(jax.tree_util.tree_map(np.asarray, out))
        tok = out["token"][:, None]

    store_cfg = {"clip_std": mem_cfg.clip_std, "cl": 8,
                 "mcam": dataclasses.asdict(mem_cfg.search.mcam)
                 | {"noise_seed": mem_cfg.search.mcam.seed}}
    values, lo, hi = ref.datastore(keys, store_cfg, wb)
    np.testing.assert_array_equal(np.asarray(store.values),
                                  np.asarray(values))
    labels = jnp.asarray(stream[1:rows + 1])
    for out in outs:
        want = ref.knn_search(out["query"], values, labels, lo, hi,
                              store_cfg, k)
        for f in ("indices", "dist", "labels", "votes"):
            np.testing.assert_array_equal(out[f], np.asarray(want[f]), f)
        mixed = ref.knn_mixture(out["lm_logprobs"], out["votes"],
                                out["labels"], 0.3, 10.0)
        np.testing.assert_allclose(out["logprobs"], np.asarray(mixed),
                                   atol=1e-5)
        np.testing.assert_array_equal(out["token"],
                                      out["logprobs"].argmax(-1))
