"""Deliverable (f): per-arch smoke tests -- reduced same-family config, one
forward + one train step on CPU, asserting output shapes + no NaNs."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, load_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.launch import steps as steps_lib
from repro.models import transformer as tfm
from repro.models.sharding import Rules

LM_ARCHS = ARCHS[:10]


def _batch(cfg, B, S, key):
    if cfg.input_mode == "tokens":
        b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    else:
        b = {"embeddings": jax.random.normal(key, (B, S, cfg.d_model))}
        if cfg.rope_type == "mrope":
            b["positions3"] = jnp.broadcast_to(
                jnp.arange(S)[None, :, None], (B, S, 3)).astype(jnp.int32)
    b["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return b


@pytest.mark.slow
@pytest.mark.parametrize("arch", LM_ARCHS)
def test_smoke_forward(arch):
    cfg = load_config(arch, smoke=True)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    batch = _batch(cfg, B, S, jax.random.PRNGKey(1))
    logits, aux = tfm.forward(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_smoke_forward_canary():
    """Fast-tier canary: one reduced arch forward on every push; the full
    arch x {forward, train, decode} matrix is @slow (weekly/full tier)."""
    test_smoke_forward("starcoder2-3b")


@pytest.mark.slow
@pytest.mark.parametrize("arch", LM_ARCHS)
def test_smoke_train_step(arch):
    cfg = load_config(arch, smoke=True)
    shape = ShapeConfig("smoke", 16, 4, "train", microbatch=2)
    tc = TrainConfig(learning_rate=1e-3)
    rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
    step, optimizer = steps_lib.make_train_step(cfg, tc, rules)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    opt_state = optimizer.init(params)
    b = _batch(cfg, 4, 16, jax.random.PRNGKey(1))
    b = {k: v.reshape((2, 2) + v.shape[1:]) for k, v in b.items()}
    params2, opt_state, metrics = jax.jit(step)(params, opt_state, b)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["applied"]) == 1.0
    # params actually changed
    d = jax.tree_util.tree_reduce(
        lambda acc, t: acc + float(jnp.sum(jnp.abs(t[0] - t[1]))),
        jax.tree_util.tree_map(lambda a, b_: (a.astype(jnp.float32),
                                              b_.astype(jnp.float32)),
                               params, params2), 0.0)
    assert d > 0.0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3-405b", "deepseek-v3-671b",
                                  "xlstm-350m", "hymba-1.5b"])
def test_smoke_decode_step(arch):
    cfg = load_config(arch, smoke=True)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    B = 2
    caches = tfm.init_cache(cfg, B, 32)
    b = _batch(cfg, B, 1, jax.random.PRNGKey(1))
    b.pop("labels")
    logits, caches = tfm.decode_step(params, cfg, b, caches, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_full_configs_match_assignment():
    """The exact published numbers from the assignment block."""
    spec = {
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
    }
    for arch, (L, D, H, KV, F, V) in spec.items():
        cfg = load_config(arch)
        assert cfg.n_layers == L and cfg.d_model == D, arch
        assert cfg.n_heads == H and cfg.n_kv_heads == KV, arch
        assert cfg.d_ff == F and cfg.vocab_size == V, arch
    assert load_config("deepseek-v3-671b").mla is not None
    assert load_config("deepseek-v3-671b").moe.n_routed == 256
    assert load_config("deepseek-moe-16b").moe.top_k == 6
    assert load_config("hymba-1.5b").ssm.d_state == 16
    sc2 = load_config("starcoder2-3b")     # hf bigcode/starcoder2-3b config
    assert (sc2.default_layer, sc2.window) == ("swa", 4096)
    assert sc2.norm_eps == 1e-5 and sc2.rope_theta == 999999.4420358813


@pytest.mark.slow
def test_param_counts_in_range():
    """Sanity: total parameter counts are near the advertised sizes."""
    import numpy as np
    expect = {"llama3-405b": 405e9, "deepseek-v3-671b": 671e9,
              "qwen1.5-110b": 111e9, "command-r-plus-104b": 104e9,
              "starcoder2-3b": 3e9, "deepseek-moe-16b": 16.4e9,
              "qwen2-vl-7b": 7.6e9, "musicgen-medium": 1.5e9,
              "hymba-1.5b": 1.5e9, "xlstm-350m": 0.35e9}
    for arch, target in expect.items():
        cfg = load_config(arch)
        aps = tfm.abstract_params(cfg)
        n = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(aps))
        assert 0.7 * target < n < 1.45 * target, (arch, n / 1e9)
