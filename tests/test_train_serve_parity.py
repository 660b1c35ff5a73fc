"""Train/serve parity: HAT's differentiable episodic forward IS the serving
forward.

The contract (ISSUE 5): `RetrievalEngine.episode_votes` -- the forward
hardware-aware training differentiates through -- produces votes/distances
BIT-IDENTICAL to `engine.search` on a `MemoryStore` programmed with the
same supports, across modes, backends (ref + fused) and sharding. The
straight-through estimators are wrappers around the shared primitives, so
no future engine refactor can silently diverge from training without
failing this file.
"""

import subprocess  # noqa: F401  (parity subprocess pattern lives in test_engine)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.avss import SearchConfig, class_mean_votes
from repro.core.memory import MemoryConfig
from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest)
from repro.launch.mesh import make_mesh


def _episode(dim=16, n=12, b=5, seed=0):
    """Float relu'd embeddings standing in for controller outputs."""
    s = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(seed), (n, dim)))
    q = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(seed + 1), (b, dim)))
    labels = jnp.arange(n, dtype=jnp.int32) % 4
    return q, s, labels


def _programmed_store(cfg, q, s, labels, capacity=None):
    """The shared train->write->serve recipe: calibrated on the SAME
    sample the asymmetric trainer quantizer saw (support + query stats),
    which makes quantization bit-identical by construction (shared
    `affine_quantize` / `clip_range`)."""
    return MemoryStore.from_episode(s, q, labels, cfg, capacity=capacity)


@pytest.mark.parametrize("noisy", [False, True])
def test_episode_votes_bit_match_full_search(noisy):
    """Noiseless AND noisy (key=None: serving noise coordinates) episodic
    forwards equal engine.search(mode='full') bit-for-bit."""
    cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
    eng = RetrievalEngine(cfg)
    q, s, labels = _episode()
    ep = eng.episode_votes(q, s, noisy=noisy)
    store = _programmed_store(cfg, q, s, labels)
    res = eng.search(store, q, SearchRequest(mode="full", noisy=noisy))
    np.testing.assert_array_equal(np.asarray(ep["votes"]),
                                  np.asarray(res.votes))
    np.testing.assert_array_equal(np.asarray(ep["dist"]),
                                  np.asarray(res.dist))
    assert ep["iterations"] == res.iterations


def test_episode_scores_equal_served_class_head():
    """The served per-class head (class_mean_votes over search votes) is
    bit-identical to the in-training episode_scores logits -- so eval
    accuracy through the store EXACTLY matches the in-training eval."""
    cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
    eng = RetrievalEngine(cfg)
    q, s, labels = _episode()
    scores = eng.episode_scores(q, s, labels, 4, noisy=False)
    store = _programmed_store(cfg, q, s, labels)
    res = eng.search(store, q, SearchRequest(mode="full", noisy=False))
    served = class_mean_votes(res.votes, store.labels, 4)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(served))


@pytest.mark.parametrize("backend", ["ref", "fused"])
@pytest.mark.parametrize("sharded", [False, True])
def test_episode_votes_match_two_phase_candidates(backend, sharded):
    """Every two-phase candidate's vote equals the episodic forward's vote
    for that support row -- ref and fused backends, sharded store included
    (the acceptance matrix of ISSUE 5)."""
    cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
    eng = RetrievalEngine(cfg)
    q, s, labels = _episode()
    ep = eng.episode_votes(q, s, noisy=False)
    store = _programmed_store(cfg, q, s, labels)
    if sharded:
        store = store.shard(make_mesh((1,), ("data",)))
    res = eng.search(store, q, SearchRequest(
        mode="two_phase", k=s.shape[0], backend=backend, noisy=False))
    votes = jnp.take_along_axis(ep["votes"], res.indices, axis=1)
    dist = jnp.take_along_axis(ep["dist"], res.indices, axis=1)
    np.testing.assert_array_equal(np.asarray(votes), np.asarray(res.votes))
    np.testing.assert_array_equal(np.asarray(dist), np.asarray(res.dist))


def test_parity_survives_empty_slots():
    """A store with unwritten slots serves the written rows bit-identically
    to the episodic forward (masked rows are -inf/-1, never compared)."""
    cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
    eng = RetrievalEngine(cfg)
    q, s, labels = _episode(n=7)
    ep = eng.episode_votes(q, s, noisy=False)
    store = _programmed_store(cfg, q, s, labels, capacity=10)
    res = eng.search(store, q, SearchRequest(mode="two_phase", k=10,
                                             noisy=False))
    valid = np.asarray(res.labels) >= 0
    assert valid.sum() == q.shape[0] * 7          # every written row found
    got = np.asarray(res.votes)[valid]
    want = np.asarray(jnp.take_along_axis(
        ep["votes"], jnp.asarray(res.indices), axis=1))[valid]
    np.testing.assert_array_equal(got, want)
    assert np.all(np.isneginf(np.asarray(res.votes)[~valid]))


def test_episode_votes_gradients_flow_and_keyed_noise_refreshes():
    """The engine entry point stays differentiable (STE path) and a PRNG
    key draws a fresh counter-hash noise stream per step."""
    cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
    eng = RetrievalEngine(cfg)
    q, s, _ = _episode(dim=8, n=6, b=3)

    def loss(qe, se):
        return eng.episode_votes(qe, se, key=jax.random.PRNGKey(7))[
            "votes"].sum()

    gq, gs = jax.grad(loss, argnums=(0, 1))(q, s)
    assert float(jnp.linalg.norm(gq)) > 0
    assert float(jnp.linalg.norm(gs)) > 0
    v1 = eng.episode_votes(q, s, key=jax.random.PRNGKey(1))["votes"]
    v2 = eng.episode_votes(q, s, key=jax.random.PRNGKey(2))["votes"]
    v1b = eng.episode_votes(q, s, key=jax.random.PRNGKey(1))["votes"]
    assert not jnp.array_equal(v1, v2)            # fresh noise per key
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v1b))  # det.


def test_svss_episode_votes_bit_match_full_search():
    """The symmetric mode shares the same contract (STE-encoded query)."""
    cfg = SearchConfig("mtmc", cl=3, mode="svss", use_kernel="ref")
    eng = RetrievalEngine(cfg)
    q, s, labels = _episode(dim=10, n=6, b=3)
    ep = eng.episode_votes(q, s, noisy=False)
    # svss quantizes query and support against the SUPPORT range (fake_quant
    # chain); serve the same way: calibrate on the support sample only
    mcfg = MemoryConfig(capacity=6, dim=10, search=cfg)
    store = MemoryStore.create(mcfg).calibrate(s).write(s, labels)
    qv = store.quantize_queries(jnp.clip(q, store.lo, store.hi))
    res = eng.search(store, qv.astype(jnp.int32),
                     SearchRequest(mode="full", noisy=False))
    np.testing.assert_array_equal(np.asarray(ep["votes"]),
                                  np.asarray(res.votes))


@pytest.mark.slow
def test_launch_hat_two_stage_end_to_end(tmp_path):
    """`launch/train.py --hat` on CPU: two-stage HAT train, the closed
    train->write->serve loop with bit-parity, and checkpoints a separate
    process can serve from (acceptance criterion of ISSUE 5)."""
    from repro.core.memory import MemoryConfig
    from repro.launch.train import train_hat

    out = train_hat(pretrain_steps=4, meta_steps=4, n_way=4, k_shot=3,
                    eval_episodes=2, ckpt_dir=str(tmp_path), log_every=2)
    assert np.isfinite(out["pre_losses"]).all()
    assert np.isfinite(out["meta_losses"]).all()
    assert out["parity"] is True
    # identical forward => identical eval accuracy, exactly
    assert out["served_acc"] == out["train_acc"]
    # the checkpointed store serves bit-identically in a fresh store object
    from repro.configs.omniglot_conv4 import get_smoke_config
    from repro.core.avss import SearchConfig
    from repro.core.hat import HATConfig
    from repro.core.mcam import MCAMConfig
    fsl = get_smoke_config()
    hat_cfg = HATConfig(search=SearchConfig(
        "mtmc", cl=fsl.cl, mode="avss", use_kernel="ref",
        mcam=MCAMConfig(sigma_device=0.15, sigma_read=0.05)))
    n = 4 * 3  # eval_way * k_shot supports
    cfg = MemoryConfig(capacity=n, dim=fsl.embed_dim, search=hat_cfg.search,
                       clip_std=hat_cfg.clip_std)
    restored = MemoryStore.restore(str(tmp_path / "store"), cfg)
    assert restored.calibrated and int(restored.size) == n
    q = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(0),
                                      (3, fsl.embed_dim)))
    eng = RetrievalEngine(hat_cfg.search)
    res = eng.search(restored, q, SearchRequest(mode="two_phase", k=4))
    assert res.predict().shape == (3,)
    assert bool((res.labels >= 0).all())
