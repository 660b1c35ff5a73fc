"""Paper-faithful end-to-end driver: Conv4 controller + HAT on procedural
Omniglot-like data, then the paper's evaluation matrix.

    PYTHONPATH=src python examples/fsl_omniglot.py \
        [--pretrain-steps 150] [--meta-steps 120] [--n-way 8] [--full]

Two-stage HAT training (paper Sec. 3.3):
  stage 1: controller + linear classifier, plain CE on all training classes;
  stage 2: episodic meta-training THROUGH the simulated MCAM (asymmetric
           fake-quant, MTMC STE, string currents + noise, sigmoid-STE SA,
           vote-based CE).
Evaluation: accuracy of {MTMC, B4E, SRE} x {standard, HAT} controllers and
SVSS vs AVSS, on held-out classes -- the deltas mirror paper Fig. 9/Table 2.
`--full` uses the paper's 200-way 10-shot geometry (slow on CPU).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.omniglot_conv4 import get_config, get_smoke_config
from repro.core import hat
from repro.core.avss import SearchConfig
from repro.core.hat import HATConfig, meta_loss, pretrain_loss
from repro.core.mcam import MCAMConfig
from repro.core.quantization import quantize_asymmetric, fake_quant, QuantSpec
from repro.data.fsl import EpisodeSampler, OmniglotLike, pretrain_batch
from repro.models.controller import apply_conv4, init_conv4
from repro.optim import adamw


def embed_apply(params, images):
    return apply_conv4(params, images)


def evaluate(params, sampler, search_cfg, episodes=6, backend="auto",
             two_phase=False, k=64):
    """Episode accuracy through the unified retrieval API: each episode's
    quantized supports are programmed into a MemoryStore (write-time MCAM
    layouts) and searched via engine.search with one typed request.

    two_phase=True evaluates the production serving path (MXU shortlist +
    exact noisy rescore) instead of the full search -- accuracies match
    whenever the 1-NN makes the shortlist (recall@k, see bench_engine)."""
    from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
    engine = RetrievalEngine(search_cfg, backend=backend)
    request = SearchRequest(mode="two_phase" if two_phase else "full", k=k)
    accs = []
    for e in range(episodes):
        ep = sampler.episode(1000 + e)
        s_emb = embed_apply(params["backbone"], jnp.asarray(ep.support_images))
        q_emb = embed_apply(params["backbone"], jnp.asarray(ep.query_images))
        if search_cfg.mode == "avss":
            qv, sv = quantize_asymmetric(q_emb, s_emb, search_cfg.enc.levels)
        else:
            sv, _, rng = fake_quant(s_emb, QuantSpec(search_cfg.enc.levels))
            qv, _, _ = fake_quant(q_emb, QuantSpec(search_cfg.enc.levels), rng)
        qv, sv = qv.astype(jnp.int32), sv.astype(jnp.int32)
        s_lab = jnp.asarray(ep.support_labels)
        store = MemoryStore.from_quantized(sv, s_lab, search_cfg)
        pred = engine.search(store, qv, request).predict()
        accs.append(float((pred == jnp.asarray(ep.query_labels)).mean()))
    return float(np.mean(accs)), float(np.std(accs))


def train_controller(fsl, ds, train_ids, hat_cfg, args, use_hat=True,
                     seed=0):
    key = jax.random.PRNGKey(seed)
    backbone = init_conv4(key, in_ch=1, width=32, embed_dim=fsl.embed_dim)
    head = {"w": jax.random.normal(jax.random.PRNGKey(1),
                                   (fsl.embed_dim, len(train_ids))) * 0.05,
            "b": jnp.zeros((len(train_ids),))}
    params = {"backbone": backbone, "head": head}
    opt = adamw(1e-3, weight_decay=1e-4)
    opt_state = opt.init(params)

    @jax.jit
    def pre_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(pretrain_loss)(
            params, batch, embed_apply)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    t0 = time.time()
    for step in range(args.pretrain_steps):
        batch = pretrain_batch(ds, train_ids, batch=32, step=step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, loss = pre_step(params, opt_state, batch)
        if step % 50 == 0:
            print(f"  [pretrain] step {step} loss {float(loss):.3f} "
                  f"({time.time()-t0:.0f}s)")

    if not use_hat:
        return params

    # stage 2: episodic meta-training through the simulated MCAM
    sampler = EpisodeSampler(ds, train_ids, n_way=args.n_way,
                             k_shot=fsl.k_shot, n_query=4, seed=11)
    opt2 = adamw(1e-4, weight_decay=1e-4)  # gentle: adapt, don't destroy
    meta_params = {"backbone": params["backbone"]}
    opt_state2 = opt2.init(meta_params)

    n_way_static = args.n_way  # keep out of the traced pytree

    @jax.jit
    def meta_step(params, opt_state, ep_arrays, key):
        episode = {**ep_arrays, "n_way": n_way_static}
        loss, grads = jax.value_and_grad(meta_loss)(
            params, episode, lambda p, x: embed_apply(p, x), hat_cfg, key)
        updates, opt_state = opt2.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    for step in range(args.meta_steps):
        ep = sampler.episode(step)
        episode = {"support_images": jnp.asarray(ep.support_images),
                   "support_labels": jnp.asarray(ep.support_labels),
                   "query_images": jnp.asarray(ep.query_images),
                   "query_labels": jnp.asarray(ep.query_labels)}
        meta_params, opt_state2, loss = meta_step(
            meta_params, opt_state2, episode, jax.random.PRNGKey(step))
        if step % 40 == 0:
            print(f"  [meta/HAT] step {step} loss {float(loss):.3f} "
                  f"({time.time()-t0:.0f}s)")
    return {"backbone": meta_params["backbone"], "head": params["head"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pretrain-steps", type=int, default=150)
    ap.add_argument("--meta-steps", type=int, default=120)
    ap.add_argument("--n-way", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="paper geometry (200-way 10-shot, CL=32); slow")
    ap.add_argument("--engine-backend", default="auto",
                    choices=["auto", "ref", "pallas"])
    ap.add_argument("--two-phase-eval", action="store_true",
                    help="evaluate via the two-phase engine path "
                         "(shortlist + exact rescore) instead of full search")
    ap.add_argument("--shortlist-k", type=int, default=64)
    args = ap.parse_args()

    fsl = get_config() if args.full else get_smoke_config()
    if not args.full:
        fsl = type(fsl)(**{**fsl.__dict__, "k_shot": 5})
    ds = OmniglotLike(n_classes=fsl.n_train_classes + fsl.n_test_classes,
                      image_size=fsl.image_size, seed=0)
    train_ids = np.arange(fsl.n_train_classes)
    test_ids = np.arange(fsl.n_train_classes,
                         fsl.n_train_classes + fsl.n_test_classes)

    mcam = MCAMConfig(sigma_device=0.15, sigma_read=0.05)
    cl = fsl.cl
    hat_cfg = HATConfig(search=SearchConfig("mtmc", cl=cl, mode="avss",
                                            mcam=mcam, use_kernel="ref"))

    print("== training controller WITHOUT HAT (standard 2-stage of [24]) ==")
    params_std = train_controller(fsl, ds, train_ids, hat_cfg, args,
                                  use_hat=False)
    print("== training controller WITH HAT (paper Sec. 3.3) ==")
    params_hat = train_controller(fsl, ds, train_ids, hat_cfg, args,
                                  use_hat=True)

    n_way = min(args.n_way, len(test_ids))
    sampler = EpisodeSampler(ds, test_ids, n_way=n_way, k_shot=fsl.k_shot,
                             n_query=4, seed=77)

    print(f"\n== evaluation on {len(test_ids)} held-out classes "
          f"({n_way}-way {fsl.k_shot}-shot, noisy MCAM) ==")
    results = {}
    for label, params in [("std", params_std), ("HAT", params_hat)]:
        for enc_name, ecl in [("mtmc", cl), ("b4e", 3), ("sre", 4)]:
            cfg = SearchConfig(enc_name, cl=ecl, mode="avss", mcam=mcam,
                               use_kernel="ref")
            acc, sd = evaluate(params, sampler, cfg,
                               backend=args.engine_backend,
                               two_phase=args.two_phase_eval,
                               k=args.shortlist_k)
            results[(label, enc_name)] = acc
            print(f"  {label:4s} {enc_name:5s} AVSS: {acc:.3f} +- {sd:.3f}")
    for mode in ("svss", "avss"):
        cfg = SearchConfig("mtmc", cl=cl, mode=mode, mcam=mcam,
                           use_kernel="ref")
        acc, sd = evaluate(params_hat, sampler, cfg,
                           backend=args.engine_backend)
        print(f"  HAT  mtmc {mode.upper()}: {acc:.3f} +- {sd:.3f}")

    d_hat = results[("HAT", "mtmc")] - results[("std", "mtmc")]
    d_enc = results[("HAT", "mtmc")] - results[("HAT", "b4e")]
    print(f"\n  HAT gain (mtmc):          {d_hat:+.3f}   (paper: +1.25%..1.8%)")
    print(f"  MTMC vs B4E (HAT ctrl):   {d_enc:+.3f}   (paper: +0.34%..4.91%)")

    serve_loop_check(params_hat, sampler, hat_cfg)


def serve_loop_check(params, sampler, hat_cfg):
    """Close the train->write->serve loop: the HAT controller's noiseless
    in-training scores (engine.episode_scores -- the exact forward stage 2
    trained through) must be BIT-IDENTICAL to serving the same supports
    through MemoryStore.calibrate/write + engine.search. This is the
    train/serve parity contract (tests/test_train_serve_parity.py)."""
    from repro.core.avss import class_mean_votes
    from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
    eng = RetrievalEngine(hat_cfg.search)
    ep = sampler.episode(4242)
    s_emb = embed_apply(params["backbone"], jnp.asarray(ep.support_images))
    q_emb = embed_apply(params["backbone"], jnp.asarray(ep.query_images))
    s_lab = jnp.asarray(ep.support_labels)
    scores = eng.episode_scores(q_emb, s_emb, s_lab, ep.n_way,
                                clip_std=hat_cfg.clip_std,
                                sa_tau=hat_cfg.sa_tau, noisy=False)
    store = MemoryStore.from_episode(s_emb, q_emb, s_lab, hat_cfg.search,
                                     clip_std=hat_cfg.clip_std)
    res = eng.search(store, q_emb, SearchRequest(mode="full", noisy=False))
    served = class_mean_votes(res.votes, store.labels, ep.n_way)
    print(f"\n== train->write->serve loop ==\n"
          f"  in-training scores == served scores (bitwise): "
          f"{bool(jnp.array_equal(scores, served))}")


if __name__ == "__main__":
    main()
