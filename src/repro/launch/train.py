"""Training launcher (CPU-runnable for reduced configs; the same code path
the dry-run lowers for the production meshes).

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b --smoke \
        --steps 50 --batch 8 --seq 128

Fault tolerance wired in: CheckpointManager (async, atomic, elastic),
PreemptionHandler (SIGTERM => final checkpoint), AnomalyDetector (NaN /
grad-spike step skipping -- also enforced inside the jitted step),
StepWatchdog (straggler signal), deterministic step-addressable data
(restart-consistent).

Hardware-aware training (paper Sec. 3.3) is a first-class launch target:

    PYTHONPATH=src python -m repro.launch.train --hat \
        --hat-pretrain-steps 40 --hat-meta-steps 40 --hat-n-way 6

runs the two-stage HAT flow (controller pretrain -> episodic meta-train
THROUGH the engine's differentiable MCAM forward), then CLOSES THE LOOP:
the trained controller's support embeddings are calibrated + written into
a `MemoryStore`, served through `engine.search`, and the served per-class
scores are checked bit-identical to the in-training evaluation (the
train/serve parity contract). Controller params and the programmed store
are checkpointed under --ckpt-dir for a separate serving process.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import load_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.data.lm import LMDataConfig, SyntheticLM, embedding_batch_for_step
from repro.launch import steps as steps_lib
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tfm
from repro.models.sharding import active_mesh, rules_for_mesh
from repro.runtime.ft import (AnomalyDetector, PreemptionHandler,
                              StepWatchdog)


def make_batch(cfg, shape, data, step, accum, mb):
    if cfg.input_mode == "tokens":
        b = data.batch_for_step(step)
    else:
        b = embedding_batch_for_step(step, shape.global_batch, shape.seq_len,
                                     cfg.d_model, cfg.vocab_size,
                                     mrope=cfg.rope_type == "mrope")
    return {k: np.asarray(v).reshape((accum, mb) + v.shape[1:])
            for k, v in b.items()}


def train(arch: str, smoke: bool, steps: int, batch: int, seq: int,
          ckpt_dir: str, resume: bool = False, model_parallel: int = 1,
          log_every: int = 10):
    cfg = load_config(arch, smoke=smoke)
    shape = ShapeConfig("custom", seq, batch, "train")
    tc = TrainConfig(total_steps=steps, checkpoint_dir=ckpt_dir,
                     learning_rate=1e-3 if smoke else 3e-4)
    mesh = make_host_mesh(model_parallel)
    rules = rules_for_mesh(mesh)
    dp = int(np.prod([mesh.shape[a] for a in rules.batch]))
    cfg = steps_lib.adapt_config(cfg, shape, dp)
    mb = steps_lib.microbatch_for(cfg, shape)
    accum = shape.global_batch // mb

    data = SyntheticLM(LMDataConfig(seq, batch, cfg.vocab_size))
    with mesh, active_mesh(mesh, rules):
        step_fn, optimizer = steps_lib.make_train_step(cfg, tc, rules)
        params = tfm.init(jax.random.PRNGKey(tc.seed), cfg)
        opt_state = optimizer.init(params)
        jstep = jax.jit(step_fn, donate_argnums=(0, 1))

        mgr = CheckpointManager(ckpt_dir, every=tc.checkpoint_every)
        start = 0
        if resume and mgr.latest_step() is not None:
            start = mgr.latest_step()
            state = mgr.restore({"params": params, "opt": opt_state,
                                 "step": jnp.zeros((), jnp.int32)})
            params, opt_state = state["params"], state["opt"]
            print(f"resumed from step {start}")

        pre = PreemptionHandler()
        anom = AnomalyDetector()
        dog = StepWatchdog()
        losses = []
        for step in range(start, steps):
            dog.start()
            b = make_batch(cfg, shape, data, step, accum, mb)
            params, opt_state, metrics = jstep(params, opt_state, b)
            loss = float(metrics["loss"])
            gn = float(metrics["grad_norm"])
            dt = dog.stop()
            losses.append(loss)
            if not anom.check(loss, gn):
                print(f"step {step}: ANOMALY skipped (loss={loss}, gn={gn})")
            if step % log_every == 0 or step == steps - 1:
                print(f"step {step:5d} loss {loss:.4f} gnorm {gn:.3f} "
                      f"{dt*1000:.0f}ms")
            mgr.maybe_save(step + 1, {"params": params, "opt": opt_state,
                                      "step": jnp.int32(step + 1)})
            if pre.preempted:
                print("preemption requested -> checkpoint + exit")
                mgr.maybe_save(step + 1,
                               {"params": params, "opt": opt_state,
                                "step": jnp.int32(step + 1)}, force=True)
                break
        mgr.wait()
    return losses


def train_hat(pretrain_steps: int = 40, meta_steps: int = 40,
              n_way: int = 6, k_shot: int = 3, n_query: int = 4,
              eval_episodes: int = 3, ckpt_dir: str = "/tmp/repro_hat_ckpt",
              seed: int = 0, log_every: int = 10) -> dict:
    """Two-stage hardware-aware training + the closed train->write->serve
    loop (see module docstring). Returns a metrics dict with the loss
    curves, the in-training/served eval accuracies, and whether every
    served prediction matched the in-training forward bit-for-bit."""
    from repro.configs.omniglot_conv4 import get_smoke_config
    from repro.core.avss import SearchConfig, class_mean_votes
    from repro.core.hat import HATConfig
    from repro.core.mcam import MCAMConfig
    from repro.data.fsl import EpisodeSampler, OmniglotLike, pretrain_batch
    from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest)
    from repro.models.controller import apply_conv4, init_conv4
    from repro.optim import adamw

    fsl = get_smoke_config()
    ds = OmniglotLike(n_classes=fsl.n_train_classes + fsl.n_test_classes,
                      image_size=fsl.image_size, seed=0)
    train_ids = np.arange(fsl.n_train_classes)
    test_ids = np.arange(fsl.n_train_classes,
                         fsl.n_train_classes + fsl.n_test_classes)
    mesh = make_host_mesh(1)                       # DP over all local devices
    hat_cfg = HATConfig(search=SearchConfig(
        "mtmc", cl=fsl.cl, mode="avss", use_kernel="ref",
        mcam=MCAMConfig(sigma_device=0.15, sigma_read=0.05)))

    k_backbone, k_head = jax.random.split(jax.random.PRNGKey(seed))
    backbone = init_conv4(k_backbone, in_ch=1, width=32,
                          embed_dim=fsl.embed_dim)
    head = {"w": jax.random.normal(k_head,
                                   (fsl.embed_dim, len(train_ids))) * 0.05,
            "b": jnp.zeros((len(train_ids),))}
    pre_opt = adamw(1e-3, weight_decay=1e-4)
    meta_opt = adamw(1e-4, weight_decay=1e-4)  # gentle: adapt, don't destroy
    pre_step, meta_step, place = steps_lib.make_hat_train_steps(
        apply_conv4, hat_cfg, pre_opt, meta_opt, n_way=n_way, mesh=mesh)

    pre_losses, meta_losses = [], []
    with mesh:
        # stage 1: transferable features (plain CE, full training label set)
        params = {"backbone": backbone, "head": head}
        opt_state = pre_opt.init(params)
        t0 = time.time()
        for step in range(pretrain_steps):
            batch = place(pretrain_batch(ds, train_ids, batch=32, step=step))
            params, opt_state, loss = pre_step(params, opt_state, batch)
            pre_losses.append(float(loss))
            if step % log_every == 0 or step == pretrain_steps - 1:
                print(f"[hat/pretrain] step {step:4d} loss {float(loss):.4f} "
                      f"({time.time()-t0:.0f}s)")

        # stage 2: episodic meta-training THROUGH the simulated MCAM
        # (episode composition and the per-step hardware-noise streams all
        # derive from `seed`, so different seeds are independent replicates)
        sampler = EpisodeSampler(ds, train_ids, n_way=n_way, k_shot=k_shot,
                                 n_query=n_query, seed=11 + seed)
        meta_params = {"backbone": params["backbone"]}
        opt_state2 = meta_opt.init(meta_params)
        for step in range(meta_steps):
            ep = sampler.episode(step)
            arrays = place({"support_images": ep.support_images,
                            "support_labels": ep.support_labels,
                            "query_images": ep.query_images,
                            "query_labels": ep.query_labels})
            meta_params, opt_state2, loss = meta_step(
                meta_params, opt_state2, arrays,
                jax.random.fold_in(jax.random.PRNGKey(seed), step))
            meta_losses.append(float(loss))
            if step % log_every == 0 or step == meta_steps - 1:
                print(f"[hat/meta]     step {step:4d} loss {float(loss):.4f} "
                      f"({time.time()-t0:.0f}s)")

    # -- close the loop: trained controller -> calibrate/write -> search ----
    eng = RetrievalEngine(hat_cfg.search)
    eval_way = min(n_way, len(test_ids))
    eval_sampler = EpisodeSampler(ds, test_ids, n_way=eval_way,
                                  k_shot=k_shot, n_query=n_query,
                                  seed=77 + seed)
    train_acc, served_acc, parity = [], [], True
    store = None
    for e in range(eval_episodes):
        ep = eval_sampler.episode(e)
        s_emb = apply_conv4(meta_params["backbone"],
                            jnp.asarray(ep.support_images))
        q_emb = apply_conv4(meta_params["backbone"],
                            jnp.asarray(ep.query_images))
        s_lab = jnp.asarray(ep.support_labels)
        # the in-training evaluation head (noiseless episodic forward)
        scores = eng.episode_scores(q_emb, s_emb, s_lab, eval_way,
                                    clip_std=hat_cfg.clip_std,
                                    sa_tau=hat_cfg.sa_tau, noisy=False)
        pred_train = jnp.argmax(scores, -1)
        # the SERVED head: the shared train->write->serve recipe --
        # bit-identical to the in-training forward by construction
        store = MemoryStore.from_episode(s_emb, q_emb, s_lab,
                                         hat_cfg.search,
                                         clip_std=hat_cfg.clip_std)
        res = eng.search(store, q_emb,
                         SearchRequest(mode="full", noisy=False))
        served = class_mean_votes(res.votes, store.labels, eval_way)
        pred_served = jnp.argmax(served, -1)
        parity &= bool(jnp.array_equal(scores, served))
        q_lab = jnp.asarray(ep.query_labels)
        train_acc.append(float((pred_train == q_lab).mean()))
        served_acc.append(float((pred_served == q_lab).mean()))

    print(f"[hat/eval] in-training acc {np.mean(train_acc):.3f}  "
          f"served acc {np.mean(served_acc):.3f}  "
          f"score bit-parity: {parity}")

    # checkpoint controller + the last programmed store for separate serving
    mgr = CheckpointManager(ckpt_dir, every=1)
    mgr.maybe_save(meta_steps, {"params": meta_params}, force=True)
    mgr.wait()
    if store is not None:
        store.save(f"{ckpt_dir}/store", step=meta_steps)
    return {"pre_losses": pre_losses, "meta_losses": meta_losses,
            "train_acc": float(np.mean(train_acc)),
            "served_acc": float(np.mean(served_acc)),
            "parity": parity, "ckpt_dir": ckpt_dir}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--hat", action="store_true",
                    help="two-stage hardware-aware training (paper Sec. "
                         "3.3) + the closed train->write->serve loop")
    ap.add_argument("--hat-pretrain-steps", type=int, default=40)
    ap.add_argument("--hat-meta-steps", type=int, default=40)
    ap.add_argument("--hat-n-way", type=int, default=6)
    ap.add_argument("--hat-k-shot", type=int, default=3)
    ap.add_argument("--hat-eval-episodes", type=int, default=3)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.hat:
        out = train_hat(args.hat_pretrain_steps, args.hat_meta_steps,
                        args.hat_n_way, args.hat_k_shot,
                        eval_episodes=args.hat_eval_episodes,
                        ckpt_dir=args.ckpt_dir)
        print(f"HAT done: served acc {out['served_acc']:.3f} "
              f"(parity={out['parity']}); checkpoints in {out['ckpt_dir']}")
        return
    losses = train(args.arch, args.smoke, args.steps, args.batch, args.seq,
                   args.ckpt_dir, args.resume, args.model_parallel)
    print(f"first-10 mean {np.mean(losses[:10]):.4f} -> "
          f"last-10 mean {np.mean(losses[-10:]):.4f}")


if __name__ == "__main__":
    main()
