"""End-to-end SERVING driver (the paper's kind: efficient retrieval):
a small LM decodes batched requests with an MCAM-backed kNN-LM datastore
fused into its distribution -- the production serve step the dry-run
lowers, executed for real at reduced scale.

    PYTHONPATH=src python examples/serve_retrieval.py \
        [--arch starcoder2-3b] [--batch 4] [--steps 12] [--lam 0.3]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import load_config
from repro.launch import serve as serve_lib
from repro.launch import steps as steps_lib
from repro.models import transformer as tfm
from repro.models.sharding import Rules


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--lam", type=float, default=0.3)
    args = ap.parse_args()

    cfg = load_config(args.arch, smoke=True)
    rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
    key = jax.random.PRNGKey(0)
    params = tfm.init(key, cfg)
    B, P = args.batch, args.prompt_len
    max_seq = P + args.steps

    # --- the kNN-LM datastore: the decoder's own hidden states over a
    # token stream, each keyed to the next token, programmed ONCE into the
    # MCAM store (quantized values + LUT projection + string-grid layout)
    mem_cfg = serve_lib.datastore_config(cfg, args.rows)
    chunk = min(serve_lib.DATASTORE_CHUNK, args.rows)
    stream = jax.random.randint(jax.random.PRNGKey(8), (args.rows + 1,), 0,
                                cfg.vocab_size)
    t0 = time.time()
    keys = serve_lib.datastore_keys(params, cfg, rules, stream, args.rows,
                                    chunk)
    store = serve_lib.build_datastore(mem_cfg, keys, stream[1:args.rows + 1],
                                      min(256, args.rows))
    print(f"datastore: {args.rows} rows of d={cfg.d_model} in "
          f"{time.time() - t0:.1f}s")

    step = steps_lib.make_serve_step_with_mcam(cfg, rules, mem_cfg,
                                               lam=args.lam)
    prefill = steps_lib.make_prefill_into_step(cfg, rules, max_seq)

    # --- batched requests: each prompt prefilled into the decode cache
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
    caches = tfm.init_cache(cfg, B, max_seq)
    first = []
    for b in range(B):
        logits, caches = prefill(params, caches, prompts[b:b + 1],
                                 jnp.int32(b))
        first.append(jnp.argmax(logits[:, 0], -1))
    tok = jnp.concatenate(first).astype(jnp.int32)[:, None]

    outs, moved = [tok], 0
    t0 = time.time()
    for i in range(args.steps):
        out, caches = step(params, caches, {"tokens": tok},
                           jnp.int32(P + i), store)
        moved += int(np.sum(np.asarray(out["token"]) != np.asarray(
            jnp.argmax(out["lm_logprobs"], -1))))
        tok = out["token"][:, None]
        outs.append(tok)
    dt = time.time() - t0
    gen = np.asarray(jnp.concatenate(outs, 1))
    print(f"decoded {args.steps} steps x {B} requests in {dt:.1f}s "
          f"({args.steps * B / dt:.1f} tok/s on CPU); the kNN head changed "
          f"{moved} of {args.steps * B} greedy tokens")
    for b in range(B):
        print(f"  req{b}: {gen[b].tolist()}")
    assert np.isfinite(np.asarray(out["logprobs"])).all()
    print("OK: serve_step_with_mcam end-to-end")


if __name__ == "__main__":
    main()
