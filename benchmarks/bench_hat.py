"""HAT trainer + encoding-sweep benchmark rows (ISSUE 5 satellite).

Two row families, merged into results/bench_summary.json by benchmarks.run:

* hat/meta_train_step -- wall time of one jitted episodic meta-train step
  through the engine's differentiable MCAM forward (the stage-2 inner
  loop of `launch/train.py --hat`), plus the stage-1 pretrain step as a
  baseline for the hardware-simulation overhead.
* encoding_sweep/* -- `engine.search` cost per encoding (mtmc / b4e /
  b4we / sre) on the same store geometry: what the paper's Table 1
  encoding choice costs at serve time (two-phase, pallas backend), with the
  word-line iteration count in the derived column.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import time_us
from repro.core.avss import SearchConfig, search_iterations
from repro.core.hat import HATConfig
from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
from repro.launch.steps import make_hat_train_steps
from repro.optim import adamw


def _hat_step_rows():
    dim, n_way, k_shot, n_query = 24, 6, 3, 4
    hat = HATConfig(search=SearchConfig("mtmc", cl=8, mode="avss",
                                        use_kernel="ref"))
    apply_fn = lambda p, x: jax.nn.relu(x @ p["w"])
    opt = adamw(1e-3)
    pre_step, meta_step, _ = make_hat_train_steps(apply_fn, hat, opt,
                                                  n_way=n_way)
    params = {"backbone": {"w": jax.random.normal(jax.random.PRNGKey(0),
                                                  (32, dim)) * 0.1},
              "head": {"w": jnp.zeros((dim, n_way)),
                       "b": jnp.zeros((n_way,))}}
    s_lab = jnp.repeat(jnp.arange(n_way), k_shot)
    q_lab = jnp.repeat(jnp.arange(n_way), n_query)
    ep = {"support_images": jax.random.normal(
              jax.random.PRNGKey(1), (len(s_lab), 32)),
          "support_labels": s_lab,
          "query_images": jax.random.normal(
              jax.random.PRNGKey(2), (len(q_lab), 32)),
          "query_labels": q_lab}
    opt_state = opt.init(params)
    us_meta, _ = time_us(
        lambda: meta_step(params, opt_state, ep, jax.random.PRNGKey(3)))
    batch = {"image": ep["support_images"], "label": s_lab}
    us_pre, _ = time_us(lambda: pre_step(params, opt_state, batch))
    geo = f"nway={n_way};kshot={k_shot};nq={n_query};dim={dim};cl=8"
    return [("hat/meta_train_step", us_meta, geo),
            ("hat/pretrain_step", us_pre, geo)]


def _encoding_sweep_rows():
    rows = []
    n, d, b, k = 512, 48, 8, 32
    for name, cl in [("mtmc", 8), ("b4e", 3), ("b4we", 2), ("sre", 4)]:
        cfg = SearchConfig(name, cl=cl, mode="avss", use_kernel="ref")
        sv = jax.random.randint(jax.random.PRNGKey(0), (n, d), 0,
                                cfg.enc.levels)
        qv = jax.random.randint(jax.random.PRNGKey(1), (b, d), 0, 4)
        store = MemoryStore.from_quantized(
            sv, jnp.arange(n, dtype=jnp.int32) % 17, cfg)
        eng = RetrievalEngine(cfg, backend="pallas")
        req = SearchRequest(mode="two_phase", k=k)
        fn = jax.jit(lambda q, st=store, e=eng, r=req: e.search(st, q, r))
        us, _ = time_us(fn, qv)
        iters = search_iterations(d, cfg.enc, "avss")
        rows.append((f"encoding_sweep/{name}_cl{cl}_two_phase", us,
                     f"N={n};d={d};B={b};k={k};levels={cfg.enc.levels};"
                     f"words={cfg.enc.length};iterations={iters}"))
    return rows


def run():
    return _hat_step_rows() + _encoding_sweep_rows()
