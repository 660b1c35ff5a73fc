"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference semantics +
the two-phase shortlist recall curve. NOTE: wall-times on this CPU container
measure the INTERPRETER, not TPU performance -- the TPU-side analysis lives
in the roofline (benchmarks/roofline.py); these rows track relative costs and
correctness at benchmark scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_us
from repro.core import avss as avss_lib
from repro.core.avss import SearchConfig
from repro.core.mcam import MCAMConfig
from repro.kernels import ops


def run():
    rows = []
    cfg = SearchConfig("mtmc", cl=8, mode="avss",
                       mcam=MCAMConfig(), use_kernel="ref")
    enc = cfg.enc
    key = jax.random.PRNGKey(0)
    N, B, d = 512, 8, 48
    sv = jax.random.randint(key, (N, d), 0, enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(1), (B, d), 0, 4)

    # reference full search
    f_ref = jax.jit(lambda q, s: avss_lib.search_quantized(q, s, cfg)["votes"])
    us, votes_ref = time_us(f_ref, qv, sv, iters=2)
    rows.append((f"kernel/ref_full_N{N}", us, "backend=jnp"))

    # pallas full search (interpret mode on CPU)
    cfg_k = SearchConfig("mtmc", cl=8, mode="avss",
                         mcam=MCAMConfig(), use_kernel="pallas")
    f_pal = jax.jit(lambda q, s: avss_lib.search_quantized(q, s, cfg_k)["votes"])
    us, votes_pal = time_us(f_pal, qv, sv, iters=2)
    np.testing.assert_allclose(np.asarray(votes_ref), np.asarray(votes_pal),
                               rtol=1e-5)
    rows.append((f"kernel/pallas_full_N{N}", us, "backend=pallas-interpret"))

    # two-phase recall@k
    full = avss_lib.search_quantized(qv, sv, cfg)
    full_best = np.asarray(jnp.argmax(
        full["votes"] - 1e-6 * full["dist"], -1))
    recalls = []
    for k in (16, 32, 64, 128):
        tp = ops.two_phase_search(qv, sv, cfg, k=k)
        sc = np.asarray(tp["votes"]) - 1e-6 * np.asarray(tp["dist"])
        tp_best = np.asarray(tp["indices"])[np.arange(B), sc.argmax(1)]
        recalls.append((k, float((full_best == tp_best).mean())))
    rows.append(("kernel/two_phase_recall", 0.0,
                 ";".join(f"k{k}={r:.2f}" for k, r in recalls)))
    return rows
