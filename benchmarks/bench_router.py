"""Recall-vs-nprobe-vs-latency for the hierarchical router (PR 10).

A class-coherent partitioned store (rows sorted by label before
`shard(n_shards=S)`, the IVF-style layout the router's class-bucket
sketch is built for) is searched at every nprobe in the sweep; each row
reports latency percentiles (one shared schema, `common.time_percentiles`)
plus recall@1 of the routed 1-NN retrieval against the exhaustive
all-shards search. `nprobe=S` must be BYTE-identical to `nprobe=None`
(asserted every run), so the curve's end point IS the baseline.

NOTE: on this CPU container the timings measure XLA CPU; the recall
curve and the routed-vs-exhaustive latency ORDERING are the signal, not
absolute wall-times -- re-measure on a real TPU before using the numbers
for capacity planning (the note is embedded in BENCH_router.json).

    PYTHONPATH=src python -m benchmarks.run --only router      # full sweep
    PYTHONPATH=src python -m benchmarks.bench_router --dry-run # CI gate

--dry-run shrinks the store (N=512, S=8), asserts the routed-parity
contracts, and skips the committed-artifact refresh -- the fast-tier CI
gate that keeps the suite importable and the contracts live.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import quantize_pair, synthetic_episode, \
    time_percentiles
from repro.core.avss import SearchConfig
from repro.engine import MemoryStore, RetrievalEngine, SearchRequest

N, S, B, D, K = 4096, 16, 16, 32, 16
NPROBES = (1, 2, 4, 8, 12, 16)


def _fixture(n, s, n_way, dim, batch):
    """Class-coherent partitioned store + quantized queries: clustered
    episode embeddings, rows SORTED by label so each shard holds few
    classes (the layout that makes a class-centroid sketch selective)."""
    cfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="pallas")
    sup, s_lab, qf, _ = synthetic_episode(
        0, n_way, n // n_way, -(-batch // n_way), dim, sep=2.2, noise=0.9)
    sv, qv = quantize_pair(sup, qf, cfg.enc.levels, cfg.mode)
    order = jnp.argsort(jnp.asarray(s_lab), stable=True)
    store = MemoryStore.from_quantized(
        sv[order], jnp.asarray(s_lab)[order].astype(jnp.int32),
        cfg).shard(n_shards=s)
    return cfg, store, qv[:batch]


def _leaves(res):
    return {f: np.asarray(getattr(res, f))
            for f in ("votes", "dist", "indices", "labels")}


def _sweep(n, s, batch, nprobes, iters=5):
    cfg, store, qv = _fixture(n, s, n_way=64, dim=D, batch=batch)
    eng = RetrievalEngine(cfg)
    rows = []

    def f(req):
        return jax.jit(lambda st, q, r=req: eng.search(st, q, r),
                       static_argnames=())

    base_req = SearchRequest(mode="two_phase", k=K)
    stats_ex, res_ex = time_percentiles(f(base_req), store, qv, iters=iters)
    ref = _leaves(res_ex)
    best_ref = ref["indices"][np.arange(qv.shape[0]),
                              np.asarray(res_ex.best())]
    rows.append((f"router/exhaustive_N{n}_S{s}", stats_ex["us"],
                 f"nprobe={s};recall=1.00", stats_ex))

    for p in nprobes:
        if p > s:
            continue
        req = SearchRequest(mode="two_phase", k=K, nprobe=p)
        stats, res = time_percentiles(f(req), store, qv, iters=iters)
        got = _leaves(res)
        if p >= s:   # contract: nprobe=S is the SAME exhaustive program
            for k, v in ref.items():
                np.testing.assert_array_equal(v, got[k], err_msg=k)
        best = got["indices"][np.arange(qv.shape[0]),
                              np.asarray(res.best())]
        recall = float((best == best_ref).mean())
        rows.append((f"router/nprobe{p}_N{n}_S{s}", stats["us"],
                     f"nprobe={p};recall={recall:.2f};"
                     f"speedup_vs_exhaustive="
                     f"{stats_ex['us'] / stats['us']:.1f}x", stats))
    return rows


def run():
    return _sweep(N, S, B, NPROBES)


def dry_run():
    """Fast-tier CI gate: a shrunken sweep plus the routed-parity
    contracts (routed == brute force restricted to the visited shards;
    nprobe=S byte-identical to nprobe=None)."""
    from repro.engine import router as router_lib
    n, s, batch = 512, 8, 6
    rows = _sweep(n, s, batch, (1, 2, s), iters=1)
    cfg, store, qv = _fixture(n, s, n_way=64, dim=D, batch=batch)
    eng = RetrievalEngine(cfg)
    p = 2
    routed = _leaves(eng.search(store, qv,
                                SearchRequest(mode="two_phase", k=K,
                                              nprobe=p)))
    full = _leaves(eng.search(store, qv,
                              SearchRequest(mode="two_phase",
                                            k=store.capacity)))
    scores = router_lib.route_scores(qv, store.sketch_sums,
                                     store.sketch_counts, cfg.enc)
    sids = np.asarray(router_lib.top_shards(scores, p))
    rows_per = store.capacity // s
    for b in range(batch):
        keep = np.isin(full["indices"][b] // rows_per, sids[b])
        for fld in ("dist", "indices", "labels", "votes"):
            np.testing.assert_array_equal(routed[fld][b],
                                          full[fld][b][keep][:K],
                                          err_msg=f"{fld}[{b}]")
    for name, us, derived, _ in rows:
        print(f"{name},{us:.1f},{derived}")
    print(f"# dry-run OK: routed parity held at N={n} S={s} nprobe={p}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="small-N parity gate (CI fast tier); no artifacts")
    if ap.parse_args().dry_run:
        dry_run()
    else:
        for name, us, derived, _ in run():
            print(f"{name},{us:.1f},{derived}")
