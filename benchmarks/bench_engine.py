"""Retrieval-engine throughput: full vs two-phase vs sharded two-phase.

Rows compare the three RetrievalEngine paths at a serving-shaped store
(N supports, B queries) plus backend variants of the shortlist. NOTE: on
this CPU container the Pallas rows measure the INTERPRETER; relative
ordering of ref-vs-two-phase and the sharded scaling shape are the signal,
not absolute wall-times (the TPU-side analysis lives in the roofline).

Run standalone for a multi-device sharded row:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --only engine
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.common import time_percentiles
from repro.core.avss import SearchConfig
from repro.core.mcam import MCAMConfig
from repro.core.memory import MemoryConfig
from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest)
from repro.launch.mesh import make_mesh

# the large-N ideal rows' store: a benchmark shape, not a phase-1 rule
N_IDEAL = 4096
N, B, D, K = 2048, 16, 48, 64
W = 256                              # streaming-write batch rows


def run():
    rows = []
    cfg = SearchConfig("mtmc", cl=8, mode="avss", mcam=MCAMConfig(),
                       use_kernel="ref")
    enc = cfg.enc
    sv = jax.random.randint(jax.random.PRNGKey(0), (N, D), 0, enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(1), (B, D), 0, 4)

    def qps(us):
        return f"qps={B / us * 1e6:.0f}"

    # full exact search (reference backend)
    eng_ref = RetrievalEngine(cfg, backend="ref")
    f_full = jax.jit(lambda q, s: eng_ref.full(q, s)["votes"])
    st_full, votes_full = time_percentiles(f_full, qv, sv, iters=2)
    us_full = st_full["us"]
    rows.append((f"engine/full_N{N}", us_full,
                 qps(us_full) + ";backend=ref", st_full))

    # two-phase: MXU shortlist + exact rescore, per shortlist backend
    votes_tp = {}
    for backend in ("ref", "pallas"):
        eng = RetrievalEngine(cfg, backend=backend)
        f_tp = jax.jit(lambda q, s, e=eng: e.two_phase(q, s, k=K)["votes"])
        st_tp, votes_tp[backend] = time_percentiles(f_tp, qv, sv, iters=3)
        us_tp = st_tp["us"]
        rows.append((f"engine/two_phase_k{K}_{backend}", us_tp,
                     qps(us_tp) + f";speedup_vs_full={us_full / us_tp:.1f}x",
                     st_tp))
    # backends must agree bit-exactly
    np.testing.assert_array_equal(np.asarray(votes_tp["ref"]),
                                  np.asarray(votes_tp["pallas"]))

    # unified API: engine.search over a programmed MemoryStore (write-time
    # proj + s_grid layouts -- the serving path). Must be bit-identical to
    # the raw two-phase call AND at least as fast per query (no per-call
    # re-layout of the store).
    labels = jnp.arange(N, dtype=jnp.int32) % 128
    store = MemoryStore.from_quantized(sv, labels, cfg)
    req = SearchRequest(mode="two_phase", k=K)
    for backend in ("ref", "pallas"):
        eng = RetrievalEngine(cfg, backend=backend)
        f_st = jax.jit(lambda st, q, e=eng: e.search(st, q, req).votes)
        st_st, votes_st = time_percentiles(f_st, store, qv, iters=3)
        us_st = st_st["us"]
        rows.append((f"engine/search_store_k{K}_{backend}", us_st,
                     qps(us_st) + f";speedup_vs_full={us_full / us_st:.1f}x",
                     st_st))
        np.testing.assert_array_equal(np.asarray(votes_tp["ref"]),
                                      np.asarray(votes_st))

    # sharded two-phase over every local device (1 on a plain CPU run;
    # launch with XLA_FLAGS=--xla_force_host_platform_device_count=8 to see
    # the multi-shard shape)
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("data",))
    svs = jax.device_put(sv, NamedSharding(mesh, P("data")))
    eng = RetrievalEngine(cfg, backend="ref")
    with mesh:
        f_sh = jax.jit(lambda q, s: eng.sharded_two_phase(
            q, s, mesh, axes=("data",), k=K)["votes"])
        st_sh, votes_sh = time_percentiles(f_sh, qv, svs, iters=3)
    us_sh = st_sh["us"]
    rows.append((f"engine/sharded_two_phase_k{K}_dev{n_dev}", us_sh,
                 qps(us_sh) + f";shards={n_dev}", st_sh))
    np.testing.assert_array_equal(np.asarray(votes_tp["ref"]),
                                  np.asarray(votes_sh))

    # shard-aware store: the same search request against store.shard(...)
    # dispatches to the sharded path (labels folded into the merge)
    sstore = store.shard(mesh, ("data",))
    with mesh:
        f_ss = jax.jit(lambda st, q: eng.search(st, q, req).votes)
        st_ss, votes_ss = time_percentiles(f_ss, sstore, qv, iters=3)
    us_ss = st_ss["us"]
    rows.append((f"engine/search_sharded_k{K}_dev{n_dev}", us_ss,
                 qps(us_ss) + f";shards={n_dev}", st_ss))
    np.testing.assert_array_equal(np.asarray(votes_tp["ref"]),
                                  np.asarray(votes_ss))

    # streaming write (the paper's cheap operation): program a W-row batch
    # into a ring store, unsharded scatter vs the shard-local write-through
    # (1-device mesh here; the multi-shard shape lives in engine_sharded)
    mcfg = MemoryConfig(capacity=N, dim=D, search=cfg)
    wvecs = jax.random.normal(jax.random.PRNGKey(2), (W, D))
    wlabs = jnp.arange(W, dtype=jnp.int32)
    base = MemoryStore.create(mcfg).calibrate(wvecs)
    f_w = jax.jit(lambda st, v, l: st.write(v, l).values)
    st_w, _ = time_percentiles(f_w, base, wvecs, wlabs, iters=3)
    us_w = st_w["us"]
    rows.append((f"engine/write_scatter_b{W}", us_w,
                 f"rows_per_s={W / us_w * 1e6:.0f}", st_w))
    sbase = base.shard(mesh, ("data",))
    with mesh:
        f_ws = jax.jit(lambda st, v, l: st.write(v, l).values)
        st_ws, vals_ws = time_percentiles(f_ws, sbase, wvecs, wlabs,
                                          iters=3)
    us_ws = st_ws["us"]
    rows.append((f"engine/write_stream_b{W}_dev{n_dev}", us_ws,
                 f"rows_per_s={W / us_ws * 1e6:.0f};shards={n_dev}",
                 st_ws))
    np.testing.assert_array_equal(np.asarray(f_w(base, wvecs, wlabs)),
                                  np.asarray(vals_ws))

    # large-N ideal serving: the ref distance vs the dot against the
    # write-time projection; bit-parity asserted
    isv = jax.random.randint(jax.random.PRNGKey(3), (N_IDEAL, D), 0,
                             enc.levels)
    istore = MemoryStore.from_quantized(
        isv, jnp.arange(N_IDEAL, dtype=jnp.int32) % 128, cfg)
    ireq = SearchRequest(mode="ideal", k=K)
    f_id = {b: jax.jit(lambda st, q, e=RetrievalEngine(cfg, backend=b):
                       e.search(st, q, ireq)) for b in ("ref", "pallas")}
    res_id = {}
    for b, f in f_id.items():
        st_id, res_id[b] = time_percentiles(f, istore, qv, iters=3)
        rows.append((f"engine/ideal_{b}_N{N_IDEAL}", st_id["us"],
                     qps(st_id["us"]), st_id))
    for key in ("votes", "dist", "indices", "labels"):
        np.testing.assert_array_equal(np.asarray(getattr(res_id["ref"], key)),
                                      np.asarray(getattr(res_id["pallas"],
                                                         key)))

    # multi-tenant serving (PR 9): B queries spread over T stacked stores,
    # ONE coalesced vmapped search_tenants call vs T sequential solo
    # engine.search calls over the same queries -- the coalesced path must
    # be bit-identical per query and is the one the TenantServer shell
    # batches into. Stores are small (serving-shaped: many tenants, few
    # rows each); the signal is the per-T scaling of coalesced dispatch
    # overhead vs the sequential python loop, not absolute wall-time.
    from repro.engine import TenantStore
    t_cap, t_dim = 64, 16
    tcfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="ref")
    treq = SearchRequest(mode="two_phase", k=8)
    teng = RetrievalEngine(tcfg)
    f_co = jax.jit(lambda ts, q, i: teng.search_tenants(ts, q, i, treq))
    for T in (1, 8, 64):
        tstores = [MemoryStore.from_quantized(
            jax.random.randint(jax.random.PRNGKey(10 + t), (t_cap, t_dim),
                               0, tcfg.enc.levels),
            jax.random.randint(jax.random.PRNGKey(200 + t), (t_cap,),
                               0, 16), tcfg) for t in range(T)]
        tts = TenantStore.stack(tstores)
        tq = jax.random.randint(jax.random.PRNGKey(300 + T), (B, t_dim),
                                0, 4)
        tids = jax.random.randint(jax.random.PRNGKey(400 + T), (B,), 0, T)
        st_co, res_co = time_percentiles(f_co, tts, tq, tids, iters=3)
        us_co = st_co["us"]
        rows.append((f"engine/tenants_coalesced_T{T}", us_co,
                     qps(us_co) + f";tenants={T}", st_co))

        # sequential: one solo search per tenant group (what serving
        # without the stack would do) -- parity-asserted against the
        # coalesced rows, timing includes the per-tenant dispatch loop
        tid_np = np.asarray(tids)
        groups = [(t, np.where(tid_np == t)[0]) for t in range(T)
                  if (tid_np == t).any()]
        f_solo = jax.jit(lambda st, q, e=teng: e.search(st, q, treq))

        def seq(ts_q=tq, gs=groups, sts=tstores):
            out = [f_solo(sts[t], ts_q[jnp.asarray(sel)]) for t, sel in gs]
            jax.block_until_ready(out)
            return out

        st_seq, res_seq = time_percentiles(seq, iters=3)
        us_seq = st_seq["us"]
        rows.append((f"engine/tenants_sequential_T{T}", us_seq,
                     qps(us_seq)
                     + f";coalesced_speedup={us_seq / us_co:.1f}x",
                     st_seq))
        for (t, sel), solo in zip(groups, res_seq):
            np.testing.assert_array_equal(
                np.asarray(res_co.labels[jnp.asarray(sel)]),
                np.asarray(solo.labels))

    # two-phase recall@k of the 1-NN decision vs the full search
    from repro.core import avss as avss_lib
    full = eng_ref.full(qv, sv)
    full_best = np.asarray(avss_lib.best_support(full))
    tp = eng_ref.two_phase(qv, sv, k=K)
    best = np.asarray(avss_lib.best_support(tp))
    tp_best = np.asarray(tp["indices"])[np.arange(B), best]
    rows.append((f"engine/two_phase_recall_k{K}", 0.0,
                 f"recall={float((full_best == tp_best).mean()):.2f}"))
    return rows
