"""Closed-loop retrieval traffic against a programmed MCAM store.

One client: it sends a batch of queries, waits until the batch's answers
(predicted labels) are on the host, and sends the next. Batches are drawn
in turn from a pool made in set-up, so a seed fixes every query.

Set-up programs the store through the normal entry points
(`MemoryStore.create`, `calibrate` on the first write batch, `shard` over
the cell's chips when it has several, then `write` batch by batch) and
warms the one search program the window runs,
`RetrievalEngine.search(store, q, SearchRequest(mode, k))` on the default
backend.

The check, once the window has closed: a sample of the store's rows (its
quantized values, programmed string cells and labels) and a sample of the
answered batches (shortlist rows and distances, noisy votes, candidate
labels and answers) against `bench.reference.avss`, which programs and
searches the same rows on its own. The store, the shortlist and each
answer's label (that of the candidate its own votes rank best) are
integers or integer-valued floats, compared exactly. The votes threshold
float32 currents, so a sound rewrite of the rescore may flip a vote whose
current lies within rounding of a threshold, and a query's best candidate
with it: their limits lie between what the reference computed in another
float32 order reads and what the bfloat16 control reads (PERF.md,
section 2).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
from bench.reference import avss as ref


def search_config(cfg: dict):
    from repro.core.avss import SearchConfig
    from repro.core.mcam import MCAMConfig
    mc = cfg["mcam"]
    return SearchConfig(
        encoding=cfg["encoding"], cl=cfg["cl"], mode=cfg["mode"],
        mcam=MCAMConfig(string_len=mc["string_len"], rho=mc["rho"],
                        sigma_device=mc["sigma_device"],
                        sigma_read=mc["sigma_read"],
                        n_thresholds=mc["n_thresholds"],
                        seed=mc["noise_seed"]))


def _row_generator(ctx):
    cfg = ctx.config
    key = jax.random.PRNGKey(ctx.seed)
    centroids = jax.jit(data.class_centroids, static_argnums=(1, 2, 3))(
        key, cfg["rows"], cfg["dim"], cfg["shots"])
    gen = jax.jit(data.rows, static_argnums=(3, 4))
    return key, centroids, lambda start, n: gen(
        key, centroids, jnp.int32(start), n, cfg["shots"])


def _create(mem_cfg, mesh):
    """An empty store, laid out over the mesh from the start when there
    is one (a store too large for one chip never exists unsharded)."""
    from repro.engine import MemoryStore
    make = lambda: MemoryStore.create(mem_cfg)
    if mesh is None:
        return jax.jit(make)()
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    shapes = jax.eval_shape(make)
    shard = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, P("data") if a.ndim and a.shape[0]
                                == mem_cfg.capacity else P()), shapes)
    return jax.jit(make, out_shardings=shard)()


def setup(ctx):
    from repro.core.memory import MemoryConfig
    from repro.engine import RetrievalEngine, SearchRequest

    cfg, wl = ctx.config, ctx.workload
    scfg = search_config(cfg)
    n, wb = cfg["rows"], cfg["write_batch"]
    key, centroids, rows = _row_generator(ctx)
    mem_cfg = MemoryConfig(capacity=n, dim=cfg["dim"], search=scfg,
                           clip_std=cfg["clip_std"])
    mesh = None
    if ctx.chips > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((ctx.chips,), ("data",), devices=ctx.devices)

    write = jax.jit(lambda st, v, lab: st.write(v, lab), donate_argnums=0)
    with ctx.timed("store_write_s"):
        store = _create(mem_cfg, mesh)
        first, _ = rows(0, min(wb, n))
        store = store.calibrate(first)
        if mesh is not None:
            store = store.shard(mesh, ("data",))
        for start in range(0, n, wb):
            v, lab = rows(start, min(wb, n - start))
            store = write(store, v, lab)
        jax.block_until_ready(store)
    ctx.log(f"store: {n} rows over {ctx.chips} chip(s) written in "
            f"{ctx.timers['store_write_s']:.3f} s")

    pool_n, batch = wl["query_pool_batches"], wl["batch"]
    pool, _ = jax.jit(data.query_pool, static_argnums=(2, 3))(
        key, centroids, pool_n, batch)
    pool = [pool[i] for i in range(pool_n)]
    engine = RetrievalEngine(scfg)
    request = SearchRequest(mode=wl["mode"], k=wl["k"])

    @jax.jit
    def search(st, q):
        res = engine.search(st, q, request)
        return res, res.predict()

    np.asarray(search(store, pool[0])[1])          # the one shape, warmed
    return {"store": store, "pool": pool, "search": search, "rows": rows}


def window(ctx, state):
    store, pool, search = state["store"], state["pool"], state["search"]
    batch = ctx.workload["batch"]
    latencies, answered = [], []
    t_start = time.perf_counter()
    deadline = t_start + ctx.seconds
    i = 0
    while True:
        t0 = time.perf_counter()
        with ctx.span("bench.search"):
            res, labels = search(store, pool[i % len(pool)])
        with ctx.span("bench.readback"):
            labels = np.asarray(labels)
        t1 = time.perf_counter()
        latencies.append(t1 - t0)
        answered.append((i % len(pool), res, labels))
        i += 1
        if t1 >= deadline:
            break
    seconds = t1 - t_start
    state["answered"] = answered
    lat = np.asarray(latencies) * 1e3
    med = float(np.median(lat))
    ctx.log(f"batches {i}: median {med:.3f} ms, p95 "
            f"{np.percentile(lat, 95):.3f}, max {lat.max():.3f} (batch "
            f"{int(lat.argmax())}), first {lat[0]:.3f}; time over 1.1x the "
            f"median {float(np.sum(np.maximum(lat - 1.1 * med, 0))):.1f} ms "
            f"in {int(np.sum(lat > 1.1 * med))} batches")
    return {"attempted": i * batch,
            "failed": int(sum((a[2] < 0).sum() for a in answered)),
            "metrics": {"qps": i * batch / seconds,
                        "search_p95_ms": float(np.percentile(latencies, 95))
                        * 1e3},
            "counts": {"batches": i, "queries": i * batch,
                       "seconds": seconds}}


def program_outputs(ctx, state) -> dict:
    """What the timed path produced, sampled by the seed, on the host:
    store rows and answered batches. Frees the store."""
    wl, answered = ctx.workload, state["answered"]
    n = ctx.config["rows"]
    rows = np.sort(ctx.rng.choice(n, min(wl["check_rows"], n),
                                  replace=False))
    batches = ctx.rng.choice(len(answered),
                             min(wl["check_batches"], len(answered)),
                             replace=False)
    store = state.pop("store")
    idx = jnp.asarray(rows)
    out = {"rows": rows,
           "values": np.asarray(store.values[idx]),
           "s_grid": np.asarray(store.s_grid[idx]),
           "labels": np.asarray(store.labels[idx]),
           "batches": []}
    for b in batches:
        pool_i, res, answer = answered[b]
        out["batches"].append({
            "pool": pool_i, "answer": answer,
            **{f: np.asarray(getattr(res, f))
               for f in ("indices", "dist", "votes", "labels")}})
    del store, answered, state["answered"]
    return out


def reference_outputs(ctx, state, prog: dict, dtype=jnp.float32,
                      reordered: bool = False) -> dict:
    """The reference's own programming of the same rows and its answers
    to the same batches, in `dtype` (`reordered`: see reference.avss)."""
    cfg, wl = ctx.config, ctx.workload
    n, wb = cfg["rows"], cfg["write_batch"]
    rows = state["rows"]
    lo, hi = ref.calibrate(rows(0, min(wb, n))[0], cfg["clip_std"], dtype)
    levels = 3 * cfg["cl"] + 1
    quant = jax.jit(ref.quantize, static_argnums=(1, 4))
    values = jnp.concatenate([quant(rows(s, min(wb, n - s))[0], levels, lo,
                                    hi, dtype) for s in range(0, n, wb)])
    labels = jnp.arange(n, dtype=jnp.int32) // cfg["shots"]
    sample = jnp.asarray(prog["rows"])
    out = {"values": np.asarray(values[sample]),
           "s_grid": np.asarray(ref.layout(values[sample], cfg["cl"],
                                           cfg["mcam"]["string_len"])),
           "labels": np.asarray(labels[sample]), "batches": []}
    pool = state["pool"]
    qidx = jnp.arange(wl["batch"], dtype=jnp.uint32)
    for b in prog["batches"]:
        qw = ref.quantize(pool[b["pool"]], 4, lo, hi, dtype)
        r = ref.search(qw, values, labels, qidx, cfg["cl"], wl["k"],
                       cfg["mcam"], dtype, reordered)
        out["batches"].append({k: np.asarray(v) for k, v in r.items()})
    return out


def _choice(b: dict) -> np.ndarray:
    """(B,) position of each query's best candidate by its own votes."""
    return np.asarray(ref.best(b["votes"], b["dist"]))


def compare(prog: dict, want: dict) -> dict:
    """Mismatched elements of the store and the shortlist; votes, and
    queries whose best candidate differs, in % of those compared; answers
    that are not the label of the candidate their own votes rank best."""
    diff = lambda a, b: int(np.sum(np.asarray(a) != np.asarray(b)))
    pb, wb = prog["batches"], want["batches"]
    pct = lambda f: 100.0 * sum(diff(f(p), f(w)) for p, w in zip(pb, wb)) \
        / max(1, sum(np.size(f(w)) for w in wb))
    chosen = lambda p, w: np.take_along_axis(
        np.asarray(w["labels"]), _choice(p)[:, None], 1)[:, 0]
    return {
        "store_mismatch": diff(prog["values"], want["values"])
        + diff(prog["s_grid"], want["s_grid"])
        + diff(prog["labels"], want["labels"]),
        "shortlist_mismatch": sum(diff(p["indices"], w["indices"])
                                  + diff(p["dist"], w["dist"])
                                  + diff(p["labels"], w["labels"])
                                  for p, w in zip(pb, wb)),
        "vote_mismatch_pct": pct(lambda b: b["votes"]),
        "choice_mismatch_pct": pct(_choice),
        "answer_mismatch": sum(diff(p["answer"], chosen(p, w))
                               for p, w in zip(pb, wb)),
    }


# votes and choices: set from the readings in PERF.md, section 2
LIMITS = {"store_mismatch": 0, "shortlist_mismatch": 0,
          "vote_mismatch_pct": 1.0, "choice_mismatch_pct": 1.0,
          "answer_mismatch": 0}


def check(ctx, state):
    prog = program_outputs(ctx, state)
    want = reference_outputs(ctx, state, prog)
    found = compare(prog, want)
    return {k: (found[k], LIMITS[k]) for k in LIMITS}


def control(ctx, state):
    """Against the float32 reference, on the same samples: the program's
    readings, those of the bfloat16 reference put in its place (the
    control), and those of the reference in another float32 order."""
    prog = program_outputs(ctx, state)
    want = reference_outputs(ctx, state, prog)
    ctl = reference_outputs(ctx, state, prog, dtype=jnp.bfloat16)
    other = reference_outputs(ctx, state, prog, reordered=True)
    return {"program": compare(prog, want), "control": compare(ctl, want),
            "reordered": compare(other, want)}
