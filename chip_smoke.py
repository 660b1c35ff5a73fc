#!/usr/bin/env python3
"""Smoke run of the served path on a TPU, through the normal entry points.

    python3 chip_smoke.py              # one chip: phases (a) and (b)
    python3 chip_smoke.py --chips 4    # four chips: the row-sharded store only
    python3 chip_smoke.py --tiny       # the same phases at toy sizes, any backend

(a) Many-class retrieval service at the paper's Omniglot geometry (d=48,
    MTMC CL=32, AVSS): a `MemoryStore` of 1,048,576 rows (~105K classes x
    10 shots) is calibrated, written in large batches through
    `MemoryStore.write`, and searched through `RetrievalEngine.search` on
    the default (kernel) backend with B=64 queries in `two_phase` (k=32)
    and `ideal`; `full` runs on a 65,536-row store with B=8. Every mode is
    checked bit for bit against `backend="ref"` on the same store and
    queries, and the `ideal` top-k against an independent float32 brute
    force over the stored values.
(b) kNN-LM decode: `launch.serve.serve("starcoder2-3b", smoke=False, ...)`
    at its published widths, batch 4: prompts prefilled into the sliding-
    window ring, a d_model-wide datastore of the decoder's own hidden
    states sized to the free HBM, two-phase MCAM retrieval every step.

`--chips 4` runs only the four-chip path: a streaming write into a store
row-sharded over a 4-device mesh, `two_phase` and `ideal` search on it, and
the same store written and searched unsharded on one device, compared bit
for bit.

Data is generated from `--seed`; nothing outside the checkout is read. The
timings printed are smoke timings of one run, not benchmark numbers. The
last line of stdout is one JSON object naming the device; any failure
raises and exits non-zero. Without a TPU the script refuses to run unless
`--tiny` is given.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_store: int        # rows of the two_phase / ideal store (phase a)
    n_full: int         # rows of the `full`-mode store (phase a)
    n_sharded: int      # rows of the four-chip store
    batch: int          # queries per two_phase / ideal search
    batch_full: int     # queries per `full` search
    write_batch: int    # rows per MemoryStore.write call
    decode_smoke: bool  # reduced decoder config (toy sizes only)
    decode_batch: int
    prompt_len: int
    decode_steps: int


FULL = Sizes(n_store=1 << 20, n_full=1 << 16, n_sharded=1 << 20, batch=64,
             batch_full=8, write_batch=1 << 17, decode_smoke=False,
             decode_batch=4, prompt_len=4, decode_steps=8)
TINY = Sizes(n_store=4096, n_full=256, n_sharded=4096, batch=8,
             batch_full=8, write_batch=1024, decode_smoke=True,
             decode_batch=2, prompt_len=2, decode_steps=2)
K = 32
SHOTS = 10          # rows per class


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def peak_bytes(device) -> str:
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use'] / 2**30:.3f} GiB"


def timed_program(name: str, fn, *args, runs: int = 3):
    """AOT-compile `fn` for `args`, run it `runs` times; prints the compile
    time and the median steady wall time (to block_until_ready)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    times, out = [], None
    for _ in range(runs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        times.append(time.perf_counter() - t0)
    steady = sorted(times)[len(times) // 2]
    log(f"{name}: compile {compile_s:.3f} s, steady {steady * 1e3:.3f} ms "
        f"(median of {runs}; smoke timing, not a benchmark)")
    return out


def assert_bit_identical(what: str, a, b) -> None:
    import numpy as np
    for field in ("votes", "dist", "indices", "labels"):
        x = np.asarray(getattr(a, field))
        y = np.asarray(getattr(b, field))
        # bytes, not values: -0.0 == 0.0 and NaN != NaN would hide a
        # difference (a device array may come back non-contiguous)
        if x.shape != y.shape or x.dtype != y.dtype \
                or x.tobytes() != y.tobytes():
            n_diff = int((x != y).sum()) if x.shape == y.shape else -1
            raise AssertionError(f"{what}: {field} differs ({n_diff} "
                                 f"elements of {x.size})")
    log(f"parity {what}: bit-identical (votes, dist, indices, labels)")


def class_data(key, n_rows: int, dim: int):
    """Centroids of n_rows // SHOTS classes and a row generator: row r
    belongs to class r // SHOTS, a noisy copy of its centroid."""
    import jax
    import jax.numpy as jnp
    n_classes = -(-n_rows // SHOTS)
    centroids = jax.random.normal(jax.random.fold_in(key, 0),
                                  (n_classes, dim))

    def rows(start: int, n: int):
        labels = (start + jnp.arange(n, dtype=jnp.int32)) // SHOTS
        noise = jax.random.normal(jax.random.fold_in(key, 1 + start),
                                  (n, dim))
        return centroids[labels] + 0.25 * noise, labels

    return centroids, rows


def queries_for(key, centroids, n: int):
    """n queries near random class centroids, with their classes."""
    import jax
    kc, kn = jax.random.split(key)
    cls = jax.random.randint(kc, (n,), 0, centroids.shape[0])
    q = centroids[cls] + 0.25 * jax.random.normal(kn, (n, centroids.shape[1]))
    return q, cls


def build_store(mem_cfg, rows, n_rows: int, write_batch: int, mesh=None):
    """create -> calibrate (on the first batch) -> [shard] -> write in
    batches through MemoryStore.write, jitted with the store donated."""
    import jax
    from repro.engine import MemoryStore
    store = jax.jit(MemoryStore.create, static_argnums=0)(mem_cfg)
    first, _ = rows(0, min(write_batch, n_rows))
    store = store.calibrate(first)
    if mesh is not None:
        store = store.shard(mesh, ("data",))
    write = jax.jit(lambda st, v, lab: st.write(v, lab), donate_argnums=0)
    t0 = time.perf_counter()
    for start in range(0, n_rows, write_batch):
        v, lab = rows(start, min(write_batch, n_rows - start))
        store = write(store, v, lab)
    jax.block_until_ready(store)
    return store, time.perf_counter() - t0


def store_bytes(store) -> int:
    import jax
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(store))


def brute_force_ideal(store, q_words, cl: int, k: int):
    """Independent float32 top-k over the stored values: the MTMC AVSS
    distance of query word q and value v is |cl*q - v| per dimension;
    ranked by (distance, row) on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    vals = store.values.astype(jnp.float32)
    dist = jax.jit(lambda q: jax.lax.map(
        lambda qb: jnp.abs(cl * qb[None, :] - vals).sum(-1),
        q.astype(jnp.float32)))(q_words)
    dist = np.asarray(dist)
    idx = np.empty((dist.shape[0], k), np.int64)
    for b, row in enumerate(dist):
        kth = np.partition(row, k - 1)[k - 1]
        cand = np.nonzero(row <= kth)[0]
        idx[b] = cand[np.lexsort((cand, row[cand]))][:k]
    return np.take_along_axis(dist, idx, 1), idx


def phase_retrieval(sizes: Sizes, key) -> None:
    import jax
    import numpy as np
    from repro.configs import load_config
    from repro.core.memory import MemoryConfig
    from repro.engine import RetrievalEngine, SearchRequest

    fsl = load_config("omniglot-conv4")
    scfg, dim = fsl.search, fsl.embed_dim
    kernel = RetrievalEngine(scfg)
    ref = RetrievalEngine(scfg, backend="ref")
    log(f"retrieval: omniglot-conv4 d={dim} {scfg.encoding} CL={scfg.cl} "
        f"{scfg.mode}; kernel backend {kernel.resolved_backend!r} vs 'ref'")

    centroids, rows = class_data(jax.random.fold_in(key, 1), sizes.n_store,
                                 dim)
    store, write_s = build_store(
        MemoryConfig(capacity=sizes.n_store, dim=dim, search=scfg), rows,
        sizes.n_store, sizes.write_batch)
    nbytes = store_bytes(store)
    log(f"store: N={sizes.n_store} rows, {nbytes / sizes.n_store:.0f} B/row, "
        f"{nbytes / 2**30:.3f} GiB on device; written in "
        f"{-(-sizes.n_store // sizes.write_batch)} batches of "
        f"{sizes.write_batch} in {write_s:.3f} s (compile included)")
    q, cls = queries_for(jax.random.fold_in(key, 2), centroids, sizes.batch)

    for mode in ("two_phase", "ideal"):
        req = SearchRequest(mode=mode, k=K)
        tag = f"{mode} N={sizes.n_store} B={sizes.batch} k={K}"
        got = timed_program(f"{tag} kernel", lambda st, x, e=kernel, r=req:
                            e.search(st, x, r), store, q)
        want = timed_program(f"{tag} ref", lambda st, x, e=ref, r=req:
                             e.search(st, x, r), store, q)
        assert_bit_identical(tag, got, want)
        acc = float(np.mean(np.asarray(got.predict()) == np.asarray(cls)))
        log(f"{tag}: 1-NN class accuracy {acc:.3f}")
        if mode == "ideal":
            d_bf, i_bf = brute_force_ideal(
                store, store.quantize_queries(q), scfg.cl, K)
            if not (np.array_equal(np.asarray(got.indices), i_bf)
                    and np.array_equal(np.asarray(got.dist), d_bf)):
                raise AssertionError(f"{tag}: top-k differs from the "
                                     f"float32 brute force")
            log(f"parity {tag}: top-k equals the float32 brute force")
    del store, got, want
    gc.collect()

    centroids, rows = class_data(jax.random.fold_in(key, 3), sizes.n_full,
                                 dim)
    store, _ = build_store(
        MemoryConfig(capacity=sizes.n_full, dim=dim, search=scfg), rows,
        sizes.n_full, sizes.write_batch)
    q, _ = queries_for(jax.random.fold_in(key, 4), centroids,
                       sizes.batch_full)
    req = SearchRequest(mode="full")
    tag = f"full N={sizes.n_full} B={sizes.batch_full}"
    got = timed_program(f"{tag} kernel", lambda st, x:
                        kernel.search(st, x, req), store, q)
    want = timed_program(f"{tag} ref", lambda st, x:
                         ref.search(st, x, req), store, q)
    assert_bit_identical(tag, got, want)


def phase_decode(sizes: Sizes) -> None:
    import numpy as np
    from repro.launch.serve import serve
    out = serve("starcoder2-3b", smoke=sizes.decode_smoke,
                batch=sizes.decode_batch, steps=sizes.decode_steps,
                prompt_len=sizes.prompt_len, retrieval=True,
                retrieval_mode="two-phase")
    toks = out["tokens"]
    if toks.shape != (sizes.decode_batch, sizes.decode_steps):
        raise AssertionError(f"decode returned tokens {toks.shape}")
    warm = np.asarray(out["latencies_s"][1:]) * 1e3
    log(f"decode starcoder2-3b smoke={sizes.decode_smoke} batch "
        f"{sizes.decode_batch}, prompt {sizes.prompt_len} prefilled, "
        f"retrieval two-phase over a d_model store: first step "
        f"{out['compile_s']:.3f} s (compile included), "
        f"{sizes.decode_steps} steps in {out['decode_s']:.3f} s, median "
        f"warm step {float(np.median(warm)) if warm.size else 0.0:.3f} ms "
        f"(smoke timing, not a benchmark)")


def phase_sharded(sizes: Sizes, key) -> None:
    import jax
    from repro.configs import load_config
    from repro.core.memory import MemoryConfig
    from repro.engine import RetrievalEngine, SearchRequest
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = make_mesh((4,), ("data",), devices=devices[:4])
    fsl = load_config("omniglot-conv4")
    scfg, dim = fsl.search, fsl.embed_dim
    mem_cfg = MemoryConfig(capacity=sizes.n_sharded, dim=dim, search=scfg)
    centroids, rows = class_data(jax.random.fold_in(key, 5),
                                 sizes.n_sharded, dim)
    sharded, write_s = build_store(mem_cfg, rows, sizes.n_sharded,
                                   sizes.write_batch, mesh=mesh)
    log(f"sharded store: N={sizes.n_sharded} over {dict(mesh.shape)}, streamed "
        f"in batches of {sizes.write_batch} in {write_s:.3f} s (compile "
        f"included)")
    single, write_s = build_store(mem_cfg, rows, sizes.n_sharded,
                                  sizes.write_batch)
    log(f"unsharded twin on {single.values.devices()}: written in "
        f"{write_s:.3f} s")
    q, _ = queries_for(jax.random.fold_in(key, 6), centroids, sizes.batch)
    engine = RetrievalEngine(scfg)
    for mode in ("two_phase", "ideal"):
        req = SearchRequest(mode=mode, k=K)
        tag = f"{mode} N={sizes.n_sharded} B={sizes.batch} k={K}"
        search = lambda st, x, r=req: engine.search(st, x, r)
        got = timed_program(f"{tag} sharded x4", search, sharded, q)
        want = timed_program(f"{tag} unsharded", search, single, q)
        assert_bit_identical(f"{tag} sharded vs unsharded", got, want)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on any backend (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); run with --tiny to rehearse "
                         f"at toy sizes")
    from repro.launch.cache import enable_compile_cache
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    sizes = TINY if args.tiny else FULL
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        phase_sharded(sizes, key)
    else:
        phase_retrieval(sizes, key)
        log(f"after retrieval: peak_bytes_in_use {peak_bytes(dev)}")
        phase_decode(sizes)
    log(f"peak_bytes_in_use {peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
