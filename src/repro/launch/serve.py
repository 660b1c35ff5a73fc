"""Serving launcher: batched-request decode loop with optional MCAM
retrieval fusion (reduced configs run on CPU; the dry-run lowers the same
serve_step for the production meshes).

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --smoke \
        --batch 4 --steps 16 [--retrieval]

`--no-smoke` serves the published config at its full widths.

Multi-tenant retrieval serving (PR 9): `TenantServer` below is the minimal
coalescing shell over `RetrievalEngine.search_tenants` -- concurrent
per-tenant queries accumulate into one device batch, run as ONE compiled
search over the stacked `TenantStore`, and scatter back per ticket. The
standalone demo:

    PYTHONPATH=src python -m repro.launch.serve --tenants 8 --steps 16
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import load_config
from repro.launch import steps as steps_lib
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.models.sharding import Rules


class TenantServer:
    """Coalesce concurrent per-tenant queries into one compiled search.

    `submit(tenant_id, query)` enqueues and returns a ticket; `flush()`
    gathers the queue into one `(B, d)` batch + `(B,)` tenant_ids, runs
    a SINGLE jitted `search_tenants` call, and scatters each result row
    back to its ticket. Per-tenant ring writes go through
    `TenantStore.write_at`, which keeps every leaf shape -- so writes
    NEVER retrace the search (`cache_entries` stays flat; asserted in
    tests/test_tenant.py). The search program is shape-polymorphic only
    in the usual jit sense: one cache entry per distinct (B, T) shape.
    """

    def __init__(self, engine, tstore, request):
        self.engine = engine
        self.tstore = tstore
        self.request = request
        self._queue: list[tuple[jax.Array, int]] = []  # (query, tenant_id)
        self._search = jax.jit(
            partial(self._search_impl, engine), static_argnames=("req",))

    @staticmethod
    def _search_impl(engine, tstore, q, tids, req):
        return engine.search_tenants(tstore, q, tids, req)

    def submit(self, tenant_id: int, query: jax.Array) -> int:
        """Enqueue one query for one tenant; returns its ticket (the
        row the next `flush()` will hand back for it)."""
        self._queue.append((query, int(tenant_id)))
        return len(self._queue) - 1

    def flush(self):
        """Run the queued queries as ONE coalesced device batch and
        return {ticket: 1-query SearchResult} (batch axis kept, so
        `.predict()` / `.best()` work per ticket)."""
        if not self._queue:
            return {}
        q = jnp.stack([query for query, _ in self._queue])
        tids = jnp.asarray([t for _, t in self._queue], jnp.int32)
        self._queue = []
        res = self._search(self.tstore, q, tids, self.request)
        return {i: jax.tree_util.tree_map(lambda a: a[i:i + 1], res)
                for i in range(q.shape[0])}

    def write(self, tenant_id: int, vectors: jax.Array,
              labels: jax.Array) -> None:
        """Per-tenant ring write-through; leaf shapes are preserved so
        the compiled search is not retraced."""
        self.tstore = self.tstore.write_at(tenant_id, vectors, labels)

    def cache_entries(self) -> int:
        return self._search._cache_size()


def serve_tenants(n_tenants: int, steps: int, batch: int, dim: int = 16,
                  capacity: int = 32, mode: str = "two_phase",
                  backend: str = "auto", k: int = 8, seed: int = 0):
    """Standalone multi-tenant retrieval demo: T calibrated stores, a
    decode-loop of coalesced search batches interleaved with per-tenant
    ring writes -- prints throughput and the jit cache entry count
    (which must stay at 1 regardless of T or the write traffic)."""
    from repro.core.avss import SearchConfig
    from repro.core.memory import MemoryConfig
    from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest,
                              TenantStore)
    scfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="ref")
    mem_cfg = MemoryConfig(capacity=capacity, dim=dim, search=scfg)
    key = jax.random.PRNGKey(seed)
    stores = []
    for t in range(n_tenants):
        kt = jax.random.fold_in(key, t)
        vecs = jax.random.normal(kt, (capacity, dim))
        labs = jax.random.randint(jax.random.fold_in(kt, 1), (capacity,),
                                  0, 16)
        stores.append(MemoryStore.create(mem_cfg).calibrate(vecs)
                      .write(vecs, labs))
    server = TenantServer(RetrievalEngine(scfg, backend=backend),
                          TenantStore.stack(stores),
                          SearchRequest(mode=mode, k=k))
    t0 = time.time()
    for step in range(steps):
        ks = jax.random.fold_in(key, 10_000 + step)
        tids = np.asarray(jax.random.randint(ks, (batch,), 0, n_tenants))
        q = jax.random.normal(jax.random.fold_in(ks, 1), (batch, dim))
        tickets = [server.submit(int(tids[i]), q[i]) for i in range(batch)]
        out = server.flush()
        assert sorted(out) == tickets
        if step % 4 == 3:  # interleaved ring writes must not retrace
            server.write(int(tids[0]),
                         jax.random.normal(jax.random.fold_in(ks, 2),
                                           (2, dim)),
                         jnp.array([3, 5]))
    preds = jnp.concatenate([out[i].predict() for i in sorted(out)])
    preds.block_until_ready()
    dt = time.time() - t0
    entries = server.cache_entries()
    print(f"tenants={n_tenants}: {steps} flushes x {batch} queries in "
          f"{dt:.2f}s ({steps * batch / dt:.1f} q/s), "
          f"jit cache entries={entries}")
    assert entries == 1, f"per-tenant retrace detected: {entries} entries"
    return preds


def serve(arch: str, smoke: bool, batch: int, steps: int, prompt_len: int,
          retrieval: bool = False, retrieval_mode: str = "two-phase",
          retrieval_backend: str = "auto", retrieval_k: int = 32,
          retrieval_fused_min_rows: int | None = None,
          retrieval_shards: int | None = None,
          retrieval_nprobe: int | None = None) -> dict:
    """Decode `steps` tokens for `batch` requests after a `prompt_len`
    random prompt. Returns the tokens (batch, steps), `compile_s` (the
    first step, compile included, to its result) and `decode_s` (the
    decode loop, which reads every token back to the host)."""
    cfg = load_config(arch, smoke=smoke)
    rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
    # jitted so a published-width init never materialises its f32 draws
    params = jax.jit(partial(tfm.init, cfg=cfg))(jax.random.PRNGKey(0))
    max_seq = prompt_len + steps
    caches = tfm.init_cache(cfg, batch, max_seq)
    step_fn = jax.jit(steps_lib.make_serve_step(cfg, rules))

    mstate = mem_cfg = None
    if retrieval:
        from repro.core.avss import SearchConfig
        from repro.core.memory import MemoryConfig
        from repro.engine import MemoryStore, RetrievalEngine
        mem_cfg = MemoryConfig(capacity=1024, dim=min(48, cfg.d_model),
                               search=SearchConfig("mtmc", cl=8, mode="avss",
                                                   use_kernel="ref"))
        vecs = jax.random.normal(jax.random.PRNGKey(7), (256, mem_cfg.dim))
        toks = jax.random.randint(jax.random.PRNGKey(8), (256,), 0,
                                  cfg.vocab_size)
        # program once at write time (values + proj + s_grid); the decode
        # loop below jits against the store's constant layouts
        mstate = MemoryStore.create(mem_cfg).calibrate(vecs).write(vecs, toks)
        if retrieval_shards:
            # logical row partition; with --retrieval-nprobe < shards the
            # decode loop routes through the per-shard sketch
            # (repro/engine/router.py) instead of searching every shard
            mstate = mstate.shard(n_shards=retrieval_shards)
        # fused-threshold override (e.g. a TPU-measured dense-vs-fused
        # crossover) applies engine-wide without a code change
        eng_kw = {} if retrieval_fused_min_rows is None else \
            {"fused_min_rows": retrieval_fused_min_rows}
        engine = (RetrievalEngine(mem_cfg.search, backend=retrieval_backend,
                                  **eng_kw)
                  if retrieval_mode in ("two-phase", "ideal") else None)
        mode = "ideal" if retrieval_mode == "ideal" else "two_phase"
        step_fn = jax.jit(steps_lib.make_serve_step_with_mcam(
            cfg, rules, mem_cfg, engine=engine, k=retrieval_k, mode=mode,
            nprobe=retrieval_nprobe))

    key = jax.random.PRNGKey(1)
    tok = jax.random.randint(key, (batch, 1), 0, cfg.vocab_size)
    compile_s = 0.0
    for t in range(prompt_len):  # warm the cache with a random prompt
        t0 = time.perf_counter()
        args = (params, caches, {"tokens": tok}, jnp.int32(t))
        out = step_fn(*args, mstate) if retrieval else step_fn(*args)
        logits, caches = out
        if t == 0:                                # the first step compiles
            jax.block_until_ready(logits)
            compile_s = time.perf_counter() - t0
        tok = jax.random.randint(jax.random.fold_in(key, t), (batch, 1), 0,
                                 cfg.vocab_size)
    t0 = time.perf_counter()
    toks = []
    for i in range(steps):
        args = (params, caches, {"tokens": tok}, jnp.int32(prompt_len + i))
        out = step_fn(*args, mstate) if retrieval else step_fn(*args)
        logits, caches = out
        tok = jnp.argmax(logits[:, 0], -1)[:, None]
        toks.append(np.asarray(tok))
    dt = time.perf_counter() - t0
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    print(f"{arch}: first step {compile_s:.2f}s (compile included); "
          f"{steps} steps x {batch} reqs in {dt:.2f}s "
          f"({steps * batch / dt:.1f} tok/s)")
    return {"tokens": np.concatenate(toks, 1), "compile_s": compile_s,
            "decode_s": dt}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default); --no-smoke serves the "
                         "published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--retrieval-mode", default="two-phase",
                    choices=["dense", "two-phase", "ideal"],
                    help="dense: softmax over the whole store (legacy "
                         "comparison path); two-phase: engine shortlist + "
                         "exact noisy rescore; ideal: engine top-k by exact "
                         "digital distance only (cheapest; streams through "
                         "the fused shortlist kernel at large N)")
    ap.add_argument("--retrieval-backend", default="auto",
                    choices=["auto", "ref", "pallas", "mxu", "fused"])
    ap.add_argument("--retrieval-k", type=int, default=32)
    ap.add_argument("--retrieval-fused-min-rows", type=int, default=None,
                    help="row count from which shortlists run the fused "
                         "kernel (default: only a dense block over "
                         "engine.SHORTLIST_DENSE_MAX_BYTES does; applies "
                         "per shard-local block on sharded stores) -- a "
                         "perf knob, results are bit-identical either way")
    ap.add_argument("--retrieval-shards", type=int, default=None,
                    help="partition the serve store into this many logical "
                         "row shards (MemoryStore.shard(n_shards=...)); "
                         "prerequisite for --retrieval-nprobe routing")
    ap.add_argument("--retrieval-nprobe", type=int, default=None,
                    help="shards visited per query ('two-phase'/'ideal' on "
                         "a partitioned store): < shards engages the "
                         "phase-0 router sketch, bit-identical to brute "
                         "force restricted to the visited shards; default "
                         "searches every shard")
    ap.add_argument("--tenants", type=int, default=None,
                    help="run the standalone multi-tenant retrieval demo "
                         "with this many tenant stores instead of the "
                         "transformer decode loop (TenantServer coalescing "
                         "shell over RetrievalEngine.search_tenants)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.tenants is not None:
        serve_tenants(args.tenants, args.steps, args.batch,
                      backend=args.retrieval_backend, k=args.retrieval_k)
        return
    serve(args.arch, args.smoke, args.batch, args.steps, args.prompt_len,
          args.retrieval, args.retrieval_mode, args.retrieval_backend,
          args.retrieval_k, args.retrieval_fused_min_rows,
          args.retrieval_shards, args.retrieval_nprobe)


if __name__ == "__main__":
    main()
