"""Serving launcher: batched-request decode with an optional kNN-LM head
served from the MCAM store (reduced configs run on CPU; the dry-run lowers
the same serve step for the production meshes).

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --smoke \
        --batch 4 --steps 16 [--retrieval]

`--no-smoke` serves the published config at its full widths. A request's
prompt is prefilled in one forward pass straight into the decode cache
(`steps.make_prefill_into_step`; a sliding-window layer's cache is a ring
of `window` slots), then every step decodes one token for every request
through the donated cache. With `--retrieval` the datastore is built first
(`datastore_keys`, `build_datastore`): the decoder's post-final-norm
hidden states over a seeded token stream standing for the corpus, each
keyed to the token that follows it, programmed into a d_model-wide
`MemoryStore` sized to the HBM the weights and the cache leave; each step
then searches it with the step's own hidden state and mixes the vote
distribution into the LM's (`steps.make_serve_step_with_mcam`).

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


DATASTORE_CHUNK = 6144       # tokens per datastore prefill
DATASTORE_WRITE = 2048       # rows per MemoryStore.write
STORE_ROW_MULTIPLE = 8192    # a store sized to free HBM is a multiple of this
# HBM kept free beside a d_model store: the temporaries of its write
# (4.5 GiB compiled for a v5e at 49,152-65,536 rows of d = 3,072)
WRITE_HEADROOM_BYTES = 4.5 * 2 ** 30


def datastore_config(cfg, rows: int, search=None):
    """MemoryConfig of a kNN-LM datastore keyed by d_model hidden states
    (MTMC CL=8, AVSS queries unless `search` says otherwise)."""
    from repro.core.avss import SearchConfig
    from repro.core.memory import MemoryConfig
    search = search or SearchConfig("mtmc", cl=8, mode="avss")
    return MemoryConfig(capacity=rows, dim=cfg.d_model, search=search)


def sized_store_rows(cfg, batch: int, max_seq: int) -> int | None:
    """Store rows that fit the device memory the weights (already placed)
    and a batch's decode cache leave, with WRITE_HEADROOM_BYTES to spare:
    a multiple of STORE_ROW_MULTIPLE; None where the device reports no
    memory."""
    from repro.engine import MemoryStore
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree_util.tree_leaves(tree))
    cache = nbytes(jax.eval_shape(lambda: tfm.init_cache(cfg, batch,
                                                         max_seq)))
    one = jax.eval_shape(lambda: MemoryStore.create(
        datastore_config(cfg, STORE_ROW_MULTIPLE)))
    row = nbytes(one) / STORE_ROW_MULTIPLE
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0) - cache \
        - WRITE_HEADROOM_BYTES
    return max(0, int(free / row)) // STORE_ROW_MULTIPLE * STORE_ROW_MULTIPLE


def datastore_keys(params, cfg, rules, stream, rows: int,
                   chunk: int = DATASTORE_CHUNK) -> np.ndarray:
    """The keys of a kNN-LM datastore over a token stream (at least
    ceil(rows / chunk) * chunk tokens): each chunk of the stream is
    prefilled as a sequence of its own, and the post-final-norm hidden
    state at each position is a key. Returns (rows, d_model) float32 on
    the host; the row at stream position t is labelled stream[t + 1]."""
    keys_of = jax.jit(lambda p, t: tfm.prefill(
        p, cfg, {"tokens": t}, t.shape[1], rules)[1][0].astype(jnp.float32))
    return np.concatenate([np.asarray(keys_of(params,
                                              stream[None, c0:c0 + chunk]))
                           for c0 in range(0, rows, chunk)])[:rows]


def build_datastore(mem_cfg, keys, labels,
                    write_batch: int = DATASTORE_WRITE):
    """A new `MemoryStore` holding keys (N, d) with labels (N,), N the
    store's capacity: calibrated on the first write batch and programmed
    through `MemoryStore.write`, `write_batch` rows at a time."""
    from repro.engine import MemoryStore
    n = mem_cfg.capacity
    if n % write_batch:
        raise ValueError(f"write batches of {write_batch} rows must tile "
                         f"the store ({n})")
    write = jax.jit(lambda st, v, lab: st.write(v, lab), donate_argnums=0)
    store = jax.jit(lambda: MemoryStore.create(mem_cfg))()
    store = store.calibrate(jnp.asarray(keys[:write_batch]))
    for s in range(0, n, write_batch):
        store = write(store, keys[s:s + write_batch],
                      labels[s:s + write_batch])
    return store


def serve(arch: str, smoke: bool, batch: int, steps: int, prompt_len: int,
          retrieval: bool = False, retrieval_mode: str = "two-phase",
          retrieval_backend: str = "auto", retrieval_k: int = 32,
          retrieval_fused_min_rows: int | None = None,
          retrieval_shards: int | None = None,
          retrieval_nprobe: int | None = None) -> dict:
    """Prefill `batch` seeded prompts of `prompt_len` tokens into the decode
    cache, then greedily decode `steps` tokens for each, reading every
    step's tokens back to the host before the next. With `retrieval`, a
    kNN-LM datastore sized to the free HBM (1,024 rows on a device that
    reports no memory) is built first. Returns the tokens (batch, steps),
    `compile_s` (the first step, compile included, to its tokens),
    `decode_s` (the whole loop) and `latencies_s`, each step's time from
    issue to tokens on the host."""
    cfg = load_config(arch, smoke=smoke)
    rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
    key = jax.random.PRNGKey(0)
    # jitted so a published-width init never materialises its f32 draws
    params = jax.jit(partial(tfm.init, cfg=cfg))(jax.random.fold_in(key, 0))
    max_seq = prompt_len + steps
    vocab = cfg.vocab_size

    store = None
    if retrieval:
        from repro.engine import RetrievalEngine
        rows = sized_store_rows(cfg, batch, max_seq) or 1024
        mem_cfg = datastore_config(cfg, rows)
        chunk = min(DATASTORE_CHUNK, rows)
        n_tok = -(-rows // chunk) * chunk + 1
        stream = jax.random.randint(jax.random.fold_in(key, 1), (n_tok,), 0,
                                    vocab, jnp.int32)
        keys = datastore_keys(params, cfg, rules, stream, rows, chunk)
        store = build_datastore(mem_cfg, keys, stream[1:rows + 1],
                                min(DATASTORE_WRITE, rows))
        if retrieval_shards:
            # logical row partition; with --retrieval-nprobe < shards the
            # decode loop routes through the per-shard sketch
            # (repro/engine/router.py) instead of searching every shard
            store = store.shard(n_shards=retrieval_shards)
        eng_kw = {} if retrieval_fused_min_rows is None else \
            {"fused_min_rows": retrieval_fused_min_rows}
        engine = RetrievalEngine(mem_cfg.search, backend=retrieval_backend,
                                 **eng_kw)
        mode = "ideal" if retrieval_mode == "ideal" else "two_phase"
        step = steps_lib.make_serve_step_with_mcam(
            cfg, rules, mem_cfg, engine=engine, k=retrieval_k, mode=mode,
            nprobe=retrieval_nprobe)
    else:
        plain = steps_lib.make_serve_step(cfg, rules)

        def greedy(params, caches, b, pos):
            logits, caches = plain(params, caches, b, pos)
            return {"token": jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                    }, caches
        greedy = jax.jit(greedy, donate_argnums=1)
        step = lambda params, caches, b, pos, _store: greedy(
            params, caches, b, pos)

    caches = jax.jit(lambda: tfm.init_cache(cfg, batch, max_seq))()
    prefill = steps_lib.make_prefill_into_step(cfg, rules, max_seq)
    prompts = jax.random.randint(jax.random.fold_in(key, 2),
                                 (batch, prompt_len), 0, vocab, jnp.int32)
    first = []
    for b in range(batch):
        logits, caches = prefill(params, caches, prompts[b:b + 1],
                                 jnp.int32(b))
        first.append(jnp.argmax(logits[:, 0], -1))
    tok = jnp.concatenate(first).astype(jnp.int32)[:, None]

    toks, latencies = [], []
    t_loop = time.perf_counter()
    for i in range(steps):
        t0 = time.perf_counter()
        out, caches = step(params, caches, {"tokens": tok},
                           jnp.int32(prompt_len + i), store)
        toks.append(np.asarray(out["token"]))
        latencies.append(time.perf_counter() - t0)
        tok = out["token"][:, None]
    dt = time.perf_counter() - t_loop
    if retrieval:
        assert np.isfinite(np.asarray(out["logprobs"])).all()
    print(f"{arch}: first step {latencies[0]:.2f}s (compile included); "
          f"{steps} steps x {batch} reqs in {dt:.2f}s "
          f"({steps * batch / dt:.1f} tok/s)")
    return {"tokens": np.stack(toks, 1), "compile_s": latencies[0],
            "decode_s": dt, "latencies_s": latencies}


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
    ap.add_argument("--retrieval", action="store_true",
                    help="kNN-LM head: a d_model-wide MCAM datastore of the "
                         "decoder's own hidden states, sized to the free "
                         "HBM, searched by every step")
    ap.add_argument("--retrieval-mode", default="two-phase",
                    choices=["two-phase", "ideal"],
                    help="two-phase: engine shortlist + exact noisy "
                         "rescore; ideal: engine top-k by exact digital "
                         "distance only")
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
