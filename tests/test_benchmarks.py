"""Benchmark harness contracts: the dry-run artifacts CI gates on.

`benchmarks.bench_router --dry-run` is the fast-job routing gate. The
multi-tenant budget test pins the serving-scale wall-clock contract: a
64-tenant coalesced search must stay inside a fixed CPU-interpret
ceiling, which a per-tenant retrace (the failure mode the one-jit-entry
invariant guards) blows by orders of magnitude.
"""

import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tenant_batch_under_wall_clock_ceiling():
    """64-tenant coalesced serving budget on CPU interpret.

    One compiled `search_tenants` program is reused across repeated
    batches over a 64-tenant stack; after the first (traced) call, the
    steady-state per-batch wall clock must stay under a generous fixed
    ceiling. An accidental per-tenant retrace -- the regression the
    single_jit_entry_across_tenants invariant pins statically -- costs a
    fresh trace+compile per batch (hundreds of ms here), so it blows
    this budget by orders of magnitude while honest interpret-mode
    slowness does not.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.avss import SearchConfig
    from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest,
                              TenantStore)

    cfg = SearchConfig("mtmc", cl=4, mode="avss", use_kernel="ref")
    rng = np.random.default_rng(0)
    stores = [MemoryStore.from_quantized(
        jnp.asarray(rng.integers(0, cfg.enc.levels, size=(8, 12))),
        jnp.asarray(rng.integers(0, 4, size=(8,))), cfg)
        for _ in range(64)]
    tstore = TenantStore.stack(stores)
    eng = RetrievalEngine(cfg)
    req = SearchRequest(mode="two_phase", k=4)
    f = jax.jit(lambda ts, q, i: eng.search_tenants(ts, q, i, req).labels)

    def batch(seed):
        r = np.random.default_rng(seed)
        return (jnp.asarray(r.integers(0, 4, size=(8, 12)), jnp.int32),
                jnp.asarray(r.integers(0, 64, size=(8,)), jnp.int32))

    f(*(tstore,) + batch(0)).block_until_ready()      # trace + compile
    t0 = time.perf_counter()
    iters = 5
    for i in range(1, iters + 1):                      # fresh data, same
        f(*(tstore,) + batch(i)).block_until_ready()   # compiled program
    per_batch = (time.perf_counter() - t0) / iters
    # steady state is ~ms on this container; 2 s absorbs CI jitter while
    # a per-batch retrace (>100 ms compile alone) still fails loudly
    assert per_batch < 2.0, \
        f"64-tenant coalesced batch took {per_batch:.2f}s steady-state " \
        f"(ceiling 2.0s): per-tenant retrace or interpret blowup"


def test_bench_router_dry_run_gate():
    """`benchmarks.bench_router --dry-run` is the fast-job routing gate:
    a shrunken recall/latency sweep whose routed-parity asserts (routed ==
    brute force restricted to the visited shards, nprobe=S byte-identical
    to the exhaustive program) run inside the subprocess. Rows must carry
    the shared name,us,derived CSV shape with a recall= field."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_router", "--dry-run"],
        capture_output=True, text=True, timeout=480, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("router/")]
    assert any("exhaustive" in l for l in lines)
    assert all("recall=" in l.split(",", 2)[2] for l in lines)
    assert "dry-run OK" in proc.stdout
