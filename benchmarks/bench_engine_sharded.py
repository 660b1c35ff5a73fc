"""Multi-device sharded-engine scaling, measured for real in a subprocess.

The in-process `engine` suite runs its sharded row on however many devices
the host exposes (1 on a plain CPU run). This suite forces an 8-device host
mesh the way tests/test_distributed.py does -- XLA_FLAGS must precede jax
init, so it MUST be a subprocess -- and sweeps shard counts over a fixed
store so the sharded scaling shape lands in the perf trajectory, plus the
per-shard shortlist dense-vs-fused comparison (steered purely by the
SearchRequest.fused_min_rows knob, bit-parity asserted). Results
are printed as harness rows AND written to results/bench_engine_sharded.json
(uploaded as a CI artifact by the weekly full job).

    PYTHONPATH=src python -m benchmarks.run --only engine_sharded
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "results", "bench_engine_sharded.json")
N_DEVICES = 8

_WORKER = """
    import json, time
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.avss import SearchConfig
    from repro.core.mcam import MCAMConfig
    from repro.core.memory import MemoryConfig
    from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
    from repro.launch.mesh import make_mesh

    N, B, D, K = 4096, 16, 48, 64
    cfg = SearchConfig("mtmc", cl=8, mode="avss", mcam=MCAMConfig(),
                       use_kernel="ref")
    sv = jax.random.randint(jax.random.PRNGKey(0), (N, D), 0, cfg.enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(1), (B, D), 0, 4)
    labels = jnp.arange(N, dtype=jnp.int32) % 512
    store = MemoryStore.from_quantized(sv, labels, cfg)
    eng = RetrievalEngine(cfg, backend="ref")
    req = SearchRequest(mode="two_phase", k=K)

    def time_us(f, *args, iters=3):
        f(*args)[0].block_until_ready()          # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(*args)
            out[0].block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e6, out

    base = jax.jit(lambda st, q: (eng.search(st, q, req).votes,))
    us1, (ref_votes,) = time_us(base, store, qv)
    records = [{"name": "engine_sharded/two_phase_k%d_dev1" % K,
                "us_per_call": us1, "shards": 1,
                "qps": B / us1 * 1e6}]
    for n_dev in (2, 4, 8):
        mesh = make_mesh((n_dev,), ("data",))
        sstore = store.shard(mesh, ("data",))
        with mesh:
            f = jax.jit(lambda st, q: (eng.search(st, q, req).votes,))
            us, (votes,) = time_us(f, sstore, qv)
        np.testing.assert_array_equal(np.asarray(ref_votes),
                                      np.asarray(votes))
        records.append({"name": "engine_sharded/two_phase_k%d_dev%d"
                                % (K, n_dev),
                        "us_per_call": us, "shards": n_dev,
                        "qps": B / us * 1e6,
                        "speedup_vs_1dev": us1 / us})

    # sharded per-shard shortlist: dense local matmul vs the fused Pallas
    # kernel (ISSUE 4 tentpole). The SearchRequest.fused_min_rows override
    # steers the dispatch without code change; bit-parity is asserted
    # against the unsharded ideal reference either way. NOTE: on this CPU
    # container the fused rows measure the Pallas INTERPRETER -- the
    # dense-vs-fused *crossover* must be measured on real TPU HBM; these
    # rows track that both routes stay wired and bit-identical.
    ideal_ref = jax.jit(lambda st, q: (eng.search(
        st, q, SearchRequest(mode="ideal", k=K)).dist,))
    _, (ref_dist,) = time_us(ideal_ref, store, qv)
    for n_dev in (2, 8):
        mesh = make_mesh((n_dev,), ("data",))
        sstore = store.shard(mesh, ("data",))
        for tag, fmr in (("dense", 1 << 30), ("fused", 1)):
            req = SearchRequest(mode="ideal", k=K, backend="mxu",
                                fused_min_rows=fmr)
            with mesh:
                f = jax.jit(lambda st, q, r=req: (eng.search(st, q, r).dist,))
                us, (dist,) = time_us(f, sstore, qv)
            np.testing.assert_array_equal(np.asarray(ref_dist),
                                          np.asarray(dist))
            records.append({"name": "engine_sharded/ideal_%s_k%d_dev%d"
                                    % (tag, K, n_dev),
                            "us_per_call": us, "shards": n_dev,
                            "shortlist": tag, "qps": B / us * 1e6})
    mesh = make_mesh((8,), ("data",))
    sstore = store.shard(mesh, ("data",))
    for tag, fmr in (("dense", 1 << 30), ("fused", 1)):
        req = SearchRequest(mode="two_phase", k=K, backend="mxu",
                            fused_min_rows=fmr)
        with mesh:
            f = jax.jit(lambda st, q, r=req: (eng.search(st, q, r).votes,))
            us, (votes,) = time_us(f, sstore, qv)
        np.testing.assert_array_equal(np.asarray(ref_votes),
                                      np.asarray(votes))
        records.append({"name": "engine_sharded/two_phase_%s_k%d_dev8"
                                % (tag, K),
                        "us_per_call": us, "shards": 8,
                        "shortlist": tag, "qps": B / us * 1e6})

    # streaming (shard-local) writes: program a W-row batch into the ring;
    # the write-through keeps programming local to each shard, so per-batch
    # time should stay flat (no cross-device scatter) as shards grow
    W = 256
    mcfg = MemoryConfig(capacity=N, dim=D, search=cfg)
    wvecs = jax.random.normal(jax.random.PRNGKey(2), (W, D))
    wlabs = jnp.arange(W, dtype=jnp.int32)
    base = MemoryStore.create(mcfg).calibrate(wvecs)
    fw = jax.jit(lambda st, v, l: (st.write(v, l).values,))
    usw1, (ref_vals,) = time_us(fw, base, wvecs, wlabs)
    records.append({"name": "engine_sharded/write_scatter_b%d_dev1" % W,
                    "us_per_call": usw1, "shards": 1,
                    "rows_per_s": W / usw1 * 1e6})
    for n_dev in (2, 4, 8):
        mesh = make_mesh((n_dev,), ("data",))
        sbase = base.shard(mesh, ("data",))
        with mesh:
            fws = jax.jit(lambda st, v, l: (st.write(v, l).values,))
            usw, (vals,) = time_us(fws, sbase, wvecs, wlabs)
        np.testing.assert_array_equal(np.asarray(ref_vals),
                                      np.asarray(vals))
        records.append({"name": "engine_sharded/write_stream_b%d_dev%d"
                                % (W, n_dev),
                        "us_per_call": usw, "shards": n_dev,
                        "rows_per_s": W / usw * 1e6,
                        "speedup_vs_1dev": usw1 / usw})
    print("JSON::" + json.dumps({
        "suite": "engine_sharded", "N": N, "B": B, "D": D, "k": K,
        "devices": len(jax.devices()), "backend": "ref",
        "note": "CPU host mesh; interpreter timings -- scaling SHAPE is "
                "the signal, parity is asserted bit-exact",
        "rows": records}))
"""


def run():
    # the child measures a forced host mesh by design: pin it to the CPU so
    # it never waits for an accelerator the parent process may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEVICES}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(_WORKER)],
                          capture_output=True, text=True, timeout=1200,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded bench subprocess failed:\n"
                           f"{proc.stderr[-2000:]}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith("JSON::"):
            payload = json.loads(line[len("JSON::"):])
    assert payload is not None, proc.stdout[-2000:]
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=1)
    rows = []
    for r in payload["rows"]:
        rate = (f"qps={r['qps']:.0f}" if "qps" in r
                else f"rows_per_s={r['rows_per_s']:.0f}")
        derived = f"{rate};shards={r['shards']}"
        if "shortlist" in r:
            derived += f";shortlist={r['shortlist']}"
        if "speedup_vs_1dev" in r:
            derived += f";speedup_vs_1dev={r['speedup_vs_1dev']:.2f}x"
        rows.append((r["name"], r["us_per_call"], derived))
    rows.append(("engine_sharded/artifact", 0.0,
                 os.path.relpath(OUT_PATH, ROOT)))
    return rows
