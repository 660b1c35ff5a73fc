"""MemoryStore: write-time MCAM layouts, ring wraparound, ragged shards,
and the unified engine.search contract (repro/engine/store.py, api.py).

The store's invariant: `values`, `proj`, `s_grid` and `labels` are written
TOGETHER (one programming operation), so at any point the store's search
artifacts are mutually consistent -- including after ring-buffer
wraparound -- and searches jit against the write-time constants instead of
re-running `layout_support` per call (asserted on compiled HLO below).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_contracts as hc
from repro.core import avss as avss_lib
from repro.core.avss import SearchConfig
from repro.core.memory import MemoryConfig
from repro.engine import (MemoryStore, RetrievalEngine, SearchRequest,
                          SearchResult)
from repro.kernels import ops as kernel_ops
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(capacity=32, dim=16, cl=4):
    return MemoryConfig(capacity=capacity, dim=dim,
                        search=SearchConfig("mtmc", cl=cl, mode="avss",
                                            use_kernel="ref"))


def _assert_consistent(store):
    """proj and s_grid must equal the write-time functions of values."""
    enc = store.cfg.search.enc
    sl = store.cfg.search.mcam.string_len
    np.testing.assert_array_equal(
        np.asarray(store.proj),
        np.asarray(kernel_ops.support_projection(store.values, enc)))
    np.testing.assert_array_equal(
        np.asarray(store.s_grid),
        np.asarray(avss_lib.layout_support(store.values, enc, sl)
                   .astype(jnp.int8)))


def test_write_programs_all_layouts():
    cfg = _cfg()
    vecs = jax.random.normal(jax.random.PRNGKey(0), (20, cfg.dim))
    labs = jnp.arange(20, dtype=jnp.int32) % 5
    store = MemoryStore.create(cfg).calibrate(vecs).write(vecs, labs)
    _assert_consistent(store)
    assert int(store.size) == 20
    assert bool(store.valid[:20].all()) and not bool(store.valid[20:].any())


def test_ring_buffer_wraparound_consistency():
    """After writing > capacity vectors, every slot's values/proj/s_grid/
    labels stay mutually consistent, and search results are bit-identical
    to a store programmed directly with the surviving arrangement -- i.e.
    `size` (24 vs 16 here) plays no role in search, which is what makes
    the old `indices < size` validity check (vacuous once size > capacity)
    safe to drop in favour of the label mask."""
    cfg = _cfg(capacity=16, dim=8)
    key = jax.random.PRNGKey(3)
    vecs = jax.random.normal(key, (24, 8))
    labs = jnp.arange(24, dtype=jnp.int32)
    store = MemoryStore.create(cfg).calibrate(vecs)
    store = store.write(vecs[:16], labs[:16]).write(vecs[16:], labs[16:])
    _assert_consistent(store)
    # ring arrangement: slots 0..7 overwritten by vectors 16..23
    np.testing.assert_array_equal(np.asarray(store.labels),
                                  np.r_[np.arange(16, 24), np.arange(8, 16)])
    assert int(store.size) == 24

    # a store programmed with the surviving arrangement in one write
    surviving = jnp.concatenate([vecs[16:24], vecs[8:16]])
    slabs = jnp.concatenate([labs[16:24], labs[8:16]])
    fresh = MemoryStore.create(cfg).calibrate(vecs).write(surviving, slabs)
    np.testing.assert_array_equal(np.asarray(store.values),
                                  np.asarray(fresh.values))

    # values/proj/s_grid/labels being elementwise equal, search parity now
    # only needs to prove `size` (24 vs 16) leaks into no mode's result
    q = vecs[18:22] + 0.01
    eng = RetrievalEngine(cfg.search)
    for mode in ("two_phase", "ideal"):
        req = SearchRequest(mode=mode, k=8)
        f = jax.jit(lambda st, qq: eng.search(st, qq, req))
        a, b = f(store, q), f(fresh, q)
        for key_ in ("votes", "dist", "indices", "labels"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, key_)), np.asarray(getattr(b, key_)),
                err_msg=f"{mode}/{key_}")


def test_search_result_pytree_roundtrips_jit():
    cfg = _cfg()
    vecs = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.dim))
    labs = jnp.arange(12, dtype=jnp.int32) % 3
    store = MemoryStore.create(cfg).calibrate(vecs).write(vecs, labs)
    eng = RetrievalEngine(cfg.search)
    req = SearchRequest(mode="two_phase", k=4)
    res = jax.jit(lambda st, q: eng.search(st, q, req))(store, vecs[:3])
    assert isinstance(res, SearchResult)
    np.testing.assert_array_equal(np.asarray(res.predict()),
                                  np.asarray(labs[:3]))


def test_from_quantized_matches_raw_two_phase():
    """Unified API over a from_quantized store == raw-array two_phase,
    bit for bit (the old->new parity contract), on every backend."""
    cfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="ref")
    sv = jax.random.randint(jax.random.PRNGKey(0), (40, 16), 0,
                            cfg.enc.levels)
    qv = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 4)
    store = MemoryStore.from_quantized(
        sv, jnp.arange(40, dtype=jnp.int32), cfg)
    for backend in ("ref", "pallas"):
        eng = RetrievalEngine(cfg, backend=backend)
        old = jax.jit(lambda s, q, e=eng: e.two_phase(q, s, k=8))(sv, qv)
        new = jax.jit(lambda st, q, e=eng: e.search(
            st, q, SearchRequest(mode="two_phase", k=8)))(store, qv)
        for key in ("votes", "dist", "indices"):
            np.testing.assert_array_equal(
                np.asarray(old[key]), np.asarray(getattr(new, key)),
                err_msg=f"{backend}/{key}")


def test_store_search_compiles_without_layout_support():
    """Acceptance: the store's grids are write-time constants -- compiling
    a store-based search emits NO layout_support ops (the named_scope tags
    them in HLO), while the raw-array path (read-time layout) does."""
    cfg = _cfg()
    vecs = jax.random.normal(jax.random.PRNGKey(0), (20, cfg.dim))
    labs = jnp.arange(20, dtype=jnp.int32)
    store = MemoryStore.create(cfg).calibrate(vecs).write(vecs, labs)
    eng = RetrievalEngine(cfg.search)
    req = SearchRequest(mode="two_phase", k=8)
    hlo_new = jax.jit(lambda st, q: eng.search(st, q, req).votes) \
        .lower(store, vecs[:2]).compile().as_text()
    hc.assert_no_layout_ops(hlo_new)
    # control: the raw-array two_phase still lays the store out under jit,
    # proving the scope tag is visible in this build's HLO text
    qv = store.quantize_queries(vecs[:2])
    hlo_old = jax.jit(lambda s, q: eng.two_phase(q, s, k=8)["votes"]) \
        .lower(store.values, qv).compile().as_text()
    hc.assert_layout_ops_present(hlo_old)


@pytest.mark.slow
def test_serve_decode_step_no_layout_under_jit():
    """The real `serve --retrieval` decode step (two-phase engine head)
    compiles with zero layout_support ops: the store programs its grids at
    write time and the jitted decode loop treats them as inputs."""
    from repro.configs import load_config
    from repro.launch import steps as steps_lib
    from repro.models import transformer as tfm
    from repro.models.sharding import Rules

    cfg = load_config("starcoder2-3b", smoke=True)
    rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
    # the kNN-LM keys are whole d_model hidden states
    mem_cfg = MemoryConfig(capacity=64, dim=cfg.d_model,
                           search=SearchConfig("mtmc", cl=8, mode="avss",
                                               use_kernel="ref"))
    vecs = jax.random.normal(jax.random.PRNGKey(7), (32, mem_cfg.dim))
    toks = jax.random.randint(jax.random.PRNGKey(8), (32,), 0,
                              cfg.vocab_size)
    store = MemoryStore.create(mem_cfg).calibrate(vecs).write(vecs, toks)
    engine = RetrievalEngine(mem_cfg.search, backend="ref")
    step = steps_lib.make_serve_step_with_mcam(cfg, rules, mem_cfg,
                                               engine=engine, k=8)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    caches = tfm.init_cache(cfg, 2, 8)
    tok = jnp.zeros((2, 1), jnp.int32)
    hlo = jax.jit(step).lower(params, caches, {"tokens": tok},
                              jnp.int32(0), store).compile().as_text()
    hc.assert_no_layout_ops(hlo)


# ---------------------------------------------------------------------------
# Store lifecycle: calibration is enforced, not assumed.
# ---------------------------------------------------------------------------


def test_quantize_queries_requires_calibration():
    """Float queries on a never-calibrated store raise instead of silently
    quantizing against the uncalibrated default (lo=0, hi=1) range; integer
    (pre-quantized) queries always pass through."""
    cfg = _cfg()
    store = MemoryStore.create(cfg)
    with pytest.raises(ValueError, match="never-calibrated"):
        store.quantize_queries(jnp.zeros((2, cfg.dim)))
    qi = jnp.ones((2, cfg.dim), jnp.int32)
    np.testing.assert_array_equal(np.asarray(store.quantize_queries(qi)), 1)
    # from_quantized stores serve integer queries; float still raises
    fq = MemoryStore.from_quantized(
        jnp.zeros((4, cfg.dim), jnp.int32), jnp.arange(4, dtype=jnp.int32),
        cfg.search)
    with pytest.raises(ValueError, match="never-calibrated"):
        fq.quantize_queries(jnp.zeros((2, cfg.dim)))
    np.testing.assert_array_equal(np.asarray(fq.quantize_queries(qi)), 1)


def test_write_requires_calibration_and_recalibrate_raises():
    """calibrate() must run before the first write -- both directions are
    enforced: writing uncalibrated raises, and re-calibrating a store with
    programmed rows (which would silently invalidate their quantized
    words) raises too."""
    cfg = _cfg()
    vecs = jax.random.normal(jax.random.PRNGKey(0), (8, cfg.dim))
    labs = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(ValueError, match="calibrate"):
        MemoryStore.create(cfg).write(vecs, labs)
    store = MemoryStore.create(cfg).calibrate(vecs).write(vecs, labs)
    with pytest.raises(ValueError, match="programmed row"):
        store.calibrate(vecs)
    # an un-written calibrated store may re-calibrate freely
    MemoryStore.create(cfg).calibrate(vecs).calibrate(vecs * 2)


def test_empty_store_predicts_sentinel_every_mode_and_backend():
    """All-masked-candidates edge: an empty store (or one holding only
    ragged pad rows) yields predict() == -1 for every query in every
    mode/backend/sharding -- never an arbitrary class label (the sentinel
    documented on SearchResult)."""
    cfg = _cfg(capacity=12, dim=8)
    store = MemoryStore.create(cfg)
    q = jax.random.randint(jax.random.PRNGKey(0), (3, 8), 0, 4)
    for mode in ("full", "two_phase", "ideal"):
        for backend in ("ref", "pallas"):
            eng = RetrievalEngine(cfg.search, backend=backend)
            res = eng.search(store, q, SearchRequest(mode=mode, k=4))
            assert (np.asarray(res.predict()) == -1).all(), (mode, backend)
            assert np.isneginf(np.asarray(res.votes)).all(), (mode, backend)
    # sharded dispatch (two_phase + ideal go through shard_map)
    mesh = make_mesh((1,), ("data",))
    sstore = store.shard(mesh, ("data",))
    eng = RetrievalEngine(cfg.search)
    for mode in ("two_phase", "ideal"):
        req = SearchRequest(mode=mode, k=4)
        res = jax.jit(lambda st, qq, r=req: eng.search(st, qq, r))(sstore, q)
        assert (np.asarray(res.predict()) == -1).all(), f"sharded/{mode}"


def test_request_backend_override_engine_is_cached():
    """SearchRequest.backend overrides resolve to ONE cached engine per
    (engine, backend): hot decode loops get the same object back every
    call instead of a rebuilt engine (and a cold jit closure)."""
    eng = RetrievalEngine(_cfg().search)
    assert eng.with_backend("auto") is eng
    a = eng.with_backend("pallas")
    assert a is eng.with_backend("pallas")
    assert a.backend == "pallas" and a.cfg is eng.cfg
    # the override engine caches too, and distinct backends stay distinct
    assert eng.with_backend("ref") is not a
    assert a.with_backend("pallas") is a


# ---------------------------------------------------------------------------
# Streaming (shard-local) writes.
# ---------------------------------------------------------------------------


def test_shard_refuses_explicit_axis_mesh():
    """jax.make_mesh defaults to Explicit axes; the store's auto-sharded
    writes need Auto ones, so shard() says so instead of failing later."""
    cfg = MemoryConfig(capacity=8, dim=4, search=SearchConfig(
        "mtmc", cl=4, mode="avss", use_kernel="ref"))
    store = MemoryStore.create(cfg)
    with pytest.raises(ValueError, match="Auto axes"):
        store.shard(jax.make_mesh((1,), ("data",)))
    assert store.shard(make_mesh((1,), ("data",))).n_shards == 1


def test_single_shard_write_dispatches_to_scatter():
    """A 1-shard mesh gives the shard_map write-through nothing to
    parallelise: there is no collective to avoid, and its per-row ring
    inversion runs 7.7x slower than the scatter (bench_engine_sharded
    write rows). `write` therefore routes single-shard stores through the
    plain scatter path -- bit-identical to the write-through (invoked
    directly here as the parity control), sharding metadata preserved.
    The no-scatter/no-collective HLO contract lives with the 8-device
    test below, where the write-through actually engages."""
    cfg = _cfg(capacity=16, dim=8)
    vecs = jax.random.normal(jax.random.PRNGKey(0), (22, 8))
    labs = jnp.arange(22, dtype=jnp.int32)
    base = MemoryStore.create(cfg).calibrate(vecs)
    mesh = make_mesh((1,), ("data",))
    sstore = base.shard(mesh, ("data",))
    assert sstore.n_shards == 1 and base.n_shards == 1
    f = jax.jit(lambda st, v, l: st.write(v, l))
    written = f(f(sstore, vecs[:12], labs[:12]), vecs[12:], labs[12:])
    assert int(written.size) == 22  # wrapped: slots 0..5 overwritten

    # parity control: the write-through path, invoked directly
    from repro.engine.store import _quantize

    def stream_write(st, v, l):
        vq = _quantize(v, st.cfg.search.enc.levels, st.lo, st.hi)
        return st._program_streamed(vq, l, v.shape[0])
    g = jax.jit(stream_write)
    streamed = g(g(sstore, vecs[:12], labs[:12]), vecs[12:], labs[12:])
    for key in ("values", "proj", "s_grid", "labels", "size"):
        np.testing.assert_array_equal(
            np.asarray(getattr(streamed, key)),
            np.asarray(getattr(written, key)), err_msg=key)
    assert written.mesh is mesh and written.axes == ("data",)
    # the dispatched write lowers to the scatter (expanded on CPU to
    # dynamic-update-slice), proving the fast path actually engaged
    hlo = jax.jit(lambda st, v, l: st.write(v, l)) \
        .lower(sstore, vecs[:12], labs[:12]).compile().as_text()
    hc.assert_scatter_write(hlo)
    # ...and matches the scatter path on the unsharded store exactly
    scattered = base.write(vecs[:12], labs[:12]).write(vecs[12:], labs[12:])
    for key in ("values", "proj", "s_grid", "labels", "size"):
        np.testing.assert_array_equal(
            np.asarray(getattr(scattered, key)),
            np.asarray(getattr(written, key)), err_msg=key)


@pytest.mark.slow
def test_streaming_write_8dev_no_collectives_ragged_wraparound():
    """Acceptance (ISSUE 3 tentpole): on a forced 8-device mesh, the
    sharded write-through (a) compiles to HLO with NO cross-device
    collectives and no scatter, (b) is bit-identical to the scatter path
    on a RAGGED-padded store with ring wraparound crossing shard
    boundaries, and (c) searches of the streamed store match the
    unsharded reference bit-for-bit. Also covers the shard->shard(other
    mesh) idempotency fix and the fully-pad-row predict() sentinel."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import Mesh
        from repro.analysis import hlo_contracts as hc
        from repro.core.avss import SearchConfig
        from repro.core.memory import MemoryConfig
        from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
        from repro.engine.store import _quantize

        cfg = MemoryConfig(capacity=100, dim=24,
                           search=SearchConfig("mtmc", cl=8, mode="avss",
                                               use_kernel="ref"))
        vecs = jax.random.normal(jax.random.PRNGKey(0), (130, 24))
        labs = jnp.arange(130, dtype=jnp.int32) % 9
        base = MemoryStore.create(cfg).calibrate(vecs)
        mesh8 = make_mesh((8,), ("data",))

        # (a) compiled write HLO: no collectives, no scatter of any form
        sstore = base.shard(mesh8, ("data",))
        assert sstore.capacity == 104, sstore.capacity  # ragged pad
        write = jax.jit(lambda st, v, l: st.write(v, l))
        hlo = write.lower(sstore, vecs[:60], labs[:60]).compile().as_text()
        hc.assert_no_collectives(hlo)
        hc.assert_no_scatter_any_spelling(hlo)
        # control: the scatter path lowers to the expanded scatter
        def old_write(st, v, l):
            vq = _quantize(v, st.cfg.search.enc.levels, st.lo, st.hi)
            start = st.size % st.cfg.capacity
            idx = (start + jnp.arange(v.shape[0])) % st.cfg.capacity
            return st._program(idx, vq, l, v.shape[0])
        hlo_old = jax.jit(old_write).lower(
            sstore, vecs[:60], labs[:60]).compile().as_text()
        hc.assert_scatter_write(hlo_old)

        # (b) bit-parity: ragged pads + ring wraparound across shards.
        # 90 rows, then 40 more -> wraps 30 past capacity back to rows
        # 0..29, crossing the 13-row shard boundaries of the padded store.
        streamed = write(write(sstore, vecs[:90], labs[:90]),
                         vecs[90:], labs[90:])
        scattered = base.write(vecs[:90], labs[:90]).write(
            vecs[90:], labs[90:]).shard(mesh8, ("data",))
        for key in ("values", "proj", "s_grid", "labels", "size"):
            np.testing.assert_array_equal(
                np.asarray(getattr(scattered, key)),
                np.asarray(getattr(streamed, key)), err_msg=key)

        # (c) search parity: streamed sharded store == unsharded reference
        unsharded = base.write(vecs[:90], labs[:90]).write(vecs[90:],
                                                           labs[90:])
        q = vecs[95:101] + 0.02
        eng = RetrievalEngine(cfg.search)
        for mode in ("two_phase", "ideal"):
            req = SearchRequest(mode=mode, k=16)
            ref = eng.search(unsharded, q, req)
            with mesh8:
                got = jax.jit(lambda st, qq, r=req: eng.search(
                    st, qq, r))(streamed, q)
            for key in ("votes", "dist", "indices", "labels"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ref, key)),
                    np.asarray(getattr(got, key)), err_msg=f"{mode}/{key}")

        # shard -> shard(other mesh): pads must not accumulate, and the
        # result must equal sharding the logical store directly
        mesh3 = Mesh(np.asarray(jax.devices()[:3]), ("data",))
        written = unsharded
        via3 = written.shard(mesh3, ("data",))
        assert via3.capacity == 102, via3.capacity     # 100 -> pad 2
        re8 = via3.shard(mesh8, ("data",))
        direct8 = written.shard(mesh8, ("data",))
        assert re8.capacity == 104, re8.capacity       # NOT pad-of-pad
        for key in ("values", "proj", "s_grid", "labels", "size"):
            np.testing.assert_array_equal(
                np.asarray(getattr(direct8, key)),
                np.asarray(getattr(re8, key)), err_msg=f"reshard/{key}")

        # fully-pad/empty sharded store: predict() == -1 everywhere
        empty = MemoryStore.create(cfg).shard(mesh8, ("data",))
        qi = jax.random.randint(jax.random.PRNGKey(3), (4, 24), 0, 4)
        for mode in ("two_phase", "ideal"):
            with mesh8:
                res = jax.jit(lambda st, qq, r=SearchRequest(mode=mode, k=8):
                              eng.search(st, qq, r))(empty, qi)
            assert (np.asarray(res.predict()) == -1).all(), mode
        print("STREAMING-WRITE-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "STREAMING-WRITE-OK" in proc.stdout


@pytest.mark.slow
def test_ragged_3way_split_capacity_100():
    """ROADMAP open item: capacity need not divide the shard count.
    A capacity-100 store sharded 3 ways pads to 102 rows with label -1
    rows that the integer-exact penalty ranks last -- votes/dist/indices/
    labels bit-identical to the unsharded search."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core.avss import SearchConfig
        from repro.core.memory import MemoryConfig
        from repro.engine import MemoryStore, RetrievalEngine, SearchRequest

        cfg = MemoryConfig(capacity=100, dim=24,
                           search=SearchConfig("mtmc", cl=8, mode="avss",
                                               use_kernel="ref"))
        vecs = jax.random.normal(jax.random.PRNGKey(0), (90, 24))
        labs = jnp.arange(90, dtype=jnp.int32) % 9
        store = MemoryStore.create(cfg).calibrate(vecs).write(vecs, labs)
        q = vecs[:6] + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                                (6, 24))
        eng = RetrievalEngine(cfg.search)
        mesh = make_mesh((3,), ("data",))
        sstore = store.shard(mesh, ("data",))
        assert sstore.capacity == 102, sstore.capacity
        assert int((sstore.labels < 0).sum()) == 12  # 10 empty + 2 pad
        for mode in ("two_phase", "ideal"):
            req = SearchRequest(mode=mode, k=16)
            local = eng.search(store, q, req)
            with mesh:
                sh = eng.search(sstore, q, req)
            for key in ("votes", "dist", "indices", "labels"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(local, key)),
                    np.asarray(getattr(sh, key)), err_msg=f"{mode}/{key}")
        print("RAGGED-3WAY-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RAGGED-3WAY-OK" in proc.stdout
