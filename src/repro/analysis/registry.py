"""Declarative contract registry: (invariant x entry-point x config) cells.

Every public entry point of the serving stack is registered here together
with the invariants its compiled program must satisfy, across the full
config matrix:

  engine.search      mode (full/two_phase/ideal) x backend (ref/mxu/fused)
                     x sharded/unsharded x packed/unpacked operand
                     x fused_min_rows (forcing both sides of the dispatch)
                     x routed (nprobe < n_shards on a host-partitioned
                     store engages the phase-0 sketch router, PR 10: the
                     `router_sketch` scope tag must appear iff routing is
                     engaged, and the sketch matmul adds no collectives)
  engine.search_tenants
                     the vmapped multi-tenant dispatch (PR 9) over a
                     ragged 5-tenant stack: same fused/layout/f64
                     invariants as engine.search, plus zero collectives
                     on the tenant axis and the
                     single_jit_entry_across_tenants cache invariant
                     for T in {1, 5, 64}
  MemoryStore.write  scatter path (unsharded / 1-shard) vs shard-local
                     write-through (multi-shard)
  episode_votes      the differentiable training twin of search

`python -m repro.analysis run` lowers each cell via
`jit(...).lower(...).compile()` on small concrete inputs, walks the HLO
text through repro/analysis/hlo_contracts.py (the ONE spelling of each
invariant -- the test suite asserts through the same functions), and
writes results/contract_report.json with pass/fail per cell and the
matched HLO lines on failure.

The fused-tag expectation of every cell is computed from the SAME dispatch
rule the engine uses (repro/engine/sharded._use_fused), so the registry
can never drift from the implementation: a cell fails when compiled
reality and the rule disagree, whichever of the two changed.

Cells needing more devices than available are recorded as skipped with a
reason (the CLI forces an 8-device host platform so nothing skips there).
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Callable

import jax
import jax.numpy as jnp

from repro.analysis import cost as cost_lib
from repro.analysis import hlo_contracts as hc
from repro.launch.mesh import make_mesh

#: k used by every search cell (small, so cells compile in milliseconds).
CELL_K = 16
#: queries per search-cell batch (the shared fixture's `qv`).
CELL_B = 5
#: fused_min_rows values forcing each side of the dispatch rule.
FMR_FORCE_FUSED = 1
FMR_FORCE_DENSE = 1 << 30


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry-point configuration and the invariants checked on it.

    build() returns the cell's artifacts: at least {"hlo": str}; fused
    cells add "expect_fused", the HBM cells add "hbm", the jit-cache cell
    "cache_size"/"expected".
    """

    entry: str
    config: dict
    invariants: tuple[str, ...]
    build: Callable[[], dict]
    skip: str = ""

    @property
    def key(self) -> str:
        return f"{self.entry}|{json.dumps(self.config, sort_keys=True)}"


# -- invariant name -> checker over cell artifacts --------------------------


def _inv_hbm_buffer_bound(art: dict) -> list[str]:
    h = art["hbm"]
    if h["measured_bytes"] <= h["bound_bytes"]:
        return []
    if not h["strict"]:
        # CPU interpret mode materialises the emulated kernel's blocks, so
        # the O(B*k + N*4d) bound only binds on real TPU HBM; the measured
        # bytes are still recorded in the report for trend tracking.
        return []
    return [f"temp buffers {h['measured_bytes']}B exceed the "
            f"O(B*k + N*4d) bound {h['bound_bytes']}B"]


def _inv_jit_cache(art: dict) -> list[str]:
    if art["cache_size"] == art["expected"]:
        return []
    return [f"{art['cache_size']} jit cache entries for one request "
            f"family (expected {art['expected']}): equal-but-distinct "
            f"SearchRequests or same-shape stores retrace"]


INVARIANTS: dict[str, Callable[[dict], list[str]]] = {
    "no_collectives": lambda a: hc.check_no_collectives(a["hlo"]),
    "no_scatter_any_spelling":
        lambda a: hc.check_no_scatter_any_spelling(a["hlo"]),
    "scatter_write_engaged": lambda a: hc.check_scatter_write(a["hlo"]),
    "no_layout_ops": lambda a: hc.check_no_layout_ops(a["hlo"]),
    "layout_ops_present": lambda a: hc.check_layout_ops_present(a["hlo"]),
    "fused_tag_iff_dispatch_rule":
        lambda a: hc.check_fused_tag(a["hlo"], a["expect_fused"]),
    "router_tag_iff_engaged":
        lambda a: hc.check_router_tag(a["hlo"], a["expect_router"]),
    "no_f64_promotion": lambda a: hc.check_no_f64(a["hlo"]),
    "hbm_buffer_bound": _inv_hbm_buffer_bound,
    "single_jit_cache_entry_per_request_family": _inv_jit_cache,
    "single_jit_entry_across_tenants":
        lambda a: hc.check_single_jit_entry_across_tenants(
            a["cache_sizes"]),
}


# -- shared fixtures (built lazily; tiny shapes, tie-heavy + masked rows) ---


@functools.lru_cache(maxsize=None)
def _fix():
    from repro.core.avss import SearchConfig
    from repro.core.memory import MemoryConfig
    from repro.engine import MemoryStore

    cfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="ref")
    base = jax.random.randint(jax.random.PRNGKey(0), (8, 20), 0,
                              cfg.enc.levels)
    sv = jnp.concatenate([base] * 9, axis=0)               # 72 rows, ties
    labels = jnp.where(jnp.arange(72) % 4 == 0, -1,
                       jnp.arange(72)).astype(jnp.int32)   # masked rows
    store = MemoryStore.from_quantized(sv, labels, cfg)
    qv = jax.random.randint(jax.random.PRNGKey(1), (CELL_B, 20), 0, 4)

    mcfg = MemoryConfig(capacity=32, dim=16,
                        search=SearchConfig("mtmc", cl=4, mode="avss",
                                            use_kernel="ref"))
    wvecs = jax.random.normal(jax.random.PRNGKey(2), (12, 16))
    wlabs = jnp.arange(12, dtype=jnp.int32)
    wstore = MemoryStore.create(mcfg).calibrate(wvecs)
    return {"cfg": cfg, "store": store, "qv": qv,
            "mcfg": mcfg, "wstore": wstore, "wvecs": wvecs, "wlabs": wlabs}


@functools.lru_cache(maxsize=None)
def _tenant_fix():
    """Ragged 5-tenant stack mirroring the tests/test_tenant.py geometry:
    one empty tenant (calibrated, never written), one tie-heavy tenant,
    masked label -1 rows, plus an interleaved query batch with repeated
    tenants (so the rank-keyed noise coordinates differ from the batch
    positions the solo search would use)."""
    import numpy as np

    from repro.core.avss import SearchConfig
    from repro.core.memory import MemoryConfig
    from repro.engine import MemoryStore, TenantStore

    cfg = SearchConfig("mtmc", cl=8, mode="avss", use_kernel="ref")
    rng = np.random.default_rng(0)
    stores = []
    for i, cap in enumerate((12, 7, 16, 5, 9)):
        if i == 3:                                      # empty tenant
            mc = MemoryConfig(capacity=cap, dim=20, search=cfg)
            sample = jnp.asarray(rng.normal(size=(8, 20)), jnp.float32)
            stores.append(MemoryStore.create(mc).calibrate(sample))
            continue
        v = rng.integers(0, cfg.enc.levels, size=(cap, 20))
        if i == 2:                                      # tie-heavy
            v = np.concatenate([v[:4]] * 4)[:cap]
        lab = rng.integers(0, 5, size=(cap,))
        lab[::4] = -1                                   # masked rows
        stores.append(MemoryStore.from_quantized(
            jnp.asarray(v), jnp.asarray(lab), cfg))
    tstore = TenantStore.stack(stores)
    tids = jnp.array([0, 2, 1, 0, 2, 4, 2, 3, 0], jnp.int32)
    qv = jnp.asarray(rng.integers(0, 4, size=(9, 20)), jnp.int32)
    return {"cfg": cfg, "tstore": tstore, "qv": qv, "tids": tids}


def _compile(fn, *args, mesh=None):
    if mesh is not None:
        with mesh:
            return jax.jit(fn).lower(*args).compile()
    return jax.jit(fn).lower(*args).compile()


def _unpacked(store):
    """The same store streaming the WIDE projection: proj_packed dropped,
    so every fused route takes the unpacked-operand path."""
    return dataclasses.replace(store, proj_packed=None)


def _expect_fused(backend: str, rows_loc: int, mode: str, fmr: int,
                  batch: int = CELL_B) -> bool:
    """The registry's expectation IS the engine's dispatch rule."""
    from repro.engine.sharded import _use_fused
    if mode == "full":
        return False
    return _use_fused(backend, batch, rows_loc, fmr)


# -- cell builders ----------------------------------------------------------


def _search_cell(mode: str, backend: str, fmr: int, packed: bool,
                 sharded: bool, n_shards: int) -> Cell:
    from repro.engine import RetrievalEngine, SearchRequest

    def build() -> dict:
        fx = _fix()
        store, qv = fx["store"], fx["qv"]
        mesh = None
        if sharded:
            mesh = make_mesh((n_shards,), ("data",))
            store = store.shard(mesh, ("data",))
        if not packed:
            store = _unpacked(store)
        eng = RetrievalEngine(fx["cfg"], backend=backend)
        req = SearchRequest(mode=mode, k=CELL_K, fused_min_rows=fmr)
        compiled = _compile(
            lambda st, q: eng.search(st, q, req).votes, store, qv,
            mesh=mesh)
        rows_loc = store.capacity // (n_shards if sharded else 1)
        art = {"hlo": compiled.as_text(), "compiled": compiled,
               "expect_fused": _expect_fused(backend, rows_loc, mode, fmr,
                                             batch=qv.shape[0])}
        if mode == "ideal" and art["expect_fused"] and not sharded:
            art["hbm"] = _hbm_stats(compiled, qv.shape[0], CELL_K,
                                    store.capacity, store.dim)
        return art

    invariants = ["fused_tag_iff_dispatch_rule", "no_layout_ops",
                  "no_f64_promotion"]
    if not sharded:
        # unsharded searches must not touch collectives at all; sharded
        # two-phase/ideal all-gather the per-shard top-k by design
        invariants.append("no_collectives")
        if (mode == "ideal"
                and _expect_fused(backend, 72, mode, fmr)):
            invariants.append("hbm_buffer_bound")
    skip = ""
    if sharded and len(jax.devices()) < n_shards:
        skip = (f"needs {n_shards} devices, have {len(jax.devices())} "
                f"(run via `python -m repro.analysis run`, which forces "
                f"an 8-device host platform)")
    return Cell(entry="engine.search",
                config={"mode": mode, "backend": backend,
                        "sharded": sharded, "packed": packed,
                        "fused_min_rows": fmr},
                invariants=tuple(invariants), build=build, skip=skip)


def _routed_cell(mode: str, backend: str, fmr: int, packed: bool,
                 nprobe: int, n_shards: int = 8) -> Cell:
    """engine.search with nprobe on a LOGICALLY partitioned store
    (`shard(n_shards=...)`, mesh-less -- so no device minimum and no
    collectives anywhere, sketch matmul included). nprobe < n_shards must
    compile the router (scope tag present); nprobe == n_shards is the
    control: the SAME exhaustive program as nprobe=None, tag absent."""
    from repro.engine import RetrievalEngine, SearchRequest

    engaged = nprobe < n_shards

    def build() -> dict:
        fx = _fix()
        store = fx["store"].shard(n_shards=n_shards)
        if not packed:
            store = _unpacked(store)
        eng = RetrievalEngine(fx["cfg"], backend=backend)
        req = SearchRequest(mode=mode, k=CELL_K, fused_min_rows=fmr,
                            nprobe=nprobe)
        compiled = _compile(
            lambda st, q: eng.search(st, q, req).votes, store, fx["qv"])
        # the routed shortlist ranks the CONCATENATED visited blocks:
        # rows_loc = nprobe * rows_per_shard; the control is exhaustive
        # over the whole (unsharded-dispatch) store
        rows_loc = (nprobe * (store.capacity // n_shards) if engaged
                    else store.capacity)
        return {"hlo": compiled.as_text(), "compiled": compiled,
                "expect_router": engaged,
                "expect_fused": _expect_fused(backend, rows_loc, mode,
                                              fmr)}

    return Cell(entry="engine.search",
                config={"mode": mode, "backend": backend, "packed": packed,
                        "fused_min_rows": fmr, "nprobe": nprobe,
                        "n_shards": n_shards},
                invariants=("router_tag_iff_engaged",
                            "fused_tag_iff_dispatch_rule", "no_layout_ops",
                            "no_f64_promotion", "no_collectives"),
                build=build)


def _hbm_stats(compiled, B: int, k: int, N: int, d: int) -> dict:
    """Temp-buffer bytes of the compiled cell vs the O(B*k + N*4d) bound
    the fused shortlist advertises (kernels/shortlist.py): the per-query
    top-k buffers plus one pass over the streamed projection, times 4 for
    dtype width and double-buffering slack. Strict only on TPU -- the CPU
    interpreter materialises emulated blocks, so there the measured bytes
    are recorded (trend data) without binding."""
    kp = 128 if k <= 128 else k                 # lane-width internal pad
    bound = 4 * 4 * (B * kp * 2 + N * 4 * d)
    measured = cost_lib.temp_bytes(compiled)
    return {"measured_bytes": measured, "bound_bytes": bound,
            "strict": jax.default_backend() == "tpu"}


def _tenant_search_cell(mode: str, backend: str, fmr: int,
                        packed: bool) -> Cell:
    from repro.engine import RetrievalEngine, SearchRequest

    def build() -> dict:
        fx = _tenant_fix()
        tstore, qv, tids = fx["tstore"], fx["qv"], fx["tids"]
        if not packed:
            tstore = _unpacked(tstore)
        eng = RetrievalEngine(fx["cfg"], backend=backend)
        req = SearchRequest(mode=mode, k=CELL_K, fused_min_rows=fmr)
        compiled = _compile(
            lambda ts, q, i: eng.search_tenants(ts, q, i, req).votes,
            tstore, qv, tids)
        # the per-query vmapped search sees every tenant at the PADDED
        # row count -- that is the rows_loc the dispatch rule acts on
        return {"hlo": compiled.as_text(), "compiled": compiled,
                "expect_fused": _expect_fused(backend, tstore.n_pad,
                                              mode, fmr, batch=qv.shape[0])}

    # the tenant axis is a pure batch axis: beyond the solo-search
    # invariants, the vmapped program must introduce ZERO collectives
    return Cell(entry="engine.search_tenants",
                config={"mode": mode, "backend": backend, "packed": packed,
                        "fused_min_rows": fmr},
                invariants=("fused_tag_iff_dispatch_rule", "no_layout_ops",
                            "no_f64_promotion", "no_collectives"),
                build=build)


def _tenant_jit_cache_cell() -> Cell:
    def build() -> dict:
        from functools import partial

        from repro.engine import (MemoryStore, RetrievalEngine,
                                  SearchRequest, TenantStore)
        fx = _tenant_fix()
        eng = RetrievalEngine(fx["cfg"])

        @partial(jax.jit, static_argnames=("req",))
        def f(ts, q, tids, req):
            return eng.search_tenants(ts, q, tids, req).votes

        req = SearchRequest(mode="two_phase", k=4)

        def mk_stack(T: int, seed: int):
            import numpy as np
            r = np.random.default_rng(seed)
            return TenantStore.stack([
                MemoryStore.from_quantized(
                    jnp.asarray(r.integers(0, fx["cfg"].enc.levels,
                                           size=(6, 8))),
                    jnp.asarray(r.integers(0, 3, size=(6,))), fx["cfg"])
                for _ in range(T)])

        # per tenant count T: fresh stores / queries / tenant_ids of the
        # same shapes must all hit ONE compiled program
        entries: dict[int, int] = {}
        for T in (1, 5, 64):
            before = int(f._cache_size())
            for trial in range(2):
                import numpy as np
                r = np.random.default_rng(100 * T + trial)
                ts = mk_stack(T, seed=T + trial)
                q = jnp.asarray(r.integers(0, 4, size=(4, 8)), jnp.int32)
                tids = jnp.asarray(r.integers(0, T, size=(4,)), jnp.int32)
                f(ts, q, tids, req).block_until_ready()
            entries[T] = int(f._cache_size()) - before
        return {"cache_sizes": entries,
                "cache_size": sum(entries.values()),   # resource row: one
                "expected": len(entries)}              # entry per T shape

    return Cell(entry="engine.search_tenants",
                config={"check": "jit cache across tenant counts"},
                invariants=("single_jit_entry_across_tenants",),
                build=build)


def _write_cell(kind: str, n_shards: int) -> Cell:
    def build() -> dict:
        fx = _fix()
        wstore, vecs, labs = fx["wstore"], fx["wvecs"], fx["wlabs"]
        if kind != "unsharded":
            mesh = make_mesh((n_shards,), ("data",))
            wstore = wstore.shard(mesh, ("data",))
        compiled = _compile(lambda st, v, l: st.write(v, l),
                            wstore, vecs, labs)
        return {"hlo": compiled.as_text(), "compiled": compiled}

    if kind == "multi_shard":
        # the shard-local write-through: programs rows in place with no
        # cross-device traffic and no scatter under any spelling
        invariants = ("no_collectives", "no_scatter_any_spelling",
                      "no_f64_promotion")
    else:
        # unsharded / 1-shard: the scatter fast path must actually engage
        # (7.7x faster there -- see MemoryStore.write), collective-free
        invariants = ("scatter_write_engaged", "no_collectives",
                      "no_f64_promotion")
    skip = ""
    if n_shards > len(jax.devices()):
        skip = (f"needs {n_shards} devices, have {len(jax.devices())} "
                f"(run via `python -m repro.analysis run`)")
    return Cell(entry="MemoryStore.write",
                config={"path": kind, "n_shards": n_shards},
                invariants=invariants, build=build, skip=skip)


def _episode_votes_cell() -> Cell:
    def build() -> dict:
        from repro.engine import RetrievalEngine
        fx = _fix()
        eng = RetrievalEngine(fx["cfg"])
        q = jax.random.normal(jax.random.PRNGKey(3), (4, 20))
        s = jax.random.normal(jax.random.PRNGKey(4), (10, 20))
        compiled = _compile(
            lambda qq, ss: eng.episode_votes(qq, ss)["votes"], q, s)
        return {"hlo": compiled.as_text(), "compiled": compiled}

    return Cell(entry="episode_votes", config={},
                invariants=("no_f64_promotion", "no_collectives"),
                build=build)


def _layout_control_cell() -> Cell:
    def build() -> dict:
        from repro.engine import RetrievalEngine
        fx = _fix()
        eng = RetrievalEngine(fx["cfg"], backend="ref")
        compiled = _compile(
            lambda s, q: eng.two_phase(q, s, k=CELL_K)["votes"],
            fx["store"].values, fx["qv"])
        return {"hlo": compiled.as_text(), "compiled": compiled}

    return Cell(entry="engine.two_phase(raw-arrays)",
                config={"control": "read-time layout"},
                invariants=("layout_ops_present",), build=build)


def _jit_cache_cell() -> Cell:
    def build() -> dict:
        from functools import partial

        from repro.engine import (MemoryStore, RetrievalEngine,
                                  SearchRequest)
        fx = _fix()
        eng = RetrievalEngine(fx["cfg"])

        @partial(jax.jit, static_argnames=("req",))
        def f(store, q, req):
            return eng.search(store, q, req).votes

        store_a = fx["store"]
        store_b = MemoryStore.from_quantized(
            jnp.flip(store_a.values, axis=0), store_a.labels, fx["cfg"])
        # equal-but-distinct request objects + distinct same-shape stores:
        # one request family, and it must hit ONE compiled program
        f(store_a, fx["qv"], SearchRequest(mode="two_phase", k=CELL_K))
        f(store_b, fx["qv"], SearchRequest(mode="two_phase", k=CELL_K))
        return {"cache_size": int(f._cache_size()), "expected": 1}

    return Cell(entry="engine.search", config={"check": "jit cache"},
                invariants=("single_jit_cache_entry_per_request_family",),
                build=build)


def build_cells() -> list[Cell]:
    """The full registered matrix (see module docstring)."""
    n_dev = len(jax.devices())
    n_shards = max(2, min(8, n_dev))            # what the CLI forces to 8
    cells: list[Cell] = []

    # engine.search, unsharded
    for mode in ("full", "two_phase", "ideal"):
        for backend in ("ref", "mxu", "fused"):
            fmrs = ((FMR_FORCE_FUSED,) if mode == "full"
                    or backend == "ref" else (FMR_FORCE_FUSED,
                                              FMR_FORCE_DENSE))
            for fmr in fmrs:
                cells.append(_search_cell(mode, backend, fmr, True,
                                          False, 1))
                if _expect_fused(backend, 72, mode, fmr):
                    # fused cells also cover the unpacked-operand route
                    cells.append(_search_cell(mode, backend, fmr, False,
                                              False, 1))

    # engine.search, sharded (two_phase/ideal dispatch through shard_map)
    for mode in ("two_phase", "ideal"):
        for backend, fmr in (("mxu", FMR_FORCE_FUSED),
                             ("mxu", FMR_FORCE_DENSE),
                             ("fused", FMR_FORCE_DENSE)):
            cells.append(_search_cell(mode, backend, fmr, True, True,
                                      n_shards))
        cells.append(_search_cell(mode, "fused", FMR_FORCE_DENSE, False,
                                  True, n_shards))

    # engine.search, routed (PR 10): nprobe over a host-partitioned store
    # -- both phase-1 dispositions (dense mxu / fused packed + unpacked)
    # plus the nprobe == n_shards control whose program must contain NO
    # router tag (it IS the exhaustive search)
    cells.append(_routed_cell("two_phase", "mxu", FMR_FORCE_DENSE, True, 2))
    cells.append(_routed_cell("two_phase", "fused", FMR_FORCE_FUSED, True,
                              2))
    cells.append(_routed_cell("ideal", "fused", FMR_FORCE_FUSED, True, 2))
    cells.append(_routed_cell("ideal", "fused", FMR_FORCE_FUSED, False, 2))
    cells.append(_routed_cell("two_phase", "mxu", FMR_FORCE_DENSE, True, 8))

    # engine.search_tenants: the vmapped multi-tenant dispatch (PR 9) --
    # one cell per representative route (full dense x ref/mxu, two-phase
    # on both sides of the fused threshold, fused ideal packed + unpacked)
    # plus the cross-tenant-count jit cache cell
    cells.append(_tenant_search_cell("full", "ref", FMR_FORCE_FUSED, True))
    cells.append(_tenant_search_cell("full", "mxu", FMR_FORCE_FUSED, True))
    cells.append(_tenant_search_cell("two_phase", "mxu", FMR_FORCE_DENSE,
                                     True))
    cells.append(_tenant_search_cell("two_phase", "fused", FMR_FORCE_FUSED,
                                     True))
    cells.append(_tenant_search_cell("ideal", "fused", FMR_FORCE_FUSED,
                                     True))
    cells.append(_tenant_search_cell("ideal", "fused", FMR_FORCE_FUSED,
                                     False))
    cells.append(_tenant_jit_cache_cell())

    # MemoryStore.write: scatter vs write-through per n_shards
    cells.append(_write_cell("unsharded", 1))
    cells.append(_write_cell("one_shard", 1))
    cells.append(_write_cell("multi_shard", n_shards))

    cells.append(_episode_votes_cell())
    cells.append(_layout_control_cell())
    cells.append(_jit_cache_cell())
    return cells


# -- runner -----------------------------------------------------------------


def run_cells(cells: list[Cell] | None = None) -> dict:
    """Build + check every cell; returns the contract report dict."""
    if cells is None:
        cells = build_cells()
    rows: list[dict] = []
    for cell in cells:
        if cell.skip:
            for inv in cell.invariants:
                rows.append({"entry": cell.entry, "config": cell.config,
                             "invariant": inv, "status": "skip",
                             "detail": cell.skip, "matched": []})
            continue
        try:
            art = cell.build()
        except Exception as e:                  # build error fails the cell
            for inv in cell.invariants:
                rows.append({"entry": cell.entry, "config": cell.config,
                             "invariant": inv, "status": "error",
                             "detail": f"{type(e).__name__}: {e}",
                             "matched": []})
            continue
        for inv in cell.invariants:
            violations = INVARIANTS[inv](art)
            row = {"entry": cell.entry, "config": cell.config,
                   "invariant": inv,
                   "status": "fail" if violations else "pass",
                   "detail": violations[0] if violations else "",
                   "matched": violations[:8]}
            if inv == "hbm_buffer_bound":
                row["hbm"] = art["hbm"]
            rows.append(row)
    summary = {s: sum(1 for r in rows if r["status"] == s)
               for s in ("pass", "fail", "error", "skip")}
    return {"meta": {"jax_backend": jax.default_backend(),
                     "devices": len(jax.devices())},
            "summary": summary, "cells": rows}
