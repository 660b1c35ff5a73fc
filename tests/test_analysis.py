"""Contract guard (repro/analysis): HLO invariant registry + AST lint.

The registry is the ONE spelling of every HLO invariant -- test_store.py
and test_engine.py assert through the same `hlo_contracts` functions the
CLI walks, so a drifted spelling fails here before it can silently stop
matching in a test. The lint tests pin each rule's firing condition and
the suppression grammar on synthetic sources, then hold the real tree
clean.
"""

import json
import os
import textwrap

import jax
import pytest

from repro.analysis import hlo_contracts as hc
from repro.analysis import lint, registry
from repro.analysis.__main__ import main as analysis_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HLO checkers: each catches an injected violation and passes clean text.
# ---------------------------------------------------------------------------


CLEAN_HLO = textwrap.dedent("""\
    HloModule jit_search
      fusion.1 = f32[5,16]{1,0} fusion(p0), kind=kLoop
      ROOT tuple.2 = (f32[5,16]) tuple(fusion.1)
""")


def test_checkers_catch_injected_violations():
    assert hc.check_no_collectives(CLEAN_HLO) == []
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        bad = CLEAN_HLO + f"  ar.1 = f32[8] {op}(x), replica_groups={{}}\n"
        assert hc.check_no_collectives(bad), op
        with pytest.raises(AssertionError):
            hc.assert_no_collectives(bad)

    assert hc.check_no_scatter_any_spelling(CLEAN_HLO) == []
    for op in ("scatter(", "dynamic-update-slice"):
        bad = CLEAN_HLO + f"  s.1 = f32[8] {op}x)\n"
        assert hc.check_no_scatter_any_spelling(bad), op

    # scatter_write is the INVERTED contract: violation when absent
    assert hc.check_scatter_write(CLEAN_HLO)
    ok = CLEAN_HLO + "  dus.1 = f32[8] dynamic-update-slice(a, b, i)\n"
    assert hc.check_scatter_write(ok) == []

    # scope checks read op_name path components, as compiled HLO spells
    # them; a function name that merely CONTAINS a tag is not a scope
    tagged = CLEAN_HLO + ("  f.2 = f32[5] fusion(x), metadata={op_name="
                          "\"jit(search)/router_sketch/dot_general\"}\n")
    assert hc.check_router_tag(tagged, True) == []
    assert hc.check_router_tag(tagged, False)
    assert hc.check_router_tag(CLEAN_HLO, False) == []
    assert hc.check_router_tag(CLEAN_HLO, True)

    # phase 1 in query chunks is a while loop in the shortlist layer; a
    # loop in another layer (the ref `full` search's lax.map) is not one
    def entry(scope: str) -> str:
        return ("ENTRY %main (p0: f32[5,16]) -> f32[5,16] {\n"
                "  %w.1 = (s32[], f32[5,16]) while(%t), condition=%c, "
                f'body=%b, metadata={{op_name="jit(search)/{scope}/while"}}'
                "\n}\n")
    assert hc.check_phase1_one_block(entry(hc.SHORTLIST_SCOPE))
    assert hc.check_phase1_one_block(entry(hc.RESCORE_SCOPE)) == []
    assert hc.check_phase1_one_block(CLEAN_HLO) == []

    layout = CLEAN_HLO + ("  l.1 = s8[4] copy(x), metadata={op_name="
                          "\"jit(f)/jvp(layout_support)/copy\"}\n")
    named = CLEAN_HLO + '  5 "test_search_compiles_without_layout_support"\n'
    assert hc.check_no_layout_ops(CLEAN_HLO) == []
    assert hc.check_no_layout_ops(named) == []
    assert hc.check_no_layout_ops(layout)
    assert hc.check_layout_ops_present(layout) == []
    assert hc.check_layout_ops_present(CLEAN_HLO)

    assert hc.check_no_f64(CLEAN_HLO) == []
    assert hc.check_no_f64(CLEAN_HLO + "  c.1 = f64[4] convert(x)\n")


# ---------------------------------------------------------------------------
# AST lint: every rule fires on a synthetic source; suppression works.
# ---------------------------------------------------------------------------


def _rules(source, path):
    return sorted({f.rule for f in lint.lint_source(
        textwrap.dedent(source), path)})


def test_lint_deprecated_shim():
    src = """
        from repro.core.memory import search
        from repro.core import memory
        memory.distributed_search(None, None)
    """
    assert _rules(src, "src/repro/models/x.py") == ["deprecated-shim"]
    # the shims' own module is exempt
    assert _rules(src, "src/repro/core/memory.py") == []


def test_lint_kernel_sort_through_partial():
    src = """
        import functools, jax
        from jax.experimental import pallas as pl
        def _kern(ref, o_ref):
            o_ref[...] = jax.lax.top_k(ref[...], 4)[0]
        def run(x):
            k = functools.partial(_kern)
            return pl.pallas_call(k, out_shape=None)(x)
    """
    assert _rules(src, "src/repro/kernels/k.py") == ["kernel-sort"]
    # annotated interpret-only branch is allowed (line-above form)
    ok = src.replace("o_ref[...] = ",
                     "# lint: allow=kernel-sort\n            o_ref[...] = ")
    assert "kernel-sort" not in _rules(ok, "src/repro/kernels/k.py")


def test_lint_serving_path_rules():
    src = """
        import jax
        def f(x):
            noise = jax.random.normal(jax.random.PRNGKey(0), x.shape)
            return x + noise + 1e-6
    """
    found = _rules(src, "src/repro/engine/e.py")
    assert found == ["float-epsilon-tiebreak", "serving-raw-random"]
    # outside serving paths neither rule applies
    assert _rules(src, "src/repro/data/d.py") == []
    # key_data is introspection, not sampling
    assert _rules("import jax\nx = jax.random.key_data",
                  "src/repro/engine/e.py") == []


def test_lint_ste_and_f64():
    src = """
        from repro.core.quantization import _ste_round_fwd
        y = x.astype("float64")
    """
    assert _rules(src, "src/repro/models/m.py") == ["f64-astype",
                                                    "ste-raw-primitive"]
    # the defining modules may touch their own primitives
    assert _rules("from repro.core.quantization import _ste_round_fwd",
                  "src/repro/core/quantization.py") == []


def test_lint_cost_call():
    src = """
        def f(compiled):
            c = compiled.cost_analysis()
            m = compiled.memory_analysis()
            return c, m
    """
    assert _rules(src, "src/repro/launch/d.py") == ["cost-call"]
    # the cost model's own package is exempt (it IS the one spelling)
    assert _rules(src, "src/repro/analysis/cost.py") == []
    # suppression names the rule
    ok = src.replace("compiled.cost_analysis()",
                     "compiled.cost_analysis()  # lint: allow=cost-call")
    ok = ok.replace("compiled.memory_analysis()",
                    "compiled.memory_analysis()  # lint: allow=cost-call")
    assert _rules(ok, "src/repro/launch/d.py") == []


def test_lint_trailing_suppression():
    src = 'import jax\nn = jax.random.normal  # lint: allow=serving-raw-random\n'
    assert lint.lint_source(src, "src/repro/engine/e.py") == []


def test_repo_tree_is_lint_clean():
    findings = lint.lint_paths([os.path.join(ROOT, "src", "repro")])
    assert findings == [], "\n".join(f.format() for f in findings)


# ---------------------------------------------------------------------------
# Registry: the matrix covers every route; small cells pass end to end.
# ---------------------------------------------------------------------------


def test_registry_matrix_covers_every_route():
    cells = registry.build_cells()
    assert len(cells) >= 28          # 95 invariant rows under the CLI
    for cell in cells:
        for inv in cell.invariants:
            assert inv in registry.INVARIANTS, inv
    search = [c for c in cells if c.entry == "engine.search"
              and "mode" in c.config]
    routed = [c for c in search if "nprobe" in c.config]
    search = [c for c in search if "nprobe" not in c.config]
    modes = {c.config["mode"] for c in search}
    backends = {c.config["backend"] for c in search}
    assert modes == {"full", "two_phase", "ideal"}
    assert backends == set(registry.CELL_BACKENDS) == {"ref", "pallas"}
    assert {c.config["sharded"] for c in search} == {True, False}
    # routed cells on both backends, plus the nprobe == n_shards
    # control with the tag-absent check
    assert {c.config["backend"] for c in routed} == {"ref", "pallas"}
    assert any(c.config["nprobe"] == c.config["n_shards"] for c in routed)
    assert any(c.config["nprobe"] < c.config["n_shards"] for c in routed)
    for c in routed:
        assert "router_tag_iff_engaged" in c.invariants, c.config
        assert "no_collectives" in c.invariants, c.config
    writes = {c.config["path"] for c in cells
              if c.entry == "MemoryStore.write"}
    assert writes == {"unsharded", "one_shard", "multi_shard"}
    assert any(c.entry == "episode_votes" for c in cells)
    # every shortlist-mode search cell, tenants included, ranks its
    # (under-budget) batch in one phase-1 block
    tenants = [c for c in cells if c.entry == "engine.search_tenants"
               and "mode" in c.config]
    assert {c.config["mode"] for c in tenants} == {"full", "two_phase",
                                                   "ideal"}
    for c in search + routed + tenants:
        assert ("phase1_one_block" in c.invariants) == (
            c.config["mode"] != "full"), c.config


def test_registry_sharded_cells_skip_without_devices():
    cell = registry._search_cell("ideal", "pallas", True,
                                 len(jax.devices()) + 1)
    assert cell.skip
    report = registry.run_cells([cell])
    assert report["summary"]["skip"] == len(cell.invariants)
    assert report["summary"]["fail"] == 0


def test_registry_small_subset_passes():
    """A cheap unsharded slice of the matrix compiles and passes in-process
    (the full matrix runs via `python -m repro.analysis run` in CI)."""
    cells = [
        registry._search_cell("two_phase", "pallas", False, 1),
        registry._write_cell("unsharded", 1),
        registry._layout_control_cell(),
    ]
    report = registry.run_cells(cells)
    assert report["summary"]["fail"] == 0, report["cells"]
    assert report["summary"]["error"] == 0, report["cells"]
    assert report["summary"]["pass"] == sum(len(c.invariants) for c in cells)
    # rows carry what the CLI prints and the diff keys on
    for row in report["cells"]:
        assert {"entry", "config", "invariant", "status", "detail",
                "matched"} <= set(row)


def test_registry_detects_broken_invariant(monkeypatch):
    """A cell whose artifacts violate its invariant FAILS (the runner is
    not a rubber stamp): a real cell built with the phase-1 budget cut
    below its (B, N) block ranks in query chunks, which the one-block
    invariant must catch."""
    from repro.engine import sharded
    cell = registry._search_cell("two_phase", "pallas", False, 1)
    assert registry.INVARIANTS["phase1_one_block"](cell.build()) == []
    monkeypatch.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES",
                        2 * 72 * 4)
    assert registry.INVARIANTS["phase1_one_block"](cell.build())


# ---------------------------------------------------------------------------
# CLI: lint and diff exit codes (run is exercised by CI on every push).
# ---------------------------------------------------------------------------


def test_cli_lint_exit_codes(tmp_path, capsys):
    bad = tmp_path / "repro" / "engine" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax\nn = jax.random.normal\n")
    assert analysis_main(["lint", str(bad)]) == 1
    assert "serving-raw-random" in capsys.readouterr().out
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert analysis_main(["lint", str(good)]) == 0


def _report(failing_keys):
    return {"meta": {}, "summary": {},
            "cells": [{"entry": e, "config": {}, "invariant": i,
                       "status": "fail", "detail": "", "matched": []}
                      for e, i in failing_keys]}


def test_cli_diff_new_failure_is_red(tmp_path, capsys):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_report([("a", "no_f64_promotion")])))
    new.write_text(json.dumps(_report([("a", "no_f64_promotion"),
                                       ("b", "no_collectives")])))
    assert analysis_main(["diff", str(old), str(new)]) == 1
    assert "NEW FAILURE" in capsys.readouterr().out
    # failures fixed (or merely pre-existing) are green
    assert analysis_main(["diff", str(new), str(old)]) == 0
    assert "fixed" in capsys.readouterr().out
