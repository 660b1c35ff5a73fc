"""Resource oracle (repro/analysis/cost): the ONE cost model.

Layer by layer: the HLO op census on golden fixtures, the cost_analysis
read/write split, the while-loop trip-count correction on an exact
synthetic model, the dry-run launcher's delegation (identity, not
re-implementation), the per-route resource report on cheap registry
cells, and the `cost-diff` CLI gate's exit codes on injected drift.
"""

import json
import textwrap

import jax

from repro.analysis import cost, registry
from repro.analysis.__main__ import main as analysis_main


# ---------------------------------------------------------------------------
# HLO-text extraction: golden census fixtures.
# ---------------------------------------------------------------------------


CENSUS_HLO = textwrap.dedent("""\
    HloModule jit_step
      %dot.1 = f32[8,128]{1,0} dot(p0, p1), lhs_contracting_dims={1}
      %cvt.1 = bf16[8,128]{1,0} convert(dot.1)
      %ag.1 = f32[16,64]{1,0} all-gather(p2), replica_groups={}
      %ars.1 = f32[16,64]{1,0} all-reduce-start(p5), replica_groups={}
      %g.1 = f32[4,4]{1,0} gather(p3, p4), offset_dims={1}
      %w.1 = (s32[], f32[8]) while(t0), condition=%cond, body=%body
      %sort.1 = f32[8,128]{1,0} sort(cvt.1), dimensions={1}
      %dus.1 = f32[8,16]{1,0} dynamic-update-slice(a, b, i0, i1)
      %c.1 = f32[2,2]{1,0} add(x, y)
""")


def test_hlo_op_census_golden():
    c = cost.hlo_op_census(CENSUS_HLO)
    assert c["dot"] == {"count": 1, "bytes": 8 * 128 * 4}
    assert c["convert"] == {"count": 1, "bytes": 8 * 128 * 2}
    assert c["all-gather"] == {"count": 1, "bytes": 16 * 64 * 4}
    # the -start spelling of an async collective still counts
    assert c["all-reduce"] == {"count": 1, "bytes": 16 * 64 * 4}
    # the all-gather line is a collective, NOT a plain gather: one match
    # per line, most specific first
    assert c["gather"] == {"count": 1, "bytes": 4 * 4 * 4}
    assert c["while"]["count"] == 1
    assert c["sort"] == {"count": 1, "bytes": 8 * 128 * 4}
    assert c["dynamic-update-slice"] == {"count": 1, "bytes": 8 * 16 * 4}
    # untracked ops (plain add) never appear
    assert "add" not in c
    assert "scatter" not in c


def test_shape_bytes_tokens():
    assert cost.shape_bytes("f32[8,128]") == 8 * 128 * 4
    assert cost.shape_bytes("bf16[16]") == 32
    assert cost.shape_bytes("s32[]") == 4          # scalar
    assert cost.shape_bytes("weird[8]") == 0       # unknown dtype
    assert cost.shape_bytes("nonsense") == 0


# ---------------------------------------------------------------------------
# cost_analysis extraction: read/write split + list-valued handling.
# ---------------------------------------------------------------------------


def test_hbm_rw_bytes_operand_terms():
    c = {"bytes accessed": 1000.0, "bytes accessed0{}": 600.0,
         "bytes accessed1{}": 200.0, "bytes accessedout{}": 200.0}
    assert cost.hbm_rw_bytes(c) == (800.0, 200.0)


def test_hbm_rw_bytes_fallback_without_operand_terms():
    c = {"bytes accessed": 1000.0, "bytes accessedout{}": 300.0}
    assert cost.hbm_rw_bytes(c) == (700.0, 300.0)
    assert cost.hbm_rw_bytes({}) == (0.0, 0.0)


def test_compiled_cost_handles_per_device_list():
    class FakeCompiled:
        def cost_analysis(self):
            return [{"flops": 42.0, "bytes accessed": 7, "utilization": {}}]

    c = cost.compiled_cost(FakeCompiled())
    assert c == {"flops": 42.0, "bytes accessed": 7.0}


# ---------------------------------------------------------------------------
# Trip-count correction: exact on a synthetic affine cost model.
# ---------------------------------------------------------------------------


def _affine(counts, accum, fixed=1000.0, micro=7.0, per_layer=(10.0, 100.0)):
    """M(counts, A) = fixed + A*(micro + sum_g counts_g * f_g) -- the shape
    XLA's once-per-while-body counting gives an unrolled variant."""
    inner = micro + sum(c * f for c, f in zip(counts, per_layer))
    return {"flops": fixed + accum * inner}


def test_scan_trip_count_totals_exact_with_accumulation():
    m1 = _affine((1, 1), 1)                       # 1117
    m2 = [_affine((2, 1), 1), _affine((1, 2), 1)]  # 1127, 1217
    m3 = _affine((1, 1), 2)                       # 1234
    got = cost.scan_trip_count_totals(m1, m2, counts=(3, 5), accum=4, m3=m3)
    # true totals: 1000 + 4*(7 + 3*10 + 5*100) = 3148
    assert got["flops"] == 3148.0


def test_scan_trip_count_totals_exact_without_accumulation():
    m1 = _affine((1, 1), 1)
    m2 = [_affine((2, 1), 1), _affine((1, 2), 1)]
    got = cost.scan_trip_count_totals(m1, m2, counts=(3, 5), accum=1)
    # micro folds into fixed when A == 1: 1007 + 3*10 + 5*100 = 1537
    assert got["flops"] == 1000.0 + 7.0 + 3 * 10.0 + 5 * 100.0


def test_scan_trip_count_clamps_negative_differences():
    m1 = {"flops": 100.0}
    m2 = [{"flops": 90.0}]                        # variant folded smaller
    got = cost.scan_trip_count_totals(m1, m2, counts=(4,), accum=1)
    assert got["flops"] >= 0.0


# ---------------------------------------------------------------------------
# The dry-run launcher DELEGATES (identity, not a copy).
# ---------------------------------------------------------------------------


def test_dryrun_is_a_thin_delegate():
    from repro.launch import dryrun
    assert dryrun.parse_collectives is cost.parse_collectives
    assert dryrun._shape_bytes is cost.shape_bytes
    assert dryrun._metrics is cost.roofline_metrics
    assert dryrun._COLLECTIVES is cost.COLLECTIVE_KINDS


def test_parse_collectives_all_reduce_doubles():
    hlo = ("HloModule m\n"
           "  a = f32[256]{0} all-reduce(x), replica_groups={}\n"
           "  b = f32[256]{0} all-gather(y), replica_groups={}\n")
    coll = cost.parse_collectives(hlo)
    assert coll["all-reduce"] == {"count": 1, "bytes": 2 * 256 * 4}
    assert coll["all-gather"] == {"count": 1, "bytes": 256 * 4}
    assert coll["total_bytes"] == 3 * 256 * 4


# ---------------------------------------------------------------------------
# The per-route resource report over registry cells.
# ---------------------------------------------------------------------------


def test_resource_report_cheap_cells():
    cells = [
        registry._layout_control_cell(),
        registry._write_cell("unsharded", 1),
        # a cell needing more devices than available -> a skip row, not
        # a hole in the report
        registry._search_cell("ideal", "pallas", True,
                              len(jax.devices()) + 1),
    ]
    report = cost.resource_report(cells)
    assert report["summary"]["routes"] == 3
    assert report["summary"]["ok"] == 2
    assert report["summary"]["skip"] == 1
    assert report["summary"]["error"] == 0
    json.dumps(report)                     # artifact must serialise as-is

    ok_rows = [r for r in report["routes"] if r["status"] == "ok"]
    for r in ok_rows:
        assert r["flops"] is not None and r["flops"] >= 0.0
        assert r["jit_entries"] == 1
        assert r["op_census"], "compiled cells carry an op census"
        assert r["peak_bytes"] >= r["temp_bytes"]
    # the search control cell does real MXU work
    layout = next(r for r in ok_rows
                  if r["entry"] == "engine.two_phase(raw-arrays)")
    assert layout["flops"] > 0
    assert layout["hbm_bytes_read"] > 0
    assert layout["hbm_bytes_written"] > 0
    skip = next(r for r in report["routes"] if r["status"] == "skip")
    assert skip["flops"] is None and skip["detail"]


def test_resource_report_jit_cache_entries():
    report = cost.resource_report([registry._jit_cache_cell()])
    (row,) = report["routes"]
    assert row["status"] == "ok"
    # no compiled program on this cell: the measured cache size IS the
    # route's jit_entries, everything else stays null
    assert row["jit_entries"] == 1
    assert row["flops"] is None


def test_route_key_matches_registry_cell_key():
    cell = registry._write_cell("unsharded", 1)
    row = cost._null_row(cell.entry, cell.config, "ok", "")
    assert cost.route_key(row) == cell.key


# ---------------------------------------------------------------------------
# cost-diff: the drift gate's exit codes on synthetic reports.
# ---------------------------------------------------------------------------


def _rrow(entry, **over):
    row = {"entry": entry, "config": {}, "status": "ok", "detail": "",
           "flops": 100.0, "hbm_bytes_read": 1000.0,
           "hbm_bytes_written": 500.0, "temp_bytes": 64,
           "peak_bytes": 2048, "jit_entries": 1, "op_census": {},
           "while_ops": 0}
    row.update(over)
    return row


def _write(path, rows):
    path.write_text(json.dumps(
        {"meta": {}, "summary": {}, "routes": rows}))


def test_cli_cost_diff_exit_codes(tmp_path, capsys):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    _write(old, [_rrow("a")])

    _write(new, [_rrow("a")])                      # identical: green
    assert analysis_main(["cost-diff", str(old), str(new)]) == 0

    _write(new, [_rrow("a", flops=110.0)])         # 10% > rtol 5%: red
    assert analysis_main(["cost-diff", str(old), str(new)]) == 1
    assert "DRIFT" in capsys.readouterr().out

    _write(new, [_rrow("a", flops=103.0)])         # 3% < rtol 5%: green
    assert analysis_main(["cost-diff", str(old), str(new)]) == 0

    _write(new, [])                                # lost route: red
    assert analysis_main(["cost-diff", str(old), str(new)]) == 1
    assert "MISSING ROUTE" in capsys.readouterr().out

    _write(new, [_rrow("a"), _rrow("b")])          # growth only: green
    assert analysis_main(["cost-diff", str(old), str(new)]) == 0
    assert "added" in capsys.readouterr().out

    _write(new, [_rrow("a", jit_entries=2)])       # jit_entries is exact
    assert analysis_main(["cost-diff", str(old), str(new)]) == 1

    # a route degrading to error status counts as missing, not silently ok
    _write(new, [_rrow("a", status="error")])
    assert analysis_main(["cost-diff", str(old), str(new)]) == 1


def test_diff_wider_rtol_tolerates_more():
    oldr = {"routes": [_rrow("a")]}
    newr = {"routes": [_rrow("a", flops=110.0)]}
    assert cost.diff_resource_reports(oldr, newr, rtol=0.05)["drifted"]
    assert not cost.diff_resource_reports(oldr, newr, rtol=0.2)["drifted"]
