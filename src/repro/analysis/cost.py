"""One cost model for the repo: static FLOPs / HBM / memory extraction.

Pass 3 of the contract guard -- the *resource oracle*. Everything that
reads ``compiled.cost_analysis()`` / ``compiled.memory_analysis()`` or
parses cost-bearing ops out of HLO text lives HERE, one spelling,
enforced by the `cost-call` lint rule (repro/analysis/lint.py): a direct
call outside repro.analysis is a lint finding.

Three layers:

* Extraction over one compiled program: `compiled_cost`, `hbm_rw_bytes`,
  `compiled_memory`, `temp_bytes`, `peak_bytes_of`, `roofline_metrics`
  (flops/bytes + per-collective payload totals -- the dry-run launcher's
  metric, lifted here), `parse_collectives` / `shape_bytes`, and the
  HLO-text op census `hlo_op_census`.

* The while-loop trip-count correction `scan_trip_count_totals`: XLA's
  cost_analysis counts each while-loop (lax.scan) body ONCE; given the
  compiled metrics of count-1 / count-2 / accum-2 variants it recovers
  true totals by finite differencing. `launch/dryrun.py` is now a thin
  delegate: it builds the compiled variants, the math is here.

* The per-route resource report over the PR-7 contract registry:
  `resource_report` walks `registry.build_cells()` (the mode x backend
  x unsharded/sharded/routed/tenant matrix) and emits one row
  {flops, hbm_bytes_read/written, temp_bytes, peak_bytes, jit_entries,
  op_census} per (entry x config) route; `diff_resource_reports` gates a
  fresh report against the committed RESOURCES_baseline.json. CLI:
  `python -m repro.analysis cost` / `cost-diff` -- a perf-regression
  gate with zero timing noise.

This module imports no jax at module scope: `launch/dryrun.py` must be
able to import it before jax initialises its forced device count.
"""

from __future__ import annotations

import json
import re
from typing import Any, Mapping, Sequence

#: dtype token -> bytes per element, for HLO shape strings like f32[8,128].
DTYPE_BYTES: dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

#: collective op kinds whose payload bytes the roofline metric sums.
COLLECTIVE_KINDS: tuple[str, ...] = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute")

#: cost-bearing opcodes the HLO-text census counts -- most specific
#: first, one match per line, so an "all-gather(" line is a collective
#: and never double-counts as a "gather".
CENSUS_OPS: tuple[str, ...] = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "dynamic-update-slice", "dynamic-slice",
    "gather", "scatter", "dot", "convert", "while", "sort", "iota",
    "transpose", "pad", "fusion", "custom-call")

#: CompiledMemoryStats attributes surfaced by `compiled_memory` (the
#: exact set and order launch/dryrun.py has always reported on stderr).
MEMORY_STATS: tuple[str, ...] = (
    "argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
    "generated_code_size_in_bytes")

#: per-operand input terms of cost_analysis ("bytes accessed0{}", ...).
_OPERAND_BYTES_RE = re.compile(r"^bytes accessed\d+\{\}$")
#: the output term ("bytes accessedout{}").
_OUTPUT_BYTES_KEY = "bytes accessedout{}"

#: fields diffed between two resource reports (route-wise).
RESOURCE_FIELDS: tuple[str, ...] = (
    "flops", "hbm_bytes_read", "hbm_bytes_written", "temp_bytes",
    "peak_bytes", "jit_entries")


# -- HLO-text extraction ----------------------------------------------------


def shape_bytes(tok: str) -> int:
    """Bytes of one HLO shape token like ``bf16[16,1024]`` (0 if unknown)."""
    m = _SHAPE_RE.match(tok)
    if not m or m.group(1) not in DTYPE_BYTES:
        return 0
    dims = m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES[m.group(1)]


def parse_collectives(hlo: str) -> dict[str, Any]:
    """Sum per-device payload bytes of every collective in partitioned HLO.

    Methodology (documented in EXPERIMENTS.md): result-shape bytes per op,
    doubled for all-reduce (reduce+broadcast phases of a ring); the (P-1)/P
    ring factor is dropped (upper bound).
    """
    out: dict[str, Any] = {k: {"count": 0, "bytes": 0}
                           for k in COLLECTIVE_KINDS}
    for line in hlo.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        for kind in COLLECTIVE_KINDS:
            # match "<kind>(" or "<kind>-start(" as the op on this line
            if re.search(rf"= [^=]*\b{kind}(-start)?\(", s):
                rhs = s.split("=", 1)[1].strip()
                # result type: everything before the op name
                head = re.split(rf"\b{kind}(-start)?\(", rhs)[0]
                shapes = _SHAPE_RE.findall(head)
                nbytes = sum(shape_bytes(f"{t}[{d}]") for t, d in shapes)
                if kind == "all-reduce":
                    nbytes *= 2
                out[kind]["count"] += 1
                out[kind]["bytes"] += nbytes
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def hlo_op_census(hlo: str,
                  ops: Sequence[str] = CENSUS_OPS) -> dict[str, dict[str, int]]:
    """Count cost-bearing ops in HLO text, with result-shape bytes.

    Returns ``{op: {"count": n, "bytes": b}}`` for every op of `ops`
    that appears; `b` sums the result-shape bytes of each matched line
    (the same methodology `parse_collectives` uses, minus the all-reduce
    doubling). One op per line, most specific first.
    """
    out: dict[str, dict[str, int]] = {}
    for line in hlo.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        for op in ops:
            if re.search(rf"= [^=]*\b{op}(-start)?\(", s):
                rhs = s.split("=", 1)[1].strip()
                head = re.split(rf"\b{op}(-start)?\(", rhs)[0]
                nbytes = sum(shape_bytes(f"{t}[{d}]")
                             for t, d in _SHAPE_RE.findall(head))
                rec = out.setdefault(op, {"count": 0, "bytes": 0})
                rec["count"] += 1
                rec["bytes"] += nbytes
                break
    return out


# -- compiled-program extraction --------------------------------------------


def compiled_cost(compiled: Any) -> dict[str, float]:
    """The numeric properties of ``compiled.cost_analysis()`` as one dict
    (first device on jax versions returning one dict per device; empty
    when the backend exposes nothing)."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return {str(k): float(v) for k, v in cost.items()
            if isinstance(v, (int, float))}


def hbm_rw_bytes(cost: Mapping[str, float]) -> tuple[float, float]:
    """(read, written) HBM bytes of one compiled program.

    XLA reports the total ``bytes accessed`` plus per-operand
    ``bytes accessed<i>{}`` and output ``bytes accessedout{}`` terms;
    read sums the operand terms (falling back to total - written when a
    backend omits them), written is the output term.
    """
    total = float(cost.get("bytes accessed", 0.0))
    written = float(cost.get(_OUTPUT_BYTES_KEY, 0.0))
    read = sum(v for k, v in cost.items() if _OPERAND_BYTES_RE.match(k))
    if read <= 0.0:
        read = max(total - written, 0.0)
    return read, written


def compiled_memory(compiled: Any) -> dict[str, Any]:
    """``memory_analysis()`` stats as a plain dict in MEMORY_STATS order
    (the exact report launch/dryrun.py prints on stderr);
    ``{"error": ...}`` when the backend exposes no stats."""
    try:
        ma = compiled.memory_analysis()
        return {k: int(getattr(ma, k)) for k in MEMORY_STATS
                if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover - backend-dependent
        return {"error": str(e)}


def temp_bytes(compiled: Any) -> int:
    """Temp-buffer (scratch) bytes of one compiled program (0 when the
    backend exposes no memory stats)."""
    return int(compiled_memory(compiled).get("temp_size_in_bytes", 0))


def peak_bytes_of(mem: Mapping[str, Any]) -> int:
    """Peak-footprint proxy from a `compiled_memory` dict: argument +
    output + temp bytes (XLA exposes no single peak stat; this is the
    live-at-entry working set plus scratch)."""
    return sum(int(mem.get(k, 0)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes"))


def roofline_metrics(compiled: Any) -> dict[str, float]:
    """Per-device flops/bytes + per-collective byte totals (UNcorrected:
    while-loop bodies counted once -- see scan_trip_count_totals)."""
    cost = compiled_cost(compiled)
    coll = parse_collectives(compiled.as_text())
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0))}
    for k in COLLECTIVE_KINDS:
        out[f"coll_{k}"] = float(coll[k]["bytes"])
    out["coll_total"] = float(coll["total_bytes"])
    return out


# -- while-loop trip-count correction ---------------------------------------


def metric_add(a: Mapping[str, float], b: Mapping[str, float],
               sa: float = 1.0, sb: float = 1.0) -> dict[str, float]:
    """Keywise linear combination ``sa*a + sb*b`` over a's keys."""
    return {k: sa * a[k] + sb * b.get(k, 0.0) for k in a}


def metric_clamp(a: Mapping[str, float]) -> dict[str, float]:
    """Keywise clamp to >= 0 (finite differences can go slightly negative
    when XLA folds a variant differently)."""
    return {k: max(v, 0.0) for k, v in a.items()}


def scan_trip_count_totals(m1: Mapping[str, float],
                           m2_groups: Sequence[Mapping[str, float]],
                           counts: Sequence[int], accum: int,
                           m3: Mapping[str, float] | None = None
                           ) -> dict[str, float]:
    """Trip-count-corrected totals by finite-differencing over scan lengths.

    XLA's cost_analysis counts each while-loop (lax.scan) body ONCE; the
    real step executes layer group g's body L_g times inside an
    accumulation loop of A steps. Given the metrics of the compiled
    variants

        m1         every layer group at count 1, accumulation 1
        m2_groups  group g at count 2 (others 1), accumulation 1
        m3         groups at 1, accumulation 2 (None when A == 1)

    the recovered terms are

        F_g      = M2_g - M1                 (one layer of group g)
        F_micro  = (M3 - M1) - sum_g F_g     (per-microbatch fixed cost)
        F_fixed  = 2*M1 - M3
        total    = F_fixed + A * (F_micro + sum_g L_g * F_g)

    (without m3: F_micro = 0, F_fixed = M1 - sum_g F_g, A = 1). Each
    difference clamps at 0. `counts` holds the true per-group layer
    counts L_g, aligned with m2_groups.
    """
    f_groups = [metric_clamp(metric_add(m2, m1, 1.0, -1.0))
                for m2 in m2_groups]
    sum_fg = {k: sum(f[k] for f in f_groups) for k in m1}
    if m3 is not None:
        f_micro = metric_clamp(metric_add(
            metric_add(m3, m1, 1.0, -1.0), sum_fg, 1.0, -1.0))
        f_fixed = metric_clamp(metric_add(
            m1, metric_add(m3, m1, 1.0, -1.0), 1.0, -1.0))
    else:
        f_micro = {k: 0.0 for k in m1}
        f_fixed = metric_clamp(metric_add(m1, sum_fg, 1.0, -1.0))
        accum = 1
    total: dict[str, float] = {}
    for k in m1:
        inner = f_micro[k] + sum(c * f[k] for c, f in zip(counts, f_groups))
        total[k] = f_fixed[k] + accum * inner
    return total


# -- the per-route resource report ------------------------------------------


def route_key(row: Mapping[str, Any]) -> str:
    """``entry|sorted-config`` -- the same key shape registry.Cell.key
    uses, so resource rows and contract cells align."""
    return f"{row['entry']}|{json.dumps(row['config'], sort_keys=True)}"


def _null_row(entry: str, config: Mapping[str, Any], status: str,
              detail: str) -> dict[str, Any]:
    return {"entry": entry, "config": dict(config), "status": status,
            "detail": detail, "flops": None, "hbm_bytes_read": None,
            "hbm_bytes_written": None, "temp_bytes": None,
            "peak_bytes": None, "jit_entries": None, "op_census": {},
            "while_ops": 0}


def resource_row(entry: str, config: Mapping[str, Any],
                 art: Mapping[str, Any]) -> dict[str, Any]:
    """One resource-report row from a built registry cell's artifacts.

    Cells that compile a program ("compiled" in art) get the full
    {flops, hbm read/written, temp, peak} set with jit_entries = 1;
    the jit-cache cell instead reports its measured entry count
    ("cache_size"); every cell with HLO text gets the op census. Rows
    with a while loop report its presence (`while_ops`) but keep XLA's
    once-per-body counting -- the static baseline must be reproducible
    without the dry-run's variant recompiles (launch/dryrun.py applies
    scan_trip_count_totals where true totals matter).
    """
    row = _null_row(entry, config, "ok", "")
    compiled = art.get("compiled")
    if compiled is not None:
        cost = compiled_cost(compiled)
        read, written = hbm_rw_bytes(cost)
        mem = compiled_memory(compiled)
        row.update(flops=float(cost.get("flops", 0.0)),
                   hbm_bytes_read=read, hbm_bytes_written=written,
                   temp_bytes=int(mem.get("temp_size_in_bytes", 0)),
                   peak_bytes=peak_bytes_of(mem), jit_entries=1)
    hlo = art.get("hlo")
    if hlo is not None:
        census = hlo_op_census(hlo)
        row["op_census"] = census
        row["while_ops"] = census.get("while", {}).get("count", 0)
    if "cache_size" in art:
        row["jit_entries"] = int(art["cache_size"])
    return row


def resource_report(cells: Sequence[Any] | None = None) -> dict[str, Any]:
    """Per-route static resource rows over the contract registry matrix.

    Builds every cell of `registry.build_cells()` (default) and extracts
    its resource row; skipped cells (not enough devices) and build errors
    become rows with a matching status, so the report always has one row
    per registered route.
    """
    import jax

    from repro.analysis import registry

    if cells is None:
        cells = registry.build_cells()
    rows: list[dict[str, Any]] = []
    for cell in cells:
        if cell.skip:
            rows.append(_null_row(cell.entry, cell.config, "skip",
                                  cell.skip))
            continue
        try:
            art = cell.build()
        except Exception as e:          # build error surfaces in the row
            rows.append(_null_row(cell.entry, cell.config, "error",
                                  f"{type(e).__name__}: {e}"))
            continue
        rows.append(resource_row(cell.entry, cell.config, art))
    summary: dict[str, Any] = {"routes": len(rows)}
    for s in ("ok", "skip", "error"):
        summary[s] = sum(1 for r in rows if r["status"] == s)
    summary["total_flops"] = float(sum(r["flops"] or 0.0 for r in rows))
    return {"meta": {"jax": jax.__version__,
                     "jax_backend": jax.default_backend(),
                     "devices": len(jax.devices())},
            "summary": summary, "routes": rows}


def diff_resource_reports(old: Mapping[str, Any], new: Mapping[str, Any],
                          rtol: float = 0.05) -> dict[str, Any]:
    """Route-wise drift between two resource reports.

    Only rows with status "ok" on both sides are compared. A route that
    was ok in `old` but is gone (or no longer ok) in `new` is `missing`
    (red); `jit_entries` must match exactly, every other RESOURCE_FIELD
    within ``rtol`` relative tolerance (absolute floor 1.0, so zero
    baselines do not trip on rounding); new routes are `added`
    (reported, never fatal -- growth is the point).
    """
    old_rows = {route_key(r): r for r in old.get("routes", [])
                if r.get("status") == "ok"}
    new_rows = {route_key(r): r for r in new.get("routes", [])
                if r.get("status") == "ok"}
    missing = sorted(set(old_rows) - set(new_rows))
    added = sorted(set(new_rows) - set(old_rows))
    drifted: list[dict[str, Any]] = []
    for key in sorted(set(old_rows) & set(new_rows)):
        o, n = old_rows[key], new_rows[key]
        for field in RESOURCE_FIELDS:
            ov, nv = o.get(field), n.get(field)
            if ov is None and nv is None:
                continue
            if ov is None or nv is None:
                drifted.append({"route": key, "field": field, "old": ov,
                                "new": nv, "rel": None})
                continue
            ov_f, nv_f = float(ov), float(nv)
            tol = 0.0 if field == "jit_entries" \
                else rtol * max(abs(ov_f), 1.0)
            if abs(nv_f - ov_f) > tol:
                drifted.append({"route": key, "field": field, "old": ov,
                                "new": nv,
                                "rel": (nv_f - ov_f) / max(abs(ov_f), 1.0)})
    return {"drifted": drifted, "missing": missing, "added": added}
