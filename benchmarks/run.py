# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig9,...]

Suites:
  table1   encoding rules (bench_encodings)
  fig3_5   mismatch-level distributions B4E vs MTMC (bench_mismatch)
  table2   SVSS vs AVSS accuracy + throughput (bench_avss)
  fig9     energy-accuracy Pareto fronts (bench_pareto)
  kernel   Pallas kernels + two-phase recall (bench_kernels)
  engine   retrieval engine: full vs two-phase vs sharded vs store-based
           unified search, plus the streaming-write and large-N ideal
           serving rows (bench_engine)
  engine_sharded  multi-device sharded scaling (search and shard-local
           streaming writes) on a forced 8-device host mesh (subprocess, like
           tests/test_distributed.py); writes
           results/bench_engine_sharded.json (CI artifact)
  router   hierarchical-routing sweep: recall@1 + latency percentiles per
           nprobe on a class-coherent partitioned store (bench_router);
           refreshes the committed repo-root BENCH_router.json
  hat      hardware-aware training step timings (episodic meta-train step
           through the engine's differentiable MCAM forward vs the plain
           pretrain step) + the per-encoding engine.search cost sweep
           (mtmc/b4e/b4we/sre) -- bench_hat
  roofline dry-run derived roofline terms (benchmarks.roofline; needs the
           dryrun sweep artifacts under results/dryrun)

Every run also consolidates the rows of ALL executed suites into
results/bench_summary.json (uploaded as a CI artifact by the weekly full
job), so the perf trajectory is tracked PR-over-PR in one file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SUITES = {
    "table1": "benchmarks.bench_encodings",
    "fig3_5": "benchmarks.bench_mismatch",
    "table2": "benchmarks.bench_avss",
    "fig9": "benchmarks.bench_pareto",
    "kernel": "benchmarks.bench_kernels",
    "engine": "benchmarks.bench_engine",
    "engine_sharded": "benchmarks.bench_engine_sharded",
    "router": "benchmarks.bench_router",
    "hat": "benchmarks.bench_hat",
    "roofline": "benchmarks.roofline",
}

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUMMARY_PATH = os.path.join(ROOT, "results", "bench_summary.json")
ROUTER_PATH = os.path.join(ROOT, "BENCH_router.json")

def _emit_router_bench(router_rows: list[dict]) -> bool:
    """Refresh the committed repo-root BENCH_router.json from the router
    suite: the recall-vs-nprobe-vs-latency curve (percentiles included),
    so the routing claim is checkable from the repo alone."""
    if not router_rows:
        return False
    with open(ROUTER_PATH, "w") as f:
        json.dump({"generated_by": "benchmarks.run --only router",
                   "measurement": "cpu xla -- recall curve and "
                                  "routed-vs-exhaustive ordering are the "
                                  "signal; re-measure on TPU for absolute "
                                  "latencies",
                   "rows": router_rows}, f, indent=1)
    return True


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(SUITES)
    print("name,us_per_call,derived")
    failed = []
    summary = {}
    import importlib
    for key, modname in SUITES.items():
        if key not in only:
            continue
        try:
            mod = importlib.import_module(modname)
            suite_rows = []
            # rows are (name, us, derived) or (name, us, derived, stats)
            # where stats is common.time_percentiles' shared schema
            for row in mod.run():
                name, us, derived = row[:3]
                print(f"{name},{us:.1f},{derived}")
                sys.stdout.flush()
                entry = {"name": name, "us_per_call": us,
                         "derived": derived}
                if len(row) > 3 and row[3]:
                    entry["percentiles"] = row[3]
                suite_rows.append(entry)
            summary[key] = suite_rows
        except Exception as e:  # keep the harness going; report at the end
            failed.append((key, repr(e)))
            print(f"{key}/ERROR,0.0,{e!r}")
    # merge into any existing summary: CI invokes the harness once per
    # suite, and the artifact should accumulate them all
    merged = {}
    try:
        with open(SUMMARY_PATH) as f:
            prev = json.load(f)
        merged = dict(prev.get("suites", {}))
    except (OSError, ValueError):
        pass
    merged.update(summary)
    # fold the contract guard's latest pass/fail counts into the artifact
    # (written by `python -m repro.analysis run`; absent = not run here)
    contracts = None
    try:
        with open(os.path.join(ROOT, "results",
                               "contract_report.json")) as f:
            contracts = json.load(f)["summary"]
    except (OSError, ValueError, KeyError):
        pass
    # ... and the resource oracle's route counts + total static FLOPs
    # (written by `python -m repro.analysis cost`; absent = not run here)
    resources = None
    try:
        with open(os.path.join(ROOT, "results",
                               "resource_report.json")) as f:
            resources = json.load(f)["summary"]
    except (OSError, ValueError, KeyError):
        pass
    os.makedirs(os.path.dirname(SUMMARY_PATH), exist_ok=True)
    with open(SUMMARY_PATH, "w") as f:
        json.dump({"generated_by": "benchmarks.run",
                   "last_run": sorted(only & set(SUITES)),
                   "failed": failed, "contracts": contracts,
                   "resources": resources,
                   "suites": merged}, f, indent=1)
    print(f"# wrote {os.path.relpath(SUMMARY_PATH, ROOT)} "
          f"({sum(len(v) for v in merged.values())} rows, "
          f"{len(merged)} suite(s))")
    if "router" in summary and _emit_router_bench(summary["router"]):
        print(f"# wrote {os.path.relpath(ROUTER_PATH, ROOT)} "
              f"(recall-vs-nprobe routing curve)")
    if failed:
        print(f"# {len(failed)} suite(s) failed: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
