#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Run from the root of a checkout that holds `BENCHMARK.json`, `bench/` and
the program (`src/`). Prints one JSON result line as the last line of
stdout, and the numbers compared with the reference, each beside its
limit, as the last lines of stderr. Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for, or when the
device is missing from `bench/peaks.json`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the checkout root, not bench/ itself (whose trace.py would shadow
    # the standard library's), and the program
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    spec = harness.load_spec()
    chips = [w["chips"] for w in spec["workloads"]
             if w["name"] == args.workload]
    if len(chips) != 1:
        print(f"bench: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips[0]:
        print(f"bench: cell {args.workload!r} needs {chips[0]} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    line = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START)
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
