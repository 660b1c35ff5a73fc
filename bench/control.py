#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 4

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, then the cell's driver `control(ctx, state)`, which
compares, on the same samples, the program's outputs and those of the
control -- the plain reference in the next precision below the one the
configuration states -- with the reference. One JSON line per seed. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(spec, name: str, seed: int, seconds: float,
             dirs=None, root=None) -> dict:
    import jax

    from bench import harness
    cell = harness.resolve(spec, name, dirs or (harness.BENCH_DIR,),
                           root or harness.ROOT)
    ctx = harness.Context(cell, seed, seconds, jax.devices()[:cell.chips])
    state = cell.driver.setup(ctx)
    with ctx.span("bench.window"):
        win = cell.driver.window(ctx, state)
    out = cell.driver.control(ctx, state)
    out.update(seed=seed, attempted=win["attempted"])
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 3
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = harness.load_spec()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(spec, args.workload, seed, args.seconds)),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
