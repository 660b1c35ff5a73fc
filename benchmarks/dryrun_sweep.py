"""Run the full dry-run sweep: every (arch x shape) cell on the single-pod
(16x16) and multi-pod (2x16x16) production meshes, one subprocess per cell
(XLA_FLAGS + device-state isolation + memory hygiene on the 1-core runner).

    PYTHONPATH=src python -m benchmarks.dryrun_sweep [--mesh single|multi|both]
        [--only arch,arch] [--results DIR]

Resumable: cells with an existing result JSON are skipped.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ARCHS = [
    # cheap first: small decode/prefill cells compile in seconds
    "xlstm-350m", "hymba-1.5b", "musicgen-medium", "starcoder2-3b",
    "qwen2-vl-7b", "deepseek-moe-16b", "qwen1.5-110b",
    "command-r-plus-104b", "llama3-405b", "deepseek-v3-671b",
]
SHAPES = ["decode_32k", "long_500k", "prefill_32k", "train_4k"]

# the paper-representative extra cell: MCAM retrieval head attached
RETRIEVAL_CELLS = [("starcoder2-3b", "decode_32k")]


def run_one(arch, shape, mesh, results_dir, retrieval=False, timeout=3600):
    tag = f"{arch}_{shape}_{mesh}" + ("_mcam" if retrieval else "")
    out = os.path.join(results_dir, tag + ".json")
    if os.path.exists(out):
        print(f"[skip] {tag} (cached)")
        return json.load(open(out))
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--out", out]
    if retrieval:
        cmd.append("--retrieval")
    t0 = time.time()
    # the dry-run lowers for a forced 512-device host platform: pin the
    # child to the CPU so it never contends for an attached accelerator
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        rec = {"arch": arch, "shape": shape, "mesh": mesh,
               "status": "timeout"}
        json.dump(rec, open(out, "w"))
        print(f"[TIMEOUT] {tag}")
        return rec
    dt = time.time() - t0
    if proc.returncode != 0 or not os.path.exists(out):
        rec = {"arch": arch, "shape": shape, "mesh": mesh, "status": "error",
               "stderr": proc.stderr[-4000:]}
        json.dump(rec, open(out, "w"), indent=1)
        print(f"[FAIL] {tag} ({dt:.0f}s)")
        print(proc.stderr[-1500:])
        return rec
    rec = json.load(open(out))
    r = rec.get("roofline", {})
    print(f"[ok] {tag} ({dt:.0f}s) status={rec['status']} "
          f"dominant={r.get('dominant', '-')} bound={r.get('bound_s', 0):.3g}s")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--only", default="")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--results", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()
    os.makedirs(args.results, exist_ok=True)
    archs = args.only.split(",") if args.only else ARCHS
    shapes = args.shapes.split(",") if args.shapes else SHAPES
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    t0 = time.time()
    n = 0
    for mesh in meshes:
        for shape in shapes:
            for arch in archs:
                run_one(arch, shape, mesh, args.results,
                        timeout=args.timeout)
                n += 1
        if mesh == "single":
            for arch, shape in RETRIEVAL_CELLS:
                run_one(arch, shape, mesh, args.results, retrieval=True,
                        timeout=args.timeout)
    print(f"sweep done: {n} cells in {(time.time()-t0)/60:.1f} min")


if __name__ == "__main__":
    main()
