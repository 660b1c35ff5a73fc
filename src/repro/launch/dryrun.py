import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^^ MUST precede every other import (jax locks the device count on first
# init). The 512 placeholder CPU devices exist ONLY for this dry-run process.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch llama3-405b --shape train_4k --mesh single [--retrieval] \
        [--out results.json]

Succeeding here proves the distribution config is coherent: shardings
legalize, the SPMD partitioner finds a schedule, per-device buffers are
bounded, and the collective set is what DESIGN.md claims. Output JSON:
  flops / bytes from compiled.cost_analysis(),
  per-collective byte totals parsed from the partitioned HLO,
  memory_analysis (argument/output/temp/peak bytes per device),
  roofline terms vs TPU v5e (197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s ICI).
"""

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import cost as cost_lib
from repro.configs import SHAPES, load_config, supports_shape
from repro.configs.base import TrainConfig
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.models.sharding import active_mesh

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link

# Cost/HLO extraction is the analysis package's cost model
# (repro/analysis/cost.py, ONE spelling for the whole repo); these
# aliases keep dryrun's long-standing surface (tests import them here).
_COLLECTIVES = cost_lib.COLLECTIVE_KINDS
_shape_bytes = cost_lib.shape_bytes
parse_collectives = cost_lib.parse_collectives


def _tree_bytes_per_device(tree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        bpe = jnp.dtype(leaf.dtype).itemsize
        shard = leaf.sharding
        nshards = getattr(shard, "num_devices", 1)
        if hasattr(shard, "shard_shape"):
            n = int(np.prod(shard.shard_shape(leaf.shape))) if leaf.shape else 1
        total += n * bpe
    return total


def _with_shardings(abs_tree, shard_tree):
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_tree, shard_tree)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense train) / 2*N*D (inference), N = active
    params (excluding embeddings), D = tokens processed."""
    aps = tfm.abstract_params(cfg)
    total = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(aps))
    embed = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2) \
        if cfg.input_mode == "tokens" or not cfg.tie_embeddings else 0
    n_params = total - embed
    if cfg.moe is not None:
        m = cfg.moe
        layers_moe = sum(cfg.moe_layers())
        expert_p = m.n_routed * 3 * cfg.d_model * m.d_ff * layers_moe
        active_p = (m.top_k / m.n_routed) * expert_p
        n_params = n_params - expert_p + active_p
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * n_params * tokens


def _compile_step(cfg, shape, mesh, rules, tc, retrieval, unroll=False):
    """Lower + compile the step `shape` dictates. Returns (compiled,
    state_bytes_per_device)."""
    p_shard = steps_lib.param_shardings(cfg, mesh, rules)
    params_abs = tfm.abstract_params(cfg)
    params_in = _with_shardings(params_abs, p_shard)
    batch_in = steps_lib.input_specs(cfg, shape, mesh, rules)

    with mesh, active_mesh(mesh, rules):
        if shape.kind == "train":
            step, optimizer = steps_lib.make_train_step(
                cfg, tc, rules, unroll_accum=unroll)
            opt_abs = jax.eval_shape(optimizer.init, params_abs)
            opt_shard = steps_lib.opt_shardings(opt_abs, params_abs, p_shard,
                                                mesh, rules)
            opt_in = _with_shardings(opt_abs, opt_shard)
            lowered = jax.jit(step).lower(params_in, opt_in, batch_in)
            state_bytes = (_tree_bytes_per_device(params_in)
                           + _tree_bytes_per_device(opt_in))
        elif shape.kind == "prefill":
            step = steps_lib.make_prefill_step(cfg, rules)
            lowered = jax.jit(step).lower(params_in, batch_in)
            state_bytes = _tree_bytes_per_device(params_in)
        else:  # decode
            c_shard, cache_abs = steps_lib.cache_shardings(
                cfg, shape.global_batch, shape.seq_len, mesh, rules)
            cache_in = _with_shardings(cache_abs, c_shard)
            pos_in = jax.ShapeDtypeStruct((), jnp.int32)
            if retrieval:
                from repro.core.memory import MemoryConfig
                from repro.engine import MemoryStore
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                # the kNN-LM datastore: d_model-wide hidden-state keys
                mem_cfg = MemoryConfig(capacity=131072, dim=cfg.d_model)
                # calibrate the abstract store: the serving store is always
                # calibrated before decode, and quantize_queries refuses
                # float queries on a never-calibrated store
                mem_abs = jax.eval_shape(
                    lambda: MemoryStore.create(mem_cfg).calibrate(
                        jnp.zeros((4, mem_cfg.dim), jnp.float32)))
                row = NamedSharding(mesh, P(tuple(mesh.axis_names)))
                rep = NamedSharding(mesh, P())
                mem_shard = jax.tree_util.tree_map(
                    lambda v: (row if getattr(v, "ndim", 0) >= 1 else rep),
                    mem_abs)
                mem_in = _with_shardings(mem_abs, mem_shard)
                step = steps_lib.make_serve_step_with_mcam(cfg, rules,
                                                           mem_cfg)
                lowered = jax.jit(step).lower(params_in, cache_in, batch_in,
                                              pos_in, mem_in)
            else:
                step = steps_lib.make_serve_step(cfg, rules)
                lowered = jax.jit(step).lower(params_in, cache_in, batch_in,
                                              pos_in)
            state_bytes = (_tree_bytes_per_device(params_in)
                           + _tree_bytes_per_device(cache_in))
        compiled = lowered.compile()
    return compiled, int(state_bytes)


# per-device flops/bytes + per-collective byte totals (UNcorrected: scan
# bodies counted once -- see _corrected_metrics)
_metrics = cost_lib.roofline_metrics


def _corrected_metrics(cfg, shape, mesh, rules, tc, retrieval) -> dict:
    """Trip-count-corrected totals. XLA's cost_analysis counts each
    while-loop (lax.scan) body ONCE; the real step executes the layer-scan
    body L_g times inside an accumulation scan of A steps. This builds the
    compiled count variants (M1 / M2_g / M3); the finite-difference
    recovery of true totals is repro.analysis.cost.scan_trip_count_totals
    (the formula is documented there)."""
    groups = [list(g) for g in cfg.layer_groups()]
    mb = steps_lib.microbatch_for(cfg, shape)
    accum = (shape.global_batch // mb) if shape.kind == "train" else 1

    def variant(counts, accum_n):
        vcfg = dataclasses.replace(
            cfg, scan_layers=False, layer_groups_override=tuple(
                (t, m, c) for (t, m, _), c in zip(groups, counts)))
        vshape = dataclasses.replace(
            shape, global_batch=(mb * accum_n if shape.kind == "train"
                                 else shape.global_batch),
            microbatch=(mb if shape.kind == "train" else 0))
        compiled, _ = _compile_step(vcfg, vshape, mesh, rules, tc, retrieval,
                                    unroll=True)
        return _metrics(compiled)

    ones = [1] * len(groups)
    m1 = variant(ones, 1)
    m2_groups = []
    for gi in range(len(groups)):
        counts = list(ones)
        counts[gi] = 2
        m2_groups.append(variant(counts, 1))
    m3 = variant(ones, 2) if shape.kind == "train" and accum > 1 else None
    layer_counts = [c for (_, _, c) in cfg.layer_groups()]
    return cost_lib.scan_trip_count_totals(m1, m2_groups, layer_counts,
                                           accum, m3=m3)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             retrieval: bool = False, calibrate: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    shape = SHAPES[shape_name]
    rules = steps_lib.rules_for(mesh, shape)  # REPRO_OPT>=3: serving rules
    cfg = load_config(arch)
    ok, why = supports_shape(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        return {**rec, "status": "skipped", "reason": why}

    dp = int(np.prod([mesh.shape[a] for a in rules.batch]))
    cfg = steps_lib.adapt_config(cfg, shape, dp)
    tc = TrainConfig()

    # 1. the deliverable: the FULL cell must lower + compile
    t0 = time.time()
    compiled, state_bytes = _compile_step(cfg, shape, mesh, rules, tc,
                                          retrieval)
    compile_s = time.time() - t0
    mem = cost_lib.compiled_memory(compiled)
    raw = _metrics(compiled)

    # 2. trip-count-corrected roofline terms
    corr = _corrected_metrics(cfg, shape, mesh, rules, tc, retrieval) \
        if calibrate else raw

    flops = corr["flops"]
    bytes_acc = corr["bytes"]
    coll_bytes = corr["coll_total"]
    mf = model_flops(cfg, shape)
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = coll_bytes / ICI_BW
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", collective_s)), key=lambda kv: kv[1])[0]
    return {
        **rec, "status": "ok", "chips": n_chips,
        "compile_s": round(compile_s, 1),
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_bytes,
        "collectives_corrected": {k: corr[f"coll_{k}"] for k in _COLLECTIVES},
        "raw_uncorrected": raw,
        "memory_analysis": mem,
        "state_bytes_per_device": int(state_bytes),
        "model_flops_total": mf,
        "useful_flops_ratio": (mf / (flops * n_chips)) if flops else None,
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "dominant": dominant,
            "bound_s": max(compute_s, memory_s, collective_s),
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    rec = run_cell(args.arch, args.shape, args.mesh == "multi",
                   retrieval=args.retrieval)
    js = json.dumps(rec, indent=1)
    print(js)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)
    if rec["status"] == "ok":
        print(f"\nMEMORY: {rec['memory_analysis']}", file=sys.stderr)
        print(f"COST: flops/dev={rec['flops_per_device']:.3e} "
              f"bytes/dev={rec['bytes_per_device']:.3e}", file=sys.stderr)
    return 0 if rec["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
