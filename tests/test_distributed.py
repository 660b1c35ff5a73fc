"""Distribution features, run in subprocesses with fake host devices
(XLA_FLAGS must be set before jax import, so these cannot run in-process)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

# every test here pays a fresh subprocess jax init (~10s) plus multi-device
# compiles -- full tier only
pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, n_devices: int = 8, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_train_step_on_multi_device_mesh():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import load_config
        from repro.configs.base import ShapeConfig, TrainConfig
        from repro.launch import steps as steps_lib
        from repro.models import transformer as tfm
        from repro.models.sharding import rules_for_mesh, active_mesh
        from repro.launch.dryrun import _with_shardings
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = rules_for_mesh(mesh)
        cfg = load_config("starcoder2-3b", smoke=True)
        tc = TrainConfig(learning_rate=1e-3)
        with mesh, active_mesh(mesh, rules):
            step, opt = steps_lib.make_train_step(cfg, tc, rules)
            params = tfm.init(jax.random.PRNGKey(0), cfg)
            p_shard = steps_lib.param_shardings(cfg, mesh, rules)
            params = jax.tree_util.tree_map(jax.device_put, params, p_shard)
            opt_state = opt.init(params)
            key = jax.random.PRNGKey(1)
            b = {"tokens": jax.random.randint(key, (2, 4, 16), 0, 256),
                 "labels": jax.random.randint(key, (2, 4, 16), 0, 256)}
            jstep = jax.jit(step)
            losses = []
            for i in range(3):
                params, opt_state, m = jstep(params, opt_state, b)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses  # same batch -> must improve
        print("LOSSES", losses)
    """)
    assert "LOSSES" in out


def test_dryrun_cell_small_mesh():
    """The dry-run machinery itself on an 8-device (2,2,2) pod mesh."""
    out = run_py("""
        import jax, json, numpy as np
        import repro.launch.mesh as mesh_lib
        # shrink the production mesh for the 8-device test environment
        mesh_lib.make_production_mesh = (
            lambda multi_pod=False: mesh_lib.make_mesh(
                (2, 2, 2) if multi_pod else (4, 2),
                ("pod", "data", "model") if multi_pod else ("data", "model")))
        import repro.launch.dryrun as dr
        dr.make_production_mesh = mesh_lib.make_production_mesh
        import repro.configs as C
        import dataclasses
        C.SHAPES = dict(C.SHAPES)
        from repro.configs.base import ShapeConfig
        C.SHAPES["train_4k"] = ShapeConfig("train_4k", 64, 8, "train", 4)
        dr.SHAPES = C.SHAPES
        real_load = C.load_config
        def fake_load(arch, smoke=False):
            return real_load(arch, smoke=True)
        dr.load_config = fake_load
        rec = dr.run_cell("deepseek-moe-16b", "train_4k", multi_pod=True)
        assert rec["status"] == "ok", rec
        assert rec["flops_per_device"] > 0
        assert rec["roofline"]["dominant"] in ("compute","memory","collective")
        print("REC", rec["roofline"]["dominant"])
    """, n_devices=8)
    assert "REC" in out


def test_pipeline_parallel_matches_sequential():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.runtime.pipeline import pipeline_apply

        mesh = make_mesh((4,), ("pipe",))
        S, M, mb, d = 4, 6, 3, 8
        keys = jax.random.split(jax.random.PRNGKey(0), S)
        Ws = jnp.stack([jax.random.normal(k, (d, d)) / np.sqrt(d)
                        for k in keys])
        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        stage = lambda W, h: jnp.tanh(h @ W)
        out = pipeline_apply(stage, Ws, x, mesh, axis="pipe")
        ref = x
        for i in range(S):
            ref = jnp.tanh(ref @ Ws[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("PIPELINE-OK")
    """, n_devices=4)
    assert "PIPELINE-OK" in out


def test_elastic_checkpoint_across_mesh_shapes():
    """Save sharded on (4,) devices, restore onto (8,)-device sharding."""
    import tempfile
    tmp = tempfile.mkdtemp()
    run_py(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(16, 4),
                           NamedSharding(mesh, P("data", None)))
        save({tmp!r}, 1, {{"w": w}})
        print("SAVED")
    """, n_devices=4)
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import restore
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        sh = {{"w": NamedSharding(mesh, P("data", None))}}
        out = restore({tmp!r}, {{"w": jax.ShapeDtypeStruct((16, 4),
                                                           jnp.float32)}},
                      shardings=sh)
        np.testing.assert_array_equal(
            np.asarray(out["w"]),
            np.arange(64, dtype=np.float32).reshape(16, 4))
        assert len(out["w"].sharding.device_set) == 8
        print("RESTORED-ELASTIC")
    """, n_devices=8)
    assert "RESTORED-ELASTIC" in out


def test_grad_compression_train_step():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import load_config
        from repro.configs.base import TrainConfig
        from repro.launch import steps as steps_lib
        from repro.models import transformer as tfm
        from repro.models.sharding import Rules
        cfg = load_config("starcoder2-3b", smoke=True)
        tc = TrainConfig(learning_rate=1e-3, grad_compression="int8")
        rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
        step, opt = steps_lib.make_train_step(cfg, tc, rules)
        params = tfm.init(jax.random.PRNGKey(0), cfg)
        opt_state = dict(opt.init(params))
        key = jax.random.PRNGKey(1)
        b = {"tokens": jax.random.randint(key, (1, 4, 16), 0, 256),
             "labels": jax.random.randint(key, (1, 4, 16), 0, 256)}
        losses = []
        jstep = jax.jit(step)
        for i in range(4):
            params, opt_state, m = jstep(params, opt_state, b)
            losses.append(float(m["loss"]))
        assert "ef_residual" in opt_state
        assert losses[-1] < losses[0], losses
        print("COMPRESSED-OK", losses)
    """, n_devices=1)
    assert "COMPRESSED-OK" in out
