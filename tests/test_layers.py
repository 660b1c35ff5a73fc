"""Every op of the served programs maps to a layer of the program.

The engine opens one `jax.named_scope` per layer (`hlo_contracts
.LAYER_SCOPES`); `op_layers` maps a compiled program's instructions to
them, and `profile.profile_layers` does the same from a profiler
recording. Here, at toy size on the CPU: the search in every mode and
backend, routed and mesh-sharded (four forced host devices), the store
write and calibration; the v5e-compiled search is checked in
tests/test_tpu_compile.py.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo_contracts as hc
from repro.analysis import profile
from repro.core.avss import SearchConfig
from repro.core.memory import MemoryConfig
from repro.engine import MemoryStore, RetrievalEngine, SearchRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, D, B = 256, 8, 4


def unmapped(hlo: str) -> list[tuple[str, str, str]]:
    """(instruction, opcode, op_name) of the ops that do work and map to
    no layer; parameters only name the program's inputs."""
    _, ops = hc.parse_hlo(hlo)
    layers = hc.op_layers(hlo)
    by_name = {o.name: o for o in ops}
    return [(n, by_name[n].opcode, by_name[n].op_name)
            for n, layer in layers.items()
            if layer is None and by_name[n].opcode != "parameter"]


def layers_in(hlo: str) -> set:
    return set(hc.op_layers(hlo).values())


@pytest.fixture(scope="module")
def toy():
    cfg = SearchConfig("mtmc", cl=4, mode="avss")
    s = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    q = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    store = MemoryStore.create(MemoryConfig(capacity=N, dim=D, search=cfg))
    return cfg, store.calibrate(s), s, q


def search_text(cfg, store, q, backend, **request) -> str:
    eng = RetrievalEngine(cfg, backend=backend)

    def search(st, q):
        res = eng.search(st, q, SearchRequest(k=8, **request))
        return res, res.predict()
    return jax.jit(search).lower(store, q).compile().as_text()


@pytest.mark.parametrize("backend", ["ref", "fused"])
@pytest.mark.parametrize("mode", ["two_phase", "ideal", "full"])
def test_search_ops_map_to_layers(toy, mode, backend):
    cfg, store, s, q = toy
    store = store.write(s, jnp.arange(N))
    text = search_text(cfg, store, q, backend, mode=mode)
    assert unmapped(text) == []
    want = {hc.QUANTIZE_SCOPE, hc.ANSWER_SCOPE,
            hc.RESCORE_SCOPE if mode != "ideal" else hc.SHORTLIST_SCOPE}
    assert want <= layers_in(text)
    if mode == "two_phase":
        assert hc.SHORTLIST_SCOPE in layers_in(text)
    if backend == "fused" and mode != "full":
        # the contract tag stays, nested inside the layer
        fused = hc.scoped_lines(text, hc.FUSED_SCOPE_TAG)
        assert fused and all(hc.SHORTLIST_SCOPE in line for line in fused)


@pytest.mark.parametrize("mode", ["two_phase", "ideal"])
def test_routed_search_ops_map_to_layers(toy, mode):
    cfg, store, s, q = toy
    store = store.write(s, jnp.arange(N)).shard(n_shards=4)
    text = search_text(cfg, store, q, "auto", mode=mode, nprobe=2)
    assert unmapped(text) == []
    assert hc.ROUTE_SCOPE in layers_in(text)
    assert all(hc.ROUTE_SCOPE in line
               for line in hc.scoped_lines(text, hc.ROUTER_SCOPE_TAG))


def test_write_and_calibrate_ops_map_to_their_layers(toy):
    cfg, store, s, q = toy
    write = jax.jit(lambda st, v, lab: st.write(v, lab)).lower(
        store, s[:64], jnp.arange(64)).compile().as_text()
    assert unmapped(write) == []
    assert layers_in(write) == {hc.WRITE_SCOPE}
    calibrate = jax.jit(lambda st, v: st.calibrate(v)).lower(
        store, s).compile().as_text()
    assert unmapped(calibrate) == []
    assert layers_in(calibrate) == {hc.CALIBRATE_SCOPE}


def test_knnlm_decode_step_and_prefill_ops_map_to_layers():
    """The kNN-LM serve step splits into the decoder, the kNN head and the
    search's own layers inside it; the serving prefill is one layer."""
    from repro.configs import load_config
    from repro.launch import serve as serve_lib
    from repro.launch import steps as steps_lib
    from repro.models import transformer as tfm
    from repro.models.sharding import Rules

    cfg = load_config("starcoder2-3b", smoke=True)
    rules = Rules(batch=(), fsdp=(), tensor=(), expert=())
    mem_cfg = serve_lib.datastore_config(cfg, 64)
    params = jax.eval_shape(lambda: tfm.init(jax.random.PRNGKey(0), cfg))
    store = jax.eval_shape(lambda: MemoryStore.create(mem_cfg).calibrate(
        jnp.zeros((4, cfg.d_model))))
    caches = jax.eval_shape(lambda: tfm.init_cache(cfg, 2, 16))
    step = steps_lib.make_serve_step_with_mcam(cfg, rules, mem_cfg, k=8)
    text = step.lower(params, caches, {"tokens": jnp.zeros((2, 1), jnp.int32)},
                      jnp.int32(3), store).compile().as_text()
    assert unmapped(text) == []
    assert {hc.DECODER_SCOPE, hc.KNN_SCOPE, hc.SHORTLIST_SCOPE,
            hc.RESCORE_SCOPE} <= layers_in(text)
    prefill = steps_lib.make_prefill_into_step(cfg, rules, 16)
    text = prefill.lower(params, caches, jnp.zeros((1, 12), jnp.int32),
                         jnp.int32(1)).compile().as_text()
    assert unmapped(text) == []
    assert layers_in(text) == {hc.PREFILL_SCOPE}


def test_sharded_search_and_write_ops_map_to_layers():
    """Four forced host devices (a subprocess: the device count is fixed
    at JAX start-up): the mesh-sharded search on both shortlist paths and
    the shard-local streamed write."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.analysis import hlo_contracts as hc
        from repro.core.avss import SearchConfig
        from repro.core.memory import MemoryConfig
        from repro.engine import MemoryStore, RetrievalEngine, SearchRequest
        from repro.launch.mesh import make_mesh

        def unmapped(text):
            _, ops = hc.parse_hlo(text)
            kind = {o.name: o.opcode for o in ops}
            return [n for n, l in hc.op_layers(text).items()
                    if l is None and kind[n] != "parameter"]

        cfg = SearchConfig("mtmc", cl=4, mode="avss")
        s = jax.random.normal(jax.random.PRNGKey(0), (256, 8))
        q = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
        store = MemoryStore.create(MemoryConfig(capacity=256, dim=8,
                                                search=cfg)).calibrate(s)
        store = store.write(s, jnp.arange(256))
        mesh = make_mesh((4,), ("data",))
        store = store.shard(mesh, ("data",))
        for backend in ("ref", "fused"):
            for mode in ("two_phase", "ideal"):
                eng = RetrievalEngine(cfg, backend=backend)
                def search(st, q):
                    res = eng.search(st, q, SearchRequest(mode=mode, k=8))
                    return res, res.predict()
                text = jax.jit(search).lower(store, q).compile().as_text()
                gathers = [l for l in hc.matched_lines(text, ("all-gather",))
                           if "metadata" in l]
                print(backend, mode, unmapped(text),
                      hc.MERGE_SCOPE in set(hc.op_layers(text).values()),
                      bool(gathers) and all(hc.MERGE_SCOPE in l
                                            for l in gathers))
        text = jax.jit(lambda st, v, lab: st.write(v, lab)).lower(
            store, s[:64], jnp.arange(64)).compile().as_text()
        print("write", unmapped(text),
              set(hc.op_layers(text).values()) == {hc.WRITE_SCOPE},
              not hc.check_no_collectives(text))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 5, proc.stdout
    for line in lines:
        assert line.endswith("[] True True"), line


HAND_HLO = (
    "HloModule jit_f, is_scheduled=true\n\n"
    "%fused_computation (param_0: f32[8]) -> f32[8] {\n"
    "  %param_0 = f32[8]{0} parameter(0)\n"
    "  ROOT %neg.1 = f32[8]{0} negate(%param_0), "
    'metadata={op_name="jit(f)/search_rescore/neg"}\n'
    "}\n\n"
    "ENTRY %main.1 (x.1: f32[8], y.1: f32[8]) -> (f32[8], f32[8]) {\n"
    '  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}\n'
    '  %y.1 = f32[8]{0} parameter(1), metadata={op_name="y"}\n'
    "  %copy.1 = f32[8]{0} copy(%x.1)\n"
    "  %bitcast.1 = f32[8]{0} bitcast(%copy.1)\n"
    "  %fusion = f32[8]{0} fusion(%bitcast.1), kind=kLoop, "
    "calls=%fused_computation, "
    'metadata={op_name="jit(f)/search_shortlist/shortlist_fused/neg"}\n'
    '  %sine.1 = f32[8]{0} sine(%y.1), metadata={op_name="jit(f)/sin"}\n'
    "  %copy.2 = f32[8]{0} copy(%fusion)\n"
    "  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%copy.2, %sine.1)\n"
    "}\n")


def test_op_layers_rule_on_hand_written_hlo():
    entry, ops = hc.parse_hlo(HAND_HLO)
    assert entry == "main.1"
    fusion = next(o for o in ops if o.name == "fusion")
    assert fusion.opcode == "fusion" and fusion.operands == ("bitcast.1",)
    assert fusion.calls == ("fused_computation",)
    assert hc.op_layers(HAND_HLO) == {
        # the innermost layer scope, not the nested contract tag
        "fusion": hc.SHORTLIST_SCOPE,
        # copy -> bitcast -> fusion: inserted ops take their user's layer,
        # parameters too
        "copy.1": hc.SHORTLIST_SCOPE, "bitcast.1": hc.SHORTLIST_SCOPE,
        "x.1": hc.SHORTLIST_SCOPE,
        # no user with a layer: the operand's
        "copy.2": hc.SHORTLIST_SCOPE, "tuple.1": hc.SHORTLIST_SCOPE,
        # an op of the program's own outside every layer stays unmapped,
        # and so does the parameter only it reads
        "sine.1": None, "y.1": None,
    }
    # the fused computation's ops never reach a trace: not in the map
    assert hc.innermost_layer("jit(f)/search_answer/search_rescore/x") == \
        hc.RESCORE_SCOPE
    assert hc.innermost_layer("jit(f)/shortlist_fused/x") is None


def test_profile_layers_reads_the_recorded_programs(tmp_path):
    """A profile recorded here of a scoped jitted function: its module's
    table maps each instruction as `op_layers` maps the compiled text."""
    def f(x):
        with jax.named_scope(hc.SHORTLIST_SCOPE):
            y = x @ x
        with jax.named_scope(hc.RESCORE_SCOPE):
            return jnp.exp(y) + 1.0
    g = jax.jit(f)
    x = jnp.ones((64, 64))
    g(x).block_until_ready()                      # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    table = profile.profile_layers(str(tmp_path))
    mods = [m for m in table if m.startswith("jit_f(")]
    assert len(mods) == 1
    text = g.lower(x).compile().as_text()
    assert table[mods[0]] == hc.op_layers(text)
    assert {hc.SHORTLIST_SCOPE, hc.RESCORE_SCOPE} <= set(
        table[mods[0]].values())


def test_profile_without_hlo_reads_empty(tmp_path):
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    assert profile.profile_layers(str(tmp_path)) == {}
    with pytest.raises(RuntimeError):
        profile.profile_layers(str(tmp_path / "missing"))
