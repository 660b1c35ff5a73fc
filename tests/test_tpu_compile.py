"""Every Pallas kernel of the served path compiles for a described TPU v5e.

Nothing runs here: each kernel is lowered with `interpret=False` and
compiled by the TPU compiler for a chip that is described, not attached,
at the widths the service uses (Omniglot geometry: d=48 -> 4d=192 LUT
columns, MTMC CL=32 -> S=64 strings of sl=24 cells). Mosaic refuses what
interpret mode accepts -- unaligned dynamic slices, unsupported casts,
blocks over the VMEM budget -- so this guards every later change at no chip
time. The topology is described inside a fixture (never at import), and
all the compiles stay in this one file: only one process may load the TPU
compiler library at a time.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.mcam import MCAMConfig
from repro.kernels import mcam_dist, mcam_search, shortlist

B, N, K4D, TOPK = 64, 65536, 192, 32
S, SL = 64, 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler library here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # entries compiled for a described chip cannot be read back without it
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("form", ["bf16", "masked", "packed8", "packed16"])
def test_shortlist_kernel_compiles(one_chip, form):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    valid = sds((N,), jnp.bool_)
    if form.startswith("packed"):
        bits = int(form[len("packed"):])
        dp = -(-K4D // (32 // bits))
        fn = lambda q, p, v: shortlist.lut_shortlist_pallas(
            q, None, TOPK, valid=v, packed=p, pack_bits=bits,
            interpret=False)
        args = (sds((B, K4D), jnp.float32), sds((N, dp), jnp.int32), valid)
    else:
        fn = lambda q, s, *v: shortlist.lut_shortlist_pallas(
            q, s, TOPK, valid=v[0] if v else None, interpret=False)
        args = (sds((B, K4D), jnp.bfloat16), sds((N, K4D), jnp.bfloat16))
        if form == "masked":
            args += (valid,)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_lut_dist_matmul_compiles(one_chip):
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    fn = lambda q, s: mcam_dist.lut_dist_matmul(q, s, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, sds((B, 512)),
                                               sds((N, 512)))


@pytest.mark.parametrize("noisy", [True, False])
def test_mcam_search_kernel_compiles(one_chip, noisy):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = MCAMConfig()
    fn = lambda q, s, w, th: mcam_search.mcam_search_pallas(
        q, s, w, th, cfg, noisy=noisy, interpret=False)
    text = _compiled_text(fn, sds((B, S, SL), jnp.int8),
                          sds((N, S, SL), jnp.int8), sds((S,), jnp.float32),
                          sds((cfg.n_thresholds,), jnp.float32))
    assert "tpu_custom_call" in text


def _two_phase_search_text(one_chip, n: int = N, dim: int = K4D // 4,
                           cl: int = 32, batch: int = B) -> str:
    """The served two_phase search at real widths (k=32 over `n` rows of
    `dim` values, MTMC `cl`, `batch` queries), compiled for the chip."""
    import dataclasses

    from repro.core.avss import SearchConfig
    from repro.core.memory import MemoryConfig
    from repro.engine import MemoryStore, RetrievalEngine, SearchRequest

    cfg = SearchConfig("mtmc", cl=cl, mode="avss", mcam=MCAMConfig(
        string_len=SL, rho=8.0, sigma_device=0.12, sigma_read=0.04,
        n_thresholds=8))
    shapes = dataclasses.replace(jax.eval_shape(lambda: MemoryStore.create(
        MemoryConfig(capacity=n, dim=dim, search=cfg))),
        calibrated=True)
    store = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    q = jax.ShapeDtypeStruct((batch, dim), jnp.float32, sharding=one_chip)
    eng = RetrievalEngine(cfg)

    def search(st, q):
        res = eng.search(st, q, SearchRequest(mode="two_phase", k=TOPK))
        return res, res.predict()
    with pytest.MonkeyPatch.context() as mp:
        # the kernels take their compiled branch for the described chip
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _compiled_text(search, store, q)


# (rows, dim, MTMC CL, batch) of each benchmark cell's store search: the
# omniglot cell's 1,572,864 rows of d=48 at CL=32, B=64, and the kNN-LM
# decode cell's 65,536 rows of d=3,072 at CL=8, one query per sequence of
# its batch of 16
CELL_GEOMETRIES = {"omniglot-1536K": (1572864, 48, 32, 64),
                   "starcoder2-3b-knnlm": (65536, 3072, 8, 16)}
# an instruction's name and the result type before its opcode
_RESULT = re.compile(
    r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s[a-z][\w\-]*\(")
_DIMS = re.compile(r"\w+\[([\d,]*)\]")


def _result_rows(text: str) -> dict[str, set[int]]:
    """{instruction: leading dims of the arrays it yields} of HLO text."""
    rows = {}
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m:
            rows[m.group(1)] = {int(d.split(",")[0])
                                for d in _DIMS.findall(m.group(2)) if d}
    return rows


@pytest.mark.parametrize("cell", sorted(CELL_GEOMETRIES))
def test_two_phase_search_ops_map_to_layers(one_chip, cell):
    """The served two_phase search at each cell's store geometry, compiled
    for the chip: its (B, N) distance block is under the dense budget, so
    phase 1 is XLA's dot and exact top-k with no Pallas kernel at all;
    every op that does work, the copies the TPU compiler inserts among
    them, maps to a layer of the program (analysis.hlo_contracts.op_layers),
    and no op of the rescore yields N rows: it gathers the B*k candidate
    rows from `s_grid` as stored, and relayouts only those."""
    from repro.analysis import hlo_contracts as hc

    n, dim, cl, batch = CELL_GEOMETRIES[cell]
    text = _two_phase_search_text(one_chip, n=n, dim=dim, cl=cl, batch=batch)
    assert hc.scoped_lines(text, hc.FUSED_SCOPE_TAG) == []
    assert "tpu_custom_call" not in text
    _, ops = hc.parse_hlo(text)
    phase1 = [o for o in ops
              if hc.innermost_layer(o.op_name) == hc.SHORTLIST_SCOPE]
    assert any(o.opcode in ("dot", "convolution") for o in phase1)
    assert any(o.op_name.endswith("/top_k") for o in phase1)
    layers = hc.op_layers(text)
    opcode = {o.name: o.opcode for o in ops}
    assert [name for name, layer in layers.items()
            if layer is None and opcode[name] != "parameter"] == []
    assert {hc.QUANTIZE_SCOPE, hc.SHORTLIST_SCOPE, hc.RESCORE_SCOPE,
            hc.ANSWER_SCOPE} == set(layers.values()) - {None}
    rows = _result_rows(text)
    assert [name for name, layer in layers.items()
            if layer == hc.RESCORE_SCOPE and n in rows[name]
            and opcode[name] not in ("parameter", "bitcast")] == []


def test_two_phase_search_over_budget_compiles_fused_kernel(one_chip):
    """The same search with its (B, N) block over the dense budget (the
    budget cut to just under B * N * 4 bytes) compiles the fused Pallas
    shortlist kernel into phase 1."""
    from repro.analysis import hlo_contracts as hc
    from repro.engine import sharded

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sharded, "SHORTLIST_DENSE_MAX_BYTES", B * N * 4 - 1)
        text = _two_phase_search_text(one_chip)
    fused = hc.scoped_lines(text, hc.FUSED_SCOPE_TAG)
    assert fused and all(hc.SHORTLIST_SCOPE in line for line in fused)
    assert any("tpu_custom_call" in line for line in fused)
