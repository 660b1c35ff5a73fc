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
