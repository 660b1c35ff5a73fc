"""chip_smoke.py rehearsed on the CPU: `--tiny` drives every phase at toy
sizes in this process; without `--tiny` the script refuses to run on a
backend that is not a TPU, and prints no result."""

import importlib.util
import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # its dataclass resolves the module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def keep_cache_config():
    """chip_smoke turns on the persistent compile cache; put it back."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_tiny_rehearsal_ends_with_device_line(capsys, keep_cache_config):
    _chip_smoke().main(["--tiny"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert any("parity full" in ln and "bit-identical" in ln for ln in lines)
    assert any("float32 brute force" in ln for ln in lines)
    dev = jax.devices()[0]
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": len(jax.devices())}}


def test_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        _chip_smoke().main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_honours_env_else_checkout(monkeypatch,
                                                 keep_cache_config):
    from repro.launch import cache
    monkeypatch.setenv(cache.CACHE_ENV, "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(cache.CACHE_ENV)
    path = cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
