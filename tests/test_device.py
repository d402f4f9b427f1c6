"""What the process runs on, stated once (``utils/device.py``): the refused
CPU fallback, the chip table, the compile cache's directory."""

import jax
import pytest

from distributed_gpu_inference_tpu.utils import device


def test_chip_spec_knows_the_published_chips_only():
    assert device.chip_spec("TPU v5 lite").chip_type == "v5e"
    assert device.chip_spec("v5litepod-16").chip_type == "v5e"
    assert device.chip_spec("TPU v5p").chip_type == "v5p"
    assert device.chip_spec("TPU v4").chip_type == "v4"
    assert device.chip_spec("TPU v6 lite").chip_type == "v6e"
    # an unknown chip has no figures — never another chip's
    assert device.chip_spec("TPU v9 hyper") is None
    assert device.chip_spec("cpu") is None


def test_require_backend_accepts_the_cpu_only_when_asked():
    # the suite asks for the CPU (tests/conftest.py)
    asked = jax.config.jax_platforms
    assert "cpu" in asked
    assert device.require_backend() == "cpu"
    # the same backend when nobody asked: JAX's silent fallback
    jax.config.update("jax_platforms", "")
    try:
        with pytest.raises(device.NoAcceleratorError):
            device.require_backend()
    finally:
        jax.config.update("jax_platforms", asked)


def test_compile_cache_directory_comes_from_outside(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.enable_compile_cache() == str(tmp_path)
    # the variable is the directory: no code path sets another
    assert jax.config.jax_compilation_cache_dir == before
