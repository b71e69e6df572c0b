"""Entry-point start-up: the device check and the compile-cache path."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import device


@pytest.fixture
def own_cache(monkeypatch, tmp_path):
    """Point the default cache path at ``tmp_path``, with the variable
    unset, and put the process's cache back afterwards, so no test
    leaves later compiles writing into the checkout."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / ".jax_cache")
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_require_tpu_fails_on_another_backend(own_cache):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(RuntimeError, match="no TPU found"):
        device.init_device(require="tpu")


def test_stamp_names_the_device(own_cache):
    stamp = device.init_device()
    d = jax.devices()[0]
    assert stamp == {"platform": d.platform, "kind": d.device_kind,
                     "count": len(jax.devices())}


def test_cache_dir_is_fixed_inside_the_checkout():
    assert device.CACHE_DIR.name == ".jax_cache"
    assert device.CACHE_DIR.parent.joinpath("chip_smoke.py").exists()


def test_cache_defaults_to_the_fixed_dir(own_cache):
    device.init_device()
    assert jax.config.jax_compilation_cache_dir == str(device.CACHE_DIR)


def test_cache_env_is_left_alone(monkeypatch, own_cache):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    jax.config.update("jax_compilation_cache_dir", "/elsewhere")
    device.init_device()
    assert jax.config.jax_compilation_cache_dir == "/elsewhere"
