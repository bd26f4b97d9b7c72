"""What the program reads off the platform: spec sheet, memory kinds,
kernel path, compile cache — each a strict lookup with no silent default."""

import types

import jax
import pytest

from repro.api import SPEC_SYSTEM
from repro.core.hardware import system_for_device
from repro.core.placement import available_memory_kinds, resolve_memory_kind
from repro.kernels import ops
from repro.launch import compile_cache


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


class TestSystemForDevice:
    def test_v5e_kind_gets_the_v5e_sheet(self):
        assert system_for_device(_device("tpu", "TPU v5 lite")) is SPEC_SYSTEM

    def test_unknown_tpu_kind_raises(self):
        with pytest.raises(ValueError, match="TPU v7x"):
            system_for_device(_device("tpu", "TPU v7x"))

    def test_host_platform_models_the_v5e(self):
        assert system_for_device(jax.devices()[0]) is SPEC_SYSTEM


class TestMemoryKinds:
    def test_exposed_kinds_resolve_to_themselves(self):
        for kind in available_memory_kinds():
            assert resolve_memory_kind(kind) == kind
        assert resolve_memory_kind(None) is None

    def test_missing_kind_raises(self):
        with pytest.raises(ValueError, match="not exposed"):
            resolve_memory_kind("no_such_memory")


def test_kernel_path_follows_the_platform():
    want = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert ops._resolve(None) == want
    assert ops._resolve("pallas") == "pallas"
    assert ops._resolve("ref") == "ref"


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_environment_directory_is_honoured(
        self, monkeypatch, restore_cache_dir
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_path_in_the_checkout(
        self, monkeypatch, restore_cache_dir
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert path.endswith("/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
