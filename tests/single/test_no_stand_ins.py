"""The program runs what it was asked to run or says why not: no silent
pure-Python core, no stale library, no cache directory of its own making."""

import os
import subprocess

import pytest

from horovod_tpu import _core, context
from horovod_tpu.exceptions import HorovodInternalError
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.env import Config


def test_select_backend_raises_when_native_core_is_missing(monkeypatch):
    def broken():
        raise OSError("libhvd_tpu_core.so: cannot open shared object file")

    monkeypatch.setattr(_core, "NativeCore", broken)
    with pytest.raises(HorovodInternalError, match="native core unavailable"):
        context._select_backend(Config.from_env())


def test_select_backend_pure_python_only_when_asked(monkeypatch):
    monkeypatch.setattr(_core, "NativeCore", lambda: pytest.fail(
        "asked for the pure-Python core, got the native one"))
    monkeypatch.setenv("HVD_TPU_PURE_PY", "1")
    core = context._select_backend(Config.from_env())
    assert isinstance(core, context.PyLocalCore)


def test_failed_native_build_is_an_error_even_with_an_old_library(
        monkeypatch):
    def failing_make(cmd, **kw):
        raise subprocess.CalledProcessError(2, cmd, stderr=b"core_api.cc: no")

    assert os.path.exists(_core._LIB_PATH)  # an older library is lying around
    monkeypatch.setattr(_core, "_lib", None)
    monkeypatch.setattr(_core.subprocess, "run", failing_make)
    with pytest.raises(RuntimeError, match="native core build failed"):
        _core._load_library()


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "given"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "given")
    # Nothing set in code: jax reads the variable itself.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert compile_cache.enable_compile_cache() == path  # never moves
        assert os.environ[compile_cache.ENV_VAR] == path  # workers inherit
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        os.environ.pop(compile_cache.ENV_VAR, None)
        jax.config.update("jax_compilation_cache_dir", before)
