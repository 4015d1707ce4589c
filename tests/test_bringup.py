"""Bring-up guards (ISSUE 21): no entry point lets a run without the chip
look like a pass, and the compile cache lives where the outside says.

All CPU, all well under a second: the chip side of these contracts is
``python chip_smoke.py`` on the chip."""

import importlib.util
import os
import re

import jax
import pytest

from dotaclient_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_root_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def jax_cache_config():
    """Hand the jax settings the helper touches back as found."""
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_include_metadata_in_key",
    )
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


class TestCompileCache:
    def test_env_var_is_the_outside_handle(
        self, monkeypatch, tmp_path, jax_cache_config
    ):
        """With JAX_COMPILATION_CACHE_DIR set the helper sets NO directory:
        whatever jax holds (it read the variable itself at import) stays."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "outside"))
        jax.config.update("jax_compilation_cache_dir", "as-jax-had-it")
        assert compile_cache.enable() == "as-jax-had-it"
        assert jax.config.jax_compilation_cache_dir == "as-jax-had-it"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        # scope names are part of an entry's key: a trace never names
        # operations as a program compiled before a scope was added did
        assert jax.config.jax_compilation_cache_include_metadata_in_key

    def test_unset_is_one_fixed_in_checkout_path(
        self, monkeypatch, jax_cache_config
    ):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        first = compile_cache.enable()
        second = compile_cache.enable()
        assert first == second == os.path.join(ROOT, ".jax_cache")
        # no pid, temp name or clock in it: a directory that moves never hits
        assert str(os.getpid()) not in first
        assert not re.search(r"tmp|temp|\d{4,}", os.path.relpath(first, ROOT))

    def test_cpu_backend_left_as_found(self, monkeypatch, jax_cache_config):
        """The suite itself runs here: nothing may start writing a cache
        into the checkout on the CPU."""
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        assert jax.default_backend() == "cpu"
        assert compile_cache.enable() is None
        assert jax.config.jax_compilation_cache_dir is None


class TestNoHiddenCpuPath:
    def test_chip_smoke_rejects_a_cpu_device_list(self, capsys):
        smoke = _load_root_script("chip_smoke")
        with pytest.raises(SystemExit) as exc:
            smoke.require_tpu(jax.devices())
        assert exc.value.code not in (0, None)
        assert "platform='cpu'" in str(exc.value.code)
        assert capsys.readouterr().out == ""   # no result line, nothing
