"""Where the persistent compilation cache goes: utils/compile_cache.py."""

import os
import subprocess
import sys

import jax

from distriflow_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_variable_set_means_no_directory_is_set_in_code(
        monkeypatch, tmp_path):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "elsewhere"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "elsewhere")
    assert calls == []
    assert not (tmp_path / "elsewhere").exists()  # jax makes it, not us


def test_unset_means_one_fixed_directory_in_the_checkout(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_compile_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want  # twice: same place
    assert calls == [("jax_compilation_cache_dir", want)] * 2
    # another process, another working directory: the same path
    code = ("import importlib.util as u; s = u.spec_from_file_location("
            f"'cc', {compile_cache.__file__!r}); m = u.module_from_spec(s); "
            "s.loader.exec_module(m); print(m.CACHE_DIR)")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd="/", capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == want, out.stderr


def test_cache_directory_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()
