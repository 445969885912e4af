"""``repro.compile_cache``: where a script's persistent compile cache
lives — the directory ``JAX_COMPILATION_CACHE_DIR`` names, else one
fixed directory in the checkout — and that importing ``repro`` turns
no cache on."""
import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_set(monkeypatch, tmp_path,
                                         restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable("/some/checkout") == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_dir_in_checkout(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(str(tmp_path), ".jax_cache")
    assert compile_cache.enable(str(tmp_path)) == want
    assert compile_cache.enable(str(tmp_path)) == want      # same path
    assert jax.config.jax_compilation_cache_dir == want


def test_fixed_dir_is_gitignored():
    root = os.path.dirname(SRC)
    with open(os.path.join(root, ".gitignore")) as fh:
        ignored = {line.strip().strip("/") for line in fh}
    assert compile_cache.DIRNAME in ignored


def test_import_sets_no_cache():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("import jax, repro, repro.api, repro.serve; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"
