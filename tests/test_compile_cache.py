"""The compile-cache helper: ``JAX_COMPILATION_CACHE_DIR`` when set, else one
fixed in-checkout path, the same across calls and processes."""
import os
import subprocess
import sys

from repro.launch.compile_cache import CHECKOUT, compile_cache_dir

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def test_honours_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache_dir()
    assert first == str(CHECKOUT / ".jax_cache") == compile_cache_dir()
    assert os.path.isfile(os.path.join(CHECKOUT, "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = _SRC
    out = subprocess.run(
        [sys.executable, "-c", "from repro.launch.compile_cache import "
         "compile_cache_dir; print(compile_cache_dir())"],
        env=env, capture_output=True, text=True, timeout=60, check=True)
    assert out.stdout.strip() == first
