"""The pre-jax environment helpers: where JAX's compile cache goes."""
import os
import subprocess
import sys
from pathlib import Path

from repro import hostenv

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = hostenv.use_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == got
    # fixed: a second call (another process of the same checkout) agrees
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert hostenv.use_compile_cache() == got


def test_compile_cache_honours_outside_setting(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert hostenv.use_compile_cache() == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


def test_hostenv_imports_no_jax():
    code = ("import sys; import repro.hostenv; "
            "assert 'jax' not in sys.modules, 'hostenv imported jax'")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True)
