"""Where the persistent compilation cache goes: ``JAX_COMPILATION_CACHE_DIR``
when set, else one fixed directory inside the checkout."""
from pathlib import Path

from repro.launch import cache


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)
    # JAX reads the variable itself: the helper sets nothing
    assert cache.enable_compile_cache() == str(tmp_path)


def test_default_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = Path(cache.compile_cache_dir())
    assert path == cache.REPO_ROOT / ".jax_cache"
    assert (cache.REPO_ROOT / "chip_smoke.py").exists()  # the checkout's root
    ignored = (cache.REPO_ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
