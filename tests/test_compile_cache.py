"""The persistent compilation cache lives at one fixed place."""

import jax

from repro.runtime import compile_cache


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_the_repo_jax_cache(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = compile_cache.REPO_CACHE_DIR.parent
    assert (root / "chip_smoke.py").is_file()
    assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", str(root / ".jax_cache"))]
