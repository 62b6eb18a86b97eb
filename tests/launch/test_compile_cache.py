"""The persistent compilation cache is set up in one place: the directory
``JAX_COMPILATION_CACHE_DIR`` names, or else one fixed, git-ignored path
inside the checkout."""
import os

import jax

from repro.launch import compile_cache


def _updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    calls = _updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_and_ignored_by_git(monkeypatch):
    calls = _updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.enable_compile_cache() == path
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert os.path.dirname(path) == root
    with open(os.path.join(root, ".gitignore")) as f:
        assert os.path.basename(path) + "/" in f.read().split()
