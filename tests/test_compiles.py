"""The shared compile counter and the compile-cache placement rule
(``repro.launch.compiles``)."""
import os

import jax
import jax.numpy as jnp

from repro.launch.compiles import compile_count, use_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_count_counts_backend_compiles():
    @jax.jit
    def _compile_probe(x):
        return x * 2 + 1

    total0, fn0 = compile_count(), compile_count(_compile_probe)
    _compile_probe(jnp.zeros(3))
    _compile_probe(jnp.ones(3))             # same shape: no second compile
    assert compile_count(_compile_probe) - fn0 == 1
    _compile_probe(jnp.zeros(5))            # a new shape compiles again
    assert compile_count(_compile_probe) - fn0 == 2
    assert compile_count("_compile_probe") - fn0 == 2
    assert compile_count() - total0 >= 2


def test_compile_cache_goes_where_the_environment_says(monkeypatch,
                                                       tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert calls == []                      # no second directory is set


def test_compile_cache_default_is_one_ignored_checkout_dir(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = os.path.join(ROOT, ".jax_cache")
    assert use_compile_cache() == path
    assert use_compile_cache() == path      # fixed: the path is in the key
    assert calls == [("jax_compilation_cache_dir", path)] * 2
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
