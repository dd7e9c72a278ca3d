"""Placement of JAX's persistent compilation cache by the entry points."""

import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_environment_wins_and_nothing_is_set(monkeypatch,
                                             restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.place_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_directory(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    placed = compile_cache.place_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert placed == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == placed
    # a fixed path: placing again gives the same directory
    assert compile_cache.place_compile_cache() == placed


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENTRY_POINTS = sorted(
    [os.path.join("benchmarks", f)
     for f in os.listdir(os.path.join(ROOT, "benchmarks"))
     if f.startswith(("bench_", "run")) and f.endswith(".py")]
    + ["chip_smoke.py", os.path.join("src", "repro", "launch", "serve.py")])
SCRIPTS = sorted(os.path.join(d, f) for d in ("benchmarks", "examples")
                 for f in os.listdir(os.path.join(ROOT, d))
                 if f.endswith(".py"))


@pytest.mark.parametrize("path", SCRIPTS)
def test_script_compiles(path):
    """Every benchmark and example script is valid Python, helpers
    included: no test imports them, so a syntax error would otherwise
    surface only when the script is run."""
    with open(os.path.join(ROOT, path)) as f:
        compile(f.read(), path, "exec")


@pytest.mark.parametrize("path", ENTRY_POINTS)
def test_entry_point_compiles_and_places_the_cache(path):
    """Every entry point is valid Python and places the cache.  No test
    imports the benchmark mains, so a syntax error in one would
    otherwise go unseen until it is run."""
    with open(os.path.join(ROOT, path)) as f:
        source = f.read()
    compile(source, path, "exec")
    assert "place_compile_cache()" in source
