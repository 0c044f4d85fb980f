"""Placement of JAX's persistent compilation cache (``repro.cache``)."""

from pathlib import Path

import jax
import pytest

from repro import cache as cache_mod
# the real helper: conftest stubs the module attribute for every test
from repro.cache import configure_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_placement_sets_nothing(monkeypatch, cache_dir_config):
    monkeypatch.setenv(cache_mod.ENV, "/somewhere/else")
    assert configure_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == cache_dir_config


def test_default_placement_is_one_fixed_ignored_path(monkeypatch,
                                                     cache_dir_config):
    monkeypatch.delenv(cache_mod.ENV, raising=False)
    first = configure_compile_cache()
    assert first == configure_compile_cache() == str(ROOT / ".cache" / "jax")
    assert jax.config.jax_compilation_cache_dir == first
    assert ".cache/" in (ROOT / ".gitignore").read_text().split()
    # autotune winners persist beside the compiled programs
    assert cache_mod.AUTOTUNE_CACHE_PATH.parent == Path(first).parent
