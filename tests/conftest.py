"""Shared fixtures.  NOTE: no XLA_FLAGS here by design — tests must see the
real single CPU device; only launch/dryrun.py forces 512 placeholders."""

import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    """The launchers place JAX's persistent compilation cache inside the
    checkout; tests that call them must not turn it on for the rest of
    their worker process (``tests/test_cache.py`` tests the helper)."""
    from repro import cache as cache_mod

    monkeypatch.setattr(cache_mod, "configure_compile_cache",
                        lambda: str(cache_mod.COMPILE_CACHE_DIR))


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
