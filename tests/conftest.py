"""Shared fixtures for the test suite.

XLA compile time dominates the tier-1 suite's wall clock (most programs
are solver bodies recompiled identically on every run), so the JAX
persistent compilation cache is enabled before anything imports jax: a
warm cache turns each compile into a disk reload.  CI persists the cache
directory across runs (actions/cache on ``JAX_COMPILATION_CACHE_DIR``);
otherwise it is the program's own in-checkout default
(``repro.compile_cache``, ``<checkout>/.jax_cache``).  Set
``JAX_COMPILATION_CACHE_DIR=""`` to disable.
"""
import os

from repro.compile_cache import CHECKOUT_CACHE

# Must happen before jax is imported anywhere (jax reads the env at setup).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CHECKOUT_CACHE))
# Small solver programs compile in well under the 1s default threshold;
# cache them too -- the suite compiles hundreds of them.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import pytest  # noqa: E402

# XLA CPU on this jaxlib SIGABRTs while serializing the sharded LM
# train-step executable into the persistent cache (mapping-solver
# programs — the bulk of suite compile time — serialize fine), so the
# cache is switched off around the LM-stack modules.
_NO_CACHE_MODULES = {"test_system", "test_train"}


@pytest.fixture(autouse=True, scope="module")
def _persistent_cache_off_for_lm_stack(request):
    if request.module.__name__.split(".")[-1] not in _NO_CACHE_MODULES:
        yield
        return
    import jax
    from jax._src import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def fresh_placement_engine():
    """The launcher's default PlacementService is a shared singleton;
    reset it around every test so one test's LRU cache, warm-start state,
    or stats can never leak into another (and a started flusher thread
    never outlives its test)."""
    from repro.launch import placement
    placement.reset_default_service()
    yield
    placement.reset_default_service()
