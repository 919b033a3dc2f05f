"""Test harness bootstrap: an 8-device CPU fake mesh.

This is the multi-chip CI story from SURVEY.md §4 — the reference cannot
simulate multi-node without hardware; JAX can
(``--xla_force_host_platform_device_count``), so every sharding/collective
test runs against a real 8-way mesh on CPU. Must run before any backend
initialisation."""

import os

from accelerate_tpu.utils.environment import force_host_platform

force_host_platform(8)

# Persistent XLA compilation cache: the suite's wall-clock is dominated by
# 8-device fake-mesh compiles, which are identical run to run. It lives
# where the one helper puts it (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.cache/jax); exporting that directory lets subprocess-launched
# scripts (CLI/examples tests) share it.
from accelerate_tpu.aot import configure_persistent_cache

# The package is not installed: subprocess-launched CLI tests that run with
# ``cwd`` outside the checkout must still find it.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["PYTHONPATH"] = os.pathsep.join(filter(None, [_CHECKOUT, os.environ.get("PYTHONPATH")]))

os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ["JAX_COMPILATION_CACHE_DIR"] = configure_persistent_cache(min_compile_time_secs=0.5)

import pytest


@pytest.fixture(autouse=True, scope="module")
def bound_live_executables():
    """Drop jit caches after every test module. With the whole suite's
    executables held live, XLA:CPU's compiler segfaults on a fresh
    compile late in the run (reproduced at ~570 live programs; either
    half of the suite — ~290 — is fine, and no single file triggers
    it). Clearing per module bounds the live set to one file's worth;
    cross-module recompiles hit the persistent disk cache, so the
    wall-clock cost is small."""
    yield
    import jax

    jax.clear_caches()


@pytest.fixture(autouse=True)
def reset_singletons():
    """Reset borg singletons between tests (reference analogue:
    AccelerateTestCase.tearDown, test_utils/testing.py:639-651)."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


@pytest.fixture
def no_persistent_compile_cache():
    """Disable jax's persistent compilation cache for one test.

    XLA:CPU's restore-from-disk-cache can hand back a non-self-contained
    executable (the PR-7 bug class): a step that carries state (overflow
    latches, error-feedback residuals) can be poisoned to NaN by it, and
    an executable serialized again from it into an ``ExecutableStore``
    loses functions. Tests of such semantics run against the freshly
    compiled executable; the disk cache is a wall-clock optimisation,
    not part of the contract."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # an initialised cache outlives the flag
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def mesh8():
    from accelerate_tpu.parallel.mesh import MeshConfig

    return MeshConfig(data=8).build()
