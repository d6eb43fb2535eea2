"""Shared test configuration: deterministic hypothesis profiles, and
compiled programs released after each test module.

Hypothesis is an optional dev dependency (requirements-dev.txt): the suite
must import cleanly without it (property tests guard with
``pytest.importorskip``), so profile registration sits in a try/except.

Profiles:

* ``dev`` (default) — hypothesis defaults minus the deadline (jit
  compilation makes first examples orders of magnitude slower than the
  rest, so wall-clock deadlines only produce flaky failures).
* ``ci`` — what the workflow selects via ``HYPOTHESIS_PROFILE=ci``:
  derandomized (the same example sequence on every run, so a red CI lane
  is reproducible locally by exporting the same variable) with a bounded
  example count to keep the tier-1 lane fast.
"""

import os

import jax
import pytest

try:
    from hypothesis import HealthCheck, settings

    settings.register_profile("dev", deadline=None)
    settings.register_profile(
        "ci", deadline=None, derandomize=True, max_examples=25,
        suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:  # pragma: no cover - exercised only without dev deps
    pass


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop JAX's compiled programs when a test module ends.

    Every XLA:CPU executable holds a few memory mappings of its own, and
    the suite compiles thousands of programs in one process: kept until
    exit they pass the kernel's per-process limit (``vm.max_map_count``,
    65530 by default) and the next compile dies with SIGSEGV.  Programs
    are still shared by the tests of one module.
    """
    yield
    jax.clear_caches()
