"""Fixtures shared by the test suites under this directory (``tests/``
and the benchmark's own ``bench/tests/``)."""
import sys

import pytest


@pytest.fixture(autouse=True)
def _fresh_sweep_cache():
    """Start every test with an empty sweep program cache.

    ``simulate_sweep`` caches its jitted program per process, and a cache
    hit traces nothing. So a test that counts ``runner._TRACE_LOG``
    entries, or that patches the engine before a sweep, must not reuse a
    program an earlier test in the same process built. Only clears a
    runner that is already imported: tests that never load JAX stay free
    of it.
    """
    runner = sys.modules.get("repro.experiments.runner")
    if runner is not None:
        runner.clear_sweep_cache()
    yield
