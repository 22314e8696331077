"""Fixtures of the benchmark's CPU tests."""

import pytest


@pytest.fixture
def restore_compile_cache():
    """The harness turns the program's compile cache on (as a run on the
    chip needs); give the suite's own cache directory back afterwards."""
    yield
    from hyperspace_tpu import compile_cache

    compile_cache.deactivate()
