"""Test configuration: CPU backend with 8 virtual devices (SURVEY.md §4.6).

Tests never require TPU hardware: manifold math runs in float64 on CPU,
Pallas kernels run in interpret mode, and distributed code runs on the
8 fake CPU devices created here.  Must run before the first jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: most suite wall-time is XLA CPU compiles,
# which are identical run to run.  First (cold) run pays full price and
# populates the cache; warm reruns — the common CI/dev loop — skip them.
# Where JAX_COMPILATION_CACHE_DIR is set, jax already holds that
# directory and no other is set here.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)

# Hermetic tile sizing: the checked-in autotune table
# (configs/scan_topk_tiles.json) is tuned for device_kind "cpu" — the
# very backend the suite runs on — so without this, checking in a
# re-tuned table would silently change every engine's chunk sizing
# under test.  Tile choice is result-invisible (tested), but sizing
# assertions must see the static model; tests that exercise tuned
# lookups monkeypatch this env var to their own table.
os.environ.setdefault("HYPERSPACE_AUTOTUNE_TABLE", "0")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The process's start-up timeline (telemetry/trace.py) is open from the
# package's import until the process's first run_loop dispatch: left
# open, whichever test first ran a loop would write the one `startup`
# event and every span() before it would record.  Closed here, every
# test sees the closed path; tests/telemetry/test_startup.py opens
# timelines of its own.
from hyperspace_tpu.telemetry import trace as _trace  # noqa: E402

_trace.close_startup()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_runtest_protocol(item, nextitem):
    """Strict single rerun for ``@pytest.mark.flaky`` tests.

    A test carrying the marker gets ONE retry when its first attempt
    fails (fresh setup/teardown both times); only the final attempt is
    reported.  Two consecutive failures fail the run exactly like an
    unmarked test — the marker absorbs a known stochastic threshold
    (e.g. the sampled-LP AUC-improvement assertion), it does not hide a
    real regression, which fails twice in a row.  Markers must cite the
    flake they cover in a comment at the use site."""
    if item.get_closest_marker("flaky") is None:
        return None
    from _pytest.runner import runtestprotocol

    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for report in reports:
        item.ihook.pytest_runtest_logreport(report=report)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True
