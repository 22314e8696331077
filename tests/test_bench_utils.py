"""Roofline / repeat-spread bench helpers.

The peaks behind every roofline field come from one table keyed by the
``device_kind`` jax reports; a device that is not in it has no roofline
(an error, never another chip's numbers).  The helper contracts are
pinned here on the CPU — cost analysis works on any backend, and the
table lookup is given the chip's kind by the test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.benchmarks.hgcn_bench import (
    DEVICE_PEAKS,
    device_peaks,
    roofline_fields,
    spread,
    step_cost,
    time_steps_all,
)


def _stepper(st):
    return st @ st, jnp.sum(st)


def test_step_cost_reports_flops_and_bounds(monkeypatch):
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(DEVICE_PEAKS, kind, DEVICE_PEAKS["TPU v5 lite"])
    c = step_cost(_stepper, jnp.ones((128, 128), jnp.float32))
    # one 128^3 matmul fwd: flops >= 2*128^3; bytes >= the operand reads
    assert c["flops_per_step"] >= 2 * 128**3
    assert c["bytes_per_step"] >= 128 * 128 * 4
    assert c["hbm_bound_ms"] > 0
    np.testing.assert_allclose(
        c["hbm_bound_ms"],
        round(c["bytes_per_step"] / 819e9 * 1e3, 6))
    # the artifact names the device the bounds are for and where its
    # peaks were published
    assert c["device_kind"] == kind
    assert c["peaks_source"] == DEVICE_PEAKS["TPU v5 lite"]["source"]


def test_roofline_fields_fraction_and_bound():
    cost = {"flops_per_step": 1e9, "bytes_per_step": 8.19e6,
            "hbm_bound_ms": 0.01, "mxu_bound_ms": 0.005}
    r = roofline_fields(cost, 1e-3)          # measured 1 ms step
    assert r["frac_hbm_roofline"] == 0.01    # 0.01 ms bound / 1 ms step
    assert r["bound"] == "hbm"
    r2 = roofline_fields({**cost, "mxu_bound_ms": 0.02}, 1e-3)
    assert r2["bound"] == "mxu"


def test_unknown_device_kind_has_no_roofline():
    """The suite runs on the CPU, which has no entry: the lookup raises
    and names the table — the step is never priced against a chip it
    did not run on — and a failing step is not swallowed either."""
    assert jax.devices()[0].device_kind not in DEVICE_PEAKS
    with pytest.raises(KeyError, match="DEVICE_PEAKS"):
        step_cost(_stepper, jnp.ones((8, 8), jnp.float32))
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("TPU v99")
    assert device_peaks("TPU v5 lite")["source"]


def test_time_steps_all_and_spread():
    times, st, loss = time_steps_all(_stepper, jnp.ones((16, 16)), 2, 3)
    assert len(times) == 3 and all(t > 0 for t in times)
    assert spread(times) >= 1.0
    assert spread([2.0, 1.0]) == 2.0
