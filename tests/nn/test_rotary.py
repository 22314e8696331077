"""Rotary tables (``nn/layers.py``): YaRN's frequencies against the
formula written out, a partial rotary that turns the head's first lanes
and leaves the rest, and the plain full-width tables the looped model
uses, to the bit as they were before YaRN came."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.nn.layers import apply_rotary, rotary_tables, yarn_inv_freq

# Laguna-S-2.1's full layers: theta, factor, original length, beta_fast,
# beta_slow, attention_factor; and Laguna-XS.2's, whose ramp differs
YARN = [(5e5, 128.0, 8192, 32.0, 1.0, 1.4852030263919618),
        (5e5, 64.0, 4096, 64.0, 1.0, 1.4158883083359672)]


def _yarn_written_out(dim, theta, factor, original, fast, slow):
    """YaRN (arXiv:2309.00071, section 3.2 with the transformers
    library's truncated correction range), in float64 numpy: pair i turns
    r_i = original / (2 pi theta^(2i/dim)) times over the original
    length; pairs with r_i > fast keep theta's frequency, pairs with
    r_i < slow take it over factor, a linear ramp in i between."""
    def pair_at(turns):   # the i where r_i = turns
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = max(math.floor(pair_at(fast)), 0), min(
        math.ceil(pair_at(slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    base = theta ** (-2 * i / dim)
    blend = np.clip((i - low) / (high - low), 0, 1)   # 0 keep, 1 divide
    return base * (1 - blend) + base / factor * blend


@pytest.mark.parametrize("theta,factor,original,fast,slow,attn", YARN)
def test_yarn_frequencies_are_the_formula(theta, factor, original, fast,
                                          slow, attn):
    got = np.asarray(yarn_inv_freq(64, theta, factor, original, fast, slow))
    want = _yarn_written_out(64, theta, factor, original, fast, slow)
    # float32 powers of theta against float64 ones
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the fastest pairs keep theta's frequency, the slowest are divided
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(theta ** (-62 / 64) / factor, rel=1e-5)
    cos, sin = rotary_tables(300, 128, theta, rotary_dim=64,
                             yarn=(factor, original, fast, slow, attn))
    assert cos.shape == (300, 1, 64)
    ang = np.arange(300)[:, None] * want[None, :]
    # a float32 angle of up to 300 rad is off by up to ~3e-5 rad
    np.testing.assert_allclose(cos[:, 0, :32], attn * np.cos(ang),
                               atol=1e-4)
    np.testing.assert_allclose(sin[:, 0, 32:], attn * np.sin(ang),
                               atol=1e-4)


def test_a_partial_rotary_turns_the_first_lanes_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 3, 128), jnp.float32)
    cos, sin = rotary_tables(50, 128, 1e4, rotary_dim=64)
    y = apply_rotary(x, cos, sin)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    # the first 64 lanes as a head of 64 is turned whole
    np.testing.assert_array_equal(
        y[..., :64], apply_rotary(x[..., :64], *rotary_tables(50, 64, 1e4)))
    # each pair (i, i + 32) turns as a complex number: its norm is kept
    pair = lambda a: a[..., :32] ** 2 + a[..., 32:64] ** 2
    np.testing.assert_allclose(pair(y), pair(x), rtol=1e-5, atol=1e-6)


# sha256 over the float32 bytes of cos, sin and a rotated x, read from the
# parent commit's rotary_tables (before YaRN and the partial width)
PARENT = {
    (4096, 128, 1000000.0):
        "b8f1cf9fa49f353163635a76b4e60b1649ccd035b49d83e812bb6eb1f0bfd7e6",
    (64, 16, 1000000.0):
        "ffa463ec1d5c01d06e1e5d66038f29faf81317840165674a06366f253ea43017",
}


@pytest.mark.parametrize("case", sorted(PARENT), ids=str)
def test_the_looped_models_tables_give_the_parents_bits(case):
    length, dh, theta = case
    cos, sin = rotary_tables(length, dh, theta)
    x = jax.random.normal(jax.random.PRNGKey(3), (length, 2, dh), jnp.float32)
    digest = hashlib.sha256()
    for a in (cos, sin, apply_rotary(x, cos, sin)):
        digest.update(np.asarray(a, np.float32).tobytes())
    assert digest.hexdigest() == PARENT[case]
