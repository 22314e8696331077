"""The dot form's grouped-query and sliding-window launches
(``flash_dot_attention(..., window=)`` with fewer K/V heads than query
heads): forward and both backward kernels in interpret mode against the
dense twin, at groups of 1, 6 and 9 and windows that cut tiles; the
window never looks back past its bound; the windowed calls' own names;
and the causal, equal-head call the looped model makes gives the parent
commit's bits."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.kernels import attention as katt


def _qkvw(seed, heads, kv_heads, n, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (heads, n, d), jnp.float32).astype(dtype)
    k, v = (jax.random.normal(ks[i], (kv_heads, n, d), jnp.float32)
            .astype(dtype) for i in (1, 2))
    return q, k, v, jax.random.normal(ks[3], (heads, n, d), jnp.float32)


def _value_and_grads(fn, q, k, v, w):
    def weighted(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    return (fn(q, k, v),) + jax.grad(weighted, argnums=(0, 1, 2))(q, k, v)


# (query heads, K/V heads, length, width, window): groups 1, 6 and 9; a
# window under one block, one that ends inside a block (100 of 128 rows,
# 700 of a 512-row block), one block exactly (512, the model's), and none
CASES = [
    (2, 2, 256, 16, 100),
    (6, 1, 300, 32, 64),
    (9, 1, 200, 16, 0),
    (6, 2, 1100, 64, 700),
    (9, 1, 1024, 128, 512),
    (12, 2, 640, 64, 0),
]


@pytest.mark.parametrize("case", CASES, ids=str)
def test_grouped_and_windowed_match_the_dense_twin(interp, case):
    heads, kv_heads, n, d, window = case
    q, k, v, w = _qkvw(n + window, heads, kv_heads, n, d)
    got = _value_and_grads(lambda q, k, v: katt.flash_dot_attention(
        q, k, v, causal=True, window=window), q, k, v, w)
    want = _value_and_grads(lambda q, k, v: katt._t_flash_dot(
        q, k, v, d ** -0.5, True, window, heads // kv_heads), q, k, v, w)
    # float32 operands at HIGHEST on both sides; the online softmax and
    # the group's sum in dk/dv add in another order than the dense form
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-5, err_msg=name)


def test_the_twin_is_the_written_out_window():
    """The twin against the mask written out: query i sees keys
    i - window < j <= i, and query head h reads K/V head h // group."""
    q, k, v, _ = _qkvw(5, 6, 2, 40, 8)
    got = katt._t_flash_dot(q, k, v, 0.5, True, 7, 3)
    i, j = np.arange(40)[:, None], np.arange(40)[None, :]
    keep = (j <= i) & (j > i - 7)
    want = []
    for h in range(6):
        s = np.asarray(q[h]) @ np.asarray(k[h // 3]).T * 0.5
        s = np.where(keep, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want.append(p / p.sum(-1, keepdims=True) @ np.asarray(v[h // 3]))
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-5, atol=1e-6)


def test_the_window_never_looks_back_past_its_bound(interp):
    """Changing keys and values at or before position i − window leaves
    row i of the output as it was, to the bit."""
    q, k, v, _ = _qkvw(3, 4, 2, 600, 32)
    window, row = 130, 450
    k2 = k.at[:, :row - window + 1].set(7.0)
    v2 = v.at[:, :row - window + 1].set(-5.0)
    a = katt.flash_dot_attention(q, k, v, causal=True, window=window)
    b = katt.flash_dot_attention(q, k2, v2, causal=True, window=window)
    assert np.array_equal(np.asarray(a[:, row:]), np.asarray(b[:, row:]))
    assert not np.array_equal(np.asarray(a[:, :row]), np.asarray(b[:, :row]))


def test_a_window_needs_causal_and_heads_whole_groups(interp):
    q, k, v, _ = _qkvw(0, 6, 4, 16, 8)
    with pytest.raises(ValueError, match="whole"):
        katt.flash_dot_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="causal"):
        katt.flash_dot_attention(q, k[:2], v[:2], window=4)


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.mark.parametrize("window,prefix", [(16, "flash_window"),
                                           (0, "flash_dot")])
def test_windowed_calls_carry_their_own_names(interp, window, prefix):
    q, k, v, w = _qkvw(1, 4, 2, 64, 16)

    def loss(q, k, v):
        return jnp.sum(katt.flash_dot_attention(
            q, k, v, causal=True, window=window) * w)

    names = _pallas_names(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        q, k, v).jaxpr, [])
    assert sorted(names) == [prefix + "_dkv", prefix + "_dq",
                             prefix + "_fwd"]


# sha256 over the float32 bytes of the output and the gradients with
# respect to q, k, v of the causal, equal-head call, read from the parent
# commit's kernel (before windows and groups) in interpret mode on this
# installation's CPU backend
PARENT = {
    (0, 2, 520, 64, "float32"):
        "d2b2e2a44767ac06f4c73731557855c6fd4d58bfd6779ca6a7b764852beaed1d",
    (1, 3, 1024, 128, "bfloat16"):
        "c4c9d37602be5ddb1fc040a7ff66e2871a7e582a0835823bdfa252e66105f65d",
}


@pytest.mark.parametrize("case", sorted(PARENT), ids=str)
def test_the_looped_models_call_gives_the_parents_bits(interp, case):
    seed, heads, n, d, dtype = case
    q, k, v, w = _qkvw(seed, heads, heads, n, d, jnp.dtype(dtype))
    got = _value_and_grads(lambda q, k, v: katt.flash_dot_attention(
        q, k, v, causal=True), q, k, v, w)
    digest = hashlib.sha256()
    for a in got:
        digest.update(np.asarray(a, np.float32).tobytes())
    assert digest.hexdigest() == PARENT[case]
