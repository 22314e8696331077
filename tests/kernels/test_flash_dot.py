"""The flash kernel's dot-product form (``flash_dot_attention``): the
score q·k × scale with no epilogue and the causal structure inside the
kernel, forward and both backward kernels, in interpret mode against
the dense twin at block-edge and non-multiple lengths, alone and under a
``jax.checkpoint`` that keeps the forward call's named results; the
calls' and the residuals' own names; and the lorentz form's outputs and
gradients unchanged to the bit by the refactor that let one recurrence
carry two forms."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.kernels import attention as katt


def _qkv(seed, lead, n, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(ks[i], lead + (n, d), jnp.float32)
               .astype(dtype) for i in range(3))
    return q, k, v, jax.random.normal(ks[3], lead + (n, d), jnp.float32)


def _value_and_grads(fn, q, k, v, w):
    def weighted(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    out = fn(q, k, v)
    return (out,) + jax.grad(weighted, argnums=(0, 1, 2))(q, k, v)


# what stands round the call: nothing; a checkpoint that keeps its inputs
# alone (the names are the identity, the forward call runs again in the
# backward); one whose policy keeps the forward call's two named results
AROUND = {
    "alone": lambda f: f,
    "checkpoint": jax.checkpoint,
    "checkpoint keeping the names": lambda f: jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(
            katt.FLASH_DOT_OUT, katt.FLASH_DOT_LSE)),
}


# 512 = one block of each kernel; 520 and 1100 leave a ragged last block
# in q and kv; 1024 is a whole number of blocks with a diagonal inside
@pytest.mark.parametrize("around", AROUND)
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("n,d", [(64, 16), (512, 128), (520, 64),
                                 (1024, 128), (1100, 128)])
def test_dot_form_matches_dense_twin(interp, n, d, causal, around):
    q, k, v, w = _qkv(n, (2,), n, d)
    scale = 1.0 / d ** 0.5
    attend = lambda q, k, v: katt.flash_dot_attention(q, k, v, causal=causal)
    got = _value_and_grads(AROUND[around](attend), q, k, v, w)
    if around != "alone":   # the names change no bit of value or gradient
        for a, b in zip(got, _value_and_grads(attend, q, k, v, w)):
            np.testing.assert_array_equal(a, b)
    want = _value_and_grads(
        lambda q, k, v: katt._t_flash_dot(q, k, v, scale, causal),
        q, k, v, w)
    # float32 operands run at HIGHEST in both; the kernel's online
    # softmax sums in another order than the dense one
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


def test_dot_form_on_the_bf16_lane(interp):
    """bf16 operands: one MXU pass with float32 scores and accumulators;
    p and dσ are rounded to bf16 for their matmuls, which the dense twin
    at the same operands does too (2^-8 a value)."""
    q, k, v, w = _qkv(7, (2,), 640, 128, jnp.bfloat16)
    got = _value_and_grads(
        lambda q, k, v: katt.flash_dot_attention(q, k, v, causal=True),
        q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: katt._t_flash_dot(q, k, v, 128 ** -0.5, True),
        q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 0.03 * np.abs(b).max(), name


def test_causal_never_looks_ahead(interp):
    """Changing the keys and values after a position leaves every
    earlier row of the output as it was, to the bit."""
    q, k, v, _ = _qkv(3, (1,), 600, 32)
    cut = 300
    k2 = k.at[:, cut:].set(7.0)
    v2 = v.at[:, cut:].set(-5.0)
    a = katt.flash_dot_attention(q, k, v, causal=True)
    b = katt.flash_dot_attention(q, k2, v2, causal=True)
    assert np.array_equal(np.asarray(a[:, :cut]), np.asarray(b[:, :cut]))
    assert not np.array_equal(np.asarray(a[:, cut:]), np.asarray(b[:, cut:]))


def test_causal_needs_a_square(interp):
    q, k, v, _ = _qkv(0, (1,), 16, 8)
    with pytest.raises(ValueError, match="Nq == Nk"):
        katt.flash_dot_attention(q[:, :8], k, v, causal=True)


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def test_the_calls_carry_their_names(interp):
    q, k, v, w = _qkv(1, (2,), 64, 16)

    def loss(q, k, v):
        return jnp.sum(katt.flash_dot_attention(q, k, v, causal=True) * w)

    names = _pallas_names(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        q, k, v).jaxpr, [])
    assert sorted(names) == ["flash_dot_dkv", "flash_dot_dq",
                             "flash_dot_fwd"]


def _saved(capsys, fn, *args):
    """jax's own account of what a backward of ``fn`` keeps: (array,
    why), without the source line."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    lines = capsys.readouterr().out.splitlines()
    return sorted((array, why.split(" from /")[0])
                  for array, why in (ln.split(" ", 1) for ln in lines))


def test_the_residuals_carry_their_names(interp, capsys):
    """What only the forward call can produce is named on the values the
    backward reads: the output in q's dtype and the rows' float32
    log-sum-exp, not the kernel's [B, Nq, 128] statistics tile; a policy
    that saves the names keeps exactly those two beside the arguments."""
    assert (katt.FLASH_DOT_OUT, katt.FLASH_DOT_LSE) == (
        "flash_dot_out", "flash_dot_lse")
    q, k, v, _ = _qkv(1, (2,), 64, 16, jnp.bfloat16)
    attend = lambda q, k, v: katt.flash_dot_attention(q, k, v, causal=True)
    named = [r for r in _saved(capsys, attend, q, k, v)
             if r[1].startswith("named")]
    assert named == [("bf16[2,64,16]", "named 'flash_dot_out'"),
                     ("f32[2,64]", "named 'flash_dot_lse'")]
    kept = _saved(capsys, AROUND["checkpoint keeping the names"](attend),
                  q, k, v)
    assert [r for r in kept if "argument" not in r[1]] == named
    assert len(kept) == 5   # q, k, v and the two
    assert len(_saved(capsys, AROUND["checkpoint"](attend), q, k, v)) == 3


# --- the lorentz form, to the bit ----------------------------------------------
# sha256 over the float32 bytes of the output and of the gradients with
# respect to q, k, v, c and tau, read from the parent commit's kernel
# (PR 32's tree) in interpret mode on this installation's CPU backend


def _hyperboloid(key, shape, c):
    sp = 0.5 * jax.random.normal(key, shape, jnp.float32)
    t = jnp.sqrt(1.0 / c + jnp.sum(sp * sp, -1, keepdims=True))
    return jnp.concatenate([t, sp], -1)


PARENT = {
    (0, (2, 2), 40, 40, 8, True):
        "ecec1d0e28a393673fdc7876bdd671a2735d2a91aaa6f9e4e21165e9945eca69",
    (1, (3,), 24, 300, 16, False):
        "add9551fcef92db90d205df34d24f24cf725b926c9ff6f87a61a8f252b00308c",
    (2, (1, 2), 264, 520, 32, True):
        "773b7a7f8526dc0e363d427bc4ad7ae6183ae9527809b12a7fa06b2112beeb7b",
}


@pytest.mark.parametrize("case", sorted(PARENT), ids=str)
def test_lorentz_form_gives_the_parents_bits(interp, case):
    seed, lead, nq, nk, d, masked = case
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    c = jnp.float32(0.7)
    q, k, v = (_hyperboloid(ks[i], lead + (n, d), c)
               for i, n in ((0, nq), (1, nk), (2, nk)))
    mask = (jax.random.uniform(ks[3], lead + (nq, nk)) > 0.3) if masked \
        else None
    w = jax.random.normal(ks[4], lead + (nq, d + 1), jnp.float32)

    def loss(q, k, v, c, tau):
        return jnp.sum(katt.flash_attention(q, k, v, c, beta=0.3, tau=tau,
                                            mask=mask) * w)

    out = katt.flash_attention(q, k, v, c, beta=0.3, tau=jnp.float32(1.3),
                               mask=mask)
    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, c,
                                                    jnp.float32(1.3))
    digest = hashlib.sha256()
    for a in (out,) + tuple(grads):
        digest.update(np.asarray(a, np.float32).tobytes())
    assert digest.hexdigest() == PARENT[case]
