"""The expert layer's held share (``nn/moe.py``): its value and gradients
against the dense form (every held expert over every token, weighted
where the router chose it), with the grouped matmul as its XLA twin and
in interpret mode; the plan puts every held (token, slot) pair on a row
of its own, an expert's rows together, and drops none; dispatch and
combine, whose backward is a gather, give autodiff's gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.nn import moe

S, D, F, K = 96, 64, 32, 10


def _weights(seed, n_experts, held):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (S, D)),
            0.3 * jax.random.normal(ks[1], (D, n_experts)),
            0.1 * jax.random.normal(ks[2], (held, D, F)),
            0.1 * jax.random.normal(ks[3], (held, D, F)),
            0.1 * jax.random.normal(ks[4], (held, F, D)))


def _dense(first, b, wr, wg, wu, wd):
    top_w, top_e = moe.route(b, wr, K, 2.5, True)
    y = 0.0
    for i in range(wg.shape[0]):
        weight = jnp.sum(jnp.where(top_e == first + i, top_w, 0.0), -1)
        y = y + weight[:, None] * (
            (jax.nn.silu(b @ wg[i]) * (b @ wu[i])) @ wd[i])
    return y


@pytest.fixture(params=["interpret", "xla"])
def kernel_mode(request, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_KERNELS", request.param)
    return request.param


@pytest.mark.parametrize("first,held,n_experts", [(8, 8, 32), (0, 4, 16),
                                                  (24, 8, 32)])
def test_held_share_is_the_dense_form(kernel_mode, first, held, n_experts):
    args = _weights(first + held, n_experts, held)
    cos = jnp.cos(jnp.arange(D, dtype=jnp.float32))
    share = lambda *a: moe.expert_share(
        *a, k=K, first=first, count=held, scale=2.5, norm_topk=True)
    with jax.default_matmul_precision("highest"):
        got = share(*args)
        want = _dense(first, *args)
        g_got = jax.grad(lambda *a: jnp.sum(share(*a) * cos),
                         argnums=range(5))(*args)
        g_want = jax.grad(lambda *a: jnp.sum(_dense(first, *a) * cos),
                          argnums=range(5))(*args)
    # float32 at full precision on both sides, summed in other orders
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip(("b", "router", "gate", "up", "down"), g_got,
                          g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


def test_the_plan_drops_no_token_and_keeps_experts_together():
    b, wr, *_ = _weights(3, 32, 8)
    top_w, top_e = moe.route(b, wr, K, 2.5, True)
    p = moe.plan(top_w, top_e, 8, 8)
    top_e, held = np.asarray(top_e), np.asarray(p.held)
    chosen = (top_e >= 8) & (top_e < 16)
    # every held slot, and only those, has a row of its own
    assert held.sum() == chosen.sum() == int(np.sum(p.groups.sizes))
    rows = np.asarray(p.pos)[held]
    assert len(set(rows.tolist())) == len(rows)
    assert np.asarray(p.row_valid).sum() == len(rows)
    np.testing.assert_array_equal(np.asarray(p.row_token)[rows],
                                  np.nonzero(held)[0])
    # a row's tile belongs to the row's expert; the weights are the
    # router's, renormalised over the top k and scaled
    tm = p.row_valid.shape[0] // p.groups.tiles.shape[0]
    expert_of_row = np.asarray(p.groups.tiles)[rows // tm]
    local = np.sort(np.where(chosen, top_e - 8, 99), axis=1)[:, :8]
    np.testing.assert_array_equal(np.sort(expert_of_row),
                                  np.sort(local[local < 99]))
    np.testing.assert_allclose(np.asarray(p.weight).sum(1), np.where(
        chosen, np.asarray(top_w), 0).sum(1), rtol=1e-6)
    assert int(p.groups.used[0]) * tm <= p.row_valid.shape[0]


def test_dispatch_and_combine_give_autodiffs_gradients():
    b, wr, *_ = _weights(5, 16, 8)
    p = moe.plan(*moe.route(b, wr, K, 2.5, True), 0, 8)
    rows = jax.random.normal(jax.random.PRNGKey(9), (p.row_valid.shape[0], D))

    def plain_combine(out, weight):
        picked = out[p.pos] * weight[..., None]
        return jnp.sum(jnp.where(p.held[..., None], picked, 0.0), 1)

    def plain_dispatch(x):
        return jnp.where(p.row_valid[:, None], x[p.row_token], 0.0)

    for fn, plain, args in (
            (lambda o, w: moe.combine(o, w, p), plain_combine,
             (rows, p.weight)),
            (lambda x: moe.dispatch(x, p), plain_dispatch, (b,))):
        np.testing.assert_allclose(fn(*args), plain(*args), rtol=1e-6)
        out_g = jax.random.normal(jax.random.PRNGKey(1),
                                  fn(*args).shape)
        got = jax.grad(lambda *a: jnp.sum(fn(*a) * out_g),
                       argnums=range(len(args)))(*args)
        want = jax.grad(lambda *a: jnp.sum(plain(*a) * out_g),
                        argnums=range(len(args)))(*args)
        for a, w in zip(got, want):
            np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-6)


def test_a_balanced_choice_follows_each_token_not_a_shared_drift():
    """Logits every token shares a large part of (a per-expert offset):
    the plain top-k sends every token to the same k experts; the balanced
    choice (each logit less its expert's mean over the earlier rows)
    spreads them near evenly, changes no weight formula (the renormalised
    softmax of the chosen experts), and no later row moves an earlier
    row's choice.  Row 0 chooses as the plain top-k does."""
    n = 32
    b, wr, *_ = _weights(4, n, 8)
    offset = 400.0 * jax.random.normal(jax.random.PRNGKey(12), (n,))
    shift = jnp.linalg.pinv(wr)            # b + u shifts every logit by
    drifted = b + (shift.T @ offset)[None]  # the same offset a token
    np.testing.assert_allclose(drifted @ wr, b @ wr + offset[None],
                               rtol=1e-3, atol=0.1)
    loads = {}
    for balanced in (False, True):
        top_w, top_e = moe.route(drifted, wr, K, 2.5, True, balanced)
        loads[balanced] = np.bincount(np.asarray(top_e).ravel(), minlength=n)
        p = jax.nn.softmax(drifted @ wr, axis=-1)
        want = jnp.take_along_axis(p, top_e, axis=-1)
        np.testing.assert_allclose(
            top_w, 2.5 * want / jnp.sum(want, -1, keepdims=True), rtol=1e-5,
            atol=1e-6)
    assert np.count_nonzero(loads[False]) == K
    assert np.count_nonzero(loads[True]) == n
    assert loads[True].max() < 3 * S * K / n
    # causal: the first half chooses alike whatever follows it
    other = drifted.at[S // 2:].set(jax.random.normal(
        jax.random.PRNGKey(13), (S - S // 2, D)))
    first = moe.route(drifted, wr, K, 2.5, True, True)[1][:S // 2]
    again = moe.route(other, wr, K, 2.5, True, True)[1][:S // 2]
    np.testing.assert_array_equal(first, again)
    np.testing.assert_array_equal(
        moe.route(drifted, wr, K, 2.5, True, True)[1][0],
        moe.route(drifted, wr, K, 2.5, True, False)[1][0])
