"""The plain reference's own pieces, each against something simpler
still: float64 numpy, optax, the program's edge layout as a set."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import correctness
from benchmark.reference import hgcn as ref


def test_exp0_lands_on_the_hyperboloid_and_log0_undoes_it():
    u = np.random.default_rng(0).normal(size=(50, 7)).astype(np.float32)
    u[0] = 0.0
    x = np.asarray(ref.exp0(jnp.asarray(u)), np.float64)
    assert np.allclose(-x[:, 0] ** 2 + (x[:, 1:] ** 2).sum(-1), -1.0,
                       atol=1e-4)
    assert np.allclose(np.asarray(ref.log0(jnp.asarray(x, jnp.float32))), u,
                       atol=1e-5)


def test_sqdist_is_arcosh_of_the_minkowski_product():
    rng = np.random.default_rng(1)
    a = np.asarray(ref.exp0(jnp.asarray(rng.normal(size=(40, 5)),
                                        jnp.float32)), np.float64)
    b = np.asarray(ref.exp0(jnp.asarray(rng.normal(size=(40, 5)),
                                        jnp.float32)), np.float64)
    inner = (a[:, 1:] * b[:, 1:]).sum(-1) - a[:, 0] * b[:, 0]
    want = np.arccosh(np.maximum(-inner, 1.0)) ** 2
    got = np.asarray(ref.sqdist(jnp.asarray(a, jnp.float32),
                                jnp.asarray(b, jnp.float32)))
    assert np.allclose(got, want, rtol=1e-3, atol=1e-4)


def test_gradients_stay_finite_at_the_singular_points():
    g = jax.grad(lambda u: jnp.sum(ref.log0(ref.exp0(u))))(jnp.zeros((3, 4)))
    assert bool(jnp.all(jnp.isfinite(g)))
    x = ref.exp0(jnp.ones((2, 4), jnp.float32))
    g = jax.grad(lambda a: jnp.sum(ref.sqdist(a, x)))(x)  # distance 0
    assert bool(jnp.all(jnp.isfinite(g)))


def test_message_graph_is_the_programs_edge_set():
    from hyperspace_tpu.data import graphs as G

    rng = np.random.default_rng(2)
    n = 200
    pairs = np.unique(np.sort(rng.integers(0, n, (600, 2)), axis=1), axis=0)
    pairs = pairs[pairs[:, 0] != pairs[:, 1]]
    send, recv, w = ref.message_graph(pairs, n)
    g = G.prepare(pairs, n, np.zeros((n, 2), np.float32))
    mask = np.asarray(g.edge_mask)
    assert set(zip(send.tolist(), recv.tolist())) == set(zip(
        np.asarray(g.senders)[mask].tolist(),
        np.asarray(g.receivers)[mask].tolist()))
    assert np.allclose(w, 1.0 / np.asarray(g.deg)[recv])
    assert np.all(np.diff(recv) >= 0)


def test_split_and_order_are_checked_not_trusted():
    n = 50
    edges = np.stack([np.arange(40), np.arange(40) + 1], axis=1)
    order = np.random.default_rng(3).permutation(n)
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    train = np.sort(rank[edges], axis=1)[6:]      # 40 - 2 - 4 = 34 pairs
    ref.check_permutation(order, n)
    ref.check_training_pairs(train, edges, order, n, 0.05, 0.10)
    with pytest.raises(ValueError):
        ref.check_permutation(np.zeros(n, np.int64), n)
    with pytest.raises(ValueError):               # one pair too few
        ref.check_training_pairs(train[1:], edges, order, n, 0.05, 0.10)
    bad = train.copy()
    bad[0] = (0, 49) if (0, 49) not in set(map(tuple, train)) else (1, 48)
    with pytest.raises(ValueError):               # not an edge
        ref.check_training_pairs(bad, edges, order, n, 0.05, 0.10)
    with pytest.raises(ValueError):               # a repeated edge
        ref.check_training_pairs(np.concatenate([train[:-1], train[:1]]),
                                 edges, order, n, 0.05, 0.10)


@pytest.mark.parametrize("clip", [0.0, 0.5])
def test_update_is_optaxs_clip_then_adamw(clip):
    import optax

    rng = np.random.default_rng(4)
    p = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
    opt = optax.chain(
        optax.clip_by_global_norm(clip if clip > 0 else float("inf")),
        optax.adamw(0.01, weight_decay=5e-4))
    st = opt.init(p)
    mu = {k: jnp.zeros_like(v) for k, v in p.items()}
    nu = {k: jnp.zeros_like(v) for k, v in p.items()}
    q = p
    for step in (1, 2, 3):
        g = {k: jnp.asarray(rng.normal(size=v.shape), jnp.float32)
             for k, v in p.items()}
        upd, st = opt.update(g, st, p)
        p = optax.apply_updates(p, upd)
        q, mu, nu = ref.adamw(q, ref.clip_by_global_norm(g, clip), mu, nu,
                              step, 0.01, 5e-4)
    for k in p:
        assert np.allclose(np.asarray(p[k]), np.asarray(q[k]), atol=1e-6)


def test_gaps_take_the_worst_leaf_against_the_larger_norm():
    want = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    got = {"a": 1.1, "b": 2.0, "tiny": 3e-9}
    # the tiny leaf is measured against the median leaf's norm (1.0)
    assert correctness.worst_leaf_gap(got, want) == pytest.approx(0.1)
    assert correctness.moved_leaves(want) == ["a", "b"]
    # a leaf turned round keeps its norm and is all difference
    turned = {"a": np.array([0.0, 1.0]), "b": np.array([2.0, 0.0])}
    straight = {"a": np.array([1.0, 0.0]), "b": np.array([2.0, 0.0])}
    assert correctness.worst_leaf_difference(turned, straight) == (
        pytest.approx(2 ** 0.5 / 1.5))
    arr = lambda d: {k: np.full(4, v / 2.0) for k, v in d.items()}
    checks = correctness.compare_training(
        {"losses": [1.0, float("nan")], "grad_norms": got,
         "change_norms": got, "grads": arr(got)},
        {"losses": [1.0, 1.0], "grad_norms": want, "change_norms": want,
         "grads": arr(want)},
        {"loss_gap_step1": 0.0, "loss_gap_step2": 1.0})
    assert correctness.all_within(checks[:1])
    assert not correctness.all_within(checks)     # NaN is never within
