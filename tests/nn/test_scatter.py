"""Sorted symmetric aggregation (nn/scatter.py): forward and VJP must match
the naive unsorted segment formulation exactly (the reindexing identity is
exact, not approximate)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.data import graphs as G
from hyperspace_tpu.nn.scatter import sym_segment_aggregate
from hyperspace_tpu.nn.gcn import segment_softmax


def _graph(n=50, seed=0):
    edges, x, labels, k = G.synthetic_hierarchy(num_nodes=n, feat_dim=8, seed=seed)
    return G.prepare(edges, n, x, pad_multiple=64)


def test_prepare_sorted_and_involution():
    g = _graph()
    assert np.all(np.diff(g.receivers) >= 0)
    rp = g.rev_perm
    assert rp is not None
    # involution, and (s, r) -> (r, s)
    np.testing.assert_array_equal(rp[rp], np.arange(len(rp)))
    np.testing.assert_array_equal(g.senders[rp], g.receivers)
    np.testing.assert_array_equal(g.receivers[rp], g.senders)
    # padding maps to itself
    assert np.all(rp[~g.edge_mask] == np.arange(len(rp))[~g.edge_mask])


def test_forward_matches_naive():
    g = _graph()
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float64)
    w = jnp.asarray(rng.random(len(g.senders)) * g.edge_mask, jnp.float64)
    got = sym_segment_aggregate(h, w, jnp.asarray(g.senders), jnp.asarray(g.receivers),
                                jnp.asarray(g.rev_perm), None, None, None,
                                g.num_nodes)
    want = jax.ops.segment_sum(w[:, None] * h[jnp.asarray(g.senders)],
                               jnp.asarray(g.receivers), g.num_nodes)
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_vjp_matches_naive():
    g = _graph()
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float64)
    w = jnp.asarray(rng.random(len(g.senders)) * g.edge_mask, jnp.float64)
    s, r, rp = map(jnp.asarray, (g.senders, g.receivers, g.rev_perm))
    t = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float64)

    def loss_sym(h, w):
        return jnp.sum(
            sym_segment_aggregate(h, w, s, r, rp, None, None, None,
                                  g.num_nodes) * t)

    def loss_naive(h, w):
        return jnp.sum(jax.ops.segment_sum(w[:, None] * h[s], r, g.num_nodes) * t)

    gh1, gw1 = jax.grad(loss_sym, argnums=(0, 1))(h, w)
    gh2, gw2 = jax.grad(loss_naive, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gh1, gh2, rtol=1e-12)
    # dw on padding edges is irrelevant (w is always masked to 0 upstream):
    # compare on real edges only
    m = jnp.asarray(g.edge_mask)
    np.testing.assert_allclose(gw1 * m, gw2 * m, rtol=1e-12)


def test_plan_path_fwd_and_vjp_match_xla(monkeypatch):
    """The production path: plan-carrying aggregation through the Pallas CSR
    kernel (interpret mode) must match the XLA path in forward AND backward —
    guards the pb/pc/pf plumbing through the custom_vjp."""
    monkeypatch.setenv("HYPERSPACE_KERNELS", "interpret")
    g = _graph()
    from hyperspace_tpu.kernels.segment import build_csr_plan

    plan = tuple(jnp.asarray(a) for a in build_csr_plan(g.receivers, g.num_nodes))
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float32)
    w = jnp.asarray(rng.random(len(g.senders)) * g.edge_mask, jnp.float32)
    s, r, rp = map(jnp.asarray, (g.senders, g.receivers, g.rev_perm))
    t = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float32)

    def loss(h, w, pb, pc, pf):
        return jnp.sum(
            sym_segment_aggregate(h, w, s, r, rp, pb, pc, pf, g.num_nodes) * t)

    out_plan = sym_segment_aggregate(h, w, s, r, rp, *plan, g.num_nodes)
    out_xla = sym_segment_aggregate(h, w, s, r, rp, None, None, None, g.num_nodes)
    np.testing.assert_allclose(np.asarray(out_plan), np.asarray(out_xla),
                               rtol=1e-5, atol=1e-5)
    gh1, gw1 = jax.grad(loss, argnums=(0, 1))(h, w, *plan)
    gh2, gw2 = jax.grad(loss, argnums=(0, 1))(h, w, None, None, None)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh2), rtol=1e-5, atol=1e-5)
    m = np.asarray(g.edge_mask)
    np.testing.assert_allclose(np.asarray(gw1) * m, np.asarray(gw2) * m,
                               rtol=1e-5, atol=1e-5)


def test_with_dw_false_zeroes_weight_grad():
    g = _graph()
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float64)
    w = jnp.asarray(rng.random(len(g.senders)) * g.edge_mask, jnp.float64)
    s, r, rp = map(jnp.asarray, (g.senders, g.receivers, g.rev_perm))

    def loss(h, w):
        return jnp.sum(
            sym_segment_aggregate(h, w, s, r, rp, None, None, None,
                                  g.num_nodes, False))

    gh, gw = jax.grad(loss, argnums=(0, 1))(h, w)
    assert np.all(np.asarray(gw) == 0.0)
    assert np.all(np.isfinite(np.asarray(gh)))


def test_sorted_segment_softmax_matches():
    g = _graph()
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal(len(g.senders)), jnp.float64)
    r = jnp.asarray(g.receivers)
    m = jnp.asarray(g.edge_mask)
    got = segment_softmax(logits, r, g.num_nodes, mask=m, indices_are_sorted=True)
    want = segment_softmax(logits, r, g.num_nodes, mask=m)
    np.testing.assert_allclose(got, want, rtol=1e-12)


# --- fused planned attention aggregation (att_aggregate_planned) --------------


def _att_oracle(h, a_s, a_r, g, n, agg_dtype=None):
    """Unfused reference: bounded logits -> exp -> num/den via plain
    segment ops (mirrors the fused op's math exactly)."""
    from hyperspace_tpu.nn.gcn import bounded_att_logits

    snd = jnp.asarray(g.senders)
    rcv = jnp.asarray(g.receivers)
    mask = jnp.asarray(g.edge_mask)
    lm = bounded_att_logits(a_s[snd] + a_r[rcv], 0.2)
    w = jnp.where(mask, jnp.exp(lm), 0.0)
    h_in = h if agg_dtype is None else h.astype(agg_dtype)[snd].astype(
        agg_dtype)
    hs = h[snd] if agg_dtype is None else h.astype(jnp.float32)[snd].astype(
        agg_dtype)
    w_in = w if agg_dtype is None else w.astype(agg_dtype)
    num = jax.ops.segment_sum(
        (w_in[:, None] * hs).astype(jnp.float32), rcv, n,
        indices_are_sorted=True)
    den = jax.ops.segment_sum(w_in.astype(jnp.float32), rcv, n,
                              indices_are_sorted=True)
    return num / jnp.maximum(den, 1e-15)[:, None]


def test_att_aggregate_planned_matches_oracle():
    from hyperspace_tpu.nn.scatter import att_aggregate_planned

    g = _graph(n=120, seed=3)
    n = g.num_nodes
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    a_s = jnp.asarray(rng.standard_normal(n), jnp.float32)
    a_r = jnp.asarray(rng.standard_normal(n), jnp.float32)
    probe = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    plan = tuple(jnp.asarray(p) for p in g.csr_plan)

    def f_fused(h, a_s, a_r):
        out = att_aggregate_planned(
            h, a_s, a_r, jnp.asarray(g.senders), jnp.asarray(g.receivers),
            jnp.asarray(g.rev_perm), jnp.asarray(g.edge_mask), plan, n,
            None, 0.2)
        return jnp.sum(out * probe)

    def f_ref(h, a_s, a_r):
        return jnp.sum(_att_oracle(h, a_s, a_r, g, n) * probe)

    np.testing.assert_allclose(float(f_fused(h, a_s, a_r)),
                               float(f_ref(h, a_s, a_r)), rtol=1e-5)
    gf = jax.grad(f_fused, argnums=(0, 1, 2))(h, a_s, a_r)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(h, a_s, a_r)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_att_aggregate_planned_bf16_close_to_f32():
    from hyperspace_tpu.nn.scatter import att_aggregate_planned

    g = _graph(n=120, seed=4)
    n = g.num_nodes
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    a_s = jnp.asarray(rng.standard_normal(n), jnp.float32)
    a_r = jnp.asarray(rng.standard_normal(n), jnp.float32)
    plan = tuple(jnp.asarray(p) for p in g.csr_plan)
    args = (jnp.asarray(g.senders), jnp.asarray(g.receivers),
            jnp.asarray(g.rev_perm), jnp.asarray(g.edge_mask), plan, n)
    o32 = att_aggregate_planned(h, a_s, a_r, *args, None, 0.2)
    o16 = att_aggregate_planned(h, a_s, a_r, *args, jnp.bfloat16, 0.2)
    np.testing.assert_allclose(np.asarray(o16, np.float32),
                               np.asarray(o32, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_att_aggregate_planned_kernel_path(monkeypatch):
    """Same parity through the actual Pallas kernels (interpret mode):
    the fused forward CSR pass and the fused backward edge kernel
    (csr_att_bwd_edges) both execute."""
    monkeypatch.setenv("HYPERSPACE_KERNELS", "interpret")
    from hyperspace_tpu.nn.scatter import att_aggregate_planned

    g = _graph(n=120, seed=5)
    n = g.num_nodes
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    a_s = jnp.asarray(rng.standard_normal(n), jnp.float32)
    a_r = jnp.asarray(rng.standard_normal(n), jnp.float32)
    probe = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    plan = tuple(jnp.asarray(p) for p in g.csr_plan)

    def f_fused(h, a_s, a_r):
        out = att_aggregate_planned(
            h, a_s, a_r, jnp.asarray(g.senders), jnp.asarray(g.receivers),
            jnp.asarray(g.rev_perm), jnp.asarray(g.edge_mask), plan, n,
            None, 0.2)
        return jnp.sum(out * probe)

    def f_ref(h, a_s, a_r):
        return jnp.sum(_att_oracle(h, a_s, a_r, g, n) * probe)

    np.testing.assert_allclose(float(f_fused(h, a_s, a_r)),
                               float(f_ref(h, a_s, a_r)), rtol=1e-4)
    gf = jax.grad(f_fused, argnums=(0, 1, 2))(h, a_s, a_r)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(h, a_s, a_r)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# --- the involution as a key-sort (involute) ----------------------------------


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[a.dtype.itemsize])


def _involutions():
    """{name: rev_perm}: `prepare`'s involution with its padding rows,
    and the straggler-local one of a cluster split (its own padding
    tail maps to itself)."""
    from hyperspace_tpu.kernels.cluster import build_cluster_split

    g = _graph(n=600, seed=7)
    assert (~g.edge_mask).any()          # padding rows: fixed points of π
    split = build_cluster_split(g.senders, g.receivers, g.edge_mask, g.deg,
                                g.num_nodes, min_pair_edges=512,
                                rev_perm=g.rev_perm)
    assert (~split.s_mask).any() and split.s_mask.sum() > 100
    return {"prepare": g.rev_perm, "stragglers": split.s_rev_local}


@pytest.mark.parametrize("which", ["prepare", "stragglers"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_involute_is_the_gather_bit_for_bit(dtype, which):
    from hyperspace_tpu.nn.scatter import involute

    rp = jnp.asarray(_involutions()[which])
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal(rp.shape[0]), dtype)
    y = jnp.asarray(rng.standard_normal(rp.shape[0]), jnp.float32)
    got = involute(rp, x)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_array_equal(_bits(got), _bits(x[rp]))
    # several payloads ride one sort, each in its own dtype
    gx, gy = jax.jit(involute)(rp, x, y)
    np.testing.assert_array_equal(_bits(gx), _bits(x[rp]))
    np.testing.assert_array_equal(_bits(gy), _bits(y[rp]))


def _gather_spelling(rev_perm, *xs):
    out = tuple(x[rev_perm] for x in xs)
    return out[0] if len(xs) == 1 else out


def _grads_of(op, dtype, g):
    """The gradients `op`'s custom VJP hands back on the graph ``g``,
    with `dtype` the dtype of what the involution permutes."""
    from hyperspace_tpu.nn import scatter

    n = g.num_nodes
    rng = np.random.default_rng(9)
    s, r, rp, m = map(jnp.asarray, (g.senders, g.receivers, g.rev_perm,
                                    g.edge_mask))
    plan = tuple(jnp.asarray(p) for p in g.csr_plan)
    h = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    a_s = jnp.asarray(rng.standard_normal(n), jnp.float32)
    a_r = jnp.asarray(rng.standard_normal(n), jnp.float32)
    probe = jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)
    if op == "att_aggregate_planned":
        agg = None if dtype == jnp.float32 else dtype
        f = lambda h, a_s, a_r: jnp.sum(probe * scatter.att_aggregate_planned(
            h, a_s, a_r, s, r, rp, m, plan, n, agg, 0.2))
        return jax.grad(f, argnums=(0, 1, 2))(h, a_s, a_r)
    w = jnp.where(m, jnp.asarray(rng.random(len(g.senders)), dtype), 0)
    f = lambda h, w: jnp.sum(probe.astype(dtype) * (
        scatter.sym_segment_aggregate(h.astype(dtype), w, s, r, rp,
                                      *plan, n)))
    return jax.grad(f, argnums=(0, 1))(h, w)


@pytest.mark.parametrize("op", ["att_aggregate_planned",
                                "sym_segment_aggregate"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_vjps_through_involute_are_the_gather_spellings(dtype, op,
                                                        monkeypatch):
    """Every ``[rev_perm]`` site of nn/scatter.py goes through
    `involute`; with the helper swapped for the plain gather the two
    custom VJPs hand back the same gradients bit for bit (the oracles
    above hold them to autodiff of the naive formulation)."""
    from hyperspace_tpu.nn import scatter

    g = _graph(n=120, seed=6)
    assert (~g.edge_mask).any()
    got = _grads_of(op, dtype, g)
    monkeypatch.setattr(scatter, "involute", _gather_spelling)
    want = _grads_of(op, dtype, g)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32)))) > 0
        np.testing.assert_array_equal(_bits(a), _bits(b))


def test_no_gather_by_the_involution_is_left_in_the_module():
    """One spelling: no subscript by ``rev_perm`` in nn/scatter.py's code
    (its docstrings may write ``x[rev_perm]``)."""
    import ast
    import inspect

    from hyperspace_tpu.nn import scatter

    picks = [node.lineno
             for node in ast.walk(ast.parse(inspect.getsource(scatter)))
             if isinstance(node, ast.Subscript)
             and isinstance(node.slice, ast.Name)
             and node.slice.id == "rev_perm"]
    assert not picks, picks
