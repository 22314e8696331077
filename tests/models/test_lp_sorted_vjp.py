"""``train_step_lp`` takes the decoder's sorted VJP (`nn.edge_dist.
pair_sqdist`) exactly where no mesh partitions the step: the same step
with the wrap off, through `_lp_step_impl`'s own ``constrain`` argument,
gives the same loss and gradients, and the mesh steps keep XLA's
scatter-add (a Pallas call cannot be partitioned)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hyperspace_tpu.data import graphs as G
from hyperspace_tpu.models import hgcn


def _setup(use_att, kind, learn_c):
    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=192, feat_dim=12, seed=0)
    split = G.split_edges(edges, 192, x, seed=0, pad_multiple=128)
    cfg = hgcn.HGCNConfig(feat_dim=12, hidden_dims=(16, 8), kind=kind,
                          use_att=use_att, learn_c=learn_c)
    model, _, state = hgcn.init_lp(cfg, split.graph, seed=0)
    return model, state, G.to_device(split.graph), split


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("use_att,kind,learn_c", [
    (False, "lorentz", False), (True, "lorentz", False),
    (False, "poincare", True)], ids=["mean", "attention", "poincare_learn_c"])
def test_step_equals_the_step_with_the_wrap_off(use_att, kind, learn_c, mode,
                                                monkeypatch):
    monkeypatch.setenv("HYPERSPACE_KERNELS", mode)
    model, state, ga, split = _setup(use_att, kind, learn_c)
    n, train_pos = split.graph.num_nodes, jnp.asarray(split.train_pos)
    # plain SGD at lr 1: the parameters' change IS the gradient
    opt = optax.sgd(1.0)
    state = state._replace(opt_state=opt.init(state.params))
    copy = lambda st: jax.tree.map(jnp.copy, st)
    on, loss_on = hgcn.train_step_lp(model, opt, n, copy(state), ga,
                                     train_pos)
    # an identity for ``constrain`` is all that differs: it turns the
    # wrap off as a mesh step's sharding hint does
    off_step = jax.jit(partial(hgcn._lp_step_impl, model, opt, n,
                               constrain=lambda x: x))
    off, loss_off = off_step(copy(state), ga, train_pos)
    np.testing.assert_allclose(float(loss_on), float(loss_off), rtol=1e-6)
    g_on = jax.tree.map(lambda a, b: a - b, state.params, on.params)
    g_off = jax.tree.map(lambda a, b: a - b, state.params, off.params)
    norm = optax.global_norm(g_off)
    assert float(norm) > 0
    diff = optax.global_norm(jax.tree.map(lambda a, b: a - b, g_on, g_off))
    # float32 sums in another order (the curvature's over every pair end)
    assert float(diff) <= 1e-4 * float(norm), (float(diff), float(norm))
    if mode == "interpret":  # the twin makes no Pallas call to name
        text = lambda fn, *a: fn.lower(*a).as_text(debug_info=True)
        assert "pair_scatter_sum" in text(hgcn.train_step_lp, model, opt, n,
                                          state, ga, train_pos)
        assert "pair_scatter_sum" not in text(off_step, state, ga, train_pos)


@pytest.mark.parametrize("axes", [{"data": 8}, {"data": 4, "model": 2}],
                         ids=["dp", "dp_tp"])
def test_mesh_steps_keep_the_scatter_add(axes, monkeypatch):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from hyperspace_tpu.parallel.mesh import make_mesh

    # where kernels are on, a wrapped decoder would show its call
    monkeypatch.setenv("HYPERSPACE_KERNELS", "interpret")
    model, state, _, split = _setup(False, "lorentz", False)
    mesh = make_mesh(axes)
    opt = hgcn.make_optimizer(model.cfg)
    train_pos = jnp.asarray(hgcn.round_up_pairs(split.train_pos, mesh))
    n = split.graph.num_nodes
    step, state, g = hgcn.make_node_sharded_step_lp(model, opt, n, mesh,
                                                    state, split)
    text = step.lower(state, g, train_pos).as_text(debug_info=True)
    assert "pair_scatter_sum" not in text
    assert "stablehlo.scatter" in text and "pair_dist" in text
