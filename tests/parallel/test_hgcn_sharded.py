"""HGCN multi-device training must match single-device (VERDICT r1 #2/#9).

The north-star workload (HGCN LP) trains through
`models/hgcn.make_sharded_step_lp` on dp-only, tp-only and dp×tp meshes
over the 8 virtual CPU devices; each must agree with the plain
single-device step — same PRNG stream both ways, so only collective
reduction order differs (float tolerance, not bitwise).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.data import graphs as G
from hyperspace_tpu.models import hgcn
from hyperspace_tpu.parallel.mesh import make_mesh


def _setup(seed=0):
    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=192, feat_dim=12, seed=seed)
    split = G.split_edges(edges, 192, x, seed=seed, pad_multiple=128)
    cfg = hgcn.HGCNConfig(feat_dim=12, hidden_dims=(16, 8))
    return cfg, split


def _run_single(cfg, split, steps, train_pos):
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
    ga = G.to_device(split.graph)
    # the mesh steps' own program on one device: `train_step_lp`'s body
    # with the decoder's sorted VJP off (an identity ``constrain``), so
    # that sharding is all that differs.  The sorted VJP against this
    # step is tests/models/test_lp_sorted_vjp.py's, at its own tolerance.
    step = jax.jit(partial(hgcn._lp_step_impl, model, opt,
                           split.graph.num_nodes, constrain=lambda x: x))
    for _ in range(steps):
        state, loss = step(state, ga, train_pos)
    return state, loss


def _run_sharded(cfg, split, steps, axes, train_pos):
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
    mesh = make_mesh(axes)
    ga = G.to_device(split.graph)
    step, state, ga = hgcn.make_sharded_step_lp(
        model, opt, split.graph.num_nodes, mesh, state, ga)
    for _ in range(steps):
        state, loss = step(state, ga, train_pos)
    return state, loss


@pytest.mark.parametrize("axes", [
    pytest.param({"data": 8}, marks=pytest.mark.slow),
    pytest.param({"data": 1, "model": 8}, marks=pytest.mark.slow),
    # dp×tp — the fast-suite representative.  Red from PR 3 to PR 8
    # under an (incorrect) "partitioner reduction-order drift"
    # diagnosis; PR 9 bisected the real op-level cause: the jax of that
    # time MISCOMPILED `concatenate` whose operands/consumers are
    # sharded over a subset of a multi-axis mesh's axes — values
    # garbled, not reordered (the reduced program is kept as
    # tests/parallel/test_node_sharded.py::
    # test_gspmd_concat_under_subset_constraint and passes on the
    # installed jax 0.9.0).
    # The supervision-pair concat instance was fixed for every mesh by
    # hgcn.split_pair_logits; this legacy pair-sharded path additionally
    # hit the bug through the Lorentz time-coordinate concatenates when
    # tp column-sharding put the model axis on the feature dim — bisect
    # evidence: poincare/euclidean (no time-coord concat) were EXACT on
    # this config, lorentz alone returned garbage (~59 vs 0.54 loss at
    # identical params).  GREEN since every Lorentz lift was rewritten
    # as pad+add (manifolds/lorentz._pad_last / with_time_coordinate,
    # bitwise-pinned by tests/manifolds/test_lorentz_padadd.py) — the
    # xfail that sat here from PR 3 is retired.
    pytest.param({"data": 4, "model": 2}),
    pytest.param({"host": 2, "data": 4}, marks=pytest.mark.slow),
])
def test_sharded_lp_matches_single_device(axes):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg, split = _setup()
    steps = 5
    mesh = make_mesh(axes)
    train_pos = jnp.asarray(hgcn.round_up_pairs(split.train_pos, mesh))
    state1, loss1 = _run_single(cfg, split, steps, train_pos)
    stateN, lossN = _run_sharded(cfg, split, steps, axes, train_pos)

    assert np.isfinite(float(loss1)) and np.isfinite(float(lossN))
    np.testing.assert_allclose(float(lossN), float(loss1), rtol=2e-5)
    p1 = jax.tree_util.tree_leaves(state1.params)
    pN = jax.tree_util.tree_leaves(stateN.params)
    for a, b in zip(p1, pN):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-6)


def test_tp_shards_kernels_and_colocates_moments():
    """The TP rule actually shards 2-D kernels over 'model' and gives Adam
    moments the same spec as their parameters."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from hyperspace_tpu.parallel.tp import state_shardings, tp_param_shardings

    cfg, split = _setup()
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
    mesh = make_mesh({"data": 2, "model": 4})
    psh = tp_param_shardings(state.params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(psh)[0]
    kernel_specs = [s.spec for p, s in flat
                    if "kernel" in str([getattr(e, "key", "") for e in p])]
    assert kernel_specs and all(sp[-1] == "model" for sp in kernel_specs)

    ssh = state_shardings(state, state.params, mesh)
    # moments mirror params: every param spec appears in the opt_state tree
    mu_specs = {str(s.spec) for s in jax.tree_util.tree_leaves(ssh.opt_state)}
    for s in jax.tree_util.tree_leaves(psh):
        assert str(s.spec) in mu_specs


def test_sharded_nc_matches_single_device():
    """NC twin of the LP equivalence: dp×tp sharded step == single device."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=192, feat_dim=12, num_classes=4, seed=0)
    tr, va, te = G.node_split_masks(192, seed=0)
    g = G.prepare(edges, 192, x, labels=labels, num_classes=ncls,
                  train_mask=tr, val_mask=va, test_mask=te)
    cfg = hgcn.HGCNConfig(feat_dim=12, hidden_dims=(16, 8), num_classes=ncls)
    lab = jnp.asarray(g.labels)
    mask = jnp.asarray(g.train_mask)

    model, opt, state1 = hgcn.init_nc(cfg, g, seed=0)
    ga1 = G.to_device(g)
    for _ in range(5):
        state1, loss1 = hgcn.train_step_nc(model, opt, state1, ga1, lab, mask)

    model, opt, stateN = hgcn.init_nc(cfg, g, seed=0)
    mesh = make_mesh({"data": 4, "model": 2})
    step, stateN, gaN = hgcn.make_sharded_step_nc(
        model, opt, mesh, stateN, G.to_device(g))
    for _ in range(5):
        stateN, lossN = step(stateN, gaN, lab, mask)

    np.testing.assert_allclose(float(lossN), float(loss1), rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(state1.params),
                    jax.tree_util.tree_leaves(stateN.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-6)


@pytest.mark.slow
def test_sharded_training_checkpoint_resume(tmp_path):
    """Orbax checkpoint/resume of the dp×tp HGCN step: a run interrupted
    at step 3 and resumed must match the uninterrupted 6-step run (the
    sharded state round-trips through the checkpoint with its shardings)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from hyperspace_tpu.train.checkpoint import CheckpointManager

    cfg, split = _setup()
    mesh = make_mesh({"data": 4, "model": 2})
    train_pos = jnp.asarray(hgcn.round_up_pairs(split.train_pos, mesh))

    def fresh():
        model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
        ga = G.to_device(split.graph)
        return hgcn.make_sharded_step_lp(
            model, opt, split.graph.num_nodes, mesh, state, ga)

    # uninterrupted reference
    step, ref_state, ga = fresh()
    for _ in range(6):
        ref_state, loss_ref = step(ref_state, ga, train_pos)

    # interrupted: 3 steps, checkpoint, new process-equivalent restart
    step, state, ga = fresh()
    for _ in range(3):
        state, _ = step(state, ga, train_pos)
    with CheckpointManager(str(tmp_path), async_save=False) as ck:
        ck.save(3, state, force=True)

    step, state2, ga = fresh()
    with CheckpointManager(str(tmp_path), async_save=False) as ck:
        state2, start = ck.restore(state2)
    assert start == 3
    for _ in range(start, 6):
        state2, loss_res = step(state2, ga, train_pos)

    np.testing.assert_allclose(float(loss_res), float(loss_ref), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state.params),
                    jax.tree_util.tree_leaves(state2.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-8)
