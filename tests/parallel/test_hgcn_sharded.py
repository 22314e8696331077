"""How `parallel/tp.py`'s rule shards a state (HGCN's parameters as the
example; the minibatch mesh steps use it, the node-sharded HGCN step
replicates its state), and that the node-sharded step's state
round-trips through a checkpoint with its shardings (8 virtual CPU
devices).  That the step computes the single-device trajectory is
tests/parallel/test_node_sharded.py's."""

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


@pytest.mark.slow
def test_sharded_training_checkpoint_resume(tmp_path):
    """Orbax checkpoint/resume of the dp×tp HGCN mesh step: a run interrupted
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
        return hgcn.make_node_sharded_step_lp(
            model, opt, split.graph.num_nodes, mesh, state, split)

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
