"""ISSUE 3 acceptance: train a tiny Poincaré embedding, export the
serving artifact, and (1) `topk_neighbors` from the LOADED artifact
matches brute-force hyperbolic distances computed from the LIVE params
— indices exactly, and bit-for-bit against the live-table engine; (2)
repeated queries at different batch sizes within one bucket trigger no
recompile (the PR-2 `jax/recompiles` counter stays flat)."""

import numpy as np
import jax.numpy as jnp

from hyperspace_tpu.manifolds import PoincareBall
from hyperspace_tpu.models import poincare_embed as pe
from hyperspace_tpu.serve import (QueryEngine, RequestBatcher,
                                  export_from_checkpoint, load_artifact)
from hyperspace_tpu.telemetry import registry as telem
from hyperspace_tpu.train.checkpoint import CheckpointManager


def _train_tiny(tmp_path, steps=12):
    from hyperspace_tpu.data import wordnet

    ds = wordnet.synthetic_tree(depth=3, branching=3)
    cfg = pe.PoincareEmbedConfig(num_nodes=ds.num_nodes, dim=4,
                                 batch_size=32, neg_samples=4,
                                 burnin_steps=0)
    state, opt = pe.init_state(cfg, seed=0)
    pairs = jnp.asarray(ds.pairs)
    for _ in range(steps):
        state, _loss = pe.train_step(cfg, opt, state, pairs)
    ckpt = str(tmp_path / "ckpt")
    with CheckpointManager(ckpt) as ck:
        ck.save(steps, state, force=True)
    return cfg, state, ckpt


def test_train_export_query_end_to_end(tmp_path):
    cfg, state, ckpt = _train_tiny(tmp_path)
    art_dir = str(tmp_path / "artifact")
    art = export_from_checkpoint(ckpt, art_dir, workload="poincare",
                                 model_config={"c": cfg.c})
    loaded = load_artifact(art_dir)
    assert loaded.fingerprint == art.fingerprint

    live_table = np.asarray(state.table)
    assert np.array_equal(loaded.table, live_table)  # params froze losslessly

    served = QueryEngine.from_artifact(loaded)
    live = QueryEngine(live_table, ("poincare", float(cfg.c)))
    q = np.asarray([0, 1, 5, 9, cfg.num_nodes - 1], np.int32)
    k = 5
    si, sd = (np.asarray(a) for a in served.topk_neighbors(q, k))
    li, ld = (np.asarray(a) for a in live.topk_neighbors(q, k))
    # served == live, bit for bit: same bytes, same executable
    assert np.array_equal(si, li)
    assert np.array_equal(sd.view(np.uint32), ld.view(np.uint32))

    # served == brute-force O(N²) hyperbolic distances from the live
    # params (the manifolds oracle, f64): exact on indices
    ball = PoincareBall(cfg.c)
    t64 = jnp.asarray(live_table, jnp.float64)
    d = np.array(jnp.stack([ball.dist(t64[i], t64) for i in q.tolist()]))
    d[np.arange(len(q)), q] = np.inf
    ref_idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    assert np.array_equal(si, ref_idx)
    np.testing.assert_allclose(
        sd, np.take_along_axis(d, ref_idx, axis=1), rtol=1e-4, atol=1e-4)


def test_no_recompile_within_bucket_after_export(tmp_path):
    cfg, _state, ckpt = _train_tiny(tmp_path, steps=4)
    art_dir = str(tmp_path / "artifact")
    export_from_checkpoint(ckpt, art_dir, workload="poincare",
                           model_config={"c": cfg.c})
    eng = QueryEngine.from_artifact(load_artifact(art_dir))
    batcher = RequestBatcher(eng, min_bucket=8, max_bucket=64, cache_size=0)
    reg = telem.default_registry()
    batcher.topk([0, 1, 2], 4)  # warmup compiles the (bucket=8, k=4) program
    before = reg.get("jax/recompiles")
    for ids in ([3], [4, 5], [6, 7, 8, 9], list(range(10, 18))):
        batcher.topk(ids, 4)
    assert reg.get("jax/recompiles") == before, (
        "batch sizes 1/2/4/8 inside the 8-bucket must share one compile")
