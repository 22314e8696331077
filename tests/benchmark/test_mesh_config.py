"""The four-chip configuration's files state what the program runs, and
its blocked reference is the plain reference."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from benchmark.reference import hgcn as ref
from benchmark.reference import hgcn_magcite as blocked
from tests.benchmark import test_configs
from tests.benchmark.helpers import REPO

NAME = "hgcn_magcite_lp"


def test_overrides_are_the_yamls_and_the_model_is_the_arxiv_cells():
    test_configs.test_overrides_are_the_yamls(NAME, [])
    cfg, arxiv = test_configs._load(NAME), test_configs._load("hgcn_arxiv_lp")
    assert cfg["model"] == arxiv["model"]
    assert cfg["check_twin"]["model"] == arxiv["check_twin"]["model"]
    assert cfg["reduced"] == []


def test_dataset_section_is_the_published_shape_and_the_programs():
    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn

    cfg = test_configs._load(NAME)
    ds = cfg["dataset"]
    shape = G.OGB_SHAPES[ds["name"]]
    assert {k: ds[k] for k in shape} == shape == ds["generator_args"]
    assert (ds["num_nodes"], ds["num_edges"]) == (736_389, 5_416_271)
    assert ds["generator"].rsplit(".", 1)[1] == "ensure_magcite_scale_dataset"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == NAME]
    assert entry[0]["source"] == cfg["source"]
    for word in ("1910.12933", "2005.00687", "ogbn-mag, paper-cites-paper"):
        assert word in cfg["source"]
    # the model section resolves from the overrides, as the arxiv cells'
    run, ov = T.split_overrides(cfg["cli_overrides"], T.RunConfig())
    assert ov.pop("dataset") == ds["name"] and run.tp == 2
    for k in ("task", "reorder"):
        ov.pop(k)
    got = dataclasses.asdict(T.apply_overrides(
        T.hgcn_mode_defaults(hgcn.HGCNConfig(feat_dim=128), ov, False),
        T._precision_default(run, ov)))
    assert list(got["hidden_dims"]) == cfg["model"]["hidden_dims"]
    assert got["agg_dtype"] == cfg["model"]["agg_dtype"] == "bfloat16"


@pytest.mark.parametrize("lanes,fp8,tol", [("float32", False, 1e-6),
                                           ("bfloat16", False, 5e-2),
                                           ("bfloat16", True, 5e-2)])
def test_blocked_reference_is_the_plain_reference(lanes, fp8, tol,
                                                  monkeypatch):
    """Several blocks of edges and of pairs, the last one padded: at
    float32 lanes to rounding; at bf16 lanes the blocked backward sums a
    node's cotangent in float32 where the unblocked gather's transpose
    sums in bf16, which is what the tolerance leaves room for."""
    monkeypatch.setattr(blocked, "EDGE_BLOCK", 256)
    monkeypatch.setattr(blocked, "PAIR_BLOCK", 128)
    rng = np.random.default_rng(0)
    n = 300
    pairs = np.unique(np.sort(rng.integers(0, n, (1500, 2)), axis=1), axis=0)
    pairs = pairs[pairs[:, 0] != pairs[:, 1]]
    graph = ref.message_graph(pairs, n)
    x = rng.normal(size=(n, 16)).astype(np.float32)
    cfg = {"hidden_dims": [16, 8], "use_att": False, "agg_dtype": lanes,
           "decoder_dtype": lanes, "lr": 0.01, "weight_decay": 5e-4,
           "neg_per_pos": 1, "clip_norm": 0.0}
    if fp8:
        cfg["fp8_lanes"] = ref.CONTROL_FP8_LANES
    params = {"W0": 0.3 * rng.normal(size=(16, 16)).astype(np.float32),
              "b0": np.zeros(16, np.float32),
              "W1": 0.3 * rng.normal(size=(16, 8)).astype(np.float32),
              "b1": np.zeros(8, np.float32), "r": np.float32(2.0),
              "t_raw": np.float32(0.54)}
    key = jax.random.PRNGKey(3)
    a = ref.train_steps(params, key, x, graph, pairs, cfg, steps=3)
    b = blocked.train_steps(params, key, x, graph, pairs, cfg, steps=3)
    np.testing.assert_allclose(b["losses"], a["losses"], rtol=tol)
    for k in a["grads"]:
        scale = max(a["grad_norms"][k], 1e-3)
        assert np.linalg.norm(b["grads"][k] - a["grads"][k]) <= tol * scale
        assert b["change_norms"][k] == pytest.approx(a["change_norms"][k],
                                                     rel=10 * tol, abs=1e-7)
    with pytest.raises(ValueError):
        blocked.embed(params, x, graph, {**cfg, "use_att": True})
