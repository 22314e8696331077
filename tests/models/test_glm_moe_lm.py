"""The MoE LM in GLM-4.7-Flash's form (``models/moe_lm.py`` with latent
attention, sigmoid routing and the MTP module) at a test size on the CPU,
where no training run is needed: the published yaml read into the
configuration, the keys the CLI refuses, the configuration's own checks,
the parameter tree and the stats vector's layout, the gauges the CLI
sets, and the sigmoid router's choice and weights.  Its agreement with
the plain reference is tests/benchmark/test_glm_cell.py's."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from hyperspace_tpu.cli import train as T
from hyperspace_tpu.models import moe_lm
from hyperspace_tpu.nn import moe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
YAML = os.path.join(REPO, "configs", "moe_lm_glm47_flash.yaml")
# the published yaml cut to what a CPU test holds (widths included)
TINY = ["num_hidden_layers=3", "hidden_size=64", "intermediate_size=128",
        "num_attention_heads=4", "num_key_value_heads=4", "q_lora_rank=24",
        "kv_lora_rank=16", "qk_nope_head_dim=12", "qk_rope_head_dim=4",
        "v_head_dim=16", "vocab_size=512", "n_routed_experts=8",
        "expert_shards=4", "moe_intermediate_size=32", "sequence_length=64"]


def _config(*extra):
    with open(YAML) as f:
        doc = yaml.safe_load(f)
    pairs = [f"{k}={json.dumps(v) if isinstance(v, list) else v}"
             for k, v in doc.items()] + list(extra)
    run, overrides = T.split_overrides(pairs, T.RunConfig())
    return T._moe_lm_config(run, overrides)[0]


def test_the_yaml_is_the_published_model():
    cfg = _config()
    n = 47
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size) == (
                2048, n, 154880, 10240, 1536, 1536)
    assert cfg.layer_types[:n] == ("latent_attention",) * n
    assert cfg.mlp_layer_types[:n] == ("dense",) + ("sparse",) * (n - 1)
    assert cfg.heads_of("latent_sparse") == 20
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (768, 512, 192, 64, 256)
    assert (cfg.rope_theta_latent, cfg.rms_norm_eps) == (1e6, 1e-5)
    assert (cfg.scoring_func, cfg.num_experts_per_tok, cfg.router_width,
            cfg.moe_routed_scaling_factor, cfg.norm_topk_prob) == (
                "sigmoid", 4, 64, 1.8, True)
    assert (cfg.num_nextn_predict_layers, cfg.mtp_loss_weight,
            cfg.mtp_kind) == (1, 0.3, "latent_sparse")
    assert cfg.sparse_layers == 47        # 46 in the stack, the MTP layer
    # the cut the benchmark runs: the router stays 64 wide
    cut = _config("num_hidden_layers=5", "n_routed_experts=8",
                  "expert_shards=8", "vocab_size=19360")
    assert (cut.kinds(), cut.router_width, cut.sparse_layers) == (
        ("latent_dense", "latent_sparse"), 64, 5)


@pytest.mark.parametrize("pair,says", [
    ("n_group=2", "n_group"),
    ("topk_group=2", "topk_group"),
    ("topk_method=greedy", "topk_method"),
    ("v_head_dim=128", "one flash call"),
    ("num_key_value_heads=2", "decompresses"),
    ("rope_scaling={'type': 'yarn', 'factor': 4}", "rope_scaling"),
    ("partial_rotary_factor=0.5", "partial_rotary_factor"),
    ("n_shared_experts=0", "n_shared_experts"),
    ("num_nextn_predict_layers=2", "num_nextn_predict_layers"),
    ("model_type=deepseek_v3", "model_type"),
])
def test_the_cli_refuses_what_the_trainer_does_not_run(pair, says):
    with pytest.raises(SystemExit, match=says):
        T.main(["moe_lm", "--yaml", YAML, *TINY, pair, "steps=1"])


@pytest.mark.parametrize("change,says", [
    ({"qk_nope_head_dim": 10}, "one flash call"),
    ({"qk_rope_head_dim": 5, "qk_nope_head_dim": 11}, "even"),
    ({"scoring_func": "softplus"}, "scoring_func"),
])
def test_the_configuration_refuses_what_it_cannot_run(change, says):
    base = {"layer_types": ("latent_attention",) * 5,
            "num_attention_heads_per_layer": (4,) * 5}
    with pytest.raises(ValueError, match=says):
        moe_lm.MoELMConfig(**base, **change)


def test_the_tree_and_the_stats_hold_the_mtp_module():
    """The MTP module's leaves beside the stack's (its layer stacked
    [1, ...] under the stack's last kind) and the optimizer's over them,
    the latent leaves' shapes, and the stats vector's two losses after
    the held rows of every sparse layer, the MTP layer's last."""
    cfg = _config(*TINY)
    params = jax.eval_shape(lambda: moe_lm.init_params(cfg))
    latent = params["kinds"]["latent_sparse"]
    assert latent["w_qb"].shape == (2, 24, 4 * 16)
    assert latent["w_kva"].shape == (2, 64, 16 + 4)
    assert latent["w_kvb"].shape == (2, 16, 4 * (12 + 16))
    assert latent["wo"].shape == (2, 4 * 16, 64)
    assert "w_head_gate" not in latent and "wq" not in latent
    mtp = params["mtp"]
    assert (mtp["w_eh"].shape, mtp["enorm"].shape) == ((128, 64), (64,))
    assert set(mtp["layer"]) == set(latent)
    assert mtp["layer"]["e_gate"].shape == (1, 8, 64, 32)
    opt = moe_lm.make_optimizer(cfg)
    moments = jax.eval_shape(lambda: opt.init(moe_lm.init_params(cfg)))
    assert moments[1][0].mu["mtp"]["w_eh"].shape == (128, 64)
    stats = [9.0, 2.0, 10.0, 11.0, 12.0, 3.0, 4.0, 5.0, 6.5, 7.5]
    st = moe_lm.read_stats(cfg, jnp.asarray(stats))
    assert st["held_rows"] == [10.0, 11.0, 12.0]
    assert st["held_experts"] == [3.0, 4.0, 5.0]
    assert (st["main_loss"], st["mtp_loss"]) == (6.5, 7.5)


def test_the_cli_sets_the_mtp_and_latent_gauges():
    """Once, where the stepper is wrapped: the MTP module's positions a
    step (each but the last has its second-next token) and the bytes of
    K and V one forward pass decompresses per head (stack and MTP layer,
    bf16)."""
    from hyperspace_tpu.telemetry import registry

    cfg = _config(*TINY, "precision=bf16")
    T._moe_lm_gauges(cfg, lambda st: st, 1, 50)
    got = registry.snapshot()
    assert got["moe_lm/tokens_per_step"] == 64
    assert got["moe_lm/mtp_positions_per_step"] == 63
    assert got["moe_lm/mla_kv_decompressed_bytes"] == 4 * 64 * 4 * 2 * 16 * 2


def test_the_sigmoid_router_chooses_on_scores_and_weighs_by_them():
    """noaux_tc with one group: the top-k of the sigmoid scores (balanced:
    of each score less its expert's mean over the earlier rows), weighted
    by the chosen scores renormalised and scaled; softmax's choice and
    weights differ."""
    b = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    w = jax.random.normal(jax.random.PRNGKey(2), (16, 12))
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(b @ w))
        for balanced in (False, True):
            top_w, top_e = moe.route(b, w, 4, 1.8, True, balanced=balanced,
                                     scoring="sigmoid")
            score = s
            if balanced:
                earlier = np.cumsum(s, axis=0) - s
                score = s - earlier / np.maximum(np.arange(32), 1)[:, None]
            want_e = np.argsort(-score, axis=1, kind="stable")[:, :4]
            np.testing.assert_array_equal(np.sort(top_e, 1),
                                          np.sort(want_e, 1))
            chosen = np.take_along_axis(s, np.asarray(top_e), 1)
            np.testing.assert_allclose(
                top_w, 1.8 * chosen / chosen.sum(1, keepdims=True),
                rtol=1e-5)
        soft_w, _ = moe.route(b, w, 4, 1.8, True)
    assert np.abs(np.asarray(soft_w) - np.asarray(top_w)).max() > 1e-2
