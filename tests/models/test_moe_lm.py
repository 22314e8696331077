"""The MoE LM (``models/moe_lm.py``) at a test size on the CPU: layers of
one kind run together, remat changes no number, the configuration's
checks, and the CLI trains the published yaml cut to a test size and
refuses what the trainer does not run.  Its agreement with the plain
reference is tests/benchmark/test_moe_cell.py's."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.cli import train as T
from hyperspace_tpu.models import moe_lm

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
YAML = os.path.join(REPO, "configs", "moe_lm_laguna_s21.yaml")
# the published yaml cut to what a CPU test holds (widths included)
TINY = ["num_hidden_layers=5", "hidden_size=64", "intermediate_size=128",
        "head_dim=16", "num_key_value_heads=2", "vocab_size=512",
        "num_experts=8", "expert_shards=4", "moe_intermediate_size=32",
        "shared_expert_intermediate_size=32", "sliding_window=16",
        "sequence_length=64", "num_attention_heads_per_layer="
        + json.dumps([4, 6, 6, 6] * 12)]


def test_runs_take_consecutive_layers_of_a_kind_together():
    cfg = moe_lm.MoELMConfig(
        num_hidden_layers=9,
        layer_types=("full_attention",) + ("sliding_attention",) * 3 + (
            "full_attention",) + ("sliding_attention",) * 3 + (
            "full_attention",),
        mlp_layer_types=("dense",) + ("sparse",) * 8,
        num_attention_heads_per_layer=(4, 6, 6, 6, 4, 6, 6, 6, 4))
    assert cfg.kinds() == ("full_dense", "sliding_sparse", "full_sparse")
    assert moe_lm.runs(cfg) == [("full_dense", 0, 1), ("sliding_sparse", 0, 3),
                                ("full_sparse", 0, 1), ("sliding_sparse", 3, 3),
                                ("full_sparse", 1, 1)]
    params = jax.eval_shape(lambda: moe_lm.init_params(cfg))
    assert params["kinds"]["sliding_sparse"]["wq"].shape == (6, 64, 96)
    assert params["kinds"]["full_sparse"]["e_gate"].shape == (2, 8, 64, 32)
    assert params["kinds"]["full_sparse"]["router"].shape == (2, 64, 32)


@pytest.mark.parametrize("change,says", [
    ({"num_attention_heads_per_layer": (4, 5, 5, 5, 4)}, "whole groups"),
    ({"num_attention_heads_per_layer": (4, 6, 4, 6, 4)}, "differ in heads"),
    ({"expert_share": 4}, "expert_share"),
    ({"layer_types": ("full_attention", "linear_attention") * 3},
     "layer_types"),
    ({"num_hidden_layers": 6}, "fewer than"),
])
def test_the_configuration_refuses_what_it_cannot_run(change, says):
    with pytest.raises(ValueError, match=says):
        moe_lm.MoELMConfig(**change)


def _loss_and_grads(cfg, params, tokens):
    return jax.value_and_grad(lambda p: moe_lm.loss_fn(cfg, p, tokens)[0])(
        params)


def test_remat_changes_no_number():
    cfg = moe_lm.MoELMConfig(matmul_precision="highest")
    params = moe_lm.init_params(cfg, 1)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 65), 0, 512)
    loss_a, g_a = _loss_and_grads(cfg, params, tokens)
    loss_b, g_b = _loss_and_grads(dataclasses.replace(cfg, remat="none"),
                                  params, tokens)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_a),
                    jax.tree_util.tree_leaves(g_b)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("shards", [1, 4])
def test_a_share_holds_its_router_fixed(shards):
    """Holding a share of the experts (``expert_shards`` > 1), the
    router's gradient is zero and its weights come out of steps bit for
    bit as they went in (no update, no decay), and the layer's input gets
    nothing through the router's scores; holding every expert, the router
    trains."""
    cfg = moe_lm.MoELMConfig(expert_shards=shards,
                             num_experts=32 // shards)
    fixed = cfg.holds_share
    assert fixed == (shards > 1)
    opt, state = moe_lm.init_state(cfg, 4)
    start = jax.tree_util.tree_map(np.asarray, state.params)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 65), 0, 512)
    _, grads = _loss_and_grads(cfg, state.params, tokens)
    stream = jax.random.randint(jax.random.PRNGKey(6), (4096,), 0, 512)
    for _ in range(3):
        state, _ = moe_lm.train_step(cfg, opt, state, stream)
    for kind in ("sliding_sparse", "full_sparse"):
        g = np.asarray(grads["kinds"][kind]["router"])
        after = np.asarray(state.params["kinds"][kind]["router"])
        assert (np.all(g == 0) and np.array_equal(
            after, start["kinds"][kind]["router"])) == fixed
        # the experts train either way
        assert not np.array_equal(np.asarray(
            state.params["kinds"][kind]["e_up"]), start["kinds"][kind]["e_up"])
    # the layer's input: held fixed, its gradient is that of the experts
    # under a routing taken as given; trained, the scores add to it
    from hyperspace_tpu.nn import moe

    w = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]),
                               start["kinds"]["sliding_sparse"])
    policy = moe_lm.precision_mod.get_policy(cfg.precision)
    b = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(8), b.shape)
    routed = moe.route(b, w["router"], cfg.num_experts_per_tok,
                       cfg.moe_routed_scaling_factor, cfg.norm_topk_prob,
                       cfg.holds_share)
    plan = moe.plan(*routed, 0, cfg.num_experts)

    def given(b):
        return moe.held_experts(b, w["e_gate"], w["e_up"], w["e_down"], plan,
                                policy.compute) + moe_lm.swiglu(
            b, w["s_gate"], w["s_up"], w["s_down"], policy.matmul)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda b: jnp.sum(moe_lm._sparse_mlp(
            cfg, policy, b, w)[0] * probe))(b)
        want = jax.grad(lambda b: jnp.sum(given(b) * probe))(b)
    # float32 at full precision, the same sums
    assert np.allclose(got, want, rtol=1e-5, atol=1e-6) == fixed


def test_the_cli_trains_the_published_yaml_cut_to_a_test_size(capsys):
    assert T.main(["moe_lm", "--yaml", YAML, *TINY, "steps=3"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["workload"] == "moe_lm" and out["steps"] == 3
    assert np.isfinite(out["loss"]) and len(out["held_rows"]) == 4
    # 64 tokens, top-10 of 32 experts, 8 held: 200 rows a layer expected
    assert 100 < np.mean(out["held_rows"]) < 300


def test_the_stats_count_rows_and_the_experts_that_got_them():
    """The state's stats vector: each sparse layer's rows routed to held
    experts, then how many held experts got any (the grouped matmul's
    work a call), the count the plan's group sizes give."""
    from hyperspace_tpu.nn import moe

    cfg = moe_lm.MoELMConfig()
    opt, state = moe_lm.init_state(cfg, 0)
    params = jax.tree_util.tree_map(jnp.copy, state.params)  # donated
    stream = jax.random.randint(jax.random.PRNGKey(3), (4096,), 0,
                                cfg.vocab_size)
    tokens = moe_lm.lm_parts.batch_at(stream, state.step, cfg)
    state, _ = moe_lm.train_step(cfg, opt, state, stream)
    st = moe_lm.read_stats(cfg, state.stats)
    assert len(st["held_rows"]) == len(st["held_experts"]) == 4
    # layer 1, the first sparse one, routed again from the same weights
    w = jax.tree_util.tree_map(lambda a: a[0],
                               params["kinds"]["sliding_sparse"])
    policy = moe_lm.precision_mod.get_policy(cfg.precision)
    rope = moe_lm.rope_of(cfg, "full", 64)
    h = moe_lm._layer(cfg, policy, "full_dense", rope,
                      params["embed"][tokens[0, :-1]],
                      jax.tree_util.tree_map(
                          lambda a: a[0], params["kinds"]["full_dense"]))[0]
    b = moe_lm.rms_norm(moe_lm._attention(
        cfg, policy, "sliding", cfg.heads_of("sliding_sparse"),
        moe_lm.rope_of(cfg, "sliding", 64), h, w), w["n_mlp"],
        cfg.rms_norm_eps)
    plan = moe.plan(*moe.route(b, w["router"], cfg.num_experts_per_tok,
                               cfg.moe_routed_scaling_factor,
                               cfg.norm_topk_prob, cfg.holds_share), 0,
                    cfg.num_experts)
    sizes = np.asarray(plan.groups.sizes)
    assert st["held_rows"][0] == sizes.sum()
    assert st["held_experts"][0] == (sizes > 0).sum() > 0


def test_the_cli_sets_the_expert_layers_gauges(capsys):
    """At each log boundary the rows routed to held experts (the most and
    the mean over the sparse layers) and the held share of all routed
    slots, out of the state's stats vector; the tokens a step once."""
    from hyperspace_tpu.telemetry import registry

    assert T.main(["moe_lm", "--yaml", YAML, *TINY, "steps=2",
                   "eval_every=1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = registry.snapshot()
    rows = out["held_rows"]
    assert got["moe_lm/tokens_per_step"] == 64
    assert got["moe_lm/max_held_rows"] == max(rows)
    assert got["moe_lm/mean_held_rows"] == pytest.approx(np.mean(rows))
    assert got["moe_lm/held_share"] == pytest.approx(
        np.mean(rows) / (64 * 10))


def test_the_yaml_is_the_published_model():
    run, overrides = T.split_overrides(
        [f"{k}={json.dumps(v) if isinstance(v, (list, dict)) else v}"
         for k, v in __import__("yaml").safe_load(open(YAML)).items()],
        T.RunConfig())
    cfg, _ = T._moe_lm_config(run, overrides)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads,
            cfg.sliding_window, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.intermediate_size,
            cfg.vocab_size, cfg.num_hidden_layers) == (
                3072, 128, 8, 512, 256, 10, 1024, 12288, 100352, 48)
    assert cfg.heads_of("full_dense") == 48
    assert cfg.heads_of("sliding_sparse") == 72
    assert cfg.rope_yarn_full == (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert (cfg.partial_rotary_full, cfg.rope_theta_full,
            cfg.rope_theta_sliding) == (0.5, 500000.0, 10000.0)
    assert cfg.router_width == 256 and cfg.moe_routed_scaling_factor == 2.5


@pytest.mark.parametrize("pair,says", [
    ("gating_types=" + json.dumps(["per_head", "none"] * 24), "gating_types"),
    ("model_type=qwen3", "model_type"),
    ("mlp_only_layers=[0, 1]", "mlp_only_layers"),
    ("moe_router_logit_softcapping=30", "softcapping"),
    ("sequence_length=2000000", "max_position_embeddings"),
])
def test_the_cli_refuses_what_the_trainer_does_not_run(pair, says):
    with pytest.raises(SystemExit, match=says):
        T.main(["moe_lm", "--yaml", YAML, *TINY, pair, "steps=1"])
