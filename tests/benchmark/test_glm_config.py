"""GLM-4.7-Flash's configuration and the pieces of its cell that need no
training run: the configuration against the published values and its
parameter count; the held shares under the sigmoid router tied to the
uncut layer; the reference's latent attention in query blocks against
its plain form and against the decompressed form the program runs; the
cell's cost count and readers; a program from before latent attention
refused at once."""

import importlib
import json
import math
import os
import types

import numpy as np
import pytest

from benchmark import costs_lm, costs_mla, costs_moe
from benchmark import run as bench_run
from benchmark.drivers import train_glm_lm as drv
from benchmark.drivers import train_lm as lm
from benchmark.guards import Refused
from benchmark.peaks import device_peaks
from benchmark.reference import glm47_flash as ref
from tests.benchmark import helpers

CELL = "glm47_flash.pretrain4k"
NEW_METRICS = {"mla_proj_time", "mla_flash_time", "mla_flash_roofline",
               "mtp_time", "glm_mlp_time", "glm_held_rows_per_step",
               "glm_route_time", "glm_dispatch_time", "glm_gmm_time",
               "glm_gmm_roofline", "glm_head_loss_time",
               "glm_optimizer_time"}


def _config():
    with open(os.path.join(helpers.REPO, "benchmark", "configs",
                           "glm47_flash.json")) as f:
        return json.load(f)


def test_configuration_is_the_published_one_but_for_the_cut():
    """Every key of the public config.json as the catalog gives it, at the
    file's top level, but the three cut; the repo's yaml holds it whole;
    the cut is stated in full; 16 bytes a parameter fill 67% of the
    chip's 16.9 GB."""
    import yaml

    cfg = _config()
    model = lm.model_of(cfg)
    with open(os.path.join(helpers.REPO, cfg["program_yaml"])) as f:
        doc = yaml.safe_load(f)
    published = {k: doc[k] for k in model}
    assert len(published) == 31
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 19360}
    assert model == {**published, **cut}
    assert cfg["published"] == {k: published[k] for k in cut} == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_size": 154880}
    assert (cfg["job"]["expert_shards"], cfg["job"]["expert_share"]) == (8, 0)
    assert cfg["recipe"]["mtp_loss_weight"] == 0.3
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == "glm47_flash")
    assert helpers.cut_faults(entry, cfg) == []
    assert entry["source"] == cfg["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "mla_mtp_pretrain_1chip"
    # the per-layer metrics this cell adds name it alone
    assert {m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]} == NEW_METRICS
    # the parameters, counted from the reference's shapes
    job = cfg["job"]
    counts = {dense: sum(math.prod(s) for s in ref.layer_shapes(
        model, job, dense).values()) for dense in (True, False)}
    assert counts[True] == pytest.approx(84.68e6, rel=1e-4)
    assert counts[False] == pytest.approx(106.83e6, rel=1e-4)
    mtp = counts[False] + 2 * 2048 + 2 * 2048 * 2048
    assert mtp == pytest.approx(115.22e6, rel=1e-4)
    params = counts[True] + 4 * counts[False] + mtp + 2 * 19360 * 2048 + 2048
    assert params == pytest.approx(706.5e6, rel=1e-4)
    assert 16 * params == pytest.approx(11.30e9, rel=1e-3)
    # and the reference's initialiser makes exactly these
    shapes = {k: v.shape for k, v in __import__("jax").eval_shape(
        lambda: ref.init_weights(0, model, job)).items()}
    assert sum(math.prod(s) for s in shapes.values()) == params


def test_the_held_shares_add_up_to_the_uncut_layer():
    """Every share of the router's experts under sigmoid scores, each
    computed by the program's expert layer as a chip that holds it would,
    with the shared expert (which every chip computes alike) counted once,
    adds up to the reference's layer with all experts held."""
    import jax

    from hyperspace_tpu.nn import moe
    from hyperspace_tpu.nn.layers import swiglu

    cfg = helpers.tiny_config(_config())
    model, job = lm.model_of(cfg), dict(cfg["job"])
    shards, held = job["expert_shards"], model["n_routed_experts"]
    whole = dict(model, n_routed_experts=held * shards)
    w = ref.layer_of(ref.init_weights(5, whole, dict(job, expert_shards=1)),
                     "l1.")                    # a sparse layer
    b = jax.random.normal(jax.random.PRNGKey(6), (64, model["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        uncut, rows = ref.expert_layer(b, w, whole, 0)
        parts = [moe.expert_share(
            b, w["router"], *(w[n][s * held:(s + 1) * held]
                              for n in ("e_gate", "e_up", "e_down")),
            k=model["num_experts_per_tok"], first=s * held, count=held,
            scale=model["routed_scaling_factor"],
            norm_topk=model["norm_topk_prob"], scoring="sigmoid")
            for s in range(shards)]
        shared = swiglu(b, w["s_gate"], w["s_up"], w["s_down"])
    # float32 at full precision, summed in another order
    np.testing.assert_allclose(sum(parts) + shared, uncut, rtol=1e-5,
                               atol=1e-6)
    assert int(rows) == 64 * model["num_experts_per_tok"]
    # no share alone is the layer
    assert all(np.abs(np.asarray(p + shared - uncut)).max() > 1e-3
               for p in parts)


def _decompressed(a, w, model):
    """MLA written out as the program runs it: K and V per head, the
    rotary key repeated over the heads, one dense causal softmax."""
    import jax
    import jax.numpy as jnp

    s, h = a.shape[0], model["num_attention_heads"]
    nope, rd = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    eps, theta, r = model["rms_norm_eps"], model["rope_theta"], \
        model["kv_lora_rank"]
    q = (ref.rms_norm(a @ w["w_qa"], w["n_qa"], eps) @ w["w_qb"]).reshape(
        s, h, -1)
    q = jnp.concatenate([q[..., :nope], ref.rope(q[..., nope:], theta)], -1)
    kva = a @ w["w_kva"]
    kv = (ref.rms_norm(kva[:, :r], w["n_kva"], eps) @ w["w_kvb"]).reshape(
        s, h, -1)
    k_r = jnp.broadcast_to(ref.rope(kva[:, None, r:], theta), (s, h, rd))
    k, v = jnp.concatenate([kv[..., :nope], k_r], -1), kv[..., nope:]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(nope + rd)
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1) @ w["wo"]


def test_the_references_latent_attention_is_the_decompressed_one():
    """The reference scores the shared latent and rotary key and sums the
    latent before W_vb; in 24-row query blocks (the last one short, so
    padded) and whole, forward and backward, it is the decompressed
    attention the program computes, to float32 rounding."""
    import jax
    import jax.numpy as jnp

    cfg = helpers.tiny_config(_config())
    model = lm.model_of(cfg)
    w = ref.layer_of(ref.init_weights(9, model, cfg["job"]), "l1.")
    w = {k: v * 10.0 if k.startswith("w_") else v for k, v in w.items()}
    a = jax.random.normal(jax.random.PRNGKey(10), (64, model["hidden_size"]))
    probe = jax.random.normal(jax.random.PRNGKey(11),
                              (64, model["hidden_size"]))

    def value_and_grad(fn):
        return jax.jit(jax.value_and_grad(lambda a: jnp.sum(fn(a) * probe)))

    with jax.default_matmul_precision("highest"):
        want = value_and_grad(lambda a: _decompressed(a, w, model))(a)
        for rows in (0, 24):
            got = value_and_grad(lambda a: ref.mla(a, w, model, rows))(a)
            for x, y in zip(got, want):
                np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-4)
        # the planted fault that drops the rotary key's term moves it
        dropped = value_and_grad(lambda a: ref.mla(
            a, w, model, 24, fault="no_rope_key"))(a)
    assert abs(float(dropped[0]) - float(want[0])) > 1e-2 * abs(
        float(want[0]))


def test_step_flops_counts_the_required_work():
    """11.76 TFLOP a step at the expected 2,048 rows a sparse layer:
    forward 956.8 MFLOP a token (by part: MLA 85.5 a layer,
    six of them; the shared expert, the held slots and the router on
    five sparse layers; layer 0's feed-forward; two head passes; the MTP
    projection), x 3, x 4,096."""
    cfg = _config()
    model, job = lm.model_of(cfg), cfg["job"]
    facts = drv.cell_facts(cfg)
    assert facts["held_rows_sum"] == 5 * 2048.0
    assert facts["step_flops"] == pytest.approx(11.7575e12, rel=1e-4)
    assert costs_mla.forward_flops(model, job, 4096) / 4096 == (
        pytest.approx(956.83e6, rel=1e-5))
    assert costs_mla.mla_flops(model, 4096) / 4096 == pytest.approx(
        43.52e6 + 41.97e6, rel=1e-3)
    # the routed experts' part follows the rows the step routed
    more = drv.cell_facts(cfg, [2 * 2048.0] * 5)["step_flops"]
    assert more - facts["step_flops"] == pytest.approx(
        3 * 5 * 2048 * 2 * 3 * 2048 * 1536)


CALL = '%{} = {}[{}] custom-call(%a), custom_call_target="tpu_custom_call"'


def _reader(name):
    spec = bench_run.load_json(os.path.join(
        helpers.REPO, "benchmark", "layer_metrics", name + ".json"))
    mod = bench_run.load_module(os.path.join(
        helpers.REPO, "benchmark", "readers", spec["reader"] + ".py"))
    return lambda red, facts: mod.read(red, facts, device_peaks(
        "TPU v5 lite"), spec)


def test_the_flash_share_counts_calls_at_256_lanes():
    facts = dict(drv.cell_facts(_config()), steps_traced=2)
    peaks = device_peaks("TPU v5 lite")
    ops = [(0.0, 2e6, CALL.format("jvp_flash_dot_fwd_.1", "bf16",
                                  "20,4096,256")),
           (2e6, 5e6, CALL.format("transpose_flash_dot_dkv.2", "bf16",
                                  "20,4096,256")),
           (5e6, 6e6, CALL.format("gmm_fwd.3", "f32", "34816,1536"))]
    red = types.SimpleNamespace(devices=[types.SimpleNamespace(
        name="d0", ops=ops, modules=[])], window_ns=1e7)
    least = sum(costs_lm.roofline_seconds(costs_lm.flash_dot_call_cost(
        w, 20, 4096, 256, 2), peaks)[0] for w in ("fwd", "dkv"))
    assert _reader("mla_flash_roofline")(red, facts) == pytest.approx(
        100.0 * least / 5e-3)
    assert _reader("mla_flash_time")(red, facts) == pytest.approx(2.5)
    assert _reader("glm_held_rows_per_step")(red, facts) == 10240.0
    # a program whose trace holds no such call: nothing to read
    bare = types.SimpleNamespace(devices=[types.SimpleNamespace(
        name="d0", ops=ops[2:], modules=[])], window_ns=1e7)
    assert _reader("mla_flash_roofline")(bare, facts) is None
    assert _reader("mla_flash_time")(bare, facts) is None
    assert _reader("glm_held_rows_per_step")(bare, {}) is None


def test_the_grouped_matmul_share_counts_the_expected_rows():
    """glm_gmm_roofline and glm_gmm_time on a gmm_fwd call of the d -> f
    matrices: 2,048 rows a call (4,096 tokens x 4 slots x 8 held of 64)
    over the 8 held experts, all with rows, by costs_moe's count."""
    facts = dict(drv.cell_facts(_config()), steps_traced=2)
    assert (facts["held_rows_mean"], facts["held_experts_mean"]) == (
        2048.0, 8.0)
    ops = [(0.0, 1e6, CALL.format("gmm_fwd.3", "f32", "34816,1536")),
           (1e6, 3e6, CALL.format("jvp_flash_dot_fwd_.1", "bf16",
                                  "20,4096,256"))]
    red = types.SimpleNamespace(devices=[types.SimpleNamespace(
        name="d0", ops=ops, modules=[])], window_ns=1e7)
    least = costs_lm.roofline_seconds(costs_moe.gmm_call_cost(
        "fwd", 2048.0, 2048, 1536, 8.0, 2), device_peaks("TPU v5 lite"))[0]
    assert _reader("glm_gmm_roofline")(red, facts) == pytest.approx(
        100.0 * least / 1e-3)
    assert _reader("glm_gmm_time")(red, facts) == pytest.approx(0.5)
    bare = types.SimpleNamespace(devices=[types.SimpleNamespace(
        name="d0", ops=ops[1:], modules=[])], window_ns=1e7)
    assert _reader("glm_gmm_roofline")(bare, facts) is None
    assert _reader("glm_gmm_time")(bare, facts) is None


def test_a_program_without_latent_attention_is_refused_at_once(monkeypatch):
    moe_lm = importlib.import_module("hyperspace_tpu.models.moe_lm")
    monkeypatch.setattr(moe_lm, "LAYER_TYPES",
                        ("full_attention", "sliding_attention"))
    ctx = types.SimpleNamespace(config=_config(), traffic={}, chips=1)
    with pytest.raises(Refused, match="no latent attention"):
        drv.run(ctx)
    monkeypatch.delattr(moe_lm, "LAYER_TYPES")
    with pytest.raises(Refused, match="no latent attention"):
        drv.run(ctx)
