"""The MoE LM's cell (``laguna_s21.pretrain4k``) at the tiny size its
configuration states, on the CPU, with the look for a chip lifted inside
the test: the run's last line; the program against the plain reference
(``benchmark/reference/laguna.py``), its twin within rounding of it; the
planted faults and the control; the held shares tied to the uncut
layer; the driver's wiring against the CLI's; the configuration against
the published values; the cell's cost count and its readers;
a program from before the trainer refused at once."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmark import correctness, costs_lm, costs_moe
from benchmark import run as bench_run
from benchmark.drivers import train_lm as lm
from benchmark.drivers import train_moe_lm as drv
from benchmark.guards import Refused
from benchmark.peaks import device_peaks
from benchmark.reference import laguna as ref
from tests.benchmark import helpers

CELL = "laguna_s21.pretrain4k"
CHECKS = {"loss_gap_step1", "loss_gap_step2", "grad_norm_gap",
          "grad_difference", "change_norm_gap", "held_rows_gap",
          "twin_loss_gap", "twin_grad_norm_gap", "twin_grad_difference",
          "compiles_in_window"}
TWIN = {c for c in CHECKS if c.startswith("twin_")}
NEW_METRICS = {"moe_route_time", "moe_dispatch_time", "moe_gmm_time",
               "moe_gmm_roofline", "window_attn_time", "window_attn_roofline",
               "moe_held_rows_per_step"}


def _config():
    with open(os.path.join(helpers.REPO, "benchmark", "configs",
                           "laguna_s21.json")) as f:
        return json.load(f)


def _limits():
    with open(os.path.join(helpers.REPO, "benchmark", "limits",
                           CELL + ".json")) as f:
        return json.load(f)


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_trace_0_then_trace_1(tmp_path, monkeypatch, capsys,
                              restore_compile_cache):
    root = helpers.tiny_root(tmp_path)
    helpers.lift_refusals(monkeypatch)
    argv = ["--workload", CELL, "--seed", str(2**31 + 33), "--seconds",
            "0.5"]
    assert bench_run.main(argv + ["--trace", "0"], root=root) == 0
    line = _last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"samples_per_s_chip", "setup_s"}
    assert set(line["checks"]) == CHECKS

    assert bench_run.main(argv + ["--trace", "1"], root=root) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    # the metrics asked of every cell; on the CPU the XLA twins ran and
    # the trace carries no scope paths, so the kernels' readers stay
    # silent (never 0); the program's own count is read
    assert {"device_idle_pct", "step_device_ms", "step_mfu_pct",
            "moe_held_rows_per_step"} <= set(line["metrics"])
    assert set(line["metrics"]) <= {"device_idle_pct", "step_device_ms",
                                    "step_mfu_pct", "loop_gap_ms"} | (
        NEW_METRICS)
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """The tiny cell's program, twin and reference readings of one seed."""
    import jax

    cfg = helpers.tiny_config(_config())
    seed = 2**31 + 7
    cache = str(tmp_path_factory.mktemp("moe_cell"))
    data_root = os.path.dirname(drv.one.ensure_dataset(cfg, cache))
    with jax.enable_x64(False):
        built, start = drv.seeded_build(cfg, {}, seed, data_root)
        _, got = drv.first_steps(built, start, 3, cfg["recipe"]["adam_b1"])
        batches = lm.host_batches(built.tokens, 3, 64, 1)
        got_twin = drv.first_step_of_twin(cfg, {}, seed, data_root)
        want = drv.follow_reference(cfg, batches, seed, blocked=False)
    return cfg, seed, batches, got, want, got_twin


def test_program_sits_on_the_reference(sound):
    """The twin (float32 lanes and matmuls) is the reference's arithmetic
    in another order: its gaps are float32 rounding, ten times inside
    the committed limits or more.  At the stated bf16 lane a 64-wide
    product rounds far coarser than at the cell's widths: the loss
    still agrees to 1e-4 and the gradient's worst leaf to 15%."""
    _, _, _, got, want, got_twin = sound
    found = drv.gaps(got, want)
    assert set(found) - {"loss_gap_step3"} == CHECKS - TWIN - {
        "compiles_in_window"}
    twin = drv.twin_gaps(got_twin, want)
    limits = _limits()["limits"]
    assert set(twin) == TWIN
    assert all(twin[k] < 0.1 * limits[k] for k in TWIN), twin
    assert max(found[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-4
    assert found["grad_norm_gap"] < 0.01
    assert found["grad_difference"] < 0.15
    assert found["change_norm_gap"] < 0.02
    assert found["held_rows_gap"] < 0.03


# what each planted fault and the control fail at the tiny size, on
# limits three times the sound program's own readings (the committed
# ones on the twin's)
EXPECT = {"no_window": {"grad_difference", "held_rows_gap",
                        "twin_grad_difference"},
          "plain_rotary": {"grad_norm_gap", "change_norm_gap",
                           "twin_grad_difference"},
          # the router is fixed: the weights' change no longer moves it,
          # the gradient's direction and norm still tell it
          "unnormed_router": {"grad_difference", "grad_norm_gap",
                              "twin_grad_difference"},
          "no_shared_expert": {"loss_gap_step1", "grad_difference",
                               "change_norm_gap", "twin_grad_difference"},
          "no_head_gate": {"loss_gap_step1", "grad_difference",
                           "twin_loss_gap", "twin_grad_difference"},
          # one step of precision down: the stated lane's numbers hardly
          # tell it from the program, the twin's do
          "control": {"twin_grad_difference"}}


@pytest.mark.parametrize("kind", ref.FAULTS + ("control",))
def test_faults_and_control_come_out_not_correct(sound, kind):
    import jax
    import jax.numpy as jnp

    cfg, seed, batches, got, want, got_twin = sound
    limits = {k: 3.0 * max(v, 1e-6) for k, v in drv.gaps(got, want).items()}
    limits.update({k: v for k, v in _limits()["limits"].items()
                   if k in TWIN})
    assert correctness.all_within(drv.compare(got, want, limits, got_twin))
    kw = {"low": jnp.bfloat16} if kind == "control" else {"fault": kind}
    with jax.enable_x64(False):
        other = drv.follow_reference(cfg, batches, seed, blocked=False, **kw)
    other["grads"] = {k: np.asarray(v) * np.float32(
        min(1.0, other["clip_norm"] / other["grad_norm"]))
        for k, v in other["grads"].items()}
    verdict = drv.compare(other, want, limits, other)
    failed = {n for n, v, lim in verdict if not v <= lim}
    assert EXPECT[kind] <= failed, verdict


def test_the_held_shares_add_up_to_the_uncut_layer():
    """Every share of the router's experts, each computed by the
    program's expert layer as a chip that holds it would, with the
    shared expert (which every chip computes alike) counted once, adds up
    to the reference's layer with all experts held."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.nn import moe
    from hyperspace_tpu.nn.layers import swiglu

    cfg = helpers.tiny_config(_config())
    model, job = lm.model_of(cfg), dict(cfg["job"])
    shards, held = job["expert_shards"], model["num_experts"]
    whole = dict(model, num_experts=held * shards)
    w = ref.init_weights(5, whole, dict(job, expert_shards=1))
    w = ref.layer_of(w, 1)                     # a sparse layer
    b = jax.random.normal(jax.random.PRNGKey(6), (64, model["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        uncut, rows = ref.expert_layer(b, w, whole, 0)
        parts = [moe.expert_share(
            b, w["router"], *(w[n][s * held:(s + 1) * held]
                              for n in ("e_gate", "e_up", "e_down")),
            k=model["num_experts_per_tok"], first=s * held, count=held,
            scale=model["moe_routed_scaling_factor"],
            norm_topk=model["norm_topk_prob"]) for s in range(shards)]
        shared = swiglu(b, w["s_gate"], w["s_up"], w["s_down"])
    # float32 at full precision, summed in another order
    np.testing.assert_allclose(sum(parts) + shared, uncut, rtol=1e-5,
                               atol=1e-6)
    assert int(rows) == 64 * model["num_experts_per_tok"]
    # no share alone is the layer
    assert all(np.abs(np.asarray(p + shared - uncut)).max() > 1e-3
               for p in parts)
    del jnp


@pytest.mark.parametrize("window", [0, 16])
def test_the_references_query_blocks_are_its_whole_attention(window):
    """On the chip the reference's attention runs 256 query rows at a
    time, one block after the other: the same sums, to float32 rounding,
    forward and backward (here 16-row blocks of 64 queries, 3 query heads
    a K/V head)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (64, 6, 16))
    k, v = (jax.random.normal(kk, (64, 2, 16)) for kk in ks[1:3])
    probe = jax.random.normal(ks[3], (64, 6, 16))

    def loss(rows):
        return lambda q, k, v: jnp.sum(
            ref.attention(q, k, v, window, rows) * probe)

    with jax.default_matmul_precision("highest"):
        whole = jax.value_and_grad(loss(0), argnums=(0, 1, 2))(q, k, v)
        blocks = jax.value_and_grad(loss(16), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(blocks),
                    jax.tree_util.tree_leaves(whole)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="no whole number"):
        ref.attention(q, k, v, window, 24)


def test_the_references_blocked_layers_are_its_plain_ones():
    """On the chip each layer is one jitted call under jax.checkpoint, one
    call serving every layer of its kind, and the head a block of rows at
    a time: the loss, the routed rows and the gradient of the plain
    functions, to float32 rounding (the tiny size)."""
    import jax

    cfg = helpers.tiny_config(_config())
    model, job = lm.model_of(cfg), cfg["job"]
    w = ref.init_weights(13, model, job)
    tokens = jax.random.randint(jax.random.PRNGKey(14), (1, 65), 0,
                                model["vocab_size"])
    plain = ref.loss_and_grads(w, tokens, model, job)
    blocked = ref.loss_and_grads(w, tokens, model, job, blocked=True)
    assert float(blocked[0]) == pytest.approx(float(plain[0]), rel=1e-6)
    assert [float(r) for r in blocked[1]] == [float(r) for r in plain[1]]
    for k in plain[2]:
        np.testing.assert_allclose(blocked[2][k], plain[2][k], rtol=1e-4,
                                   atol=1e-7, err_msg=k)


def test_the_references_fixed_router_stays_as_made():
    """The cell holds a share, so the router is held fixed: the reference
    gives it a zero gradient and leaves it out of AdamW, so it comes out
    of steps as it went in while every other matrix moves; the program
    does the same (tests/models/test_moe_lm.py)."""
    import jax

    cfg = helpers.tiny_config(_config())
    model, job = lm.model_of(cfg), cfg["job"]
    assert job["expert_shards"] > 1
    w = ref.init_weights(17, model, job)
    tokens = jax.random.randint(jax.random.PRNGKey(18), (2, 1, 65), 0,
                                model["vocab_size"])
    _, _, grads = ref.loss_and_grads(w, tokens[0], model, job)
    routers = sorted(k for k in w if k.endswith(".router"))
    assert len(routers) == 4
    assert all(not np.any(np.asarray(grads[k])) for k in routers)
    out = ref.train_steps(w, list(tokens), model, job, drv.reference_opt(cfg))
    moved = {k for k, v in out["change_norms"].items() if v > 0}
    assert moved == set(w) - set(routers)


def test_first_step_loss_is_the_clis(tmp_path, capsys, monkeypatch,
                                     restore_compile_cache):
    from hyperspace_tpu.cli import train as T

    helpers.one_device(monkeypatch)
    cfg = helpers.tiny_config(_config())
    seed = 3
    data_root = os.path.dirname(drv.one.ensure_dataset(
        cfg, str(tmp_path / "cache")))
    pairs = lm.program_pairs(cfg) + [f"seed={seed}", f"data_root={data_root}"]
    assert T.main(["moe_lm", *pairs, "steps=1"]) == 0
    cli = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert cli["steps"] == 1 and cli["source"] == "disk"
    assert cli["num_tokens"] == 16384 and cli["tokens_per_step"] == 64
    built = drv.build(cfg, {}, seed, data_root)
    _, loss = built.stepper(built.state)
    assert float(loss) == pytest.approx(cli["loss"], rel=1e-6)


def test_configuration_is_the_published_one_but_for_the_cut():
    """Every key of the public config.json as published, at the file's
    top level, but the three cut; the repo's yaml holds it whole; the cut
    is stated in full; 16 bytes a parameter fill 77% of the chip."""
    import yaml

    cfg = _config()
    model = lm.model_of(cfg)
    with open(os.path.join(helpers.REPO, cfg["program_yaml"])) as f:
        doc = yaml.safe_load(f)
    published = {k: doc[k] for k in model}
    assert len(published) == 29
    cut = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 12544}
    assert model == {**published, **cut}
    assert cfg["published"] == {k: published[k] for k in cut} == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    assert (cfg["job"]["expert_shards"], cfg["job"]["expert_share"]) == (32, 0)
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == "laguna_s21")
    assert helpers.cut_faults(entry, cfg) == []
    assert entry["source"] == cfg["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "moe_pretrain_1chip"
    # the per-layer metrics this cell adds name it alone
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    # the parameters, counted from the reference's shapes
    shapes = [ref.layer_shapes(model, cfg["job"], i) for i in range(5)]
    params = sum(int(np.prod(s)) for layer in shapes for s in layer.values())
    params += 2 * 12544 * 3072 + 3072
    assert params == pytest.approx(810.98e6, rel=1e-3)
    assert 16 * params == pytest.approx(12.98e9, rel=1e-3)


def test_step_flops_counts_the_required_work():
    """13.74 TFLOP a step at the expected 1,280 rows a sparse layer:
    forward 1,118 MFLOP a token, x 3, x 4,096 (the window's pairs, the
    causal half, each layer's projections, the routed experts over their
    rows, the head)."""
    cfg = _config()
    facts = drv.cell_facts(cfg)
    assert facts["held_rows_mean"] == 1280.0
    assert facts["step_flops"] == pytest.approx(13.74e12, rel=2e-3)
    per_token = costs_moe.forward_flops(lm.model_of(cfg), cfg["job"], 4096)
    assert per_token / 4096 == pytest.approx(1118e6, rel=2e-3)
    # the routed experts' part follows the rows the step routed
    more = drv.cell_facts(cfg, [2 * 1280.0] * 4)["step_flops"]
    assert more - facts["step_flops"] == pytest.approx(
        3 * 4 * 1280 * 2 * 3 * 3072 * 1024)
    assert costs_moe.window_pairs(4096, 512) == 512 * 513 / 2 + 3584 * 512


CALL = '%{} = {}[{}] custom-call(%a), custom_call_target="tpu_custom_call"'


def _red(ops):
    return types.SimpleNamespace(devices=[types.SimpleNamespace(
        name="d0", ops=ops, modules=[])], window_ns=1e7)


def _reader(name):
    spec = bench_run.load_json(os.path.join(
        helpers.REPO, "benchmark", "layer_metrics", name + ".json"))
    mod = bench_run.load_module(os.path.join(
        helpers.REPO, "benchmark", "readers", spec["reader"] + ".py"))
    return lambda red, facts: mod.read(red, facts, device_peaks(
        "TPU v5 lite"), spec)


def test_the_kernels_shares_count_calls_at_their_shapes():
    facts = dict(drv.cell_facts(_config()), steps_traced=2)
    peaks = device_peaks("TPU v5 lite")
    ops = [(0.0, 1e6, CALL.format("jvp_flash_window_fwd_.1", "bf16",
                                  "72,4096,128")),
           (1e6, 3e6, CALL.format("transpose_flash_window_dkv.2", "bf16",
                                  "8,4096,128")),
           (3e6, 4e6, CALL.format("gmm_fwd.3", "f32", "34816,1024")),
           (4e6, 6e6, CALL.format("gmm_dw.4", "f32", "8,1024,3072")),
           (6e6, 7e6, CALL.format("flash_dot_fwd.5", "bf16", "48,4096,128"))]
    red = _red(ops)
    least = lambda cost: costs_lm.roofline_seconds(cost, peaks)[0]
    win = [costs_moe.window_call_cost(w, 72, 8, 4096, 128, 512)
           for w in ("fwd", "dkv")]
    assert _reader("window_attn_roofline")(red, facts) == pytest.approx(
        100.0 * sum(least(c) for c in win) / 3e-3)
    gmm = [costs_moe.gmm_call_cost("fwd", 1280.0, 3072, 1024, 8),
           costs_moe.gmm_call_cost("dw", 1280.0, 1024, 3072, 8)]
    assert _reader("moe_gmm_roofline")(red, facts) == pytest.approx(
        100.0 * sum(least(c) for c in gmm) / 3e-3)
    # only the experts that got a row have matrices to read: a step that
    # routed no row to held experts requires nothing and reads 0, never
    # the weights' bytes over a call that skipped every tile
    few = dict(drv.cell_facts(_config(), [4.0] * 4, [2.0] * 4),
               steps_traced=2)
    gmm = [costs_moe.gmm_call_cost("fwd", 4.0, 3072, 1024, 2.0),
           costs_moe.gmm_call_cost("dw", 4.0, 1024, 3072, 2.0)]
    assert _reader("moe_gmm_roofline")(red, few) == pytest.approx(
        100.0 * sum(least(c) for c in gmm) / 3e-3)
    idle = dict(drv.cell_facts(_config(), [0.0] * 4, [0.0] * 4),
                steps_traced=2)
    assert _reader("moe_gmm_roofline")(red, idle) == 0.0
    assert _reader("window_attn_time")(red, facts) == pytest.approx(1.5)
    assert _reader("moe_gmm_time")(red, facts) == pytest.approx(1.5)
    assert _reader("moe_held_rows_per_step")(red, facts) == 5120.0
    # a program from before the trainer: no calls, no count
    bare = _red(ops[4:])
    for name in ("window_attn_roofline", "moe_gmm_roofline",
                 "window_attn_time", "moe_gmm_time"):
        assert _reader(name)(bare, facts) is None
        assert _reader(name)(red, {"steps_traced": 2}) is None or (
            name.endswith("_time"))
    assert _reader("moe_held_rows_per_step")(bare, {}) is None


def test_a_program_without_the_trainer_is_refused_at_once(monkeypatch):
    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "hyperspace_tpu.models.moe_lm" else real(name, *a)))
    ctx = types.SimpleNamespace(config=_config(), traffic={}, chips=1)
    with pytest.raises(Refused, match="no moe_lm trainer"):
        drv.run(ctx)
