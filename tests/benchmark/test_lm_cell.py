"""The looped language model's cell at the tiny size its configuration
states, on the CPU, with the look for a chip lifted inside the test: the
run's last line, the comparison that decides ``correct`` against the
plain reference, the planted faults and the control, the driver's
wiring against the CLI's, and the configuration against the catalog's
published values."""

import json
import os

import numpy as np
import pytest

from benchmark import correctness
from benchmark import run as bench_run
from benchmark.drivers import train_lm as drv
from benchmark.reference import looplm as ref
from tests.benchmark import helpers

CELL = "ouro_2p6b.pretrain4k"
CHECKS = ({f"loss_gap_step{i}" for i in (1, 2)}
          | {f"ce_gap_pass{t}" for t in (2, 3, 4)}
          | {f"exit_prob_gap_pass{t}" for t in (1, 2, 3, 4)}
          | {"grad_norm_gap", "grad_difference", "change_norm_gap",
             "twin_loss_gap", "twin_ce_gap", "twin_exit_prob_gap",
             "twin_grad_norm_gap", "twin_grad_difference",
             "compiles_in_window"})
TWIN = {c for c in CHECKS if c.startswith("twin_")}
LM_METRICS = {"lm_attention_time", "lm_mlp_time", "lm_head_loss_time",
              "lm_recompute_time", "lm_unattributed_time"}
# accepted metrics whose readers find the same things in this cell's
# step (scope ``optimizer``, run_loop's span, every Pallas call)
SHARED_METRICS = {"optimizer_ms", "loop_host_ms", "pallas_kernel_ms"}


def _config():
    with open(os.path.join(helpers.REPO, "benchmark", "configs",
                           "ouro_2p6b.json")) as f:
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
    assert line["checks"]["compiles_in_window"] == {"value": 0.0,
                                                    "limit": 0.0}

    assert bench_run.main(argv + ["--trace", "1"], root=root) == 0
    line = _last_line(capsys)
    assert line["correct"] is True
    # the metrics asked of every cell; the CPU's trace carries no scope
    # paths and the XLA twin ran, so the cell's own readers stay silent
    # (never 0), and no other cell's metric is asked of it
    assert {"device_idle_pct", "step_device_ms", "step_mfu_pct"} <= set(
        line["metrics"])
    assert set(line["metrics"]) <= {
        "device_idle_pct", "step_device_ms", "step_mfu_pct", "loop_gap_ms",
        "flash_dot_roofline", "flash_dot_fwd_time",
        "flash_dot_bwd_time"} | LM_METRICS | SHARED_METRICS
    assert all(m["value"] > 0 for m in line["metrics"].values())


# scope paths as XLA writes them for models/looplm.py's step (read off a
# lowered tiny step): forward, backward, and the backward's recomputation
PATHS = {
    "fwd_attn": "jit(train_step)/jvp(ut_step)/while/body/closed_call/"
                "layer/attn/dot_general",
    "bwd_attn": "jit(train_step)/transpose(jvp(ut_step))/while/body/"
                "closed_call/checkpoint/layer/attn/dot_general",
    "remat_mlp": "jit(train_step)/transpose(jvp(ut_step))/while/body/"
                 "closed_call/checkpoint/rematted_computation/layer/mlp/mul",
    "head": "jit(train_step)/jvp(head)/dot_general",
    "head_loss": "jit(train_step)/transpose(jvp(head))/jvp(head)/"
                 "checkpoint/rematted_computation/loss/reduce_max",
    "gate": "jit(train_step)/jvp(exit_gate)/jit(log_sigmoid)/neg",
    "loss": "jit(train_step)/transpose(jvp(loss))/mul",
    "optimizer": "jit(train_step)/optimizer/sqrt",
    "embed": "jit(train_step)/jvp(embed)/gather",
    "norm": "jit(train_step)/jvp(final_norm)/rsqrt",
    "while": "jit(train_step)/jvp(ut_step)/while",
    "bare": "",
}
WANT = {"lm_attention_time": {"fwd_attn", "bwd_attn"},
        "lm_mlp_time": {"remat_mlp"},
        "lm_head_loss_time": {"head", "head_loss", "gate", "loss"},
        "optimizer_ms": {"optimizer"},
        "lm_recompute_time": {"remat_mlp", "head_loss"},
        "lm_unattributed_time": {"bare"}}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_scope_metrics_pick_their_operations(metric):
    """Each scope metric's spec against one operation a path, 1 ms each:
    it sums the ones it names and no other; the scan's while operation
    (which spans its body's operations) is in none."""
    from benchmark import scope_reduce

    with open(os.path.join(helpers.REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    names = sorted(PATHS)
    dev = scope_reduce.ScopedDevice("d0", [
        (i * 1e6, (i + 1) * 1e6,
         "%while.3 = (...) while(...)" if n == "while" else f"%fusion.{i}",
         PATHS[n]) for i, n in enumerate(names)])
    picked = {n for i, n in enumerate(names) if scope_reduce.path_ns(
        scope_reduce.ScopedDevice("d0", [dev.ops[i]]),
        spec.get("patterns", ["*"]), spec.get("exclude_paths", ()),
        spec.get("exclude_ops", ())) > 0}
    assert picked == WANT[metric]


def test_flash_dot_roofline_counts_calls_not_steps():
    """The share is the calls' least time over their events' time: 2 fwd
    events of 1 ms and one dq of 2 ms at the cell's shapes; a program
    without the kernel (the parent's) gives nothing and does not raise."""
    import types

    from benchmark import costs_lm
    from benchmark.peaks import device_peaks
    from benchmark.run import load_json, load_module

    spec = load_json(os.path.join(helpers.REPO, "benchmark",
                                  "layer_metrics", "flash_dot_roofline.json"))
    reader = load_module(os.path.join(helpers.REPO, "benchmark", "readers",
                                      "kernel_roofline.py"))
    peaks = device_peaks("TPU v5 lite")
    call = ('%{} = bf16[16,4096,128] custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    ops = [(0.0, 1e6, call.format("jvp_flash_dot_fwd_.1")),
           (1e6, 2e6, call.format("checkpoint_flash_dot_fwd.7")),
           (2e6, 4e6, call.format("transpose_jvp_flash_dot_dq__.1")),
           (4e6, 9e6, "%fusion.2 = f32[8] fusion(%flash_dot_fwd.1)")]
    red = types.SimpleNamespace(devices=[types.SimpleNamespace(
        name="d0", ops=ops, modules=[])], window_ns=1e7)
    facts = drv.cell_facts(_config())
    least = costs_lm.roofline_seconds(costs_lm.flash_dot_call_cost(
        "fwd", 16, 4096, 128, 2), peaks)[0]
    assert least == pytest.approx(0.349e-3, rel=1e-2)  # flops bind
    assert reader.read(red, facts, peaks, spec) == pytest.approx(
        100.0 * 3 * least / 4e-3)
    assert reader.read(red, {"steps_traced": 3}, peaks, spec) is None
    red.devices[0].ops = ops[3:]
    assert reader.read(red, facts, peaks, spec) is None
    # the same events through op_time's patterns
    op_time = load_module(os.path.join(helpers.REPO, "benchmark",
                                       "readers", "op_time.py"))
    red.devices[0].ops = ops
    # (the accepted pallas_kernel_ms takes all three calls: their sum)
    for name, ms in (("flash_dot_fwd_time", 2.0), ("flash_dot_bwd_time", 2.0),
                     ("pallas_kernel_ms", 4.0)):
        spec = load_json(os.path.join(helpers.REPO, "benchmark",
                                      "layer_metrics", name + ".json"))
        assert op_time.read(red, {"steps_traced": 1}, peaks,
                            spec) == pytest.approx(ms)


def test_step_flops_is_the_references_matmuls():
    """``costs_lm.step_flops`` against a count taken another way: XLA's
    own count of the reference's forward at the tiny size (dense
    attention computes the whole square, so the causal half is added
    back; the elementwise work XLA also counts is a few per cent)."""
    import jax

    from benchmark import costs_lm

    cfg = helpers.tiny_config(_config())
    model, seq = drv.model_of(cfg), cfg["job"]["sequence_length"]
    w = jax.eval_shape(lambda: ref.init_weights(0, model))
    toks = jax.ShapeDtypeStruct((seq + 1,), np.int32)
    xla = jax.jit(lambda w, t: ref.forward(w, t, model)).lower(
        w, toks).compile().cost_analysis()["flops"]
    ours = costs_lm.forward_flops_per_token(model, seq) * seq
    passes, layers = model["total_ut_steps"], model["num_hidden_layers"]
    hd = model["num_attention_heads"] * model["head_dim"]
    whole_square = ours + passes * layers * 2 * 2.0 * hd * seq * (seq - 1) / 2
    assert whole_square <= xla <= 1.15 * whole_square
    assert costs_lm.step_flops(model, seq, seq) == 3.0 * ours
    # and the cell's own count, from its published widths: 56.9 TFLOP
    assert drv.cell_facts(_config())["step_flops"] == pytest.approx(
        56.9e12, rel=2e-3)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """The tiny cell's program and reference readings of one seed."""
    import jax

    cfg = helpers.tiny_config(_config())
    seed = 2**31 + 7
    cache = str(tmp_path_factory.mktemp("lm_cell"))
    data_root = os.path.dirname(drv.one.ensure_dataset(cfg, cache))
    with jax.enable_x64(False):
        built, start = drv.seeded_build(cfg, {}, seed, data_root)
        _, got = drv.first_steps(built, start, 3,
                                 cfg["recipe"]["adam_b1"])
        batches = drv.host_batches(built.tokens, 3, 64, 1)
        got_twin = drv.first_step_of_twin(cfg, {}, seed, data_root)
        want = drv.follow_reference(cfg, batches, seed, blocked=False)
    return cfg, seed, batches, got, want, got_twin


def _committed_twin_limits():
    with open(os.path.join(helpers.REPO, "benchmark", "limits",
                           CELL + ".json")) as f:
        return {k: v for k, v in json.load(f)["limits"].items()
                if k in TWIN}


def test_program_sits_on_the_reference(sound):
    """At the tiny size the stated bf16 lane rounds 64-wide products; the
    limits here are ten times the reading of this seed, far under what
    any of the faults or the control reads (next test)."""
    _, _, _, got, want, got_twin = sound
    found = drv.gaps(got, want)
    # (the third step's loss and the first pass's cross-entropy are read
    # and not held: the limits file says why)
    assert set(found) - {"loss_gap_step3", "ce_gap_pass1"} == (
        CHECKS - TWIN - {"compiles_in_window"})
    # the twin (float32 lanes, float32 matmuls): within rounding of the
    # reference, ten times inside its committed limits or more
    twin = drv.twin_gaps(got_twin, want)
    assert set(twin) == TWIN
    limits = _committed_twin_limits()
    assert all(twin[k] < 0.1 * limits[k] for k in TWIN), twin
    assert max(found[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 2e-3
    assert max(found[f"ce_gap_pass{t}"] for t in (1, 2, 3, 4)) < 2e-3
    assert max(found[f"exit_prob_gap_pass{t}"] for t in (1, 2, 3, 4)) < 2e-3
    assert found["grad_norm_gap"] < 0.02
    assert found["grad_difference"] < 0.05
    assert found["change_norm_gap"] < 0.1


@pytest.mark.parametrize("kind", ref.FAULTS + ("control",))
def test_faults_and_control_come_out_not_correct(sound, kind):
    """Each put in the program's place and in its twin's, under limits
    three times the sound program's own readings on the stated lane and
    the committed ones on the twin's: the verdict is not correct, and
    the twin's committed limits alone say so."""
    import jax
    import jax.numpy as jnp

    cfg, seed, batches, got, want, got_twin = sound
    limits = {k: 3.0 * max(v, 1e-6) for k, v in drv.gaps(got, want).items()}
    limits.update(_committed_twin_limits())
    assert correctness.all_within(drv.compare(got, want, limits, got_twin))
    kw = {"low": jnp.bfloat16} if kind == "control" else {"fault": kind}
    with jax.enable_x64(False):
        other = drv.follow_reference(cfg, batches, seed, blocked=False, **kw)
    # the readings a program would hand in: its own clipped gradient
    other["grads"] = {k: np.asarray(v) * np.float32(
        min(1.0, other["clip_norm"] / other["grad_norm"]))
        for k, v in other["grads"].items()}
    verdict = drv.compare(other, want, limits, other)
    assert not correctness.all_within(verdict)
    failed = {n for n, v, lim in verdict if not v <= lim}
    expect = {"three_passes": {"exit_prob_gap_pass4", "grad_difference",
                               "twin_exit_prob_gap"},
              "last_pass_loss": {"loss_gap_step1", "grad_difference",
                                 "twin_loss_gap"},
              "uniform_exit": {"exit_prob_gap_pass1", "grad_difference",
                               "twin_exit_prob_gap"},
              # the loss and the pass means stay near ln V: the gradient
              # of half the tokens is what tells
              "half_head_blocks": {"grad_difference", "change_norm_gap",
                                   "twin_grad_norm_gap"},
              "unchained_passes": {"ce_gap_pass2", "exit_prob_gap_pass2",
                                   "grad_difference", "twin_ce_gap"},
              # one step of precision down: the stated lane's numbers
              # hardly tell it from the program, the twin's do
              "control": {"twin_grad_norm_gap"}}[kind]
    assert expect | {"twin_grad_difference"} <= failed, verdict


def test_every_limit_lies_between_its_readings(tmp_path, capsys):
    """The committed limits: each between the sound runs' largest reading
    and the smallest of the variant that sets it; and the calibration's
    ``--rejudge`` gives recorded gaps their verdict under them."""
    import importlib.util

    with open(os.path.join(helpers.REPO, "benchmark", "limits",
                           CELL + ".json")) as f:
        doc = json.load(f)
    assert set(doc["limits"]) == CHECKS - {"compiles_in_window"}
    for name, limit in doc["limits"].items():
        r = doc["readings"][name]
        assert r["lower"] < limit < r["upper"], name
        assert r["set_by"] in ref.FAULTS + ("control",), name
    spec = importlib.util.spec_from_file_location(
        "calibrate_lm", os.path.join(helpers.REPO, "scripts",
                                     "calibrate_lm.py"))
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    sound = {n: doc["readings"][n]["lower"] for n in doc["limits"]}
    out = tmp_path / "recorded.jsonl"
    out.write_text("\n".join(json.dumps(line) for line in (
        {"seed": 1, "program": {"gaps": sound}},
        {"seed": 1, "half_head_blocks": {"gaps": dict(
            sound, grad_difference=0.6)}})) + "\n")
    assert cal.main(["--workload", CELL, "--rejudge", str(out)]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert lines[0]["correct"] is True and lines[0]["failed"] == []
    assert lines[1]["failed"] == ["grad_difference"]
    assert lines[2] == {"correct": {"program": "1 of 1",
                                    "half_head_blocks": "0 of 1"}}


def test_first_step_loss_is_the_clis(tmp_path, capsys, monkeypatch,
                                     restore_compile_cache):
    from hyperspace_tpu.cli import train as T

    helpers.one_device(monkeypatch)
    cfg = helpers.tiny_config(_config())
    seed = 3
    data_root = os.path.dirname(drv.one.ensure_dataset(
        cfg, str(tmp_path / "cache")))
    pairs = drv.program_pairs(cfg) + [f"seed={seed}",
                                      f"data_root={data_root}"]
    assert T.main(["looplm", *pairs, "steps=1"]) == 0
    cli = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert cli["steps"] == 1 and cli["source"] == "disk"
    assert cli["num_tokens"] == 16384 and cli["tokens_per_step"] == 64

    built = drv.build(cfg, {}, seed, data_root)
    _, loss = built.stepper(built.state)
    assert float(loss) == pytest.approx(cli["loss"], rel=1e-6)


def test_configuration_is_the_catalogs_but_for_the_depth():
    """Every key of the public config.json as published, at the file's
    top level as in its source, but the depth; the repo's yaml holds the
    row whole; the cut is stated in full."""
    import yaml

    cfg = _config()
    model = drv.model_of(cfg)
    with open(os.path.join(helpers.REPO, cfg["program_yaml"])) as f:
        doc = yaml.safe_load(f)
    published = {k: doc[k] for k in model}
    assert len(published) == 20 and published["num_hidden_layers"] == 48
    assert model == {**published, "num_hidden_layers": 8}
    assert "model" not in cfg and all(cfg[k] == v for k, v in model.items())
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["total_ut_steps"]) == (
                2048, 128, 5632, 49152, 4)
    # the job's and the recipe's keys are the yaml's too
    assert {k: doc[k] for k in cfg["job"]} == cfg["job"]
    assert {k: doc[k] for k in cfg["recipe"]} == cfg["recipe"]
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "ouro_2p6b")
    assert helpers.cut_faults(entry, cfg) == []
    assert entry["source"] == cfg["source"]
    # 16 bytes a parameter: eight layers and the whole vocabulary
    m = model
    layer = 4 * m["hidden_size"] ** 2 + 3 * m["hidden_size"] * m[
        "intermediate_size"] + 4 * m["hidden_size"]
    params = (8 * layer + 2 * m["vocab_size"] * m["hidden_size"]
              + 2 * m["hidden_size"] + 1)
    assert 9.7e9 < 16 * params < 9.9e9
