"""The control of ``correct`` comes out as not correct, under the
committed limits, through the harness's own comparison.

The control is the plain reference put in the program's place one step
of precision down: everything the configuration leaves at float32
computed in bfloat16 and, with ``fp8``, the stated bf16 lanes' values
stored in fp8 as well.  On the chip, at the cells' own size,
``benchmark/calibrate.py`` reads both on three seeds a cell (PERF.md
gives the readings).  Here they are kept at a size a test run can hold:
the twin's limits are the committed ones; the limits on the stated
lanes' numbers are loosened as in every tiny run (a few thousand pairs,
not two million, round far coarser).  The program passes; each control
fails, and fails the twin's committed limits by itself.
"""

import json
import os

import jax.numpy as jnp
import pytest

from benchmark import correctness
from benchmark.drivers import train_fullgraph as drv
from benchmark.reference import hgcn as ref
from tests.benchmark import helpers


@pytest.mark.parametrize("config", ["hgcn_arxiv_lp", "hgcn_att_arxiv_lp"])
def test_controls_fail_where_the_program_passes(config, tmp_path,
                                                monkeypatch,
                                                restore_compile_cache):
    helpers.one_device(monkeypatch)
    with open(os.path.join(helpers.REPO, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = helpers.tiny_config(json.load(f))
    with open(os.path.join(helpers.REPO, "benchmark", "limits",
                           config + ".fullgraph.json")) as f:
        limits = {k: v if k.startswith("twin_") else 0.5
                  for k, v in json.load(f)["limits"].items()}
    twin_cfg = drv.twin_config(cfg)
    seed, steps = 2**31 + 5, 3
    data_root = drv.ensure_dataset(cfg, str(tmp_path / "cache"))
    built, start_tree, weights, key = drv.seeded_build(cfg, {}, seed,
                                                       data_root)
    _, got = drv.first_steps(built, start_tree, steps)
    order, train_pos = built.order, built.split.train_pos
    built, start_tree, _, _ = drv.seeded_build(twin_cfg, {}, seed, data_root,
                                               (weights, key))
    _, got_twin = drv.first_steps(built, start_tree, 1)
    x, graph = drv.reference_inputs(cfg, data_root, order, train_pos)

    def follow(compute, fp8=False):
        model = dict(cfg["model"])
        if fp8:
            model["fp8_lanes"] = ref.CONTROL_FP8_LANES
        kw = dict(compute=jnp.dtype(compute))
        return (ref.train_steps(weights, key, x, graph, train_pos, model,
                                steps=steps, **kw),
                ref.train_steps(weights, key, x, graph, train_pos,
                                twin_cfg["model"], steps=1, **kw))

    want, want_twin = follow("float32")

    def checks(main, twin):
        return correctness.compare_training(main, want, limits,
                                            (twin, want_twin))

    program = checks(got, got_twin)
    assert correctness.all_within(program), program
    for control in (follow("bfloat16", fp8=True), follow("bfloat16")):
        verdict = checks(*control)
        assert not correctness.all_within(verdict)
        failed = {n for n, v, lim in verdict if not v <= lim}
        assert {"twin_loss_gap", "twin_grad_norm_gap",
                "twin_grad_difference"} & failed, verdict
        # and stands three times clear of the program's own reading
        floor = {n: v for n, v, _ in program}
        assert any(v >= 3.0 * max(floor[n], 1e-7)
                   for n, v, _ in verdict if n in failed)
