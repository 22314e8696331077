"""GLM-4.7-Flash's cell (``glm47_flash.pretrain4k``) at the tiny size its
configuration states, on the CPU, with the look for a chip lifted inside
the test: one traced run of the harness, its last line and the program
against the plain reference (``benchmark/reference/glm47_flash.py``,
which computes latent attention from the compressed form where the
program decompresses it), the twin within rounding of it.  The planted
faults are tests/benchmark/test_glm_faults.py's."""

import contextlib
import io
import json
import os

import pytest

from benchmark import run as bench_run
from tests.benchmark import helpers

CELL = "glm47_flash.pretrain4k"
SEED = 2**31 + 41
CHECKS = {"loss_gap_step2", "mtp_loss_gap_step1", "mtp_loss_gap_step2",
          "grad_norm_gap", "grad_difference", "change_norm_gap",
          "held_rows_gap", "twin_loss_gap", "twin_mtp_loss_gap",
          "twin_grad_norm_gap", "twin_grad_difference", "compiles_in_window"}
TWIN = {c for c in CHECKS if c.startswith("twin_")}
NEW_METRICS = {"mla_proj_time", "mla_flash_time", "mla_flash_roofline",
               "mtp_time", "glm_mlp_time", "glm_held_rows_per_step",
               "glm_route_time", "glm_dispatch_time", "glm_gmm_time",
               "glm_gmm_roofline", "glm_head_loss_time",
               "glm_optimizer_time"}


def _limits():
    with open(os.path.join(helpers.REPO, "benchmark", "limits",
                           CELL + ".json")) as f:
        return json.load(f)["limits"]


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """The run's last line."""
    from hyperspace_tpu import compile_cache

    root = helpers.tiny_root(tmp_path_factory.mktemp("glm_cell"))
    mp = pytest.MonkeyPatch()
    try:
        helpers.lift_refusals(mp)
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            assert bench_run.main(["--workload", CELL, "--seed", str(SEED),
                                   "--seconds", "0.5", "--trace", "1"],
                                  root=root) == 0
    finally:
        mp.undo()
        compile_cache.deactivate()
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_traced_run_is_correct_and_reads_its_metrics(cell):
    """The twin (float32 lanes and matmuls) is the reference's arithmetic
    in another order, the attention in another form: its gaps are float32
    rounding, ten times inside the committed limits or more.  At the
    stated bf16 lane a 64-wide product rounds far coarser than at the
    cell's widths: the losses still agree to 1e-4."""
    line = cell
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    checks = {k: v["value"] for k, v in line["checks"].items()}
    assert set(checks) == CHECKS
    limits = _limits()
    assert all(checks[k] < 0.1 * limits[k] for k in TWIN), checks
    assert max(checks[k] for k in CHECKS if "loss_gap_step" in k) < 1e-4
    assert checks["grad_norm_gap"] < 0.01
    assert checks["held_rows_gap"] < 0.05
    assert checks["compiles_in_window"] == 0
    # the metrics asked of every cell; on the CPU the XLA twins ran and
    # the trace carries no scope paths, so the kernels' and scopes'
    # readers stay silent (never 0); the program's own count is read
    metrics = line["metrics"]
    assert {"device_idle_pct", "step_device_ms", "step_mfu_pct",
            "glm_held_rows_per_step"} <= set(metrics)
    assert set(metrics) <= {"device_idle_pct", "step_device_ms",
                            "step_mfu_pct", "loop_gap_ms"} | NEW_METRICS
    assert all(m["value"] > 0 for m in metrics.values())
