"""The four-chip cell at a tiny size on four virtual CPU devices: the
driver is pinned to ``cli.train.main``, a sound run comes out correct
with every key of the contract, and each of the cell's two calibrated
faults comes out not correct under limits set from the sound run."""

import json
import os

import pytest

from benchmark import mesh_faults
from benchmark import run as bench_run
from benchmark.drivers import train_mesh as drv
from tests.benchmark import helpers, mesh_helpers
from tests.benchmark.test_faults import _failed, _run, _sound_then_limits

CELL = mesh_helpers.CELL


def _config():
    with open(os.path.join(helpers.REPO, "benchmark", "configs",
                           "hgcn_magcite_lp.json")) as f:
        return helpers.tiny_config(json.load(f))


def test_first_step_loss_is_the_clis_on_four_devices(
        tmp_path, capsys, monkeypatch, restore_compile_cache):
    from hyperspace_tpu.cli import train as T

    mesh_helpers.four_devices(monkeypatch)
    cfg, seed = _config(), 3
    data_root = drv.one.ensure_dataset(cfg, str(tmp_path / "cache"))
    pairs = cfg["cli_overrides"] + [f"seed={seed}",
                                    f"data_root={data_root}"]
    assert T.main(["hgcn", *pairs, "steps=1"]) == 0
    cli = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert cli["steps"] == 1 and cli["source"] == "disk"
    assert cli["dataset"] == "ogbn-mag-cites"
    assert cli["num_nodes"] == helpers.TINY_GRAPH["num_nodes"]
    assert 0.0 < cli["roc_auc"] < 1.0  # the final eval ran, on the mesh

    run, overrides = drv.one.program_run_config(cfg, {}, seed, data_root)
    built = drv.build(run, overrides, split_seed=seed)
    assert dict(built.mesh.shape) == {"data": 2, "model": 2}
    _, loss = built.stepper(built.state)
    assert float(loss) == pytest.approx(cli["loss"], rel=1e-6)


def test_sound_run_then_each_fault(tmp_path, monkeypatch, capsys,
                                   restore_compile_cache):
    root = helpers.tiny_root(tmp_path)
    mesh_helpers.lift_refusals(monkeypatch)
    sound = _sound_then_limits(root, CELL, capsys)
    assert sound["device"]["count"] == 4 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"samples_per_s_chip", "setup_s"}
    again = _run(root, CELL, capsys)
    assert again["correct"] is True, _failed(again)

    with mesh_faults.exchange_left_out():
        line = _run(root, CELL, capsys)
    assert line["correct"] is False
    # a third or more of every sum is gone: the loss itself moves
    assert "loss_gap_step1" in _failed(line)

    with mesh_faults.a_data_shards_pairs_left_out():
        line = _run(root, CELL, capsys)
    assert line["correct"] is False
    # the loss is whole, its gradient is not; under the committed limits
    # of the twin alone
    assert "loss_gap_step1" not in _failed(line)
    assert {"twin_grad_difference"} <= set(_failed(line))


def test_calibration_script_reads_program_control_and_faults(
        tmp_path, monkeypatch, capsys, restore_compile_cache):
    """``benchmark/calibrate_mesh.py`` end to end at a tiny size: the
    program within the limits on every seed, the control and both faults
    outside them, the readings written where a chip call brings them
    back from."""
    from benchmark import calibrate_mesh

    root = helpers.tiny_root(tmp_path)
    mesh_helpers.lift_refusals(monkeypatch)
    monkeypatch.setattr(calibrate_mesh, "ROOT", root)
    assert calibrate_mesh.main(["--workload", CELL, "--seeds", "2",
                                "--controls", "1", "--faults", "1"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] == {
        "program": "2 of 2", "control": "0 of 1",
        "fault_no_exchange": "0 of 1", "fault_shard_pairs": "0 of 1"}
    with open(os.path.join(root, "chiprun_out",
                           f"calibrate_{CELL}.json")) as f:
        doc = json.load(f)
    assert len(doc["seeds"]) == 2
    assert doc["summary"]["program.twin_grad_difference"]["max"] < 2e-4


def test_the_programs_batch_is_held_to_the_stated_one():
    """The reference is handed the program's batch, so the driver checks
    it against its own reckoning first."""
    import numpy as np

    pos = np.arange(14).reshape(7, 2)
    mesh = {"data": 2, "model": 2}
    drv.check_batch(np.resize(pos, (8, 2)), pos, mesh)
    drv.check_batch(pos, pos, {"data": 1, "model": 4})
    for wrong in (pos, np.resize(pos, (8, 2))[::-1], pos[:6],
                  np.vstack([pos, pos[-1:]])):
        with pytest.raises(ValueError, match="multiple of 2"):
            drv.check_batch(wrong, pos, mesh)
