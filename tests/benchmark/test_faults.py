"""``correct`` comes out false when the timed path is broken underneath.

The look for a chip is lifted, the rest of a run is driven at a tiny
size, and the fault is planted in the program, under the driver: once
for each fault a one-chip training cell can have.  The twin's limits are
the committed ones; the limits on the stated bf16 lanes' numbers are set
the way the real ones are, from a sound run of the same seed (three
times its own reading).  The sound run passes, and each fault has to
fail at least one number under the very same limits: the state left
unchanged reads 1 on the gradient and the change, half of the batch left
out fails the twin's committed limits by itself.
"""

import json
import os

import pytest

from benchmark import run as bench_run
from tests.benchmark import helpers

SEED = 12


def _run(root, cell, capsys):
    argv = ["--workload", cell, "--seed", str(SEED), "--seconds", "0.3",
            "--trace", "0"]
    assert bench_run.main(argv, root=root) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _sound_then_limits(root, cell, capsys):
    """A sound run under loose limits; then limits of three times its
    readings (an exact 0 keeps the limit 0)."""
    sound = _run(root, cell, capsys)
    assert sound["correct"] is True
    limits = {k: (v["limit"] if k.startswith("twin_") else 3.0 * v["value"])
              for k, v in sound["checks"].items()
              if k != "compiles_in_window"}
    path = os.path.join(root, "benchmark", "limits", cell + ".json")
    with open(path, "w") as f:
        json.dump({"limits": limits}, f)
    return sound


def _failed(line):
    return [k for k, v in line["checks"].items()
            if v["value"] is None or v["value"] > v["limit"]]


@pytest.mark.parametrize("cell", ["hgcn_arxiv_lp.fullgraph",
                                  "hgcn_att_arxiv_lp.fullgraph"])
def test_faults_come_out_not_correct(cell, tmp_path, monkeypatch, capsys,
                         restore_compile_cache):
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.models import hgcn

    root = helpers.tiny_root(tmp_path)
    helpers.lift_refusals(monkeypatch)
    _sound_then_limits(root, cell, capsys)
    again = _run(root, cell, capsys)
    assert again["correct"] is True, _failed(again)

    real = hgcn.train_step_lp

    def unchanged(model, opt, n, state, g, train_pos):
        keep = jax.tree_util.tree_map(jnp.array, state)
        _, loss = real(model, opt, n, state, g, train_pos)
        return keep, loss

    monkeypatch.setattr(hgcn, "train_step_lp", unchanged)
    line = _run(root, cell, capsys)
    assert line["correct"] is False
    # nothing moved: the gradient read from Adam's moment and the
    # parameters' change both read 1 by the gap's measure
    assert line["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)

    def half_batch(model, opt, n, state, g, train_pos):
        return real(model, opt, n, state, g,
                    train_pos[: train_pos.shape[0] // 2])

    monkeypatch.setattr(hgcn, "train_step_lp", half_batch)
    line = _run(root, cell, capsys)
    assert line["correct"] is False
    # under the committed limits of the twin alone
    assert {"twin_grad_difference"} <= set(_failed(line))
