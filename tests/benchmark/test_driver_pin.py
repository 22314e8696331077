"""The driver repeats ``cli.train.run_hgcn``'s wiring, since it may not
edit it: the same configuration and seed at a tiny size give the same
first-step loss from ``cli.train.main`` and from the driver's build."""

import json
import os

import pytest

from benchmark.drivers import train_fullgraph as drv
from tests.benchmark import helpers


@pytest.mark.parametrize("config", ["hgcn_arxiv_lp", "hgcn_att_arxiv_lp"])
def test_first_step_loss_is_the_clis(config, tmp_path, capsys, monkeypatch,
                                     restore_compile_cache):
    from hyperspace_tpu.cli import train as T

    helpers.one_device(monkeypatch)

    with open(os.path.join(helpers.REPO, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = helpers.tiny_config(json.load(f))
    seed = 3
    data_root = drv.ensure_dataset(cfg, str(tmp_path / "cache"))
    pairs = cfg["cli_overrides"] + [f"seed={seed}",
                                    f"data_root={data_root}"]
    assert T.main(["hgcn", *pairs, "steps=1"]) == 0
    cli = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert cli["steps"] == 1 and cli["source"] == "disk"
    assert cli["num_nodes"] == helpers.TINY_GRAPH["num_nodes"]

    run, overrides = drv.program_run_config(cfg, {}, seed, data_root)
    built = drv.build(run, overrides, split_seed=seed)
    _, loss = built.stepper(built.state)
    assert float(loss) == pytest.approx(cli["loss"], rel=1e-6)
