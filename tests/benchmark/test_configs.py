"""The configuration files state what the program runs: their
``cli_overrides`` are the repo yaml's keys (plus the attention arm's
overrides that ``chip_smoke.py`` passes), and their ``model`` section is
the ``HGCNConfig`` those resolve to."""

import dataclasses
import json
import os

import pytest
import yaml

from tests.benchmark.helpers import REPO

ATT_EXTRA = ["use_att=true", "lr=0.003", "clip_norm=1.0"]


def _load(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,extra", [("hgcn_arxiv_lp", []),
                                        ("hgcn_att_arxiv_lp", ATT_EXTRA)])
def test_overrides_are_the_yamls(name, extra):
    cfg = _load(name)
    with open(os.path.join(REPO, cfg["program_yaml"])) as f:
        doc = yaml.safe_load(f)
    doc.pop("steps")  # the window sets the step count
    want = [f"{k}={json.dumps(v) if isinstance(v, list) else v}"
            for k, v in doc.items()]
    assert [o.lower() for o in cfg["cli_overrides"]] == [
        o.lower() for o in want + extra]


@pytest.mark.parametrize("name", ["hgcn_arxiv_lp", "hgcn_att_arxiv_lp"])
def test_model_section_is_the_resolved_config(name):
    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.models import hgcn
    from hyperspace_tpu.nn.decoders import FermiDiracDecoder

    cfg = _load(name)
    run, ov = T.split_overrides(cfg["cli_overrides"], T.RunConfig())
    for k in ("task", "dataset", "reorder"):
        ov.pop(k)
    base = T.hgcn_mode_defaults(hgcn.HGCNConfig(feat_dim=128), ov, False)
    got = dataclasses.asdict(T.apply_overrides(
        base, T._precision_default(run, ov)))
    model = cfg["model"]
    for k in ("kind", "c", "learn_c", "feat_dim", "use_att", "dropout", "lr",
              "weight_decay", "clip_norm", "neg_per_pos", "agg_dtype",
              "decoder_dtype"):
        assert got[k] == model[k], k
    assert list(got["hidden_dims"]) == model["hidden_dims"]
    assert got["dtype"].__name__ == model["dtype"]
    assert (FermiDiracDecoder.r_init, FermiDiracDecoder.t_init) == (
        model["decoder_r_init"], model["decoder_t_init"])
    ds = cfg["dataset"]
    assert (ds["num_nodes"], ds["num_edges"], ds["feat_dim"]) == (
        169_343, 1_166_243, 128)
