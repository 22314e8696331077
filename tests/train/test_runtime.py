"""Train-runtime tests: checkpoint round-trip with re-projection, JSONL
logging, benchmark harness, CLI override plumbing (SURVEY.md §5)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.manifolds import PoincareBall
from hyperspace_tpu.train.checkpoint import CheckpointManager, reproject_params
from hyperspace_tpu.train.logging import MetricsLogger, read_jsonl
from hyperspace_tpu.train.profiling import benchmark_step, compiled_cost


def test_checkpoint_roundtrip(tmp_path):
    state = {
        "params": {"table": jnp.linspace(0, 1, 12).reshape(3, 4)},
        "step": jnp.asarray(7, jnp.int32),
        "key": jax.random.PRNGKey(3),
    }
    with CheckpointManager(str(tmp_path / "ckpt"), async_save=False) as mgr:
        assert mgr.save(7, state)
        mgr.wait()
        zeros = jax.tree_util.tree_map(jnp.zeros_like, state)
        restored, step = mgr.restore(zeros)
    assert step == 7
    np.testing.assert_allclose(
        np.asarray(restored["params"]["table"]), np.asarray(state["params"]["table"]))
    assert int(restored["step"]) == 7


def test_checkpoint_restore_reprojects(tmp_path):
    ball = PoincareBall(1.0)
    params = {"emb": jnp.asarray([[0.999999, 0.0], [0.1, 0.2]]),
              "dense": jnp.ones((2, 2))}
    tags = {"emb": ball, "dense": None}
    with CheckpointManager(str(tmp_path / "c2"), async_save=False) as mgr:
        mgr.save(0, params)
        mgr.wait()
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        restored, _ = mgr.restore(zeros, project=reproject_params(tags, params))
    # on-ball leaf got clamped inside the boundary; Euclidean untouched
    assert float(jnp.linalg.norm(restored["emb"][0])) < 1.0
    np.testing.assert_allclose(np.asarray(restored["dense"]), 1.0)


def test_restore_skips_uncommitted_step_dir(tmp_path):
    """An interrupted save's leftover (empty) step dir must not become
    the restore target: restore(step=None) and peek_latest_step must
    agree on the newest COMMITTED step, or the stream resume offset
    desyncs from the restored state (ADVICE r5)."""
    from hyperspace_tpu.train.checkpoint import peek_latest_step

    d = tmp_path / "c4"
    with CheckpointManager(str(d), async_save=False) as mgr:
        mgr.save(5, {"x": jnp.asarray(5)})
        mgr.wait()
        (d / "9").mkdir()  # interrupted save: all-digit but uncommitted
        assert mgr.latest_committed_step() == 5
        restored, step = mgr.restore({"x": jnp.asarray(0)})
    assert step == 5 and int(restored["x"]) == 5
    assert peek_latest_step(str(d)) == 5  # the two accountings agree


def test_checkpoint_interval_and_retention(tmp_path):
    with CheckpointManager(str(tmp_path / "c3"), async_save=False,
                           max_to_keep=2, save_interval_steps=5) as mgr:
        for s in range(12):
            mgr.save(s, {"x": jnp.asarray(s)})
        mgr.wait()
        assert mgr.latest_step() == 10
        restored, step = mgr.restore({"x": jnp.asarray(0)})
    assert int(restored["x"]) == 10


@pytest.mark.slow
def test_metrics_logger_tensorboard_sink(tmp_path):
    pytest.importorskip("torch.utils.tensorboard")
    tb_dir = str(tmp_path / "tb")
    with MetricsLogger(str(tmp_path / "m.jsonl"),
                       tensorboard_dir=tb_dir) as lg:
        lg.log(1, loss=0.5)
        lg.log(2, loss=0.25)
    assert any(f.startswith("events.") for f in os.listdir(tb_dir))


def test_metrics_logger(tmp_path):
    p = str(tmp_path / "m.jsonl")
    with MetricsLogger(p) as log:
        log.log(1, loss=0.5)
        log.log(2, loss=0.25, roc_auc=0.9)
    recs = read_jsonl(p)
    assert [r["step"] for r in recs] == [1, 2]
    assert recs[1]["roc_auc"] == 0.9
    assert all("ts" in r for r in recs)


def test_metrics_logger_event_records(tmp_path):
    p = str(tmp_path / "e.jsonl")
    with MetricsLogger(p) as log:
        log.event("run_manifest", config={"steps": 5}, backend="cpu")
        # one bad field reprs ONLY itself — siblings keep their structure
        log.event("weird", blob=object(), config={"steps": 7})
    recs = read_jsonl(p)
    assert recs[0]["event"] == "run_manifest"
    assert recs[0]["config"] == {"steps": 5}
    assert recs[1]["event"] == "weird" and "object" in recs[1]["blob"]
    assert recs[1]["config"] == {"steps": 7}


def test_benchmark_step_runs():
    f = jax.jit(lambda: jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    stats = benchmark_step(f, warmup=1, iters=3)
    assert stats["iters"] == 3
    assert stats["min_s"] <= stats["mean_s"] <= stats["max_s"]


def test_benchmark_step_warmup_zero_regression():
    # warmup=0 used to hit `out` unbound before block_until_ready
    # (NameError); an intentionally-cold timing run must just work
    f = jax.jit(lambda: jnp.ones((4, 4)) * 2)
    stats = benchmark_step(f, warmup=0, iters=2)
    assert stats["iters"] == 2 and stats["min_s"] > 0


def test_compiled_cost_reports_flops():
    cost = compiled_cost(lambda a, b: a @ b, jnp.ones((16, 16)), jnp.ones((16, 16)))
    if cost:  # backend-dependent; CPU provides it
        assert cost.get("flops", 0) > 0


def test_cost_analysis_dict_shapes():
    """A dict comes back as a plain dict, a backend without analysis
    (None) as {}, and a backend error is not swallowed."""
    import pytest

    from hyperspace_tpu.train.profiling import cost_analysis_dict

    class Fake:
        def __init__(self, ret=None, raise_=False):
            self._ret, self._raise = ret, raise_

        def cost_analysis(self):
            if self._raise:
                raise RuntimeError("no analysis on this backend")
            return self._ret

    assert cost_analysis_dict(Fake({"flops": 2.0})) == {"flops": 2.0}
    assert cost_analysis_dict(Fake(None)) == {}
    with pytest.raises(RuntimeError):
        cost_analysis_dict(Fake(raise_=True))


def test_read_jsonl_tolerates_truncated_final_line(tmp_path):
    import pytest

    p = tmp_path / "crashed.jsonl"
    p.write_text('{"step": 1, "loss": 0.5}\n{"step": 2, "lo')  # hard kill
    recs = read_jsonl(str(p))
    assert [r["step"] for r in recs] == [1]
    # corruption in the MIDDLE is a real error, not a crash artifact
    p2 = tmp_path / "corrupt.jsonl"
    p2.write_text('{"step": 1}\nnot json at all\n{"step": 3}\n')
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(str(p2))


def test_cli_override_coercion():
    from hyperspace_tpu.cli.train import RunConfig, apply_overrides, split_overrides

    run, wl = split_overrides(["steps=12", "lr=0.5", "multihost=true"], RunConfig())
    assert run.steps == 12 and run.multihost is True
    assert wl == {"lr": "0.5"}

    from hyperspace_tpu.models.hgcn import HGCNConfig

    cfg = apply_overrides(HGCNConfig(), {"lr": "0.5", "hidden_dims": "[8, 4]",
                                         "use_att": "true"})
    assert cfg.lr == 0.5 and tuple(cfg.hidden_dims) == (8, 4) and cfg.use_att is True
    with pytest.raises(SystemExit):
        apply_overrides(HGCNConfig(), {"nope": "1"})


@pytest.mark.slow
def test_cli_end_to_end_poincare(tmp_path, capsys):
    from hyperspace_tpu.cli import train as cli

    rc = cli.main(["poincare", "steps=30", "dim=4", "batch_size=32",
                   f"log={tmp_path}/run.jsonl"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    res = json.loads(out)
    assert res["workload"] == "poincare" and "map" in res
    assert os.path.exists(tmp_path / "run.jsonl")


@pytest.mark.slow
def test_cli_checkpoint_resume_poincare(tmp_path, capsys):
    """Interrupted-and-resumed CLI run matches an uninterrupted one: the
    checkpoint carries table, RSGD count, and PRNG key, so steps
    [k, N) replay identically (restart-from-checkpoint recovery model)."""
    from hyperspace_tpu.cli import train as cli

    common = ["poincare", "dim=4", "batch_size=32", "neg_samples=4"]

    cli.main(common + ["steps=20", f"ckpt_dir={tmp_path}/full", "ckpt_every=1"])
    full = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    cli.main(common + ["steps=12", f"ckpt_dir={tmp_path}/ab", "ckpt_every=1"])
    capsys.readouterr()
    cli.main(common + ["steps=20", f"ckpt_dir={tmp_path}/ab", "ckpt_every=1",
                       "resume=true"])
    resumed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert resumed["map"] == pytest.approx(full["map"], abs=1e-9)
    assert resumed["mean_rank"] == pytest.approx(full["mean_rank"], abs=1e-9)


@pytest.mark.slow
def test_cli_scan_chunk_poincare(tmp_path, capsys):
    """scan_chunk trains through train_epoch_scan with the step budget
    rounded up to a chunk multiple, and checkpoint steps stay truthful."""
    from hyperspace_tpu.cli import train as cli
    from hyperspace_tpu.train.checkpoint import CheckpointManager

    rc = cli.main(["poincare", "steps=20", "scan_chunk=8", "dim=4",
                   "batch_size=32", f"ckpt_dir={tmp_path}/ck"])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["steps"] == 24  # 20 rounded up to a multiple of 8
    with CheckpointManager(f"{tmp_path}/ck") as ck:
        assert ck.latest_step() == 24
