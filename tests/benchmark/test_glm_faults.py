"""Each planted fault of ``benchmark/reference/glm47_flash.py`` and the
control (the reference one step of precision down) come out not correct
under the committed limits of ``glm47_flash.pretrain4k``: the first
step's losses and gradient of the reference with the fault, handed in as
a program would hand them, against the sound reference's, at the tiny
size the configuration states, on the CPU.  A fault's reference stands
in for the twin (float32 lanes), so the twin's limits judge it."""

import json
import os

import numpy as np
import pytest

from benchmark.drivers import train_glm_lm as drv
from benchmark.drivers import train_lm as lm
from benchmark.reference import glm47_flash as ref
from tests.benchmark import helpers

CELL = "glm47_flash.pretrain4k"
SEED = 2**31 + 43


def _config():
    with open(os.path.join(helpers.REPO, "benchmark", "configs",
                           "glm47_flash.json")) as f:
        return helpers.tiny_config(json.load(f))


def _readings(config, tokens, clipped, **kw):
    """The first step's readings of the reference from the seed's
    weights (``clipped``: its gradient as its own clip left it)."""
    import jax

    model, job = lm.model_of(config), config["job"]
    opt = drv.reference_opt(config)
    with jax.enable_x64(False):
        _, (rows, main, mtp), grads = ref.loss_and_grads(
            drv.make_weights(SEED, config), tokens, model, job,
            opt["mtp_weight"], blocked=True, **kw)
    gnorm = float(ref.global_norm(grads))
    scale = np.float32(min(1.0, opt["clip_norm"] / gnorm) if clipped else 1)
    return {"losses": [float(main)], "mtp_losses": [float(mtp)],
            "held_rows": [float(r) for r in rows], "grad_norm": gnorm,
            "clip_norm": opt["clip_norm"],
            "grads": {k: np.asarray(v) * scale for k, v in grads.items()}}


@pytest.fixture(scope="module")
def sound():
    import jax

    config = _config()
    with jax.enable_x64(False):
        tokens = jax.random.randint(jax.random.PRNGKey(44), (1, 65), 0,
                                    config["vocab_size"])
    return config, tokens, _readings(config, tokens, clipped=False)


# what each fails at the tiny size (its own float32 twin against the
# sound reference's)
EXPECT = {"bf16_stream": {"twin_grad_difference", "twin_grad_norm_gap",
                          "twin_mtp_loss_gap"},
          "no_rope_key": {"twin_grad_difference", "twin_grad_norm_gap",
                          "twin_loss_gap", "twin_mtp_loss_gap"},
          "no_kv_norm": {"twin_grad_difference", "twin_grad_norm_gap",
                         "twin_loss_gap", "twin_mtp_loss_gap"},
          "nope_scale": {"twin_grad_difference", "twin_grad_norm_gap"},
          "softmax_router": {"twin_grad_difference", "twin_grad_norm_gap",
                             "twin_loss_gap", "twin_mtp_loss_gap"},
          "mtp_raw_embed": {"twin_grad_difference", "twin_grad_norm_gap",
                            "twin_mtp_loss_gap"},
          "control": {"twin_grad_difference", "twin_grad_norm_gap",
                      "twin_mtp_loss_gap"}}


@pytest.mark.parametrize("kind", ref.FAULTS + ("control",))
def test_faults_and_control_come_out_not_correct(sound, kind):
    import jax.numpy as jnp

    config, tokens, want = sound
    with open(os.path.join(helpers.REPO, "benchmark", "limits",
                           CELL + ".json")) as f:
        limits = {k: v for k, v in json.load(f)["limits"].items()
                  if k.startswith("twin_")}
    # the sound reference, handed in as a program would, passes
    scale = np.float32(min(1.0, want["clip_norm"] / want["grad_norm"]))
    same = dict(want, grads={k: v * scale for k, v in want["grads"].items()})
    assert all(v == 0 for _, v, _ in drv.compare(same, want, limits, same))
    kw = {"low": jnp.bfloat16} if kind == "control" else {"fault": kind}
    other = _readings(config, tokens, clipped=True, **kw)
    verdict = drv.compare(other, want, limits, other)
    failed = {n for n, v, lim in verdict if not v <= lim}
    assert EXPECT[kind] <= failed, verdict
