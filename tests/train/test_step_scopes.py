"""The layer scopes of the HGCN link-prediction step reach the lowered
program (``jax.named_scope`` is HLO metadata): forward and, for the two
scopes whose backward is most of the step, under ``transpose(`` too.
The scope round a ``custom_vjp`` call follows into its backward rule on
the installed jax, so ``nn/scatter.py``'s rules open none of their own
(a second ``aggregate`` would read ``aggregate/aggregate``), and
`nn.edge_dist.pair_sqdist`'s sort, gathers and kernel call land under
``pair_dist`` with ``transpose(`` in the path, where
``pair_dist_bwd_ms`` reads them."""

import re

import pytest

from tests.tiny_lp import lp_step

FORWARD = ["negatives", "loss", "optimizer", "pair_dist", "decoder",
           "encoder/conv0/linear", "encoder/conv0/aggregate",
           "encoder/conv0/act", "encoder/conv1/linear",
           "encoder/conv1/aggregate", "encoder/conv1/act"]
BACKWARD = ["pair_dist", "encoder/conv0/aggregate",
            "encoder/conv1/aggregate", "encoder/conv1/linear"]


@pytest.fixture(scope="module")
def paths():
    """{arm: the op_name paths of the lowered step, operation cut off}."""
    mp = pytest.MonkeyPatch()
    mp.setenv("HYPERSPACE_KERNELS", "interpret")
    out = {}
    try:
        for att in (False, True):
            step, state = lp_step(att)
            text = step.lower(state).as_text(debug_info=True)
            out[att] = set(re.findall(
                r'loc\("jit\(train_step_lp\)/([^"]*)/[^/"]*"', text))
    finally:
        mp.undo()
    return out


def _holds(paths, scope, backward):
    return any((p.startswith("transpose(") == backward)
               and re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", p)
               for p in paths)


@pytest.mark.parametrize("use_att", [False, True], ids=["mean", "attention"])
@pytest.mark.parametrize("scope", FORWARD)
def test_forward_scope_in_lowered_step(paths, use_att, scope):
    assert _holds(paths[use_att], scope, backward=False), sorted(
        paths[use_att])


@pytest.mark.parametrize("use_att", [False, True], ids=["mean", "attention"])
@pytest.mark.parametrize("scope", BACKWARD)
def test_backward_scope_in_lowered_step(paths, use_att, scope):
    assert _holds(paths[use_att], scope, backward=True), sorted(
        paths[use_att])


@pytest.mark.parametrize("use_att", [False, True], ids=["mean", "attention"])
def test_kernels_sit_inside_aggregate_and_nothing_is_scoped_twice(
        paths, use_att):
    kernels = ("cluster_a", "csr_")
    inside = [p for p in paths[use_att]
              if any(f"/{k}" in p for k in kernels)]
    assert inside and all("/aggregate/" in p for p in inside), inside
    assert not [p for p in paths[use_att] if "aggregate/aggregate" in p]


@pytest.mark.parametrize("use_att", [False, True], ids=["mean", "attention"])
def test_decoder_backward_sits_under_pair_dist(paths, use_att):
    """The sorted VJP's kernel call, hence the rule it is called from."""
    inside = [p for p in paths[use_att] if "pair_scatter_sum" in p]
    assert inside, sorted(paths[use_att])
    assert all(p.startswith("transpose(") and "/pair_dist/" in p
               and "pair_dist/pair_dist" not in p for p in inside), inside
