"""Every Pallas call of the HGCN link-prediction step carries its
kernel's public name (``pl.pallas_call(name=...)``): the device profile
then shows ``%cluster_aggregate.<k>`` where it showed ``%conv0.<k>``,
and the benchmark's per-kernel metrics match on it."""

import collections

import jax
import pytest

from tests.tiny_lp import lp_step

# kernel -> the pallas_calls one step makes of it (two layers; forward
# and backward where the backward reuses the kernel).  The decoder's
# backward (nn.edge_dist.pair_sqdist) makes two: rows_to_columns turns its
# re-gathered rows, pair_scatter_sum sums the cotangent rows, a
# block-CSR sum under a name of its own, so that a trace tells the
# decoder's call from the aggregation's four
DECODER = {"rows_to_columns": 1, "pair_scatter_sum": 1}
MEAN = {"cluster_aggregate": 4, "csr_segment_sum": 4, **DECODER}
ATT = {"cluster_att_fwd": 2, "cluster_att_bwd": 2, "csr_segment_sum": 4,
       "csr_segment_reduce_1d": 2, "csr_att_bwd_edges": 2,
       "csr_segment_expand_1d": 2, **DECODER}


def _sub_jaxprs(params):
    for v in params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _pallas_names(jaxpr) -> collections.Counter:
    names = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names[eqn.params["name"]] += 1
        else:
            for inner in _sub_jaxprs(eqn.params):
                names += _pallas_names(inner)
    return names


@pytest.fixture(scope="module")
def names(interp_module):
    return {att: _pallas_names(jax.make_jaxpr(lp_step(att)[0])(
        lp_step(att)[1]).jaxpr) for att in (False, True)}


@pytest.fixture(scope="module")
def interp_module():
    mp = pytest.MonkeyPatch()
    mp.setenv("HYPERSPACE_KERNELS", "interpret")
    yield
    mp.undo()


@pytest.mark.parametrize("use_att,want", [(False, MEAN), (True, ATT)],
                         ids=["mean", "attention"])
def test_every_pallas_call_of_the_step_is_named(names, use_att, want):
    assert dict(names[use_att]) == want


@pytest.mark.parametrize("kernel", sorted(set(MEAN) | set(ATT)))
def test_each_public_kernel_name_is_on_the_path(names, kernel):
    assert names[False][kernel] + names[True][kernel] > 0
