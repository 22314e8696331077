"""The dot form's causal equal-head call at 256 lanes a head (latent
attention's decompressed heads, ``models/moe_lm.py``): the forward and
both backward kernels in interpret mode against the dense twin, at a
length whose blocks the 256-lane budget shrinks; and the blocks each
width takes, the 128-lane calls' as they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.kernels import attention as katt

def _rows_of_q_and_k_blocks(jaxpr, out):
    """{kernel name: (rows of its query block, rows of its key block)}:
    the first two array operands' block shapes, after the scalars."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            q, k = eqn.params["grid_mapping"].block_mappings[1:3]
            out[eqn.params["name"]] = tuple(
                getattr(m.block_shape[1], "block_size", m.block_shape[1])
                for m in (q, k))
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _rows_of_q_and_k_blocks(inner, out)
    return out


@pytest.mark.parametrize("d,fwd,bwd", [(128, (512, 512), (512, 256)),
                                       (256, (512, 256), (256, 256))])
def test_the_blocks_each_width_takes(interp, d, fwd, bwd):
    """At 4,096 positions, as the calls are traced: 128 lanes keep the
    blocks the looped model's and Laguna's calls ran with; 256 lanes
    halve the key block forward and both blocks backward, under the same
    VMEM budget."""
    q = jax.ShapeDtypeStruct((1, 4096, d), jnp.bfloat16)
    loss = lambda q, k, v: katt.flash_dot_attention(
        q, k, v, causal=True).astype(jnp.float32).sum()
    got = _rows_of_q_and_k_blocks(jax.make_jaxpr(jax.grad(
        loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, {})
    assert got == {"flash_dot_fwd": fwd, "flash_dot_dq": bwd,
                   "flash_dot_dkv": bwd}


def test_causal_calls_at_256_lanes_match_the_dense_twin(interp):
    """Two heads of 1,024 positions at 256 lanes: forward blocks 512 x
    256 (two query blocks, four key blocks, the diagonal's cut), backward
    256 x 256; output and the three gradients against the dense
    softmax(q kᵀ / 16 + causal) v, float32 at HIGHEST on both sides."""
    ks = jax.random.split(jax.random.PRNGKey(256), 4)
    q, k, v, w = (jax.random.normal(kk, (2, 1024, 256), jnp.float32)
                  for kk in ks)

    def value_and_grads(fn):
        weighted = lambda q, k, v: jnp.sum(fn(q, k, v) * w)
        return (fn(q, k, v),) + jax.grad(weighted, argnums=(0, 1, 2))(q, k, v)

    got = value_and_grads(lambda q, k, v: katt.flash_dot_attention(
        q, k, v, causal=True))
    want = value_and_grads(lambda q, k, v: katt._t_flash_dot(
        q, k, v, 1.0 / 16.0, True))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-5, err_msg=name)
