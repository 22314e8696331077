"""The grouped matmul (``kernels/gmm.py``): forward and both backward
calls, in interpret mode and as the XLA twin, against each row
multiplied by its own expert's matrix; tiles past the used ones are
neither computed nor read; an expert without rows gets a zero gradient;
the calls' own names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.kernels.gmm import Groups, gmm

TM = 8


def _groups(sizes, tiles_total):
    """Groups of ``sizes`` rows an expert, each padded to whole tiles."""
    padded = [-(-s // TM) * TM for s in sizes]
    tiles = []
    for e, p in enumerate(padded):
        tiles += [e] * (p // TM)
    used = len(tiles)
    tiles += [len(sizes) - 1] * (tiles_total - used)
    valid = np.zeros(tiles_total * TM, bool)
    expert = np.zeros(tiles_total * TM, np.int32)
    at = 0
    for e, (s, p) in enumerate(zip(sizes, padded)):
        valid[at:at + s] = True
        expert[at:at + p] = e
        at += p
    return (Groups(jnp.asarray(tiles, jnp.int32),
                   jnp.asarray([used], jnp.int32),
                   jnp.asarray(sizes, jnp.int32)), valid, expert, used)


# experts' rows: one empty, one over several tiles, one a tile exactly
CASES = {"ragged": ([5, 0, 17, 3], 10), "whole tiles": ([8, 16, 8], 6),
         "one expert": ([11], 4), "no rows": ([0, 0], 3)}


@pytest.fixture(params=["interpret", "xla"])
def kernel_mode(request, monkeypatch):
    monkeypatch.setenv("HYPERSPACE_KERNELS", request.param)
    return request.param


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_rows_meet_their_experts(kernel_mode, case, dtype):
    sizes, n_tiles = CASES[case]
    groups, valid, expert, used = _groups(sizes, n_tiles)
    e, k, n = len(sizes), 32, 24
    ks = jax.random.split(jax.random.PRNGKey(len(sizes) + n_tiles), 3)
    x = jax.random.normal(ks[0], (n_tiles * TM, k), jnp.float32)
    x = jnp.where(jnp.asarray(valid)[:, None], x, 0.0)   # padding rows 0
    w = jax.random.normal(ks[1], (e, k, n), jnp.float32)
    g = jax.random.normal(ks[2], (n_tiles * TM, n), jnp.float32)
    keep = jnp.asarray(valid)[:, None]

    def loss(x, w):
        out = gmm(x, w, groups, TM, dtype)
        return jnp.sum(jnp.where(keep, out * g, 0.0)), out

    (_, out), (dx, dw) = jax.value_and_grad(loss, (0, 1), has_aux=True)(x, w)

    def dense(x, w):
        xc, wc = x.astype(dtype).astype(jnp.float32), w.astype(dtype).astype(
            jnp.float32)
        out = jnp.einsum("rk,rkn->rn", xc, wc[jnp.asarray(expert)],
                         precision=jax.lax.Precision.HIGHEST)
        return jnp.sum(jnp.where(keep, out * g, 0.0)), out

    (_, want), (dx_w, dw_w) = jax.value_and_grad(dense, (0, 1),
                                                 has_aux=True)(x, w)
    assert out.dtype == jnp.float32 and dw.dtype == jnp.float32
    # float32 operands at full precision; bf16 ones are rounded once on
    # each side alike, and the backward rounds the cotangent to bf16 too
    tol = dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=0.15)
    np.testing.assert_allclose(np.where(valid[:, None], out, 0.0),
                               np.where(valid[:, None], want, 0.0), **tol)
    np.testing.assert_allclose(np.where(valid[:, None], dx, 0.0),
                               np.where(valid[:, None], dx_w, 0.0), **tol)
    np.testing.assert_allclose(dw, dw_w, **tol)
    for i, s in enumerate(sizes):
        if s == 0:     # never visited: zero, not what the buffer held
            assert not np.any(np.asarray(dw[i]))


def test_tiles_past_the_used_ones_are_never_read(interp):
    """Rows of unused tiles may hold anything (a kernel leaves them
    unwritten): NaN there changes no row in use and no gradient."""
    groups, valid, _, used = _groups([5, 9], 6)
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 16), jnp.float32)
    x = jnp.where(jnp.asarray(valid)[:, None], x, 0.0)
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8), jnp.float32)
    poisoned = x.at[used * TM:].set(jnp.nan)
    a = gmm(x, w, groups, TM, jnp.float32)
    b = gmm(poisoned, w, groups, TM, jnp.float32)
    np.testing.assert_array_equal(a[:used * TM], b[:used * TM])
    gw = lambda x: jax.grad(lambda w: jnp.sum(
        gmm(x, w, groups, TM, jnp.float32)[:used * TM]))(w)
    np.testing.assert_array_equal(gw(x), gw(poisoned))


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def test_the_calls_carry_their_names(interp):
    groups, _, _, _ = _groups([5, 9], 4)
    x = jnp.ones((32, 16), jnp.float32)
    w = jnp.ones((2, 16, 8), jnp.float32)
    names = _pallas_names(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(gmm(x, w, groups, TM)[:8]), (0, 1)))(x, w).jaxpr,
        [])
    assert sorted(names) == ["gmm_dw", "gmm_dx", "gmm_fwd"]


# (rows' width k, columns n, float32 result?) of the four calls of an
# expert layer at two cells' widths: d -> f and back forward (a float32
# result), the rows' gradient of each (a result in the rows' lane)
_CALLS = {"laguna_s21": [(3072, 1024, True), (1024, 3072, True),
                         (1024, 3072, False), (3072, 1024, False)],
          "glm47_flash": [(2048, 1536, True), (1536, 2048, True),
                          (1536, 2048, False), (2048, 1536, False)]}


@pytest.mark.parametrize("cell, itemsize, want", [
    ("laguna_s21", 2, [256, 512, 512, 256]),
    ("laguna_s21", 4, [128, 512, 512, 128]),
    ("glm47_flash", 2, [512, 512, 512, 512]),
    ("glm47_flash", 4, [128, 256, 256, 128])])
def test_column_tiles_keep_the_grid_step_in_scoped_vmem(cell, itemsize,
                                                        want):
    """A call's column tile at 256-row tiles: the widest whose weight
    block is under 2 MB and whose grid step stays in the v5e's scoped
    VMEM.  Laguna's calls, bf16 and float32 alike, and GLM's bf16 ones
    keep the weight block's tile; GLM's float32 [2,048, 256] block
    would ask 16.58 MiB of the 16 (the compiler's reading) and narrows."""
    from hyperspace_tpu.kernels import gmm as G

    got = [G._lane_tile(n, k, itemsize, lambda t, k=k, f=f: G._vmem_bytes(
        256, k, t, itemsize, itemsize, 4 if f else itemsize)
        <= G._SCOPED_VMEM) for k, n, f in _CALLS[cell]]
    assert got == want
    assert G._vmem_bytes(256, 2048, 256, 4, 4, 4) > G._SCOPED_VMEM
