"""Grouped matrix multiplication for an expert layer: rows sorted by the
expert they were routed to, each row multiplied by its own expert's
matrix (``nn/moe.py``).

Layout (:class:`Groups`): the rows ``x [R, K]`` come in row tiles of
``tm`` rows, each tile holding rows of ONE expert (a group's rows are
padded up to whole tiles with zero rows); ``tiles [R / tm]`` names each
tile's expert and ``used`` how many tiles, from the first, hold routed
rows.  ``R`` is a static bound (every token routed to the held experts
at once); the routed rows of a step are far fewer, and the kernels'
time follows them:

- ``gmm_fwd`` — ``out[r] = x[r] @ W[e(r)]``, grid (N tiles, used row
  tiles): the tile table and ``used`` ride as scalar prefetch, and the
  row axis's extent is ``used`` itself (a grid bound read at run time),
  so no step is spent on a tile past it.  Rows of those tiles are left
  unwritten: callers read only rows that hold a routed token.
  Consecutive tiles of one expert read the same weight block, fetched
  once;
- ``gmm_dx`` — the same grid with the expert's matrix transposed: the
  gradient for the rows;
- ``gmm_dw`` — ``dW[e] = Σ_{r in e} x[r]ᵀ g[r]``, grid (K tiles, N
  tiles, used row tiles) with the rows innermost: a float32 accumulator is
  cleared at a group's first tile and written at its last; an expert
  with no rows is never visited, and its gradient is zeroed after.

Operands are cast to the compute lane (bf16: one MXU pass) inside the
call, products are float32, and a float32 weight's gradient leaves in
float32 (``precision.lane_matmul``'s contract).  The XLA twin
(``ragged_dot`` forward, a one-hot contraction for the weights'
gradient) serves the CPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperspace_tpu.kernels import _support as S

# bytes of one weight block a grid step may hold (double-buffered twice)
_W_BLOCK_BYTES = 2 * 1024 * 1024
# scoped VMEM a gmm_fwd / gmm_dx grid step may ask for: Mosaic's default
# limit on the v5e, 16 MiB, less 1 MiB for what _vmem_bytes leaves out
_SCOPED_VMEM = 15 * 1024 * 1024


class Groups(NamedTuple):
    """Where each expert's rows lie: ``tiles`` int32 [R / tm] (the expert
    of each row tile), ``used`` int32 [1] (tiles from the first that hold
    routed rows), ``sizes`` int32 [E] (routed rows of each expert, before
    the padding to whole tiles)."""

    tiles: jax.Array
    used: jax.Array
    sizes: jax.Array


def _lane_tile(n: int, k: int = 1, itemsize: int = 2,
               fits=lambda t: True) -> int:
    """The widest of 512/256/128 columns that divides ``n``, keeps a
    [k, tile] block under the budget and ``fits``; else the narrowest
    that divides ``n``; ``n`` whole where none does.  A tile splits the
    output's columns only, never a sum."""
    tiles = [t for t in (512, 256, 128) if n % t == 0]
    return next((t for t in tiles
                 if k * t * itemsize <= _W_BLOCK_BYTES and fits(t)),
                tiles[-1] if tiles else n)


def _vmem_bytes(tm: int, k: int, tn: int, x_size: int, w_size: int,
                out_size: int) -> int:
    """Scoped VMEM of a gmm_fwd / gmm_dx grid step, as the compiler lays
    it out for a v5e (read off it, for both cells' widths): the x, w and
    out blocks double-buffered, and, past 128 columns, the product's own
    scratch, 16 bytes an element of the x block for float32 operands
    (six MXU passes) and 2 for narrower ones."""
    blocks = 2 * (tm * k * x_size + k * tn * w_size + tm * tn * out_size)
    return blocks + (0 if tn <= 128 else tm * k * (16 if x_size >= 4 else 2))


def _precision(dtype):
    """float32 operands in truth (six MXU passes: the check twin's lane);
    narrower ones in one pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _last_used(used):
    return jnp.maximum(used[0] - 1, 0)


def _row_steps(groups: Groups):
    """The row axis's extent: the used tiles, read at run time (one step,
    which computes nothing, where no row is routed)."""
    return jnp.maximum(groups.used[0], 1)


def _fwd_body(tiles, used, x_ref, w_ref, o_ref, *, transpose: bool):
    @pl.when(pl.program_id(1) < used[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transpose else (((1,), (0,)),
                                                           ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims, preferred_element_type=jnp.float32,
            precision=_precision(x_ref.dtype)).astype(o_ref.dtype)


def _gmm_call(x, w, groups: Groups, tm: int, transpose: bool, mode_: str,
              name: str, out_dtype=jnp.float32):
    """out [R, N] of x [R, K] and w [E, K, N] (``transpose``: w [E, N, K],
    its experts' matrices transposed), a float32 product stored as
    ``out_dtype``."""
    r, k = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    tn = _lane_tile(n, k, w.dtype.itemsize, lambda t: _vmem_bytes(
        tm, k, t, x.dtype.itemsize, w.dtype.itemsize,
        jnp.dtype(out_dtype).itemsize) <= _SCOPED_VMEM)
    row = lambda j, m, tiles, used: (jnp.minimum(m, _last_used(used)), 0)
    if transpose:
        w_spec = pl.BlockSpec((1, tn, k), lambda j, m, tiles, used: (
            tiles[jnp.minimum(m, _last_used(used))], j, 0))
    else:
        w_spec = pl.BlockSpec((1, k, tn), lambda j, m, tiles, used: (
            tiles[jnp.minimum(m, _last_used(used))], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, _row_steps(groups)),
        in_specs=[pl.BlockSpec((tm, k), row), w_spec],
        out_specs=pl.BlockSpec((tm, tn), lambda j, m, tiles, used: (
            jnp.minimum(m, _last_used(used)), j)),
    )
    return pl.pallas_call(
        functools.partial(_fwd_body, transpose=transpose),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=S.interpret_flag(mode_),
        name=name,
    )(groups.tiles, groups.used, x, w)


def _dw_body(tiles, used, x_ref, g_ref, o_ref, acc_ref):
    m = pl.program_id(2)
    last = used[0] - 1

    @pl.when(m <= last)
    def _():
        e = tiles[m]
        first_of_group = jnp.logical_or(m == 0, tiles[jnp.maximum(m - 1, 0)]
                                        != e)
        last_of_group = jnp.logical_or(
            m == last, tiles[jnp.minimum(m + 1, last)] != e)

        @pl.when(first_of_group)
        def _clear():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_precision(x_ref.dtype))

        @pl.when(last_of_group)
        def _write():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _dw_call(x, g, groups: Groups, n_experts: int, tm: int, mode_: str):
    """dW [E, K, N] float32 of x [R, K] and g [R, N]; experts without
    rows are zero."""
    r, k = x.shape
    n = g.shape[1]
    tk, tn = _lane_tile(k), _lane_tile(n)   # a [tk, tn] float32 accumulator
    clamp = lambda m, used: jnp.minimum(m, _last_used(used))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k // tk, n // tn, _row_steps(groups)),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda a, b, m, tiles, used: (
                clamp(m, used), a)),
            pl.BlockSpec((tm, tn), lambda a, b, m, tiles, used: (
                clamp(m, used), b)),
        ],
        out_specs=pl.BlockSpec((1, tk, tn), lambda a, b, m, tiles, used: (
            tiles[clamp(m, used)], a, b)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
    )
    dw = pl.pallas_call(
        _dw_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, k, n), jnp.float32),
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=S.interpret_flag(mode_),
        name="gmm_dw",
    )(groups.tiles, groups.used, x, g)
    return jnp.where((groups.sizes > 0)[:, None, None], dw, 0.0)


# --- the XLA twin ---------------------------------------------------------------


def _row_experts(groups: Groups, tm: int, rows: int):
    """(expert of each row, whether its tile is in use)."""
    tile = jnp.arange(rows) // tm
    return groups.tiles[tile], tile < groups.used[0]


def _t_fwd(x, w, groups: Groups, tm: int, transpose: bool):
    if transpose:
        w = jnp.swapaxes(w, 1, 2)
    n_used_rows = groups.used[0] * tm
    counts = jnp.sum(jax.nn.one_hot(groups.tiles, w.shape[0], dtype=jnp.int32)
                     * (jnp.arange(groups.tiles.shape[0]) < groups.used[0])
                     [:, None], axis=0) * tm
    out = jax.lax.ragged_dot(x, w, counts, precision=_precision(x.dtype),
                             preferred_element_type=jnp.float32)
    return jnp.where((jnp.arange(x.shape[0]) < n_used_rows)[:, None], out,
                     0.0)


def _t_dw(x, g, groups: Groups, n_experts: int, tm: int):
    expert, live = _row_experts(groups, tm, x.shape[0])
    onehot = jax.nn.one_hot(expert, n_experts, dtype=x.dtype) * live[:, None]
    return jnp.einsum("re,rk,rn->ekn", onehot, x, g,
                      precision=_precision(x.dtype),
                      preferred_element_type=jnp.float32)


# --- the differentiable call ------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(x, w, groups, tm, compute, mode_):
    return _gmm_fwd(x, w, groups, tm, compute, mode_)[0]


def _gmm_fwd(x, w, groups, tm, compute, mode_):
    xc, wc = x.astype(compute), w.astype(compute)
    if mode_ == "xla":
        out = _t_fwd(xc, wc, groups, tm, False)
    else:
        out = _gmm_call(xc, wc, groups, tm, False, mode_, "gmm_fwd")
    return out, (xc, w, groups, jnp.zeros((0,), x.dtype))


def _gmm_bwd(tm, compute, mode_, res, g):
    xc, w, groups, x_like = res
    gc, wc = g.astype(compute), w.astype(compute)
    if mode_ == "xla":
        dx = _t_fwd(gc, wc, groups, tm, True)
        dw = _t_dw(xc, gc, groups, w.shape[0], tm)
    else:
        # the rows' gradient leaves in the rows' own dtype (rounded once,
        # from the float32 product): half the bytes of a float32 [R, K]
        dx = _gmm_call(gc, wc, groups, tm, True, mode_, "gmm_dx",
                       x_like.dtype)
        dw = _dw_call(xc, gc, groups, w.shape[0], tm, mode_)
    return dx.astype(x_like.dtype), dw.astype(w.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(x, w, groups: Groups, tm: int, compute=jnp.bfloat16):
    """``out[r] = x[r] @ w[e(r)]`` for the rows of the used tiles, float32
    [R, N]; x [R, K] with R a multiple of ``tm``, w [E, K, N].  Rows of
    tiles past ``groups.used`` are left unwritten on the kernel path (the
    twin gives zeros there): read only the rows that hold routed tokens.
    Differentiable in x and w (module doc)."""
    if x.shape[0] % tm:
        raise ValueError(f"{x.shape[0]} rows are no whole number of "
                         f"{tm}-row tiles")
    return _gmm(x, w, groups, int(tm), jnp.dtype(compute), S.mode())
