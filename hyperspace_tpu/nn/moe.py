"""A sparse expert layer that holds a share of its experts
(``models/moe_lm.py``).

The layer is told which experts it holds: ``first`` and ``count`` of the
router's ``n_experts``.  It routes every token over all of them and
computes the part of the result its own experts give; what the experts
held elsewhere would add is left out (on one chip the exchange that would
bring it does not exist, and nothing stands in for it):

    p = softmax(b W_r)                      (float32, over all experts)
    T = top-k of p;  w_e = scale · p_e / Σ_T p   (``norm_topk``)
    y = Σ_{e ∈ T, e held} w_e · SwiGLU_e(b)

``scoring="sigmoid"`` (DeepSeek-V3's ``noaux_tc``, one group) puts
p = sigmoid(b W_r) in softmax's place: T is chosen on p plus a
per-expert selection bias and the weights are p alone; no code here
keeps such a bias, so T is chosen on p (a bias at its initial 0), or,
``balanced``, on p less its running mean as below.

``balanced`` routing chooses T on each logit (sigmoid: each score) less
its expert's mean over the earlier rows (a per-expert bias that moves the
choice and not the weights, as auxiliary-loss-free balancing's does; row
0 chooses on its own): a drift that every token's stream shares then
moves no expert ahead of the rest, and no later row changes an earlier
row's choice.

Dispatch (:func:`plan`): each token keeps at most ``c = min(k, count)``
of its slots (the held ones; a token cannot pick more held experts than
that), so ``P = S·c`` (token, slot) pairs bound the routed rows.  The
pairs are sorted by expert and each expert's rows padded to whole row
tiles of the grouped matmul (``kernels/gmm.py``); the rows array has the
static bound ``R = P + count·tm`` and only the used tiles are computed.
:func:`dispatch` gathers the rows, :func:`combine` adds each row's
weighted result back to its token; each is a gather both ways (its
backward is the other's gather, not a scatter), and both read a row only
where it holds a routed token, so rows a kernel left unwritten are never
read.  No token is dropped.

Named scopes: ``moe/route``, ``moe/dispatch``, ``moe/gmm``,
``moe/combine``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from hyperspace_tpu.kernels.gmm import Groups, gmm

ROW_TILE = 256


class Plan(NamedTuple):
    """Where each (token, slot) pair's row lies (module doc)."""

    weight: jax.Array      # f32 [S, c]: w_e of the slot, 0 where not held
    held: jax.Array        # bool [S, c]
    pos: jax.Array         # int32 [S, c]: the pair's row (0 where not held)
    row_token: jax.Array   # int32 [R]: the token a row holds
    row_pair: jax.Array    # int32 [R]: the pair a row holds
    row_valid: jax.Array   # bool [R]: the row holds a routed token
    groups: Groups


def row_tile(pairs: int, count: int) -> int:
    """Rows of a grouped-matmul tile: ``ROW_TILE``, or the mean group
    rounded up to 8 rows where a group holds fewer."""
    return min(ROW_TILE, max(8, -(-(-(-pairs // count)) // 8) * 8))


def route(b, w_router, k: int, scale: float, norm_topk: bool,
          balanced: bool = False, scoring: str = "softmax"):
    """(top-k weights [S, k] float32, top-k experts [S, k]) of the normed
    stream b [S, d]: the router's logits in float32 at full precision
    (a choice among experts is discrete; the whole stream's logits are
    S × n_experts, a small matmul), softmax (or sigmoid, ``scoring``),
    top-k (``balanced``: chosen on the logits, or the sigmoid's scores,
    less each expert's mean over the earlier rows, module doc),
    renormalised over the k and scaled."""
    with jax.named_scope("moe/route"):
        logits = jnp.matmul(b.astype(jnp.float32), w_router,
                            precision=jax.lax.Precision.HIGHEST)
        if scoring == "sigmoid":
            p = score = jax.nn.sigmoid(logits)
        else:
            p, score = jax.nn.softmax(logits, axis=-1), logits
        if balanced:
            earlier = jnp.cumsum(score, axis=0) - score
            rows = jnp.arange(logits.shape[0], dtype=jnp.float32)[:, None]
            score = score - earlier / jnp.maximum(rows, 1.0)
        top_e = jax.lax.top_k(score, k)[1]
        top_p = jnp.take_along_axis(p, top_e, axis=-1)
        if norm_topk:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        return scale * top_p, top_e


def plan(top_w, top_e, first: int, count: int) -> Plan:
    """The layout of the held experts' rows (module doc)."""
    s, k = top_e.shape
    c = min(k, count)
    with jax.named_scope("moe/dispatch"):
        local = top_e - first
        held = (local >= 0) & (local < count)
        # the held slots first, in their top-k order; a token holds <= c
        order = jnp.argsort(~held, axis=-1, stable=True)[:, :c]
        held = jnp.take_along_axis(held, order, axis=-1)
        local = jnp.where(held, jnp.take_along_axis(local, order, axis=-1),
                          count)
        weight = jnp.where(held, jnp.take_along_axis(top_w, order, axis=-1),
                           0.0)
        pairs = s * c
        tm = row_tile(pairs, count)
        rows = -(-pairs // tm) * tm + count * tm
        flat = local.reshape(pairs)
        sizes = jnp.sum(flat[:, None] == jnp.arange(count)[None, :], axis=0,
                        dtype=jnp.int32)
        padded = -(-sizes // tm) * tm
        start = jnp.cumsum(sizes) - sizes
        pstart = jnp.cumsum(padded) - padded
        sort = jnp.argsort(flat, stable=True).astype(jnp.int32)
        rank = jnp.zeros((pairs,), jnp.int32).at[sort].set(
            jnp.arange(pairs, dtype=jnp.int32))
        e = jnp.minimum(flat, count - 1)
        pos = jnp.where(flat < count, pstart[e] + rank - start[e], rows)
        pair_ids = jnp.arange(pairs, dtype=jnp.int32)
        row_pair = jnp.zeros((rows,), jnp.int32).at[pos].set(pair_ids,
                                                             mode="drop")
        row_valid = jnp.zeros((rows,), bool).at[pos].set(True, mode="drop")
        ends = jnp.cumsum(padded)
        tiles = jnp.minimum(jnp.searchsorted(
            ends, jnp.arange(rows // tm, dtype=jnp.int32) * tm, side="right"),
            count - 1).astype(jnp.int32)
        groups = Groups(tiles, (ends[-1:] // tm).astype(jnp.int32), sizes)
        return Plan(weight, held.reshape(s, c),
                    jnp.where(flat < count, pos, 0).reshape(s, c),
                    row_pair // c, row_pair, row_valid, groups)


@jax.custom_vjp
def dispatch(x, pl_: Plan):
    """The rows [R, d]: row r holds its token's x, zero rows elsewhere."""
    return jnp.where(pl_.row_valid[:, None], x[pl_.row_token], 0)


def _dispatch_fwd(x, pl_):
    return dispatch(x, pl_), pl_


def _slots(pl_, rows, fn):
    """Σ over a token's slots j of fn(j, its slot's row of ``rows``) where
    the slot is held, one slot at a time (never an [S, c, d] gather)."""
    total = 0.0
    for j in range(pl_.pos.shape[1]):
        total = total + jnp.where(pl_.held[:, j, None],
                                  fn(j, rows[pl_.pos[:, j]]), 0.0)
    return total


def _dispatch_bwd(pl_, g):
    # each token's rows, gathered and summed: the gather of combine
    dx = _slots(pl_, g, lambda j, row: row.astype(jnp.float32))
    return dx.astype(g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(out, weight, pl_: Plan):
    """y [S, d] float32: each token's held rows times their weights."""
    return _slots(pl_, out, lambda j, row: weight[:, j, None] * row)


def _combine_fwd(out, weight, pl_):
    return combine(out, weight, pl_), (out, weight, pl_)


def _combine_bwd(res, g):
    out, weight, pl_ = res
    # a row's cotangent is its token's times its weight: the gather of
    # dispatch; a weight's is its row's result against its token's
    w_row = weight.reshape(-1)[pl_.row_pair]
    d_out = jnp.where(pl_.row_valid[:, None],
                      w_row[:, None] * g[pl_.row_token], 0.0)
    d_w = jnp.stack([jnp.where(pl_.held[:, j], jnp.sum(
        out[pl_.pos[:, j]] * g, axis=-1), 0.0)
        for j in range(pl_.pos.shape[1])], axis=1)
    return d_out.astype(out.dtype), d_w, None


combine.defvjp(_combine_fwd, _combine_bwd)


def held_experts(b, w_gate, w_up, w_down, pl_: Plan, compute):
    """Σ over the held slots of w_e · SwiGLU_e(b), float32 [S, d]: the
    rows gathered on the compute lane, three grouped matmuls, the rows
    added back to their tokens.  w_gate/w_up [count, d, f], w_down
    [count, f, d]."""
    tm = pl_.row_valid.shape[0] // pl_.groups.tiles.shape[0]
    with jax.named_scope("moe/dispatch"):
        rows = dispatch(b.astype(compute), pl_)
    with jax.named_scope("moe/gmm"):
        g = gmm(rows, w_gate, pl_.groups, tm, compute)
        u = gmm(rows, w_up, pl_.groups, tm, compute)
        h = (jax.nn.silu(g) * u).astype(compute)
        out = gmm(h, w_down, pl_.groups, tm, compute)
    with jax.named_scope("moe/combine"):
        return combine(out, pl_.weight, pl_)


@functools.partial(jax.jit, static_argnames=("k", "first", "count", "scale",
                                             "norm_topk", "compute",
                                             "scoring"))
def expert_share(b, w_router, w_gate, w_up, w_down, *, k, first, count,
                 scale, norm_topk, compute=jnp.float32, scoring="softmax"):
    """The whole layer's held share on its own (tests): route, plan and
    the held experts' weighted sum."""
    top_w, top_e = route(b, w_router, k, scale, norm_topk, scoring=scoring)
    return held_experts(b, w_gate, w_up, w_down,
                        plan(top_w, top_e, first, count), compute)
