"""The plain reference of ``benchmark/reference/hgcn.py`` for a graph
whose edge and pair arrays do not fit one chip whole: the same model,
loss and update from the same functions, with the two passes that are
linear in the edges and in the pairs computed block by block.

One device, the whole graph, float32, no ``shard_map``, no kernels, no
knowledge of how the program shards anything.  At 11-12 M message edges
a ``[E, 128]`` array of messages is 6 GB in float32 and autodiff keeps
one for each layer, so

- the mean aggregation sums the messages of ``EDGE_BLOCK`` edges at a
  time into the ``[n, f]`` result (a ``lax.scan`` whose body is linear
  in the messages: nothing is kept for the backward but the indices);
- the decoder scores ``PAIR_BLOCK`` pairs at a time and sums their BCE
  terms (the body rematerialised in the backward, so no ``[P, 33]``
  array is kept either).

At float32 lanes, float32 sums in another order are all that separates
this from the unblocked reference; at bf16 lanes the backward's sum over
a node's edges is float32 here where the unblocked gather's transpose
sums in the lane's type (a test holds the two together at a size both
fit, each lane at its own tolerance).
Mean aggregation only: the configuration that uses this file has no
attention arm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import hgcn as ref

EDGE_BLOCK = 1 << 20
PAIR_BLOCK = 1 << 20


def _blocks(arrays, block: int, fill):
    """Each array padded with its ``fill`` to a whole number of blocks
    and reshaped ``[blocks, block, ...]``."""
    size = arrays[0].shape[0]
    pad = -size % block
    out = []
    for a, f in zip(arrays, fill):
        if pad:
            a = jnp.concatenate(
                [a, jnp.full((pad,) + a.shape[1:], f, a.dtype)])
        out.append(a.reshape((-1, block) + a.shape[1:]))
    return out


def aggregate(m, graph, cfg):
    """``ref._aggregate``'s mean arm, ``EDGE_BLOCK`` edges at a time:
    padding edges carry weight 0."""
    send, recv, inv_deg = graph
    lane = jnp.dtype(cfg["agg_dtype"] or m.dtype)
    # the lane's values, held in float32 (exactly): the backward then
    # sums a node's cotangent over the blocks in float32, as the forward
    # sums its messages, and rounds it to the lane once
    stored = ref._stored(m.astype(lane), cfg, "agg").astype(jnp.float32)
    blocks = _blocks((send, recv, inv_deg), EDGE_BLOCK, (0, 0, 0.0))

    def body(acc, blk):
        s, r, w = blk
        msg = w.astype(lane)[:, None] * stored[s].astype(lane)
        return acc.at[r].add(msg.astype(jnp.float32)), None

    acc, _ = jax.lax.scan(body, jnp.zeros(m.shape, jnp.float32),
                          blocks)
    return acc.astype(m.dtype)


def embed(params, x, graph, cfg, compute=jnp.float32):
    """``ref.embed`` with the blocked aggregation."""
    if cfg["use_att"]:
        raise ValueError("the blocked reference has the mean arm only")
    h = ref.exp0(x.astype(compute))
    layers = len(cfg["hidden_dims"])
    for i in range(layers):
        u = ref.log0(h)
        m = u @ params[f"W{i}"].astype(compute) + params[f"b{i}"].astype(
            compute)
        a = aggregate(m, graph, cfg)
        h = ref.exp0(a if i == layers - 1 else jnp.maximum(a, 0.0))
    return h


def loss_fn(params, x, graph, pairs, labels, cfg, compute=jnp.float32):
    """``ref.loss_fn``: the mean BCE over the pairs, ``PAIR_BLOCK`` of
    them at a time."""
    z = embed(params, x, graph, cfg, compute)
    lane = jnp.dtype(cfg["decoder_dtype"] or z.dtype)
    z = ref._stored(z.astype(lane), cfg, "decoder")
    t = jax.nn.softplus(params["t_raw"]) + 1e-4
    count = pairs.shape[0]
    live = jnp.ones(count, jnp.float32)
    blocks = _blocks((pairs, labels, live), PAIR_BLOCK, (0, 0.0, 0.0))

    @jax.checkpoint
    def body(total, blk):
        p, y, keep = blk
        sq = ref.sqdist(z[p[:, 0]], z[p[:, 1]]).astype(jnp.float32)
        logit = (params["r"] - sq) / t
        return total + jnp.sum(keep * (jax.nn.softplus(logit)
                                       - y * logit)), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), blocks)
    return total / count


def train_steps(params, key, x, graph, pairs_pos, cfg, steps: int = 3,
                compute=jnp.float32):
    """``ref.train_steps`` over the blocked loss: the same negatives, the
    same clip and AdamW, the same readings.  ``pairs_pos`` is the batch
    of positives the program trains on, as the configuration states it
    (the training pairs, repeated up to the batch's stated size)."""
    n = x.shape[0]
    pos = jnp.asarray(np.asarray(pairs_pos), jnp.int32)
    n_neg = pos.shape[0] * int(cfg.get("neg_per_pos", 1))
    labels = jnp.concatenate([jnp.ones(pos.shape[0], jnp.float32),
                              jnp.zeros(n_neg, jnp.float32)])
    x = jnp.asarray(x, jnp.float32)
    graph = tuple(jnp.asarray(a) for a in graph)
    cfg = dict(cfg)

    @jax.jit
    def value_and_grad(p, x, graph, pairs, labels):
        return jax.value_and_grad(loss_fn)(p, x, graph, pairs, labels, cfg,
                                           compute)

    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    start = params
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad_norms, first = [], None, None
    with jax.default_matmul_precision(cfg.get("matmul_precision",
                                              "default")):
        for step in range(1, steps + 1):
            key, neg = ref.negatives(key, n_neg, n)
            pairs = jnp.concatenate([pos, neg.astype(jnp.int32)], axis=0)
            loss, grads = value_and_grad(params, x, graph, pairs, labels)
            grads = ref.clip_by_global_norm(grads, cfg.get("clip_norm", 0.0))
            if grad_norms is None:
                grad_norms, first = ref._leaf_norms(grads), grads
            params, mu, nu = ref.adamw(params, grads, mu, nu, step,
                                       cfg["lr"], cfg["weight_decay"])
            losses.append(float(loss))
    moved = {k: params[k] - start[k] for k in params}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": ref._leaf_norms(moved),
            "grads": {k: np.asarray(v, np.float32) for k, v in first.items()}}
