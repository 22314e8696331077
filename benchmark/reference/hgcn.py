"""Plain reference for full-graph HGCN link prediction (Chami et al.,
NeurIPS 2019), Lorentz model, as the repo's configurations state it.

Straight ``jax.numpy`` in float32: no kernels, no plans, no sorted
layouts, no sharding.  Matmuls run at the precision the configuration
states (``matmul_precision``; the repo's yaml leaves jax's default, which
on the v5e is one bf16 pass with float32 accumulation).  It imports nothing of
``hyperspace_tpu`` and is handed nothing the program derived: the raw
dataset arrays, the node relabeling (checked to be a permutation), the
training pairs (checked to be a subset of the dataset's undirected
edges), the weights and the PRNG key the harness made from ``--seed``.

The model, as the configuration file states it (``c`` = 1):

    h0      = exp0(x)                                   features are origin-tangent coordinates
    layer l : u = log0(h) ; m = u W_l + b_l ; a = AGG(m) ; h = exp0(act(a))
    AGG mean: a_i = sum_{j in N(i)} m_j / |N(i)|        N(i) has i itself; messages in `agg_dtype`
    AGG att : w_ij = exp(B tanh(leaky(m_j.a_s + m_i.a_d, .2) / B)), B = 30
              a_i  = sum_j w_ij m_j / sum_j w_ij
    decoder : logit_uv = (r - d(z_u, z_v)^2) / (softplus(t_raw) + 1e-4)   distance pass in `decoder_dtype`
    loss    : mean BCE over the training pairs (label 1) and as many uniform random pairs (label 0)
    update  : clip by global norm (if set), then AdamW(lr, wd)

Departures from the paper, shared with the program and noted here: the
aggregation runs in the tangent space at the origin, the bias is added
there, and attention logits are squashed to +-30 instead of max-shifted.

``compute`` is the dtype of everything the configuration leaves at
float32.  The control of ``correct`` takes every part one step down:
``compute`` bfloat16 and, where a lane is stated as bf16, that lane's
values stored in fp8 (``CONTROL_FP8_LANES``: e4m3 for the messages, e5m2
for the decoder's points, whose range e4m3 cannot hold), forward only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ATT_BOUND = 30.0
LEAKY_SLOPE = 0.2
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
MAX_ARG = 40.0  # largest argument given to cosh/sinh in float32
CONTROL_FP8_LANES = {"agg": "float8_e4m3fn", "decoder": "float8_e5m2"}


# --- the graph, from raw pairs -------------------------------------------


def check_permutation(order: np.ndarray, n: int) -> None:
    order = np.asarray(order)
    if order.shape != (n,) or not np.array_equal(np.sort(order),
                                                 np.arange(n)):
        raise ValueError("node order is not a permutation of the nodes")


def canonical_keys(pairs: np.ndarray, n: int) -> np.ndarray:
    p = np.sort(np.asarray(pairs, np.int64), axis=1)
    return p[:, 0] * n + p[:, 1]


def check_training_pairs(train_pos, raw_edges, order, n: int,
                         val_frac: float, test_frac: float) -> None:
    """The training pairs are distinct undirected edges of the dataset
    (in the relabeled ids) and as many as the split fractions leave."""
    rank = np.empty(n, np.int64)
    rank[np.asarray(order)] = np.arange(n)
    canon = np.unique(canonical_keys(rank[np.asarray(raw_edges)], n))
    keys = canonical_keys(train_pos, n)
    if len(np.unique(keys)) != len(keys):
        raise ValueError("training pairs repeat an edge")
    if not np.isin(keys, canon).all():
        raise ValueError("a training pair is not an edge of the dataset")
    want = len(canon) - int(len(canon) * val_frac) - int(len(canon)
                                                         * test_frac)
    if len(keys) != want:
        raise ValueError(f"{len(keys)} training pairs, the split leaves "
                         f"{want}")


def message_graph(train_pos: np.ndarray, n: int):
    """(senders, receivers, 1/deg per edge): both directions of every
    training pair plus one self-loop a node, duplicates dropped, in
    receiver order."""
    e = np.asarray(train_pos, np.int64)
    loops = np.stack([np.arange(n)] * 2, axis=1)
    e = np.concatenate([e, e[:, ::-1], loops], axis=0)
    key = np.unique(e[:, 1] * n + e[:, 0])
    recv, send = key // n, key % n
    deg = np.bincount(recv, minlength=n).astype(np.float32)
    return (send.astype(np.int32), recv.astype(np.int32),
            (1.0 / deg[recv]).astype(np.float32))


# --- Lorentz maps at the origin ------------------------------------------


def _eps(dtype) -> float:
    return 1e-7 if jnp.dtype(dtype) == jnp.float32 else 1e-4


@jax.custom_jvp
def _sqrt(x):
    """sqrt with the stated guard at 0: the value clamps at 0 and the
    derivative is bounded by 1 / (2 sqrt(eps)), so a zero vector's norm
    and a zero distance have finite gradients."""
    return jnp.sqrt(jnp.maximum(x, 0.0))


@_sqrt.defjvp
def _sqrt_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    y = _sqrt(x)
    return y, t / jnp.maximum(2.0 * y, 2.0 * jnp.sqrt(
        jnp.asarray(_eps(y.dtype), y.dtype)))


def _norm(u):
    return _sqrt(jnp.sum(u * u, axis=-1, keepdims=True))


def exp0(u):
    """Origin-tangent coordinates [.., d] -> hyperboloid point [.., d+1]."""
    n = _norm(u)
    t = jnp.minimum(n, MAX_ARG)
    scale = jnp.where(t < 1e-3, 1.0 + t * t / 6.0,
                      jnp.sinh(t) / jnp.maximum(t, 1e-3))
    space = scale * u
    time = _sqrt(1.0 + jnp.sum(space * space, axis=-1, keepdims=True))
    return jnp.concatenate([time, space], axis=-1)


def log0(x):
    """Hyperboloid point -> origin-tangent coordinates: arcosh(x0) along
    the space part; arcosh(x0) = arsinh(|space|) on the hyperboloid."""
    space = x[..., 1:]
    n = _norm(space)
    d = jnp.log(n + _sqrt(1.0 + n * n))
    tiny = 1e-12 if x.dtype == jnp.float32 else 1e-7
    return d * space / jnp.maximum(n, tiny)


def sqdist(x, y):
    """Squared geodesic distance: arcosh(-<x, y>_L)^2, in x's dtype."""
    inner = (jnp.sum(x[..., 1:] * y[..., 1:], axis=-1)
             - x[..., 0] * y[..., 0])
    u = jnp.maximum(-inner - 1.0, 0.0)
    d = jnp.log1p(u + _sqrt(u * (u + 2.0)))
    return d * d


# --- the model -----------------------------------------------------------


@jax.custom_vjp
def _through(x, like):
    """x rounded to ``like``'s dtype and back, forward only: how a lane
    whose values are *stored* one notch lower reads them.  The backward
    passes the cotangent unchanged."""
    return x.astype(like.dtype).astype(x.dtype)


_through.defvjp(lambda x, like: (_through(x, like), None),
                lambda _, g: (g, None))


def _stored(x, cfg, lane: str):
    """The control's fp8 step for a bf16 lane (``cfg["fp8_lanes"]``)."""
    kind = (cfg.get("fp8_lanes") or {}).get(lane)
    return x if kind is None else _through(x, jnp.zeros((), jnp.dtype(kind)))


def _aggregate(m, params, layer, graph, cfg):
    send, recv, inv_deg = graph
    n = m.shape[0]
    lane = jnp.dtype(cfg["agg_dtype"] or m.dtype)
    if cfg["use_att"]:
        a_s = (m @ params[f"a_src{layer}"].astype(m.dtype))[:, 0]
        a_d = (m @ params[f"a_dst{layer}"].astype(m.dtype))[:, 0]
        pre = a_s[send] + a_d[recv]
        pre = jnp.where(pre >= 0, pre, LEAKY_SLOPE * pre)
        w = jnp.exp(ATT_BOUND * jnp.tanh(pre / ATT_BOUND)).astype(lane)
        msg = w[:, None] * _stored(m.astype(lane), cfg, "agg")[send]
        num = jax.ops.segment_sum(msg.astype(jnp.float32), recv, n)
        den = jax.ops.segment_sum(w.astype(jnp.float32), recv, n)
        return (num / jnp.maximum(den, 1e-15)[:, None]).astype(m.dtype)
    msg = inv_deg.astype(lane)[:, None] * _stored(m.astype(lane), cfg,
                                                  "agg")[send]
    return jax.ops.segment_sum(msg.astype(jnp.float32), recv,
                               n).astype(m.dtype)


def embed(params, x, graph, cfg, compute=jnp.float32):
    h = exp0(x.astype(compute))
    layers = len(cfg["hidden_dims"])
    for i in range(layers):
        u = log0(h)
        m = u @ params[f"W{i}"].astype(compute) + params[f"b{i}"].astype(
            compute)
        a = _aggregate(m, params, i, graph, cfg)
        h = exp0(a if i == layers - 1 else jnp.maximum(a, 0.0))
    return h


def loss_fn(params, x, graph, pairs, labels, cfg, compute=jnp.float32):
    z = embed(params, x, graph, cfg, compute)
    lane = jnp.dtype(cfg["decoder_dtype"] or z.dtype)
    z = _stored(z.astype(lane), cfg, "decoder")
    sq = sqdist(z[pairs[:, 0]], z[pairs[:, 1]]).astype(jnp.float32)
    t = jax.nn.softplus(params["t_raw"]) + 1e-4
    logit = (params["r"] - sq) / t
    # BCE with logits: softplus(logit) - label * logit
    return jnp.mean(jax.nn.softplus(logit) - labels * logit)


def negatives(key, count: int, n: int):
    """The step's feed of negatives from the carried key, as the
    configuration states it: (next key, [count, 2] uniform node pairs)."""
    key, k_neg, _ = jax.random.split(key, 3)
    return key, jax.random.randint(k_neg, (count, 2), 0, n)


def _leaf_norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32))))) for k, v in tree.items()}


def clip_by_global_norm(grads, max_norm: float):
    if not max_norm or max_norm <= 0:
        return grads
    g = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in grads.values()))
    scale = jnp.where(g < max_norm, 1.0, max_norm / g)
    return {k: v * scale for k, v in grads.items()}


def adamw(params, grads, mu, nu, count, lr, wd):
    out_p, out_mu, out_nu = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        m = ADAM_B1 * mu[k] + (1.0 - ADAM_B1) * g
        v = ADAM_B2 * nu[k] + (1.0 - ADAM_B2) * g * g
        m_hat = m / (1.0 - ADAM_B1 ** count)
        v_hat = v / (1.0 - ADAM_B2 ** count)
        out_p[k] = p - lr * (m_hat / (jnp.sqrt(v_hat) + ADAM_EPS) + wd * p)
        out_mu[k], out_nu[k] = m, v
    return out_p, out_mu, out_nu


def train_steps(params, key, x, graph, train_pos, cfg, steps: int = 3,
                compute=jnp.float32):
    """Follow the first ``steps`` optimizer steps.

    Returns the loss of each step, the first gradient as the optimizer
    gets it (after the clip) and the parameters' change over the steps,
    each leaf by leaf with its norm.
    """
    n = x.shape[0]
    pos = jnp.asarray(np.asarray(train_pos), jnp.int32)
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
    precision = cfg.get("matmul_precision", "default")
    with jax.default_matmul_precision(precision):
        for step in range(1, steps + 1):
            key, neg = negatives(key, n_neg, n)
            pairs = jnp.concatenate([pos, neg.astype(jnp.int32)], axis=0)
            loss, grads = value_and_grad(params, x, graph, pairs, labels)
            grads = clip_by_global_norm(grads, cfg.get("clip_norm", 0.0))
            if grad_norms is None:
                grad_norms, first = _leaf_norms(grads), grads
            params, mu, nu = adamw(params, grads, mu, nu, step,
                                   cfg["lr"], cfg["weight_decay"])
            losses.append(float(loss))
    moved = {k: params[k] - start[k] for k in params}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _leaf_norms(moved),
            "grads": {k: np.asarray(v, np.float32) for k, v in first.items()}}
