"""Plain reference of Laguna (poolside, huggingface.co/poolside/Laguna-S-2.1
config.json): forward, loss, gradients and AdamW steps in straightforward
``jax.numpy``, float32, every matmul under
``jax.default_matmul_precision("highest")``, dense attention with an
explicit mask, a Python loop over the layers and over the held experts.
No scan, no kernel, no sorting of tokens by expert.  Nothing of
``hyperspace_tpu`` is imported.

    RMSNorm_g(x) = x / sqrt(mean(x^2) + eps) * g
    one layer (pre-norm), H_l = num_attention_heads_per_layer[l]:
        a = RMSNorm(h); q = a Wq (H_l heads of head_dim), k, v = a Wk, a Wv
            (num_key_value_heads); query head g reads K/V head g // (H_l/H_kv)
        full_attention layers: YaRN rotary (rope_parameters.full_attention)
            on the first partial_rotary_factor of each head's lanes, cos and
            sin times attention_factor, the other lanes as they are;
        sliding_attention layers: plain rotary (theta 1e4) on all lanes and
            the mask also needs key > query - sliding_window
        o_g = softmax(q_g k^T / sqrt(head_dim) + mask) v
        o_g <- sigmoid(a W_gate)_g * o_g                (gating: per-head)
        h <- h + concat(o) Wo
        b = RMSNorm(h)
        dense layers (mlp_only_layers):  h <- h + SwiGLU(b)
        sparse layers: p = softmax(b W_router) over all the router's
            experts; T = top-k(p); w_e = scale * p_e / sum_T p;
            h <- h + sum_{e in T, e held} w_e SwiGLU_e(b) + SwiGLU_shared(b)
    the model: h = E[tokens]; the layers; RMSNorm; z = h Whead (untied);
        loss = mean cross-entropy against the next token

The expert share: the router is as wide as the deployment (the held
``num_experts`` times ``expert_shards``), and the layer adds the held
experts' part alone (experts ``expert_share * num_experts`` onwards), as
the program does; :func:`expert_layer` with every expert held is the
uncut layer the tests tie the shares to.

Set by this reference where the config says nothing (the configuration's
``assumed`` carries the same list): the router's scoring is a softmax
(no scoring_func, no correction bias, softcap 0); no q/k norm; the shared
expert has no gate; the per-head gate reads the attention's normed input
a; no auxiliary balancing loss; the recipe is AdamW 0.9/0.95/1e-8, decay
0.1 on matrices, lr 3e-4, global-norm clip 1.0, N(0, 0.02^2) init.

Set by the cut (a share held: the job's ``expert_shards`` > 1), so that
the held experts keep a deployment share's load: the router keeps its
initial weights (no update, no decay) and its scores pass no gradient,
to it or to b (the top-k weights still scale the experts' outputs); and
T is chosen on r_e(t) - mean_{s<t} r_e(s), each logit less its expert's
mean over the sequence's earlier tokens (token 0 on its own logits), the
weights still from p.  With 8 of 256 experts held and nothing in place
of the rest, a trained router learns to route nothing to the held ones;
a fixed one, as training aligns the tokens' streams, sends every token
to the same few experts.

On the chip the same functions are computed in blocks (``blocked=True``):
each layer one jitted call under ``jax.checkpoint``, its attention a block
of query rows at a time, the head a block of rows at a time.  The
arithmetic is the same.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.reference.looplm import _adamw_jit, adamw_step, global_norm

ATTN_MATS = ("wq", "wk", "wv", "wo", "w_head_gate")
DENSE_MATS = ("w_gate", "w_up", "w_down")
SPARSE_MATS = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
               "s_down")
GAINS = ("n_attn", "n_mlp")
HEAD_BLOCK_ROWS = 1024
QUERY_BLOCK_ROWS = 256

FAULTS = ("no_window", "plain_rotary", "unnormed_router", "no_shared_expert",
          "no_head_gate")


def layer_kind(model: dict, i: int) -> tuple:
    """(attention kind, feed-forward kind, query heads) of layer i."""
    return (model["layer_types"][i].split("_")[0],
            model["mlp_layer_types"][i],
            int(model["num_attention_heads_per_layer"][i]))


def held_range(model: dict, job: dict) -> tuple:
    """(first held expert, held count, router width)."""
    count = int(model["num_experts"])
    shards = int(job.get("expert_shards", 1))
    return int(job.get("expert_share", 0)) * count, count, count * shards


def layer_shapes(model: dict, job: dict, i: int) -> dict:
    d, dh = int(model["hidden_size"]), int(model["head_dim"])
    kv = int(model["num_key_value_heads"])
    _, mlp, heads = layer_kind(model, i)
    out = {"wq": (d, heads * dh), "wk": (d, kv * dh), "wv": (d, kv * dh),
           "wo": (heads * dh, d), "w_head_gate": (d, heads),
           "n_attn": (d,), "n_mlp": (d,)}
    if mlp == "dense":
        f = int(model["intermediate_size"])
        out.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        _, e, width = held_range(model, job)
        f = int(model["moe_intermediate_size"])
        fs = int(model["shared_expert_intermediate_size"])
        out.update(router=(d, width), e_gate=(e, d, f), e_up=(e, d, f),
                   e_down=(e, f, d), s_gate=(d, fs), s_up=(d, fs),
                   s_down=(fs, d))
    return out


def init_weights(seed: int, model: dict, job: dict, std: float = 0.02):
    """{name: array}: matrices ~ N(0, std^2), gains 1.  A layer's leaves
    are named ``l<i>.<leaf>``."""
    d, v = int(model["hidden_size"]), int(model["vocab_size"])
    key = jax.random.PRNGKey(int(seed))
    normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)
    key, ke, kh = jax.random.split(key, 3)
    out = {"embed": normal(ke, (v, d)), "head": normal(kh, (d, v)),
           "final_norm": jnp.ones((d,), jnp.float32)}
    for i in range(int(model["num_hidden_layers"])):
        for name, shape in layer_shapes(model, job, i).items():
            if name in GAINS:
                out[f"l{i}.{name}"] = jnp.ones(shape, jnp.float32)
            else:
                key, k = jax.random.split(key)
                out[f"l{i}.{name}"] = normal(k, shape)
    return out


def layer_of(weights: dict, i: int) -> dict:
    pre = f"l{i}."
    return {k[len(pre):]: w for k, w in weights.items() if k.startswith(pre)}


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def yarn_frequencies(dim, theta, factor, original_max, beta_fast, beta_slow):
    """YaRN's frequencies of ``dim`` rotated lanes (the transformers
    library's ``_compute_yarn_parameters`` with its default truncation)."""
    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return (interpolation * (1 - extrapolation_factor)
            + extrapolation * extrapolation_factor)


def rope(x, params: dict, fault=None):
    """x [S, H, D] at positions 0..S-1: rotate-half over the first
    ``partial_rotary_factor`` of the lanes, YaRN where ``rope_type`` says
    so (cos and sin times ``attention_factor``).  ``plain_rotary`` (a
    planted fault) turns every lane at theta's plain frequencies."""
    s, _, dh = x.shape
    theta = float(params["rope_theta"])
    dim = int(dh * float(params.get("partial_rotary_factor", 1)))
    scale = 1.0
    if fault == "plain_rotary":
        dim = dh
    if params["rope_type"] == "yarn" and fault != "plain_rotary":
        inv = yarn_frequencies(dim, theta, float(params["factor"]),
                               int(params["original_max_position_embeddings"]),
                               float(params["beta_fast"]),
                               float(params["beta_slow"]))
        scale = float(params["attention_factor"])
    else:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (scale * jnp.concatenate([jnp.cos(ang)] * 2, -1))[:, None, :]
    sin = (scale * jnp.concatenate([jnp.sin(ang)] * 2, -1))[:, None, :]
    xr, rest = x[..., :dim], x[..., dim:]
    x1, x2 = xr[..., : dim // 2], xr[..., dim // 2:]
    turned = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, rest], -1)


def attention(q, k, v, window: int, rows: int):
    """softmax(q k^T / sqrt(D) + mask) v, q [S, H, D], k/v [S, H_kv, D]:
    causal, and key > query - window where ``window``; ``rows`` query rows
    at a time, one block after the other (``lax.map``, each block under
    jax.checkpoint, so one block's scores live at once), or all at once
    (0)."""
    s, heads, dh = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)

    def block(qb, first):
        i = first + jnp.arange(qb.shape[0])[:, None]
        j = jnp.arange(s)[None, :]
        keep = j <= i
        if window:
            keep = keep & (j > i - window)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dh)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(scores, axis=-1).astype(v.dtype), v)

    if not rows or rows >= s:
        return block(q, 0)
    if s % rows:
        raise ValueError(f"{s} query rows are no whole number of {rows}-row "
                         "blocks")
    out = jax.lax.map(jax.checkpoint(lambda a: block(*a)),
                      (q.reshape(s // rows, rows, heads, dh),
                       jnp.arange(0, s, rows)))
    return out.reshape(s, heads, dh)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(b, router, k: int, scale: float, norm_topk: bool,
          balanced=False):
    """(top-k weights [S, k], top-k experts [S, k]); ``balanced``: chosen
    on each logit less its expert's mean over the earlier tokens."""
    r = b.astype(jnp.float32) @ router.astype(jnp.float32)
    p = jax.nn.softmax(r, axis=-1)
    score = r
    if balanced:
        # row t of ``average`` holds 1/t on the columns s < t (row 0: none)
        s = r.shape[0]
        earlier = jnp.tril(jnp.ones((s, s), jnp.float32), -1)
        average = earlier / jnp.maximum(jnp.sum(earlier, axis=1,
                                                keepdims=True), 1.0)
        score = r - average @ r
    top_e = jax.lax.top_k(score, k)[1]
    top_p = jnp.take_along_axis(p, top_e, axis=-1)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return scale * top_p, top_e


def expert_layer(b, w: dict, model: dict, first: int, *, fault=None,
                 cast=lambda x: x, fixed=False):
    """(sum over held experts of w_e SwiGLU_e(b) + SwiGLU_shared(b), rows
    routed to held experts): every held expert over every token, weighted
    by its top-k weight where it was chosen (0 elsewhere).  ``fixed``:
    the router's scores pass no gradient and its choice is balanced (the
    module doc)."""
    b_r, router = b, w["router"]
    if fixed:
        b_r, router = jax.lax.stop_gradient(b), jax.lax.stop_gradient(router)
    top_w, top_e = route(b_r, router, int(model["num_experts_per_tok"]),
                         float(model["moe_routed_scaling_factor"]),
                         bool(model["norm_topk_prob"])
                         and fault != "unnormed_router", balanced=fixed)
    y = 0.0
    rows = 0
    for e in range(w["e_gate"].shape[0]):
        chosen = top_e == first + e
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1)
        rows = rows + jnp.sum(chosen)
        y = y + cast(weight[:, None]) * swiglu(b, w["e_gate"][e],
                                               w["e_up"][e], w["e_down"][e])
    if fault != "no_shared_expert":
        y = y + swiglu(b, w["s_gate"], w["s_up"], w["s_down"])
    return y, rows


def layer(h, w, *, model: dict, job: dict, i: int, rows: int = 0,
          low=None, fault=None):
    """Layer i over h [S, d]: (h, rows routed to held experts)."""
    cast = (lambda x: x) if low is None else (lambda x: x.astype(low))
    w = {name: cast(leaf) for name, leaf in w.items()}
    attn, mlp, heads = layer_kind(model, i)
    eps = float(model["rms_norm_eps"])
    s, dh = h.shape[0], int(model["head_dim"])
    kv = int(model["num_key_value_heads"])
    a = cast(rms_norm(h, w["n_attn"], eps))
    q = (a @ w["wq"]).reshape(s, heads, dh)
    k = (a @ w["wk"]).reshape(s, kv, dh)
    v = (a @ w["wv"]).reshape(s, kv, dh)
    rope_params = model["rope_parameters"][model["layer_types"][i]]
    q, k = cast(rope(q, rope_params, fault)), cast(rope(k, rope_params, fault))
    window = (int(model["sliding_window"])
              if attn == "sliding" and fault != "no_window" else 0)
    o = attention(q, k, v, window, rows)
    if fault != "no_head_gate":
        o = o * cast(jax.nn.sigmoid(a @ w["w_head_gate"]))[..., None]
    h = h + cast(o.reshape(s, heads * dh) @ w["wo"])
    b = cast(rms_norm(h, w["n_mlp"], eps))
    if mlp == "dense":
        return h + cast(swiglu(b, w["w_gate"], w["w_up"], w["w_down"])), 0
    first, _, _ = held_range(model, job)
    y, routed = expert_layer(b, w, model, first, fault=fault, cast=cast,
                             fixed=int(job.get("expert_shards", 1)) > 1)
    return h + cast(y), routed


def token_ce(h, head, targets, low=None):
    """Cross-entropy of each row of h [R, d] against its target."""
    if low is not None:
        h, head = h.astype(low), head.astype(low)
    z = (h @ head).astype(jnp.float32)
    return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, targets[:, None], axis=-1)[:, 0]


def forward(weights: dict, tokens, model: dict, job: dict, *, blocked=False,
            low=None, fault=None):
    """tokens [S + 1] -> (ce [S], rows routed to held experts of each
    sparse layer)."""
    inputs, targets = tokens[:-1], tokens[1:]
    h = weights["embed"][inputs]
    if low is not None:
        h = h.astype(low)
    routed = []
    n = int(model["num_hidden_layers"])
    for i in range(n):
        # layers of one kind compute alike: one jitted call serves them
        first = next(j for j in range(n)
                     if layer_kind(model, j) == layer_kind(model, i))
        fn = (_blocked_layer(first, low, fault, json.dumps([model, job]))
              if blocked else functools.partial(
                  layer, model=model, job=job, i=i, low=low, fault=fault))
        h, r = fn(h, layer_of(weights, i))
        if model["mlp_layer_types"][i] == "sparse":
            routed.append(r)
    h = rms_norm(h, weights["final_norm"], float(model["rms_norm_eps"]))
    if low is not None:
        h = h.astype(low)
    if blocked:
        ce_fn = _blocked_ce(low)
        ce = jnp.concatenate([
            ce_fn(h[r:r + HEAD_BLOCK_ROWS], weights["head"],
                  targets[r:r + HEAD_BLOCK_ROWS])
            for r in range(0, h.shape[0], HEAD_BLOCK_ROWS)])
    else:
        ce = token_ce(h, weights["head"], targets, low)
    return ce, routed


@functools.lru_cache(maxsize=16)
def _blocked_layer(i, low, fault, docs: str):
    """Layer i (and every layer of its kind) as one jitted call that
    keeps nothing but its inputs for the backward, its attention a block
    of query rows at a time (``docs``: the model and the job as JSON, a
    key of the cache)."""
    model, job = json.loads(docs)
    return jax.jit(jax.checkpoint(functools.partial(
        layer, model=model, job=job, i=i, rows=QUERY_BLOCK_ROWS, low=low,
        fault=fault)))


@functools.lru_cache(maxsize=4)
def _blocked_ce(low):
    return jax.jit(jax.checkpoint(functools.partial(token_ce, low=low)))


def loss_fn(weights, tokens, model, job, **kw):
    """tokens [B, S + 1]: (mean cross-entropy, rows routed to held experts
    a sparse layer, summed over the sequences)."""
    out = [forward(weights, row, model, job, **kw) for row in tokens]
    loss = sum(jnp.mean(ce) for ce, _ in out) / len(out)
    return loss, [sum(r) for r in zip(*(rows for _, rows in out))]


def loss_and_grads(weights, tokens, model, job, **kw):
    with jax.default_matmul_precision("highest"):
        (loss, rows), grads = jax.value_and_grad(
            lambda w: loss_fn(w, tokens, model, job, **kw),
            has_aux=True)(weights)
    return loss, rows, grads


def train_steps(weights: dict, batches, model: dict, job: dict, opt: dict, *,
                blocked=False, remake=None, **kw) -> dict:
    """Follow ``len(batches)`` optimizer steps from ``weights``; each
    batch is tokens [B, S + 1].  Returns each step's loss, step 1's
    routed rows, gradient (norm and leaves, on the host), and the
    per-leaf norm of the parameters' change after the last step.
    ``blocked`` gives ``weights`` up to the optimizer step; ``remake()``
    then makes the start again for the change.  Where a share is held
    (``expert_shards`` > 1) the routers stay out of the optimizer."""
    start = None if blocked else weights
    fixed = {k for k in weights if int(job.get("expert_shards", 1)) > 1
             and k.endswith(".router")}
    mu = {k: jnp.zeros_like(v) for k, v in weights.items() if k not in fixed}
    nu = {k: jnp.zeros_like(v) for k, v in weights.items() if k not in fixed}
    count = 0
    out = {"losses": []}
    step = _adamw_jit if blocked else adamw_step
    for i, tokens in enumerate(batches):
        loss, rows, grads = loss_and_grads(weights, tokens, model, job,
                                           blocked=blocked, **kw)
        out["losses"].append(float(loss))
        if i == 0:
            out.update(held_rows=[float(r) for r in rows],
                       grad_norm=float(global_norm(grads)),
                       grads=jax.device_get(grads))
        moving, mu, nu, count = step(
            {k: v for k, v in weights.items() if k not in fixed},
            {k: v for k, v in grads.items() if k not in fixed}, mu, nu,
            count, opt)
        weights = {**{k: weights[k] for k in fixed}, **moving}
        del grads, moving
    del mu, nu
    start = remake() if start is None else start
    out["change_norms"] = {k: float(jnp.linalg.norm(
        (weights[k] - start[k]).ravel())) for k in weights}
    return out
