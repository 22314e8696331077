"""Plain reference of GLM-4.7-Flash (zai-org, huggingface.co/zai-org/
GLM-4.7-Flash/blob/main/config.json, ``model_type glm4_moe_lite``):
forward, loss, gradients and AdamW steps in straightforward
``jax.numpy``, float32, every matmul under
``jax.default_matmul_precision("highest")``, dense causal attention with
an explicit mask, a Python loop over the layers and over the held
experts.  No scan, no kernel, no sorting of tokens by expert.  Nothing of
``hyperspace_tpu`` is imported.  The config's key names are DeepSeek-V2/
V3's, whose published forms are taken (arXiv:2405.04434 §2.1,
arXiv:2412.19437 §2.1–2.2):

    RMSNorm_g(x) = x / sqrt(mean(x^2) + eps) * g
    one layer (pre-norm), H = num_attention_heads:
        a = RMSNorm(h)
        latent attention (MLA), computed from the compressed form:
            c_q = RMSNorm(a W_qa)  (q_lora_rank lanes);  q = c_q W_qb,
                each head [q_nope (qk_nope_head_dim) | q_rope
                (qk_rope_head_dim)]
            [c_kv (kv_lora_rank) | k_r (qk_rope_head_dim)] = a W_kva
            c = RMSNorm(c_kv);  W_kvb's columns are each head's
                [W_kb (qk_nope_head_dim) | W_vb (v_head_dim)]
            rotary (theta rope_theta, rope_scaling null, the whole rope
                slice) on q_rope and k_r; k_r is one key for all heads
            score_h(i, j) = (q_nope_h(i) W_kb_h^T . c(j) + q_rope_h(i) .
                k_r(j)) / sqrt(qk_nope_head_dim + qk_rope_head_dim),
                = q_h . [c W_kb_h | k_r] (the decompressed key)
            o_h = (softmax(score_h + causal) c) W_vb_h  (= softmax(..) c W_vb_h)
            h <- h + concat(o) W_o
        b = RMSNorm(h)
        layers < first_k_dense_replace: h <- h + SwiGLU(b)  (intermediate_size)
        later layers: s = sigmoid(b W_router) over all the router's experts;
            T = top-k chosen on s (+ a selection bias: below); w_e =
            routed_scaling_factor * s_e / sum_T s  (norm_topk_prob);
            h <- h + sum_{e in T, e held} w_e SwiGLU_e(b) + SwiGLU_shared(b)
            (the shared expert n_shared_experts x moe_intermediate_size wide)
    the model: h = E[tokens]; the layers; z = RMSNorm(h) Whead (untied);
        L_main = mean cross-entropy against the next token
    multi-token prediction (num_nextn_predict_layers 1), positions
        i = 0 .. S-2: x_i = W_eh [RMSNorm_hnorm(h_i); RMSNorm_enorm(E[t_{i+1}])]
        (h the stack's output before its final norm), one more sparse
        layer over those S-1 positions, the same final norm and head,
        L_mtp = mean cross-entropy against t_{i+2};
        loss = L_main + mtp_loss_weight * L_mtp

The expert share: the router is as wide as the deployment (the held
``n_routed_experts`` times ``expert_shards``), and the layer adds the
held experts' part alone (experts ``expert_share * n_routed_experts``
onwards), as the program does; :func:`expert_layer` with every expert
held is the uncut layer the tests tie the shares to.

Set by this reference where the config says nothing (the configuration's
``assumed`` carries the same list): rotate-half order on the rope lanes
(with seeded weights the interleaved order is the same function up to a
permutation of W_qb's and W_kva's columns); the MTP module's input is
[h; E] in that order, h before the final norm, and it shares the final
norm with the main head (DeepSeek-V3's checkpoints carry a norm of
their own there, 1 at init); no sequence-wise auxiliary loss; the
selection bias of noaux_tc stays at its initial 0 (no balancing
update); mtp_loss_weight 0.3 (DeepSeek-V3's early lambda); AdamW
0.9/0.95/1e-8, decay 0.1 on matrices, lr 3e-4, global-norm clip 1.0,
N(0, 0.02^2) init.

Set by the cut (a share held: the job's ``expert_shards`` > 1), so that
the held experts keep a deployment share's load: the router keeps its
initial weights (no update, no decay) and its scores pass no gradient,
to it or to b (the top-k weights still scale the experts' outputs); and
T is chosen on s_e(t) - mean_{u<t} s_e(u), each score less its expert's
mean over the sequence's earlier tokens (token 0 on its own scores), in
the selection bias's place, the weights still s alone.

On the chip the same functions are computed in blocks (``blocked=True``):
each layer one jitted call under ``jax.checkpoint``, its attention a block
of query rows at a time, the heads a block of rows at a time.  The
arithmetic is the same.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.reference.laguna import rms_norm, swiglu, token_ce
from benchmark.reference.looplm import _adamw_jit, adamw_step, global_norm

GAINS = ("n_attn", "n_qa", "n_kva", "n_mlp")
HEAD_BLOCK_ROWS = 1024
QUERY_BLOCK_ROWS = 256

# the planted faults: a bf16 residual stream and norms; the rotary key's
# term dropped from the score; the key/value latent unnormed; the score
# scaled by the nope lanes alone; softmax scores in the router; the MTP
# module fed the unnormed embedding
FAULTS = ("bf16_stream", "no_rope_key", "no_kv_norm", "nope_scale",
          "softmax_router", "mtp_raw_embed")


def held_range(model: dict, job: dict) -> tuple:
    """(first held expert, held count, router width)."""
    count = int(model["n_routed_experts"])
    shards = int(job.get("expert_shards", 1))
    return int(job.get("expert_share", 0)) * count, count, count * shards


def is_dense(model: dict, i: int) -> bool:
    return i < int(model["first_k_dense_replace"])


def layer_shapes(model: dict, job: dict, dense: bool) -> dict:
    d, heads = int(model["hidden_size"]), int(model["num_attention_heads"])
    nope, rd = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv, rq = int(model["v_head_dim"]), int(model["q_lora_rank"])
    rkv = int(model["kv_lora_rank"])
    out = {"n_attn": (d,), "w_qa": (d, rq), "n_qa": (rq,),
           "w_qb": (rq, heads * (nope + rd)), "w_kva": (d, rkv + rd),
           "n_kva": (rkv,), "w_kvb": (rkv, heads * (nope + dv)),
           "wo": (heads * dv, d), "n_mlp": (d,)}
    if dense:
        f = int(model["intermediate_size"])
        out.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        _, e, width = held_range(model, job)
        f = int(model["moe_intermediate_size"])
        fs = f * int(model["n_shared_experts"])
        out.update(router=(d, width), e_gate=(e, d, f), e_up=(e, d, f),
                   e_down=(e, f, d), s_gate=(d, fs), s_up=(d, fs),
                   s_down=(fs, d))
    return out


def init_weights(seed: int, model: dict, job: dict, std: float = 0.02):
    """{name: array}: matrices ~ N(0, std^2), gains 1.  Layer i's leaves
    are named ``l<i>.<leaf>``, the MTP module's ``mtp.enorm``,
    ``mtp.hnorm``, ``mtp.w_eh`` and ``mtp.l.<leaf>`` (its layer)."""
    d, v = int(model["hidden_size"]), int(model["vocab_size"])
    key = jax.random.PRNGKey(int(seed))
    normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)
    key, ke, kh = jax.random.split(key, 3)
    out = {"embed": normal(ke, (v, d)), "head": normal(kh, (d, v)),
           "final_norm": jnp.ones((d,), jnp.float32)}
    layers = [(f"l{i}.", is_dense(model, i))
              for i in range(int(model["num_hidden_layers"]))]
    if int(model["num_nextn_predict_layers"]):
        key, k = jax.random.split(key)
        out.update({"mtp.enorm": jnp.ones((d,), jnp.float32),
                    "mtp.hnorm": jnp.ones((d,), jnp.float32),
                    "mtp.w_eh": normal(k, (2 * d, d))})
        layers.append(("mtp.l.", False))
    for pre, dense in layers:
        for name, shape in layer_shapes(model, job, dense).items():
            if name in GAINS:
                out[pre + name] = jnp.ones(shape, jnp.float32)
            else:
                key, k = jax.random.split(key)
                out[pre + name] = normal(k, shape)
    return out


def layer_of(weights: dict, pre: str) -> dict:
    return {k[len(pre):]: w for k, w in weights.items() if k.startswith(pre)}


def rope(x, theta: float):
    """x [S, ..., D] at positions 0..S-1, rotate-half over all D lanes."""
    s, dim = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (s,) + (1,) * (x.ndim - 2) + (dim,)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1).reshape(shape)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1).reshape(shape)
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mla(a, w, model: dict, rows: int = 0, fault=None,
        cast=lambda x: x):
    """Causal MLA of the normed stream a [S, d] from the compressed form
    (module doc): each head's query is carried into the key/value latent
    (q_nope W_kb^T), scored against the shared latent c and the shared
    rotary key, and its weights sum c before W_vb carries them out.
    ``rows`` query rows at a time (``lax.map``, each block under
    jax.checkpoint), or all at once (0); a last block that is short is
    padded with query rows whose results are dropped."""
    s = a.shape[0]
    heads, eps = int(model["num_attention_heads"]), float(model["rms_norm_eps"])
    nope, rd = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv, rkv = int(model["v_head_dim"]), int(model["kv_lora_rank"])
    theta = float(model["rope_theta"])
    c_q = cast(rms_norm(a @ w["w_qa"], w["n_qa"], eps))
    q = (c_q @ w["w_qb"]).reshape(s, heads, nope + rd)
    q_nope, q_rope = q[..., :nope], cast(rope(q[..., nope:], theta))
    kva = a @ w["w_kva"]
    c = kva[:, :rkv]
    if fault != "no_kv_norm":
        c = rms_norm(c, w["n_kva"], eps)
    c = cast(c)
    k_r = cast(rope(kva[:, rkv:], theta))                       # [S, rd]
    w_kvb = w["w_kvb"].reshape(rkv, heads, nope + dv)
    w_kb, w_vb = w_kvb[..., :nope], w_kvb[..., nope:]
    q_lat = cast(jnp.einsum("shn,rhn->shr", q_nope, w_kb))      # [S, H, rkv]
    scale = 1.0 / math.sqrt(nope if fault == "nope_scale" else nope + rd)

    def block(qb_lat, qb_rope, first):
        i = first + jnp.arange(qb_lat.shape[0])[:, None]
        keep = jnp.arange(s)[None, :] <= i
        scores = jnp.einsum("qhr,kr->hqk", qb_lat, c)
        if fault != "no_rope_key":
            scores = scores + jnp.einsum("qhd,kd->hqk", qb_rope, k_r)
        scores = jnp.where(keep[None], scores * scale, -jnp.inf)
        o_lat = jnp.einsum("hqk,kr->qhr",
                           cast(jax.nn.softmax(scores, axis=-1)), c)
        return jnp.einsum("qhr,rhv->qhv", cast(o_lat), w_vb)

    if not rows or rows >= s:
        o = block(q_lat, q_rope, 0)
    else:
        n = -(-s // rows)
        pad = lambda x: jnp.pad(x, ((0, n * rows - s),) + ((0, 0),) * (
            x.ndim - 1)).reshape((n, rows) + x.shape[1:])
        o = jax.lax.map(jax.checkpoint(lambda t: block(*t)),
                        (pad(q_lat), pad(q_rope), jnp.arange(0, n * rows,
                                                             rows)))
        o = o.reshape((n * rows,) + o.shape[2:])[:s]
    return cast(o.reshape(s, heads * dv)) @ w["wo"]


def route(b, router, k: int, scale: float, norm_topk: bool,
          balanced=False, scoring="sigmoid"):
    """(top-k weights [S, k], top-k experts [S, k]): sigmoid scores s,
    chosen on s (``balanced``: on s less each expert's mean over the
    earlier tokens), weighted by s renormalised over the k and scaled.
    ``scoring="softmax"`` (a planted fault) puts softmax in sigmoid's
    place."""
    r = b.astype(jnp.float32) @ router.astype(jnp.float32)
    p = jax.nn.softmax(r, axis=-1) if scoring == "softmax" else (
        jax.nn.sigmoid(r))
    score = p
    if balanced:
        # row t of ``average`` holds 1/t on the columns u < t (row 0: none)
        s = r.shape[0]
        earlier = jnp.tril(jnp.ones((s, s), jnp.float32), -1)
        average = earlier / jnp.maximum(jnp.sum(earlier, axis=1,
                                                keepdims=True), 1.0)
        score = p - average @ p
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
    top_w, top_e = route(
        b_r, router, int(model["num_experts_per_tok"]),
        float(model["routed_scaling_factor"]), bool(model["norm_topk_prob"]),
        balanced=fixed,
        scoring="softmax" if fault == "softmax_router" else "sigmoid")
    y = 0.0
    rows = 0
    for e in range(w["e_gate"].shape[0]):
        chosen = top_e == first + e
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1)
        rows = rows + jnp.sum(chosen)
        y = y + cast(weight[:, None]) * swiglu(b, w["e_gate"][e],
                                               w["e_up"][e], w["e_down"][e])
    return y + swiglu(b, w["s_gate"], w["s_up"], w["s_down"]), rows


def _casts(low, fault):
    """(cast of everything, cast of the stream and the norms' outputs)."""
    cast = (lambda x: x) if low is None else (lambda x: x.astype(low))
    if fault == "bf16_stream":
        return cast, lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return cast, cast


def layer(h, w, *, model: dict, job: dict, dense: bool, rows: int = 0,
          low=None, fault=None):
    """One layer over h [S, d]: (h, rows routed to held experts)."""
    cast, stream = _casts(low, fault)
    w = {name: cast(leaf) for name, leaf in w.items()}
    eps = float(model["rms_norm_eps"])
    a = stream(rms_norm(h, w["n_attn"], eps))
    h = stream(h + cast(mla(a, w, model, rows, fault, cast)))
    b = stream(rms_norm(h, w["n_mlp"], eps))
    if dense:
        return stream(h + cast(swiglu(b, w["w_gate"], w["w_up"],
                                      w["w_down"]))), 0
    first, _, _ = held_range(model, job)
    y, routed = expert_layer(b, w, model, first, fault=fault, cast=cast,
                             fixed=int(job.get("expert_shards", 1)) > 1)
    return stream(h + cast(y)), routed


def _mtp_input(h, embed, w, model, low=None, fault=None):
    """W_eh [RMSNorm(h); RMSNorm(e)] of the S-1 positions' h and their
    next tokens' embeddings e."""
    cast, stream = _casts(low, fault)
    eps = float(model["rms_norm_eps"])
    e = embed if fault == "mtp_raw_embed" else rms_norm(
        embed, w["mtp.enorm"], eps)
    x = jnp.concatenate([rms_norm(h, w["mtp.hnorm"], eps), e], -1)
    return stream(cast(stream(x)) @ cast(w["mtp.w_eh"]))


def _ce(h, weights, targets, model, low, fault, blocked):
    """The final norm, the head and each row's cross-entropy."""
    _, stream = _casts(low, fault)
    h = stream(rms_norm(h, weights["final_norm"],
                        float(model["rms_norm_eps"])))
    if low is not None:
        h = h.astype(low)
    if not blocked:
        return token_ce(h, weights["head"], targets, low)
    ce_fn = _blocked_ce(low)
    return jnp.concatenate([
        ce_fn(h[r:r + HEAD_BLOCK_ROWS], weights["head"],
              targets[r:r + HEAD_BLOCK_ROWS])
        for r in range(0, h.shape[0], HEAD_BLOCK_ROWS)])


def forward(weights: dict, tokens, model: dict, job: dict, *, blocked=False,
            low=None, fault=None):
    """tokens [S + 1] -> (main ce [S], MTP ce [S - 1] or None, rows routed
    to held experts of each sparse layer, the MTP layer's last)."""
    inputs, targets = tokens[:-1], tokens[1:]
    _, stream = _casts(low, fault)
    h = weights["embed"][inputs]
    if low is not None:
        h = h.astype(low)
    h = stream(h)
    docs = json.dumps([model, job])

    def run(dense, h, w):
        if blocked:
            return _blocked_layer(dense, low, fault, docs)(h, w)
        return layer(h, w, model=model, job=job, dense=dense, low=low,
                     fault=fault)

    routed = []
    for i in range(int(model["num_hidden_layers"])):
        h, r = run(is_dense(model, i), h, layer_of(weights, f"l{i}."))
        if not is_dense(model, i):
            routed.append(r)
    ce = _ce(h, weights, targets, model, low, fault, blocked)
    ce_mtp = None
    if int(model["num_nextn_predict_layers"]):
        x = _mtp_input(h[:-1], weights["embed"][inputs[1:]], weights, model,
                       low, fault)
        x, r = run(False, x, layer_of(weights, "mtp.l."))
        routed.append(r)
        ce_mtp = _ce(x, weights, tokens[2:], model, low, fault, blocked)
    return ce, ce_mtp, routed


@functools.lru_cache(maxsize=16)
def _blocked_layer(dense, low, fault, docs: str):
    """A dense or a sparse layer as one jitted call that keeps nothing but
    its inputs for the backward, its attention a block of query rows at
    a time (``docs``: the model and the job as JSON, a key of the
    cache)."""
    model, job = json.loads(docs)
    return jax.jit(jax.checkpoint(functools.partial(
        layer, model=model, job=job, dense=dense, rows=QUERY_BLOCK_ROWS,
        low=low, fault=fault)))


@functools.lru_cache(maxsize=4)
def _blocked_ce(low):
    return jax.jit(jax.checkpoint(functools.partial(token_ce, low=low)))


def loss_fn(weights, tokens, model, job, mtp_weight: float, **kw):
    """tokens [B, S + 1]: (L_main + mtp_weight L_mtp, (rows routed to held
    experts a sparse layer summed over the sequences, L_main, L_mtp))."""
    out = [forward(weights, row, model, job, **kw) for row in tokens]
    main = sum(jnp.mean(ce) for ce, _, _ in out) / len(out)
    rows = [sum(r) for r in zip(*(rows for _, _, rows in out))]
    if out[0][1] is None:
        return main, (rows, main, None)
    mtp = sum(jnp.mean(c) for _, c, _ in out) / len(out)
    return main + mtp_weight * mtp, (rows, main, mtp)


def loss_and_grads(weights, tokens, model, job, mtp_weight, **kw):
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(
            lambda w: loss_fn(w, tokens, model, job, mtp_weight, **kw),
            has_aux=True)(weights)
    return loss, aux, grads


def train_steps(weights: dict, batches, model: dict, job: dict, opt: dict, *,
                blocked=False, remake=None, **kw) -> dict:
    """Follow ``len(batches)`` optimizer steps from ``weights``; each
    batch is tokens [B, S + 1].  Returns each step's main and MTP losses,
    step 1's routed rows, gradient (norm and leaves, on the host), and the
    per-leaf norm of the parameters' change after the last step.
    ``blocked`` gives ``weights`` up to the optimizer step; ``remake()``
    then makes the start again for the change.  ``opt`` holds AdamW's
    recipe and ``mtp_weight``.  Where a share is held (``expert_shards``
    > 1) the routers stay out of the optimizer."""
    start = None if blocked else weights
    fixed = {k for k in weights if int(job.get("expert_shards", 1)) > 1
             and k.endswith(".router")}
    mu = {k: jnp.zeros_like(v) for k, v in weights.items() if k not in fixed}
    nu = {k: jnp.zeros_like(v) for k, v in weights.items() if k not in fixed}
    count = 0
    out = {"losses": [], "mtp_losses": []}
    step = _adamw_jit if blocked else adamw_step
    for i, tokens in enumerate(batches):
        _, (rows, main, mtp), grads = loss_and_grads(
            weights, tokens, model, job, opt["mtp_weight"], blocked=blocked,
            **kw)
        out["losses"].append(float(main))
        out["mtp_losses"].append(float("nan") if mtp is None else float(mtp))
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
