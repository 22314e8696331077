"""Plain reference of the looped language model (Ouro, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741; widths from
huggingface.co/ByteDance/Ouro-2.6B config.json): forward, loss, gradients
and AdamW steps in straightforward ``jax.numpy``, float32, every matmul
under ``jax.default_matmul_precision("highest")``, dense attention with
an explicit causal mask, a Python loop over passes and layers.  No scan,
no kernel, no recomputation.  Nothing of ``hyperspace_tpu`` is imported.

    RMSNorm_g(x) = x / sqrt(mean(x^2) + eps) * g
    one layer (a norm before and after each sub-layer):
        a = RMSNorm_1(h); q, k, v = a Wq, a Wk, a Wv, split into heads
        q, k <- RoPE(q, k; position, theta)            (rotate-half form)
        o = softmax(q k^T / sqrt(head_dim) + causal) v
        h <- h + RMSNorm_2(o Wo)
        b = RMSNorm_3(h)
        h <- h + RMSNorm_4((silu(b Wgate) * (b Wup)) Wdown)
    the model: h = E[tokens]; for t = 1..T: layers 1..L over h (the same
        weights every t); h <- RMSNorm_f(h); z_t = h Whead;
        lambda_t = sigmoid(h . w_g + b_g); the next pass starts from h
    exit distribution: p_t = lambda_t prod_{j<t}(1 - lambda_j), t < T;
        p_T = prod_{j<T}(1 - lambda_j)
    loss = mean over tokens of [sum_t p_t CE(z_t, next) - beta H(p)]

What is certain, what is recalled and what is guessed (no download
here: ``modeling_ouro.py`` could not be opened, so nothing below was
checked against it; the configuration's ``assumed`` carries the same
marks, and each stays until the file is checked in the repository):

- certain (the catalog row and the paper's title mechanism): the widths,
  the loop of ``total_ut_steps`` passes over one stack of shared weights;
- RECALLED from the public file, unchecked: four norms a layer (a norm
  before and after each sub-layer); the exit gate is one
  ``Linear(hidden_size, 1)`` with a bias (``early_exit_gate``) that
  reads the final norm's output of its pass;
- GUESSED: that the final norm sits inside the loop over the passes, so
  the next pass starts from the normed state (the other reading: the
  passes chain on the raw residual stream and the norm only feeds head
  and gate).  The two differ by one RMSNorm of 2048 lanes a pass: under
  0.01% of the step's time either way, but it is what ``correct``
  certifies;
- no biases anywhere but the gate's; no dropout; rotary positions in
  the rotate-half form over the whole head width (the Llama family's
  convention, which the config's keys follow);
- training objective: the paper's stage-one entropy-regularised loss
  with a uniform prior, beta fixed; the paper's second stage (the gate
  trained against the loss improvement) is not here.

On the chip the same functions are computed in blocks (``blocked=True``):
each layer application is one jitted call under ``jax.checkpoint`` and
the head a block of rows at a time, so that the 32 layer applications'
score matrices never live together.  The arithmetic is the same.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LAYER_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_GAINS = ("n1", "n2", "n3", "n4")
HEAD_BLOCK_ROWS = 1024


def init_weights(seed: int, model: dict, std: float = 0.02):
    """{name: array}: matrices ~ N(0, std^2), gains 1, the gate's bias 0.
    A layer's leaves are named ``l<i>.<leaf>``."""
    d, f = int(model["hidden_size"]), int(model["intermediate_size"])
    v, n_layers = int(model["vocab_size"]), int(model["num_hidden_layers"])
    hd = int(model["num_attention_heads"]) * int(model["head_dim"])
    shapes = {"wq": (d, hd), "wk": (d, hd), "wv": (d, hd), "wo": (hd, d),
              "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    key = jax.random.PRNGKey(int(seed))

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    out = {}
    key, ke, kh, kg = jax.random.split(key, 4)
    out["embed"] = normal(ke, (v, d))
    out["head"] = normal(kh, (d, v))
    out["gate_w"] = normal(kg, (d,))
    out["gate_b"] = jnp.zeros((), jnp.float32)
    out["final_norm"] = jnp.ones((d,), jnp.float32)
    for i in range(n_layers):
        for name in LAYER_MATS:
            key, k = jax.random.split(key)
            out[f"l{i}.{name}"] = normal(k, shapes[name])
        for name in LAYER_GAINS:
            out[f"l{i}.{name}"] = jnp.ones((d,), jnp.float32)
    return out


def layer_of(weights: dict, i: int) -> dict:
    return {n: weights[f"l{i}.{n}"] for n in LAYER_MATS + LAYER_GAINS}


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [S, H, D], positions 0..S-1, rotate-half form."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(h, w, *, heads: int, head_dim: int, eps: float, theta: float,
          low=None):
    """One layer over h [S, d].  ``low``: the control's dtype (the
    residual stream, norms and softmax one step of precision down)."""
    cast = (lambda x: x) if low is None else (lambda x: x.astype(low))
    w = {name: cast(leaf) for name, leaf in w.items()}
    s = h.shape[0]
    a = cast(rms_norm(h, w["n1"], eps))
    q = (a @ w["wq"]).reshape(s, heads, head_dim)
    k = (a @ w["wk"]).reshape(s, heads, head_dim)
    v = (a @ w["wv"]).reshape(s, heads, head_dim)
    q, k = cast(rope(q, theta)), cast(rope(k, theta))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", cast(jax.nn.softmax(scores, axis=-1)), v)
    h = h + cast(rms_norm(o.reshape(s, heads * head_dim) @ w["wo"],
                          w["n2"], eps))
    b = cast(rms_norm(h, w["n3"], eps))
    m = (jax.nn.silu(b @ w["w_gate"]) * (b @ w["w_up"])) @ w["w_down"]
    return h + cast(rms_norm(m, w["n4"], eps))


def token_ce(h, head, targets, low=None):
    """Cross-entropy of each row of h [R, d] against its target."""
    if low is not None:
        h, head = h.astype(low), head.astype(low)
    z = (h @ head).astype(jnp.float32)
    return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, targets[:, None], axis=-1)[:, 0]


def exit_distribution(lam):
    """lam [T, S] -> p [T, S]: p_t = lam_t prod_{j<t}(1 - lam_j), the
    last pass takes what is left."""
    t_steps = lam.shape[0]
    stay = jnp.ones_like(lam[0])
    p = []
    for t in range(t_steps - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    p.append(stay)
    return jnp.stack(p)


FAULTS = ("three_passes", "last_pass_loss", "uniform_exit",
          "half_head_blocks", "unchained_passes")


def forward(weights: dict, tokens, model: dict, *, blocked=False, low=None,
            fault=None):
    """tokens [S + 1] -> (ce [T, S], p [T, S]): each pass's per-token
    cross-entropy against the next token, and the exit distribution.
    ``fault`` (scripts/calibrate_lm.py alone) plants one of ``FAULTS``
    here: a pass left out, the gate ignored (every pass weighted alike),
    or every pass started from the embedding (the loop not chained)."""
    n_layers = int(model["num_hidden_layers"])
    t_steps = int(model["total_ut_steps"]) - (fault == "three_passes")
    eps = float(model["rms_norm_eps"])
    kw = dict(heads=int(model["num_attention_heads"]),
              head_dim=int(model["head_dim"]), eps=eps,
              theta=float(model["rope_theta"]), low=low)
    layer_fn, ce_fn = _blocked_fns(**kw) if blocked else (
        functools.partial(layer, **kw),
        functools.partial(token_ce, low=low))
    inputs, targets = tokens[:-1], tokens[1:]
    h = embedded = weights["embed"][inputs]
    if low is not None:
        h = embedded = h.astype(low)
    ces, lams = [], []
    for _ in range(t_steps):
        if fault == "unchained_passes":
            h = embedded
        for i in range(n_layers):
            h = layer_fn(h, layer_of(weights, i))
        h = rms_norm(h, weights["final_norm"], eps)
        if low is not None:
            h = h.astype(low)
        if blocked:
            rows = range(0, h.shape[0], HEAD_BLOCK_ROWS)
            ces.append(jnp.concatenate([
                ce_fn(h[r:r + HEAD_BLOCK_ROWS], weights["head"],
                      targets[r:r + HEAD_BLOCK_ROWS]) for r in rows]))
        else:
            ces.append(ce_fn(h, weights["head"], targets))
        lams.append(jax.nn.sigmoid(
            h.astype(jnp.float32) @ weights["gate_w"] + weights["gate_b"]))
    p = exit_distribution(jnp.stack(lams))
    if fault == "uniform_exit":
        p = jnp.full_like(p, 1.0 / t_steps)
    return jnp.stack(ces), p


@functools.lru_cache(maxsize=4)
def _blocked_fns(**kw):
    """The layer and the head's rows, each one jitted call that keeps
    nothing but its inputs for the backward."""
    return (jax.jit(jax.checkpoint(functools.partial(layer, **kw))),
            jax.jit(jax.checkpoint(functools.partial(token_ce,
                                                     low=kw["low"]))))


def loss_terms(ce, p, beta: float, fault=None):
    """(loss, per-pass mean cross-entropy [T], mean exit probability [T])."""
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    per_token = jnp.sum(p * ce, axis=0) - beta * entropy
    if fault == "half_head_blocks":
        # the head run on the first half of its row blocks only, and the
        # mean taken over what ran: a loss near ln V does not show it
        kept = ce.shape[1] // 2
        per_token, ce = per_token[:kept], ce[:, :kept]
    loss = jnp.mean(per_token)
    if fault == "last_pass_loss":  # the earlier passes' paths cut
        loss = jnp.mean(ce[-1])
    return loss, jnp.mean(ce, axis=1), jnp.mean(p, axis=1)


def loss_fn(weights, tokens, model, beta, *, fault=None, **kw):
    """tokens [B, S + 1]: the mean over the sequences."""
    out = [loss_terms(*forward(weights, row, model, fault=fault, **kw),
                      beta, fault) for row in tokens]
    return tuple(sum(o[i] for o in out) / len(out) for i in range(3))


def loss_and_grads(weights, tokens, model, beta, **kw):
    def with_aux(w):
        loss, ce_t, p_t = loss_fn(w, tokens, model, beta, **kw)
        return loss, (ce_t, p_t)

    with jax.default_matmul_precision("highest"):
        (loss, (ce_t, p_t)), grads = jax.value_and_grad(
            with_aux, has_aux=True)(weights)
    return loss, ce_t, p_t, grads


def global_norm(tree: dict):
    return jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in tree.values()))


def adamw_step(weights, grads, mu, nu, count, opt: dict):
    """Global-norm clip, Adam's moments with bias correction, decoupled
    weight decay on matrices only."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, opt["clip_norm"] / gnorm)
    count = count + 1
    new_w, new_mu, new_nu = {}, {}, {}
    for k, w in weights.items():
        g = grads[k] * scale
        new_mu[k] = b1 * mu[k] + (1 - b1) * g
        new_nu[k] = b2 * nu[k] + (1 - b2) * g * g
        step = (new_mu[k] / (1 - b1 ** count)) / (
            jnp.sqrt(new_nu[k] / (1 - b2 ** count)) + eps)
        decay = opt["weight_decay"] if w.ndim >= 2 else 0.0
        new_w[k] = w - opt["lr"] * (step + decay * w)
    return new_w, new_mu, new_nu, count


_adamw_jit = jax.jit(adamw_step, donate_argnums=(0, 2, 3))


def train_steps(weights: dict, batches, model: dict, opt: dict, *,
                blocked=False, remake=None, **kw) -> dict:
    """Follow ``len(batches)`` optimizer steps from ``weights``; each
    batch is tokens [B, S + 1].  Returns the readings the benchmark
    compares: each step's loss, step 1's per-pass cross-entropies, mean
    exit probabilities, gradient (norm and leaves, on the host), and the
    per-leaf norm of the parameters' change after the last step.
    ``blocked`` gives ``weights`` up to the optimizer step; ``remake()``
    then makes the start again for the change (no second copy is held
    meanwhile)."""
    start = None if blocked else weights
    mu = {k: jnp.zeros_like(v) for k, v in weights.items()}
    nu = {k: jnp.zeros_like(v) for k, v in weights.items()}
    count = 0
    out = {"losses": []}
    step = _adamw_jit if blocked else adamw_step
    for i, tokens in enumerate(batches):
        loss, ce_t, p_t, grads = loss_and_grads(
            weights, tokens, model, opt["beta"], blocked=blocked, **kw)
        out["losses"].append(float(loss))
        if i == 0:
            out.update(ce=[float(v) for v in ce_t],
                       exit_prob=[float(v) for v in p_t],
                       grad_norm=float(global_norm(grads)),
                       grads=jax.device_get(grads))
        weights, mu, nu, count = step(weights, grads, mu, nu, count, opt)
        del grads
    del mu, nu
    start = remake() if start is None else start
    out["change_norms"] = {k: float(jnp.linalg.norm(
        (weights[k] - start[k]).ravel())) for k in weights}
    return out
