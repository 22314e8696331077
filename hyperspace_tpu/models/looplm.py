"""LoopLM — a looped language model (Ouro; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): ONE stack of transformer
layers run ``total_ut_steps`` times over the same weights, an exit gate
after each pass, and a training loss that is an expectation over the
passes.

The equations are the published Euclidean ones — the curvature-zero case
of this framework (``manifolds/euclidean.py``), never a hyperbolic
variant under the model's name (ROADMAP north star):

    RMSNorm_g(x) = x / sqrt(mean(x²) + ε) ⊙ g
    layer (a norm before AND after each sub-layer, four gains):
        a = RMSNorm₁(h);  q, k, v = aW_q, aW_k, aW_v  (heads of head_dim)
        q, k ← RoPE(q, k; position, θ)                (rotate-half)
        o = softmax(q kᵀ / √head_dim + causal) v
        h ← h + RMSNorm₂(o W_o)
        b = RMSNorm₃(h)
        h ← h + RMSNorm₄((silu(b W_gate) ⊙ (b W_up)) W_down)
    model: h = E[tokens]; for t = 1 … T: layers 1 … L over h (the same
        weights every t); h ← RMSNorm_f(h); z_t = h W_head;
        λ_t = σ(h·w_g + b_g); pass t+1 starts from this normed h
    exit distribution: p_t = λ_t ∏_{j<t}(1 − λ_j) (t < T),
        p_T = ∏_{j<T}(1 − λ_j)
    loss = mean over tokens of [ Σ_t p_t · CE(z_t, next) − β · H(p) ]

How one step holds T × L layer applications (``benchmark/reference/
looplm.py`` is the plain form of the same arithmetic):

- the layers' weights are stacked ``[L, …]``; ONE ``lax.scan`` runs all
  T·L layer applications, application j indexing layer j mod L of the
  stacked weights it closes over, so every pass reads the SAME weights
  and autodiff sums each weight's gradient over its T uses in one
  carried accumulator (:func:`forward` says why not T loops);
- ``remat="layer"`` wraps an application in ``jax.checkpoint``: the
  backward keeps each application's input and, by name, the flash
  forward call's output and row statistics (what only the kernel can
  produce: half the input's bytes), and recomputes the rest — one path
  at every size and precision, the same algorithm with one more thing
  kept (:func:`_keep_flash_results`);
- the last layer of a pass is followed by the final norm alone (a
  ``lax.cond`` on the layer index; its output starts the next pass), and
  the loop hands out the T normed streams ``[T, S, d]``;
- the head, its cross-entropy and the gate's logits run ONCE, after the
  loop, over the T·S rows of all passes: ``HEAD_BLOCK_ROWS`` rows at a
  time under ``jax.checkpoint``, so one block's ``[rows, V]`` logits live
  at a time (never a pass's ``[S, V]``, let alone all T), and the loop
  over the layers never holds an array of the head's shape
  (:func:`forward` says what that saves);
- precision lanes (``precision.Policy``): float32 parameters, gradients
  and moments; matmul operands and the attention kernel's q/k/v on the
  ``compute`` lane, cast at their use (``precision.lane_matmul``: no
  second copy of the weights is held, and a weight's gradient sums over
  its T uses in float32) with float32 products; norms, rotary, softmax,
  gate, loss and optimizer in float32;
- attention is ``kernels.attention.flash_dot_attention`` (causal inside
  the kernel; the dense XLA twin on the CPU).

Named scopes for the device trace: ``embed``, ``ut_step``, ``layer/attn``,
``layer/mlp``, ``final_norm``, ``head``, ``exit_gate``, ``loss``,
``optimizer``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from hyperspace_tpu.telemetry.trace import importing, span

with importing("optax"):
    import optax

from hyperspace_tpu import precision as precision_mod
from hyperspace_tpu.kernels.attention import flash_dot_attention
from hyperspace_tpu.models import lm_parts
from hyperspace_tpu.models.lm_parts import batch_at
from hyperspace_tpu.nn.layers import (apply_rotary, rms_norm, rotary_tables,
                                      swiglu)

LAYER_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_GAINS = ("n1", "n2", "n3", "n4")
# stats carried in the state, one vector a step: the loss, the gradient's
# global norm before the clip, then T per-pass mean cross-entropies and T
# mean exit probabilities
STATS_HEAD = 2
# rows of the head's logits computed (and recomputed) at a time
HEAD_BLOCK_ROWS = lm_parts.HEAD_BLOCK_ROWS


@dataclasses.dataclass(frozen=True)
class LoopLMConfig:
    # the published config.json's keys (configs/looplm_ouro_2p6b.yaml
    # holds Ouro-2.6B's values; these defaults are a test size)
    hidden_size: int = 64
    intermediate_size: int = 176
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 16
    vocab_size: int = 512
    total_ut_steps: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # the job
    sequence_length: int = 64
    sequences_per_step: int = 1
    remat: str = "layer"            # "layer" | "none"
    # the objective and the optimizer (each `assumed`: the catalog row
    # gives none of them)
    entropy_beta: float = 0.1
    lr: float = 3e-4
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    weight_decay: float = 0.1       # on matrices only
    clip_norm: float = 1.0
    init_std: float = 0.02
    precision: str = "f32"          # precision.Policy preset
    # jax's matmul precision for what the policy leaves float32 (on the
    # TPU a float32 matmul at "default" is one bf16 pass; "highest" is
    # float32 in truth, six passes: the benchmark's check twin)
    matmul_precision: str = "default"

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("LoopLM is plain multi-head: "
                             "num_key_value_heads == num_attention_heads")
        if self.hidden_act != "silu" or self.tie_word_embeddings:
            raise ValueError("LoopLM runs the published silu gate and "
                             "untied embeddings only")
        if self.remat not in ("layer", "none"):
            raise ValueError(f"remat={self.remat!r}: want layer|none")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps >= 1")


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array
    stats: jax.Array   # [STATS_HEAD + 2T] float32, the last step's


def init_params(cfg: LoopLMConfig, seed: int = 0) -> dict:
    """Matrices ~ N(0, init_std²), gains 1, the gate's bias 0; the
    layers' leaves stacked ``[L, …]``."""
    d, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hd = cfg.num_attention_heads * cfg.head_dim
    n = cfg.num_hidden_layers
    shapes = {"wq": (d, hd), "wk": (d, hd), "wv": (d, hd), "wo": (hd, d),
              "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    keys = jax.random.split(jax.random.PRNGKey(seed), 3 + len(shapes))
    normal = lambda k, shape: cfg.init_std * jax.random.normal(
        k, shape, jnp.float32)
    layers = {name: normal(k, (n,) + shapes[name])
              for name, k in zip(LAYER_MATS, keys[3:])}
    layers.update({g: jnp.ones((n, d), jnp.float32) for g in LAYER_GAINS})
    return {"embed": normal(keys[0], (v, d)),
            "head": normal(keys[1], (d, v)),
            "gate_w": normal(keys[2], (d,)),
            "gate_b": jnp.zeros((), jnp.float32),
            "final_norm": jnp.ones((d,), jnp.float32),
            "layers": layers}


@functools.lru_cache(maxsize=8)
def make_optimizer(cfg: LoopLMConfig):
    """Global-norm clip, then AdamW with decay on matrices only (the
    stacked gains are [L, d]: told from matrices by name, not by rank).
    One object a configuration: it is a static argument of the jitted
    step, and a second, equal optimizer would compile the step again."""
    def decayed(params):
        mask = jax.tree_util.tree_map(lambda _: False, params)
        mask.update(embed=True, head=True)
        mask["layers"] = {k: k in LAYER_MATS for k in params["layers"]}
        return mask

    return optax.chain(
        optax.clip_by_global_norm(cfg.clip_norm),
        optax.adamw(cfg.lr, b1=cfg.adam_b1, b2=cfg.adam_b2, eps=cfg.adam_eps,
                    weight_decay=cfg.weight_decay, mask=decayed))


def init_state(cfg: LoopLMConfig, seed: int = 0, params=None):
    """(optimizer, state); ``params`` puts a given tree (the benchmark's
    weights) in place of the seed's own, so that no second copy is made."""
    info = {"model": "looplm"}
    with span("init", info):
        params = init_params(cfg, seed) if params is None else params
        opt = make_optimizer(cfg)
        stats = jnp.zeros((STATS_HEAD + 2 * cfg.total_ut_steps,),
                          jnp.float32)
        state = TrainState(params, opt.init(params),
                           jnp.zeros((), jnp.int32), stats)
        info["params"] = sum(
            int(a.size) for a in jax.tree_util.tree_leaves(params))
    return opt, state


# --- the forward pass ---------------------------------------------------------


def _layer(cfg: LoopLMConfig, policy, rope, h, w):
    """One layer application over h [S, d] (float32 residual stream)."""
    s = h.shape[0]
    heads, dh = cfg.num_attention_heads, cfg.head_dim
    mm = policy.matmul
    with jax.named_scope("layer"):
        with jax.named_scope("attn"):
            a = rms_norm(h, w["n1"], cfg.rms_norm_eps)
            split = lambda x: x.reshape(s, heads, dh)
            q = apply_rotary(split(mm(a, w["wq"])), *rope)
            k = apply_rotary(split(mm(a, w["wk"])), *rope)
            v = split(mm(a, w["wv"]))
            # [S, H, D] -> [H, S, D] on the compute lane for the kernel
            to_heads = lambda x: policy.cast_compute(x).transpose(1, 0, 2)
            o = flash_dot_attention(to_heads(q), to_heads(k), to_heads(v),
                                    causal=True)
            o = o.transpose(1, 0, 2).reshape(s, heads * dh)
            h = h + rms_norm(mm(o, w["wo"]), w["n2"], cfg.rms_norm_eps)
        with jax.named_scope("mlp"):
            b = rms_norm(h, w["n3"], cfg.rms_norm_eps)
            m = swiglu(b, w["w_gate"], w["w_up"], w["w_down"], mm)
            h = h + rms_norm(m, w["n4"], cfg.rms_norm_eps)
    return h


def exit_log_probs(gate_logits):
    """gate_logits [T, S] -> log p [T, S], in log space: log p_t = log
    λ_t + Σ_{j<t} log(1 − λ_j), the last pass takes what is left."""
    log_lam = jax.nn.log_sigmoid(gate_logits)
    log_stay = jax.nn.log_sigmoid(-gate_logits)
    before = jnp.cumsum(log_stay, axis=0) - log_stay   # Σ_{j<t}
    return jnp.concatenate([(log_lam + before)[:-1], before[-1:]], axis=0)


def _keep_flash_results():
    """The policy of ``remat="layer"``: of an application, beyond its
    input, the flash forward call's output and row statistics are kept
    (``lm_parts.keep_flash_results``); gauge ``looplm/remat_kept_bytes``."""
    return lm_parts.keep_flash_results("looplm/remat_kept_bytes")


def forward(cfg: LoopLMConfig, params, tokens):
    """tokens [S + 1] int32 -> (ce [T, S], log p [T, S]).

    ONE ``lax.scan`` over all T·L layer applications: application j runs
    layer j mod L (the stacked weights are closed over and indexed, so
    every pass reads the same ones), and the last layer of a pass is
    followed, under ``lax.cond``, by the final norm, whose output starts
    the next pass.  Under ``remat="layer"`` an application is checkpointed
    with :func:`_keep_flash_results`: its input, the flash call's output
    and row statistics are kept, the rest recomputed (the kernel runs
    T·L times forward and never again in the backward; 17 MB an
    application at Ouro's widths beside the 33.5 MB input).  The loop
    hands out ``hs``, the T normed streams
    ``[T, S, d]``: it rides the carry beside ``h`` and takes every
    application's output at slot j // L, outside the checkpoint and under
    no cond, so XLA updates it in place and a pass's last write is its
    normed stream.  The head's cross-entropy and the gate's logits then
    run ONCE, over all T·S rows, after the loop.

    Why one flat loop, and why the head is not in it — where the gradient
    of what a loop closes over lives (each form compiled for a described
    v5e at Ouro-2.6B's widths, 8 layers, S = 4096; temporaries):
    a scan's backward carries one accumulator for each closed-over array.
    The stacked layers' are indexed, and XLA adds a slice's gradient in
    place (``dynamic-update-slice`` on the carry).  What a ``lax.cond``
    inside the body closes over is added WHOLE at every application, its
    untaken branch first writing the zeros: with the head under the
    end-of-pass cond that was an ``f32[2048, 49152]`` add 32 times a step
    and 8.34 GB; only the final norm's ``[d]`` gain is left there, and
    this form takes 6.84 GB.  A scan over the passes round a scan over
    the layers (no cond at all) takes 10.9 GB: the inner loop's gradient
    is a fresh ``[L, …]`` stack a pass that the outer loop adds whole.  T
    loops in Python leave T such stacks alive until the optimizer adds
    them (21.0 GB with the head inside)."""
    policy = precision_mod.get_policy(cfg.precision)
    inputs, targets = tokens[:-1], tokens[1:]
    n_layers, passes, seq = (cfg.num_hidden_layers, cfg.total_ut_steps,
                             inputs.shape[0])
    with jax.named_scope("embed"):
        rope = rotary_tables(seq, cfg.head_dim, cfg.rope_theta)
        h = params["embed"][inputs]

    def final_norm(h):
        with jax.named_scope("final_norm"):
            return rms_norm(h, params["final_norm"], cfg.rms_norm_eps)

    def application(h, j):
        i = j % n_layers
        h = _layer(cfg, policy, rope, h, jax.tree_util.tree_map(
            lambda a: a[i], params["layers"]))
        return jax.lax.cond(i == n_layers - 1, final_norm, lambda h: h, h)

    if cfg.remat == "layer":
        application = jax.checkpoint(application,
                                     policy=_keep_flash_results())

    def body(carry, j):
        h, hs = carry
        h = application(h, j)
        return (h, jax.lax.dynamic_update_index_in_dim(
            hs, h, j // n_layers, 0)), None

    with jax.named_scope("ut_step"):
        (_, hs), _ = jax.lax.scan(
            body, (h, jnp.zeros((passes,) + h.shape, h.dtype)),
            jnp.arange(passes * n_layers))
    with jax.named_scope("head"):  # its ``loss`` scope lies inside
        # a row's cross-entropy does not know its pass: all T·S rows as one
        ce = lm_parts.blocked_token_ce(
            policy, hs.reshape(passes * seq, -1), params["head"],
            jnp.tile(targets, passes), HEAD_BLOCK_ROWS).reshape(passes, seq)
    with jax.named_scope("exit_gate"):
        # a float32 reduction on the vector unit, not an MXU pass
        gate = jnp.sum(hs * params["gate_w"], axis=-1) + params["gate_b"]
        log_p = exit_log_probs(gate)
    return ce, log_p


def loss_fn(cfg: LoopLMConfig, params, tokens):
    """tokens [B, S + 1] -> (loss, (per-pass mean CE [T], mean exit
    probability [T])), means over the B·S tokens."""
    with jax.default_matmul_precision(cfg.matmul_precision):
        rows = [forward(cfg, params, row) for row in tokens]
    ce, log_p = (jnp.stack(part, axis=1) for part in zip(*rows))
    with jax.named_scope("loss"):
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.mean(jnp.sum(p * ce, axis=0) - cfg.entropy_beta * entropy)
        return loss, (jnp.mean(ce, axis=(1, 2)), jnp.mean(p, axis=(1, 2)))


# --- the step -----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "opt"),
                   donate_argnames=("state",))
def train_step(cfg: LoopLMConfig, opt, state: TrainState, stream):
    """One optimizer step on the step's own slice of the token stream —
    a single XLA program; the state is donated."""
    tokens = batch_at(stream, state.step, cfg)
    (loss, (ce_t, p_t)), grads = jax.value_and_grad(
        functools.partial(loss_fn, cfg), has_aux=True)(state.params, tokens)
    with jax.named_scope("optimizer"):
        gnorm = optax.global_norm(grads)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        stats = jnp.concatenate([jnp.stack([loss, gnorm]), ce_t, p_t])
    return TrainState(params, opt_state, state.step + 1, stats), loss


def read_stats(cfg: LoopLMConfig, stats) -> dict:
    """The state's stats vector by name (host side)."""
    t = cfg.total_ut_steps
    vals = [float(v) for v in stats]
    return {"loss": vals[0], "grad_norm": vals[1],
            "ce": vals[STATS_HEAD:STATS_HEAD + t],
            "exit_prob": vals[STATS_HEAD + t:STATS_HEAD + 2 * t]}
