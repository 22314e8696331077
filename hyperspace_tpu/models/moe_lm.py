"""MoE LM — a decoder with sliding-window and full grouped-query attention,
per-head output gates, and sparse expert feed-forwards beside a shared
expert (Laguna; huggingface.co/poolside/Laguna-S-2.1 config.json); or
with latent attention, sigmoid routing and a multi-token-prediction
module (GLM-4.7-Flash, ``glm4_moe_lite``;
huggingface.co/zai-org/GLM-4.7-Flash config.json, in DeepSeek-V2/V3's
published forms, arXiv:2405.04434 §2.1, arXiv:2412.19437 §2.1–2.2).

The equations (pre-norm; h [S, d] is the float32 residual stream; layer
ℓ's kind is ``layer_types[ℓ]`` and ``mlp_layer_types[ℓ]``):

    a = RMSNorm(h);  q = a W_q (H_ℓ heads),  k, v = a W_k, a W_v
        (``num_key_value_heads``; query head g reads K/V head g // (H_ℓ/H_kv))
    full layers:    YaRN rotary over the first ``partial_rotary_factor``
                    of each head's lanes (its frequencies and
                    ``attention_factor`` on cos and sin), the rest as is
    sliding layers: plain rotary over the whole head; the mask also
                    needs key > query − ``sliding_window``
    o_g = softmax(q_g kᵀ / √D + mask) v;  o_g ← σ(a W_gate)_g · o_g
        (``gating`` per-head: one gate a head, read off the same normed a)
    h ← h + concat(o) W_o
    latent layers (MLA; no K/V heads of their own, no gate):
        c_q = RMSNorm(a W_qa);  q = c_q W_qb, each head [q_nope | q_rope]
        [c_kv | k_r] = a W_kva;  each head [k_nope | v] = RMSNorm(c_kv) W_kvb
        rotary (θ ``rope_theta_latent``) on q_rope and on k_r, ONE key
        shared by every head;  k = [k_nope | k_r]
        o = softmax(q kᵀ / √(nope + rope) + causal) v;  h ← h + concat(o) W_o
        (K and V are decompressed per head for one flash call, the
        training path DeepSeek-V3 takes)
    b = RMSNorm(h)
    dense layers:  h ← h + SwiGLU(b)                (``intermediate_size``)
    sparse layers: h ← h + Σ_{e ∈ top-k, e held} w_e SwiGLU_e(b)
                          + SwiGLU_shared(b)       (``nn/moe.py``)
    the head: RMSNorm, an untied head, cross-entropy on the next token
    multi-token prediction (``num_nextn_predict_layers`` 1): at position i
        h'_i = W_eh [RMSNorm(h_i); RMSNorm(E[t_{i+1}])]  (h the stack's
        output before the final norm, E the shared embedding), one more
        layer of the last layer's kind, the shared final norm and head,
        cross-entropy on t_{i+2} over the S − 1 positions that have it;
        the objective is L_main + ``mtp_loss_weight`` · L_mtp

The router (``nn/moe.py``): ``scoring_func`` softmax (Laguna) or sigmoid
(``topk_method`` noaux_tc: the choice on the scores plus a selection
bias, the weights the scores alone).

The expert share: the router is ``num_experts × expert_shards`` wide (all
the deployment's experts), and this program holds ``num_experts`` of
them, share ``expert_share``; the experts held elsewhere add nothing here
(``nn/moe.py``).  Holding a share (``expert_shards`` > 1, ``holds_share``)
the program routes as the cut needs, so that the held experts keep the
share of the slots a deployment gives them (``num_experts /
router_width`` when routing is even):

- the router is held fixed: its weights stay as they were made and its
  scores pass no gradient, to the router or to the layer's input (the
  top-k weights still scale the experts' outputs).  With nothing
  standing in for the other shares (they add exactly zero), a trained
  router learns within a few steps to route nothing to the held experts;
- the choice is balanced (``nn/moe.py`` ``route``): as training aligns
  the tokens' streams, a fixed router would otherwise send every token to
  the same few experts within ~10 steps.

How a step is laid out:

- the layers are grouped by kind (attention kind, feed-forward kind):
  each kind's weights are stacked ``[layers of the kind, …]``, since the
  kinds' shapes differ; a run of consecutive layers of one kind is one
  ``lax.scan`` over its stack, a lone layer one call;
- ``remat="layer"``: each layer under ``jax.checkpoint``, keeping its
  input and the flash forward call's output and row statistics
  (``lm_parts.keep_flash_results``); the rest, routing and the grouped
  matmuls among it, is recomputed in the backward;
- the head and its cross-entropy run ``lm_parts.HEAD_BLOCK_ROWS`` rows at
  a time (``lm_parts.blocked_token_ce``);
- precision lanes (``precision.Policy``): float32 parameters, gradients
  and moments; matmul operands on the compute lane with float32
  products; norms, rotary, softmax, the router (its logits at full
  float32 precision), the top-k weights, loss and optimizer in float32.

Named scopes: ``embed``, ``attn`` (``attn/mla_q``: q's down-projection,
norm, up-projection and rotary; ``attn/mla_kv``: the joint
down-projection, norm, up-projection and k_r's rotary and broadcast),
``mlp`` (and ``nn/moe.py``'s ``moe/route``, ``moe/dispatch``,
``moe/gmm``, ``moe/combine`` inside it, ``moe/shared`` beside them),
``final_norm``, ``head``, ``loss``, ``mtp`` (the module whole: its
norms, W_eh, its layer, its head and loss), ``optimizer``.  Gauges (set
where the CLI logs): ``moe_lm/max_held_rows`` and
``moe_lm/mean_held_rows`` (rows routed to held experts, over the sparse
layers, the MTP layer's among them), ``moe_lm/held_share`` (of all the
step's routed slots), ``moe_lm/remat_kept_bytes``,
``moe_lm/mtp_positions_per_step``, ``moe_lm/mla_kv_decompressed_bytes``.
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
from hyperspace_tpu.nn import moe
from hyperspace_tpu.nn.layers import (apply_rotary, rms_norm, rotary_tables,
                                      swiglu)

ATTN_MATS = ("wq", "wk", "wv", "wo", "w_head_gate")
LAYER_TYPES = ("full_attention", "sliding_attention", "latent_attention")
DENSE_MATS = ("w_gate", "w_up", "w_down")
SPARSE_MATS = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
               "s_down")
GAINS = ("n_attn", "n_mlp", "n_qa", "n_kva", "enorm", "hnorm")
# stats carried in the state, one vector a step: the loss, the gradient's
# global norm before the clip, then the rows routed to held experts in
# each sparse layer (the MTP layer's last), then the held experts that got
# any row in each; with an MTP module, then the main and the MTP losses
STATS_HEAD = 2


@dataclasses.dataclass(frozen=True)
class MoELMConfig:
    # the published config.json's keys (configs/moe_lm_laguna_s21.yaml
    # holds Laguna-S-2.1's values; these defaults are a test size).  The
    # per-layer lists are read for the first num_hidden_layers layers
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 5
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    vocab_size: int = 512
    rms_norm_eps: float = 1e-6
    layer_types: tuple = ("full_attention", "sliding_attention",
                          "sliding_attention", "sliding_attention",
                          "full_attention")
    mlp_layer_types: tuple = ("dense", "sparse", "sparse", "sparse",
                              "sparse")
    num_attention_heads_per_layer: tuple = (4, 6, 6, 6, 4)
    sliding_window: int = 16
    # rotary: full layers (YaRN over a part of the head), sliding layers
    rope_theta_full: float = 5e5
    partial_rotary_full: float = 0.5
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor); () = plain rotary on full layers
    rope_yarn_full: tuple = (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    rope_theta_sliding: float = 1e4
    partial_rotary_sliding: float = 1.0
    # experts: ``num_experts`` held here of num_experts × expert_shards
    num_experts: int = 8
    expert_shards: int = 4
    expert_share: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 32
    shared_expert_intermediate_size: int = 32
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    scoring_func: str = "softmax"   # | "sigmoid" (topk_method noaux_tc)
    # latent attention (layer_types "latent_attention")
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 12
    qk_rope_head_dim: int = 4
    v_head_dim: int = 16
    rope_theta_latent: float = 1e6
    # multi-token prediction: 0 or 1 module after the stack
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    # the job
    sequence_length: int = 64
    sequences_per_step: int = 1
    remat: str = "layer"            # "layer" | "none"
    lr: float = 3e-4
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    weight_decay: float = 0.1       # on matrices only
    clip_norm: float = 1.0
    init_std: float = 0.02
    precision: str = "f32"          # precision.Policy preset
    matmul_precision: str = "default"

    def __post_init__(self):
        n = self.num_hidden_layers
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            if len(getattr(self, key)) < n:
                raise ValueError(f"{key} names fewer than "
                                 f"num_hidden_layers={n} layers")
        if set(self.layer_types[:n]) - set(LAYER_TYPES):
            raise ValueError(f"layer_types {sorted(set(self.layer_types))}")
        if set(self.mlp_layer_types[:n]) - {"dense", "sparse"}:
            raise ValueError(
                f"mlp_layer_types {sorted(set(self.mlp_layer_types))}")
        for kind in self.kinds():
            heads = {self.num_attention_heads_per_layer[i]
                     for i in self.layers_of(kind)}
            if len(heads) != 1:
                raise ValueError(f"layers of kind {kind} differ in heads")
            if not kind.startswith("latent") and (
                    heads.pop() % self.num_key_value_heads):
                raise ValueError("query heads are no whole groups of "
                                 "num_key_value_heads")
        if any(k.startswith("latent") for k in self.kinds()) and (
                self.qk_nope_head_dim + self.qk_rope_head_dim
                != self.v_head_dim or self.qk_rope_head_dim % 2):
            raise ValueError(
                "latent attention runs one flash call: qk_nope_head_dim + "
                f"qk_rope_head_dim ({self.qk_nope_head_dim} + "
                f"{self.qk_rope_head_dim}) must equal v_head_dim "
                f"({self.v_head_dim}), the rotated lanes even")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func={self.scoring_func!r}: want "
                             "softmax|sigmoid")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(f"num_nextn_predict_layers="
                             f"{self.num_nextn_predict_layers}: 0 or 1")
        if not 0 <= self.expert_share < self.expert_shards:
            raise ValueError(f"expert_share={self.expert_share} of "
                             f"{self.expert_shards} shares")
        if self.remat not in ("layer", "none"):
            raise ValueError(f"remat={self.remat!r}: want layer|none")

    def kind_of(self, i: int) -> str:
        return (self.layer_types[i].split("_")[0] + "_"
                + self.mlp_layer_types[i])

    def kinds(self) -> tuple:
        """The layer kinds present, in order of first use."""
        return tuple(dict.fromkeys(self.kind_of(i)
                                   for i in range(self.num_hidden_layers)))

    def layers_of(self, kind: str) -> list:
        return [i for i in range(self.num_hidden_layers)
                if self.kind_of(i) == kind]

    def heads_of(self, kind: str) -> int:
        return self.num_attention_heads_per_layer[self.layers_of(kind)[0]]

    @property
    def router_width(self) -> int:
        return self.num_experts * self.expert_shards

    @property
    def holds_share(self) -> bool:
        """A share of the experts is held: the router is held fixed and
        its choice balanced (module doc)."""
        return self.expert_shards > 1

    @property
    def mtp_kind(self):
        """The MTP module's layer kind (the stack's last), or None."""
        return (self.kind_of(self.num_hidden_layers - 1)
                if self.num_nextn_predict_layers else None)

    @property
    def sparse_layers(self) -> int:
        """Sparse layers, the MTP module's among them."""
        return sum(t == "sparse"
                   for t in self.mlp_layer_types[:self.num_hidden_layers]) + (
            bool(self.mtp_kind and self.mtp_kind.endswith("_sparse")))


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array
    stats: jax.Array   # [STATS_HEAD + 2 sparse layers] float32, the last step's


def kind_shapes(cfg: MoELMConfig, kind: str) -> dict:
    """{leaf: shape of one layer's} of a layer kind."""
    d, dh = cfg.hidden_size, cfg.head_dim
    heads, kv = cfg.heads_of(kind), cfg.num_key_value_heads
    if kind.startswith("latent"):
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        out = {"w_qa": (d, cfg.q_lora_rank), "n_qa": (cfg.q_lora_rank,),
               "w_qb": (cfg.q_lora_rank, heads * qk),
               "w_kva": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
               "n_kva": (cfg.kv_lora_rank,),
               "w_kvb": (cfg.kv_lora_rank,
                         heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
               "wo": (heads * cfg.v_head_dim, d), "n_attn": (d,),
               "n_mlp": (d,)}
    else:
        out = {"wq": (d, heads * dh), "wk": (d, kv * dh),
               "wv": (d, kv * dh), "wo": (heads * dh, d),
               "w_head_gate": (d, heads), "n_attn": (d,), "n_mlp": (d,)}
    if kind.endswith("_dense"):
        f = cfg.intermediate_size
        out.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        e, f = cfg.num_experts, cfg.moe_intermediate_size
        fs = cfg.shared_expert_intermediate_size
        out.update(router=(d, cfg.router_width), e_gate=(e, d, f),
                   e_up=(e, d, f), e_down=(e, f, d), s_gate=(d, fs),
                   s_up=(d, fs), s_down=(fs, d))
    return out


def init_params(cfg: MoELMConfig, seed: int = 0) -> dict:
    """Matrices ~ N(0, init_std²), gains 1; each kind's leaves stacked
    ``[layers of the kind, …]``."""
    d, v = cfg.hidden_size, cfg.vocab_size
    key = jax.random.PRNGKey(seed)
    normal = lambda k, shape: cfg.init_std * jax.random.normal(
        k, shape, jnp.float32)
    key, ke, kh = jax.random.split(key, 3)
    params = {"embed": normal(ke, (v, d)), "head": normal(kh, (d, v)),
              "final_norm": jnp.ones((d,), jnp.float32), "kinds": {}}

    def stacked(kind, n):
        nonlocal key
        leaves = {}
        for name, shape in kind_shapes(cfg, kind).items():
            if name in GAINS:
                leaves[name] = jnp.ones((n,) + shape, jnp.float32)
            else:
                key, k = jax.random.split(key)
                leaves[name] = normal(k, (n,) + shape)
        return leaves

    for kind in cfg.kinds():
        params["kinds"][kind] = stacked(kind, len(cfg.layers_of(kind)))
    if cfg.mtp_kind:
        key, k = jax.random.split(key)
        params["mtp"] = {"enorm": jnp.ones((d,), jnp.float32),
                         "hnorm": jnp.ones((d,), jnp.float32),
                         "w_eh": normal(k, (2 * d, d)),
                         "layer": stacked(cfg.mtp_kind,
                                          cfg.num_nextn_predict_layers)}
    return params


@functools.lru_cache(maxsize=8)
def make_optimizer(cfg: MoELMConfig):
    """Global-norm clip, then AdamW with decay on matrices only (the
    gains, told by name) and, where ``holds_share``, not on the router,
    whose gradient is zero: its update is exactly zero.  One object a
    configuration (a static argument of the jitted step)."""
    def decays(leaves):
        return {k: k not in GAINS and not (cfg.holds_share and k == "router")
                for k in leaves}

    def decayed(params):
        mask = {"embed": True, "head": True, "final_norm": False}
        mask["kinds"] = {kind: decays(leaves)
                         for kind, leaves in params["kinds"].items()}
        if "mtp" in params:
            mask["mtp"] = {**decays({k: 0 for k in params["mtp"]
                                     if k != "layer"}),
                           "layer": decays(params["mtp"]["layer"])}
        return mask

    return optax.chain(
        optax.clip_by_global_norm(cfg.clip_norm),
        optax.adamw(cfg.lr, b1=cfg.adam_b1, b2=cfg.adam_b2, eps=cfg.adam_eps,
                    weight_decay=cfg.weight_decay, mask=decayed))


def init_state(cfg: MoELMConfig, seed: int = 0, params=None):
    """(optimizer, state); ``params`` puts a given tree (the benchmark's
    weights) in place of the seed's own."""
    info = {"model": "moe_lm"}
    with span("init", info):
        params = init_params(cfg, seed) if params is None else params
        opt = make_optimizer(cfg)
        stats = jnp.zeros((STATS_HEAD + 2 * cfg.sparse_layers
                           + 2 * bool(cfg.mtp_kind),), jnp.float32)
        state = TrainState(params, opt.init(params),
                           jnp.zeros((), jnp.int32), stats)
        info["params"] = sum(
            int(a.size) for a in jax.tree_util.tree_leaves(params))
    return opt, state


# --- the forward pass ---------------------------------------------------------


def rope_of(cfg: MoELMConfig, attn: str, length: int):
    """(cos, sin) of a layer's attention kind (latent: of the rotated
    slice alone)."""
    if attn == "latent":
        return rotary_tables(length, cfg.qk_rope_head_dim,
                             cfg.rope_theta_latent)
    if attn == "full":
        return rotary_tables(
            length, cfg.head_dim, cfg.rope_theta_full,
            rotary_dim=int(cfg.head_dim * cfg.partial_rotary_full),
            yarn=tuple(cfg.rope_yarn_full) or None)
    return rotary_tables(
        length, cfg.head_dim, cfg.rope_theta_sliding,
        rotary_dim=int(cfg.head_dim * cfg.partial_rotary_sliding))


def _latent_attention(cfg, policy, heads: int, rope, h, w):
    """MLA (module doc): q from its compressed form, K and V decompressed
    per head from the joint latent, the rotary key shared by the heads."""
    s, eps, mm = h.shape[0], cfg.rms_norm_eps, policy.matmul
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    with jax.named_scope("attn"):
        a = rms_norm(h, w["n_attn"], eps)
        with jax.named_scope("mla_q"):
            c_q = rms_norm(mm(a, w["w_qa"]), w["n_qa"], eps)
            q = mm(c_q, w["w_qb"]).reshape(s, heads, -1)
            q = jnp.concatenate([q[..., :nope],
                                 apply_rotary(q[..., nope:], *rope)], -1)
        with jax.named_scope("mla_kv"):
            kva = mm(a, w["w_kva"])
            c_kv, k_r = kva[:, :cfg.kv_lora_rank], kva[:, cfg.kv_lora_rank:]
            kv = mm(rms_norm(c_kv, w["n_kva"], eps),
                    w["w_kvb"]).reshape(s, heads, nope + dv)
            k_r = apply_rotary(k_r[:, None, :], *rope)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_r, (s, heads, k_r.shape[-1]))], -1)
            v = kv[..., nope:]
        to_heads = lambda x: policy.cast_compute(x).transpose(1, 0, 2)
        o = flash_dot_attention(to_heads(q), to_heads(k), to_heads(v),
                                causal=True)
        o = o.transpose(1, 0, 2).astype(jnp.float32)
        return h + mm(o.reshape(s, heads * dv), w["wo"])


def _attention(cfg, policy, attn: str, heads: int, rope, h, w):
    if attn == "latent":
        return _latent_attention(cfg, policy, heads, rope, h, w)
    s, dh, kv = h.shape[0], cfg.head_dim, cfg.num_key_value_heads
    mm = policy.matmul
    with jax.named_scope("attn"):
        a = rms_norm(h, w["n_attn"], cfg.rms_norm_eps)
        q = apply_rotary(mm(a, w["wq"]).reshape(s, heads, dh), *rope)
        k = apply_rotary(mm(a, w["wk"]).reshape(s, kv, dh), *rope)
        v = mm(a, w["wv"]).reshape(s, kv, dh)
        to_heads = lambda x: policy.cast_compute(x).transpose(1, 0, 2)
        o = flash_dot_attention(
            to_heads(q), to_heads(k), to_heads(v), causal=True,
            window=cfg.sliding_window if attn == "sliding" else None)
        gate = jax.nn.sigmoid(mm(a, w["w_head_gate"]))          # [S, H]
        o = o.transpose(1, 0, 2).astype(jnp.float32) * gate[..., None]
        return h + mm(o.reshape(s, heads * dh), w["wo"])


def _sparse_mlp(cfg, policy, b, w):
    """(the held experts' and the shared expert's sum, [rows routed to
    held experts, held experts that got any row])."""
    b_r, w_r = b, w["router"]
    if cfg.holds_share:
        b_r, w_r = jax.lax.stop_gradient(b_r), jax.lax.stop_gradient(w_r)
    top_w, top_e = moe.route(b_r, w_r, cfg.num_experts_per_tok,
                             cfg.moe_routed_scaling_factor,
                             cfg.norm_topk_prob, balanced=cfg.holds_share,
                             scoring=cfg.scoring_func)
    plan = moe.plan(top_w, top_e, cfg.expert_share * cfg.num_experts,
                    cfg.num_experts)
    y = moe.held_experts(b, w["e_gate"], w["e_up"], w["e_down"], plan,
                         policy.compute)
    with jax.named_scope("moe/shared"):
        y = y + swiglu(b, w["s_gate"], w["s_up"], w["s_down"], policy.matmul)
    sizes = plan.groups.sizes
    return y, jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(
        jnp.float32)


def _layer(cfg, policy, kind: str, rope, h, w):
    """One layer over h [S, d]: (h, [rows routed to held experts, held
    experts that got any row]; zeros on a dense layer)."""
    attn, mlp = kind.split("_")
    h = _attention(cfg, policy, attn, cfg.heads_of(kind), rope, h, w)
    with jax.named_scope("mlp"):
        b = rms_norm(h, w["n_mlp"], cfg.rms_norm_eps)
        if mlp == "dense":
            y, rows = swiglu(b, w["w_gate"], w["w_up"], w["w_down"],
                             policy.matmul), jnp.zeros((2,), jnp.float32)
        else:
            y, rows = _sparse_mlp(cfg, policy, b, w)
        return h + y, rows


def runs(cfg: MoELMConfig) -> list:
    """[(kind, first index in its stack, length)]: the layers in order,
    consecutive layers of one kind taken together."""
    out, seen = [], {}
    for i in range(cfg.num_hidden_layers):
        kind = cfg.kind_of(i)
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, at, 1))
    return out


def _mtp(cfg, policy, layer_fn, tokens, h, params):
    """The MTP module's cross-entropy [S − 1] on the second-next token
    (module doc).  It runs over all S positions, the last of which has no
    target: causal attention keeps that row out of every other, so the
    first S − 1 rows are exactly the module over S − 1 positions, at the
    stack's shapes.  Also its layer's [1, 2] held rows."""
    w, eps, seq = params["mtp"], cfg.rms_norm_eps, h.shape[0]
    with jax.named_scope("mtp"):
        e = params["embed"][tokens[1:]]
        x = policy.matmul(jnp.concatenate(
            [rms_norm(h, w["hnorm"], eps), rms_norm(e, w["enorm"], eps)],
            -1), w["w_eh"])
        x, r = layer_fn(x, jax.tree_util.tree_map(lambda a: a[0],
                                                  w["layer"]))
        x = rms_norm(x, params["final_norm"], eps)
        targets = jnp.concatenate([tokens[2:], tokens[:1]])
        ce = lm_parts.blocked_token_ce(policy, x, params["head"], targets)
        return ce[:seq - 1], r[None]


def forward(cfg: MoELMConfig, params, tokens):
    """tokens [S + 1] int32 -> (ce [S], the MTP module's ce [S − 1] or
    None, [sparse layers, 2]: each sparse layer's rows routed to held
    experts and held experts with a row)."""
    policy = precision_mod.get_policy(cfg.precision)
    inputs, targets = tokens[:-1], tokens[1:]
    seq = inputs.shape[0]
    with jax.named_scope("embed"):
        # in the layers' order: a set's order would move with the
        # process's string hashing, and the program's text with it
        ropes = {attn: rope_of(cfg, attn, seq) for attn in dict.fromkeys(
            cfg.layer_types[i].split("_")[0]
            for i in range(cfg.num_hidden_layers))}
        h = params["embed"][inputs]

    def layer_fn(kind):
        fn = functools.partial(_layer, cfg, policy, kind,
                               ropes[kind.split("_")[0]])
        if cfg.remat == "layer":
            fn = jax.checkpoint(fn, policy=lm_parts.keep_flash_results(
                "moe_lm/remat_kept_bytes"))
        return fn

    rows = []
    for kind, at, n in runs(cfg):
        fn, stack = layer_fn(kind), params["kinds"][kind]
        if n == 1:
            h, r = fn(h, jax.tree_util.tree_map(lambda a: a[at], stack))
            r = r[None]
        else:
            h, r = jax.lax.scan(fn, h, jax.tree_util.tree_map(
                lambda a: a[at:at + n], stack))
        if kind.endswith("_sparse"):
            rows.append(r)
    ce_mtp = None
    if cfg.mtp_kind:
        ce_mtp, r = _mtp(cfg, policy, layer_fn(cfg.mtp_kind), tokens, h,
                         params)
        if cfg.mtp_kind.endswith("_sparse"):
            rows.append(r)
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("head"):  # its ``loss`` scope lies inside
        ce = lm_parts.blocked_token_ce(policy, h, params["head"], targets)
    held = (jnp.concatenate(rows) if rows
            else jnp.zeros((0, 2), jnp.float32))
    return ce, ce_mtp, held


def loss_fn(cfg: MoELMConfig, params, tokens):
    """tokens [B, S + 1] -> (loss, ([sparse layers, 2]: rows routed to
    held experts and held experts with a row, summed over the B
    sequences; [main loss, MTP loss] or None))."""
    with jax.default_matmul_precision(cfg.matmul_precision):
        out = [forward(cfg, params, row) for row in tokens]
    with jax.named_scope("loss"):
        loss = jnp.mean(jnp.stack([ce for ce, _, _ in out]))
        rows = sum(r for _, _, r in out)
        if not cfg.mtp_kind:
            return loss, (rows, None)
        mtp = jnp.mean(jnp.stack([c for _, c, _ in out]))
        return (loss + cfg.mtp_loss_weight * mtp,
                (rows, jnp.stack([loss, mtp])))


# --- the step -----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "opt"),
                   donate_argnames=("state",))
def train_step(cfg: MoELMConfig, opt, state: TrainState, stream):
    """One optimizer step on the step's own slice of the token stream —
    a single XLA program; the state is donated."""
    tokens = lm_parts.batch_at(stream, state.step, cfg)
    (loss, (rows, parts)), grads = jax.value_and_grad(
        functools.partial(loss_fn, cfg), has_aux=True)(state.params, tokens)
    with jax.named_scope("optimizer"):
        gnorm = optax.global_norm(grads)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        stats = jnp.concatenate([jnp.stack([loss, gnorm]), rows.T.ravel()]
                                + ([] if parts is None else [parts]))
    return TrainState(params, opt_state, state.step + 1, stats), loss


def read_stats(cfg: MoELMConfig, stats) -> dict:
    """The state's stats vector by name (host side)."""
    vals, n = [float(v) for v in stats], cfg.sparse_layers
    out = {"loss": vals[0], "grad_norm": vals[1],
           "held_rows": vals[STATS_HEAD:STATS_HEAD + n],
           "held_experts": vals[STATS_HEAD + n:STATS_HEAD + 2 * n]}
    if cfg.mtp_kind:
        out["main_loss"], out["mtp_loss"] = vals[STATS_HEAD + 2 * n:][:2]
    return out
