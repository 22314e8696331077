"""Work a looped language model's training step *requires*, from shapes
(the rule of ``benchmark/costs.py``: what the algorithm needs, nothing of
how a program goes about it — no recomputation, no padding, no second
read of an operand).  Matmuls only: norms, rotary, softmax, the gate and
the optimizer are elementwise passes a thousandth of the count.

Shapes: ``model`` holds the published keys (hidden_size, intermediate_
size, num_attention_heads, head_dim, vocab_size, num_hidden_layers,
total_ut_steps); ``tokens`` is the number of positions a step trains on
and ``seq`` the length of one sequence (causal attention sees half the
square of it).
"""

from __future__ import annotations


def layer_matmul_weights(model: dict) -> int:
    """Multiply-adds a token in one layer's seven projections."""
    d, f = int(model["hidden_size"]), int(model["intermediate_size"])
    hd = int(model["num_attention_heads"]) * int(model["head_dim"])
    return 4 * d * hd + 3 * d * f


def attention_forward_flops_per_token(model: dict, seq: int) -> float:
    """Scores and weighted values of one layer, causal: a token at
    position i attends i + 1 keys, (seq + 1) / 2 on average; two
    matmuls of heads x head_dim multiply-adds a key."""
    hd = int(model["num_attention_heads"]) * int(model["head_dim"])
    return 2 * 2.0 * hd * (seq + 1) / 2.0


def forward_flops_per_token(model: dict, seq: int) -> float:
    """Every pass over every layer, and the head once a pass."""
    passes = int(model["total_ut_steps"])
    per_layer = 2.0 * layer_matmul_weights(model) \
        + attention_forward_flops_per_token(model, seq)
    head = 2.0 * int(model["hidden_size"]) * int(model["vocab_size"])
    return passes * (int(model["num_hidden_layers"]) * per_layer + head)


def step_flops(model: dict, tokens: int, seq: int) -> float:
    """One optimizer step: forward, and a backward of twice the forward
    (each matmul's two transposed products)."""
    return 3.0 * tokens * forward_flops_per_token(model, seq)


# --- the flash kernel's dot form, one call (all heads of one sequence) ---------
# what each call has to compute and move, once: the forward call two
# matmuls over the causal half of the square; the backward's four (dV, dP,
# dQ, dK) split two to each of its calls — the score tile both of them
# recompute is how this kernel goes about it, not what a backward needs.


def flash_dot_call_cost(which: str, heads: int, seq: int, head_dim: int,
                        itemsize: int = 2) -> dict:
    """{"flops", "bytes"} of one ``flash_dot_<which>`` call."""
    pairs = seq * (seq + 1) / 2.0
    matmul = 2.0 * pairs * head_dim * heads
    rows = seq * head_dim * heads * itemsize     # one [S, D] operand
    stats = seq * heads * 4                      # one float32 a row
    if which == "fwd":    # reads q k v, writes o and the row's lse
        return {"flops": 2 * matmul, "bytes": 4 * rows + stats}
    if which == "dq":     # reads q k v do, lse and di; writes dq
        return {"flops": 2 * matmul, "bytes": 5 * rows + 2 * stats}
    if which == "dkv":    # reads q k v do, lse and di; writes dk dv
        return {"flops": 2 * matmul, "bytes": 6 * rows + 2 * stats}
    raise KeyError(which)


def roofline_seconds(cost: dict, peaks: dict) -> tuple:
    """(least seconds, which bound binds)."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "flops" if t_flops >= t_bytes else "bytes"
