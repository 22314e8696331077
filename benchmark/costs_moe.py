"""Work a training step of the MoE LM (``hyperspace_tpu.models.moe_lm``)
*requires*, from shapes and the rows the step routed to held experts
(the rule of ``benchmark/costs.py``: what the algorithm needs, nothing of
how a program goes about it — no recomputation, no padding of a group to
whole tiles, no row of the static bound that holds no token).  Matmuls
only: norms, rotary, softmax, routing and the optimizer are elementwise
passes a thousandth of the count.

``model`` holds the published keys (per-layer lists read for the first
``num_hidden_layers`` layers) with ``num_experts`` the held count;
``job`` the job's keys (``expert_shards``); ``held_rows`` the rows routed
to held experts in each sparse layer in one step (the program's stats
vector reads them), or None for the expected count S·k·held/router.
"""

from __future__ import annotations


def window_pairs(seq: int, window: int) -> float:
    """(query, key) pairs a causal window keeps: query i sees
    min(i + 1, window) keys."""
    w = min(window, seq)
    return w * (w + 1) / 2.0 + (seq - w) * w


def _layers(model: dict):
    n = int(model["num_hidden_layers"])
    return [(model["layer_types"][i].split("_")[0],
             model["mlp_layer_types"][i],
             int(model["num_attention_heads_per_layer"][i]))
            for i in range(n)]


def expected_rows(model: dict, job: dict, seq: int) -> float:
    """Rows a sparse layer routes to held experts, on average: each token
    picks k of the router's experts, held/router of them held."""
    width = int(model["num_experts"]) * int(job.get("expert_shards", 1))
    return seq * int(model["num_experts_per_tok"]) * int(
        model["num_experts"]) / width


def forward_flops(model: dict, job: dict, seq: int, held_rows=None) -> float:
    """One sequence's forward: every layer's projections, attention over
    the pairs its mask keeps, its feed-forward (the routed experts over
    the rows routed to them), and the head."""
    d, dh = int(model["hidden_size"]), int(model["head_dim"])
    kv = int(model["num_key_value_heads"])
    total = 2.0 * seq * d * int(model["vocab_size"])        # the head
    sparse = [m for _, m, _ in _layers(model)].count("sparse")
    rows = list(held_rows) if held_rows is not None else [
        expected_rows(model, job, seq)] * sparse
    width = int(model["num_experts"]) * int(job.get("expert_shards", 1))
    for attn, mlp, heads in _layers(model):
        proj = 2 * heads * dh + 2 * kv * dh + heads       # q o, k v, gate
        total += 2.0 * seq * d * proj
        pairs = (window_pairs(seq, int(model["sliding_window"]))
                 if attn == "sliding" else seq * (seq + 1) / 2.0)
        total += 2 * 2.0 * heads * dh * pairs
        if mlp == "dense":
            total += 2.0 * seq * 3 * d * int(model["intermediate_size"])
        else:
            f = int(model["moe_intermediate_size"])
            fs = int(model["shared_expert_intermediate_size"])
            total += 2.0 * seq * d * width                   # router
            total += 2.0 * seq * 3 * d * fs                  # shared expert
            total += 2.0 * rows.pop(0) * 3 * d * f           # held experts
    return total


def step_flops(model: dict, job: dict, seq: int, sequences: int = 1,
               held_rows=None) -> float:
    """One optimizer step: forward, and a backward of twice the forward.
    ``held_rows``: the step's rows of each sparse layer, all sequences."""
    per_seq = None if held_rows is None else [r / sequences
                                              for r in held_rows]
    return 3.0 * sequences * forward_flops(model, job, seq, per_seq)


# --- one call of a kernel ---------------------------------------------------


def window_call_cost(which: str, heads: int, kv_heads: int, seq: int,
                     head_dim: int, window: int, itemsize: int = 2) -> dict:
    """{"flops", "bytes"} of one ``flash_window_<which>`` call (all heads of
    one sequence): two matmuls over the pairs the window keeps forward,
    the backward's four split two to each call; q, o and do have the
    query heads, k, v, dk and dv the K/V heads, each read or written
    once."""
    matmul = 2.0 * window_pairs(seq, window) * head_dim * heads
    q_rows = seq * head_dim * heads * itemsize
    kv_rows = seq * head_dim * kv_heads * itemsize
    stats = seq * heads * 4
    if which == "fwd":    # reads q k v, writes o and the row's lse
        return {"flops": 2 * matmul, "bytes": 2 * q_rows + 2 * kv_rows
                + stats}
    if which == "dq":     # reads q k v do, lse and di; writes dq
        return {"flops": 2 * matmul, "bytes": 3 * q_rows + 2 * kv_rows
                + 2 * stats}
    if which == "dkv":    # reads q k v do, lse and di; writes dk dv
        return {"flops": 2 * matmul, "bytes": 2 * q_rows + 4 * kv_rows
                + 2 * stats}
    raise KeyError(which)


def gmm_call_cost(which: str, rows: float, k: int, n: int, experts: float,
                  itemsize: int = 2) -> dict:
    """{"flops", "bytes"} of one grouped-matmul call over ``rows`` routed
    rows: ``fwd`` and ``dx`` multiply [rows, k] by the experts' [k, n]
    (``dx``'s matrices transposed) into [rows, n], float32 out of
    ``fwd``, the rows' dtype out of ``dx``; ``dw`` sums [rows, k]ᵀ
    [rows, n] into the experts' float32 [k, n].  ``experts`` is the
    experts that got any row: each of their matrices is read (or
    written) once, and an expert without rows requires nothing."""
    flops = 2.0 * rows * k * n
    weights = experts * k * n
    if which == "fwd":
        return {"flops": flops, "bytes": rows * k * itemsize
                + weights * itemsize + rows * n * 4}
    if which == "dx":
        return {"flops": flops, "bytes": rows * k * itemsize
                + weights * itemsize + rows * n * itemsize}
    if which == "dw":
        return {"flops": flops, "bytes": rows * (k + n) * itemsize
                + weights * 4}
    raise KeyError(which)
