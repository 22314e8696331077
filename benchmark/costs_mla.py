"""Work a training step of the MoE LM in GLM-4.7-Flash's form
(``hyperspace_tpu.models.moe_lm``: latent attention, sigmoid routing, a
multi-token-prediction module) *requires*, from shapes and the rows the
step routed to held experts (the rule of ``benchmark/costs.py``: what the
algorithm needs, nothing of how a program goes about it — no
recomputation, no padding, and the MTP module over the S - 1 positions
that have a target, not the row the program adds to keep the stack's
shapes).  Matmuls only: norms, rotary, softmax, routing and the
optimizer are elementwise passes a thousandth of the count.

``model`` holds the published keys (``n_routed_experts`` the held
count), ``job`` the job's (``expert_shards``); ``held_rows`` the rows
routed to held experts in each sparse layer in one step, the MTP
layer's last (the program's stats vector reads them), or None for the
expected count S·k·held/router.

Attention is counted as the training path computes it (K and V
decompressed per head): the score and the weighted sum over the causal
half of the square at the query/key width and the value width.  The
flash calls' own cost is ``costs_lm.flash_dot_call_cost`` at that width:
the kernel reads each head's decompressed K, the shared rotary key's
copies among it, so nothing there is counted apart.
"""

from __future__ import annotations


def sparse_layers(model: dict) -> int:
    """Sparse layers, the MTP module's among them."""
    n = int(model["num_hidden_layers"])
    return (n - min(int(model["first_k_dense_replace"]), n)
            + int(model["num_nextn_predict_layers"]))


def expected_rows(model: dict, job: dict, seq: int) -> float:
    """Rows a sparse layer routes to held experts, on average: each token
    picks k of the router's experts, held/router of them held."""
    width = int(model["n_routed_experts"]) * int(job.get("expert_shards", 1))
    return seq * int(model["num_experts_per_tok"]) * int(
        model["n_routed_experts"]) / width


def mla_flops(model: dict, seq: int) -> float:
    """One latent-attention layer over ``seq`` causal positions: the
    five projections and the two matmuls of the attention core."""
    d, heads = int(model["hidden_size"]), int(model["num_attention_heads"])
    nope, rd = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv, rq = int(model["v_head_dim"]), int(model["q_lora_rank"])
    rkv = int(model["kv_lora_rank"])
    weights = (d * rq + rq * heads * (nope + rd) + d * (rkv + rd)
               + rkv * heads * (nope + dv) + heads * dv * d)
    pairs = seq * (seq + 1) / 2.0
    return 2.0 * seq * weights + 2.0 * pairs * heads * (nope + rd + dv)


def sparse_mlp_flops(model: dict, job: dict, seq: int, rows: float) -> float:
    """The router, the shared expert over every token and the held
    experts over ``rows``."""
    d, f = int(model["hidden_size"]), int(model["moe_intermediate_size"])
    width = int(model["n_routed_experts"]) * int(job.get("expert_shards", 1))
    fs = f * int(model["n_shared_experts"])
    return (2.0 * seq * d * width + 2.0 * seq * 3 * d * fs
            + 2.0 * rows * 3 * d * f)


def forward_flops(model: dict, job: dict, seq: int, held_rows=None) -> float:
    """One sequence's forward: the stack, the head, and the MTP module's
    projection, layer and head over its S - 1 positions."""
    d, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    rows = list(held_rows) if held_rows is not None else [
        expected_rows(model, job, seq)] * sparse_layers(model)
    total = 2.0 * seq * d * vocab                             # the head
    for i in range(int(model["num_hidden_layers"])):
        total += mla_flops(model, seq)
        if i < int(model["first_k_dense_replace"]):
            total += 2.0 * seq * 3 * d * int(model["intermediate_size"])
        else:
            total += sparse_mlp_flops(model, job, seq, rows.pop(0))
    for _ in range(int(model["num_nextn_predict_layers"])):
        m = seq - 1
        total += 2.0 * m * 2 * d * d                          # W_eh
        total += mla_flops(model, m)
        total += sparse_mlp_flops(model, job, m, rows.pop(0))
        total += 2.0 * m * d * vocab                          # its head
    return total


def step_flops(model: dict, job: dict, seq: int, sequences: int = 1,
               held_rows=None) -> float:
    """One optimizer step: forward, and a backward of twice the forward.
    ``held_rows``: the step's rows of each sparse layer, all sequences."""
    per_seq = None if held_rows is None else [r / sequences
                                              for r in held_rows]
    return 3.0 * sequences * forward_flops(model, job, seq, per_seq)
