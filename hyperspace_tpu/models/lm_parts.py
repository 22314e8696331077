"""What the token language models share (``models/looplm.py``,
``models/moe_lm.py``): the step's slice of the packed token stream, the
head's cross-entropy a block of rows at a time, and the checkpoint policy
that keeps the flash forward call's results."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hyperspace_tpu.kernels.attention import FLASH_DOT_LSE, FLASH_DOT_OUT
from hyperspace_tpu.telemetry import registry

HEAD_BLOCK_ROWS = 1024


def batch_at(stream, step, cfg):
    """Step i's tokens [B, S + 1] from the packed stream: sequence b of
    step i starts at (i·B + b)·S, wrapping; the data order is fixed.
    ``cfg`` names ``sequence_length`` S and ``sequences_per_step`` B."""
    s, b = cfg.sequence_length, cfg.sequences_per_step
    first = (step * b + jnp.arange(b, dtype=jnp.int32)) * s
    idx = first[:, None] + jnp.arange(s + 1, dtype=jnp.int32)[None, :]
    return stream[idx % stream.shape[0]]


def token_ce(policy, h, head, targets):
    """Each row's cross-entropy against its target: float32 logits from
    compute-lane operands, float32 log-sum-exp."""
    z = policy.matmul(h, head)
    with jax.named_scope("loss"):
        return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, targets[:, None], axis=-1)[:, 0]


def blocked_token_ce(policy, h, head, targets, block_rows=HEAD_BLOCK_ROWS):
    """:func:`token_ce` a block of ``block_rows`` rows at a time (ONE
    ``lax.map``), each block recomputed in the backward: a block's
    ``[rows, V]`` logits and their cotangent are all that lives of the
    whole ``[rows, V]``, and the head's gradient sums in the map's own
    carry.  Rows that do not fill the last block are padded with zeros and
    their results dropped, so no size computes more than a block at
    once."""
    block = jax.checkpoint(functools.partial(token_ce, policy))
    rows = h.shape[0]
    n = -(-rows // block_rows)
    if n == 1:
        return block(h, head, targets)
    pad = n * block_rows - rows
    return jax.lax.map(
        lambda xs: block(xs[0], head, xs[1]),
        (jnp.pad(h, ((0, pad), (0, 0))).reshape(n, block_rows, -1),
         jnp.pad(targets, (0, pad)).reshape(n, block_rows))
    ).reshape(-1)[:rows]


def keep_flash_results(gauge: str):
    """A ``jax.checkpoint`` policy that keeps, of what it is asked about,
    only what the flash forward call alone produces — its output
    ``[H, S, D]`` on the compute lane and the rows' float32 log-sum-exp
    ``[H, S]``, named in ``kernels/attention.py`` — so the backward never
    runs that call a second time; q, k, v and everything else are
    recomputed.  jax asks the policy about every equation while it splits
    a checkpointed call into what runs forward and what backward; the
    bytes it grants are the registry's ``gauge``, set at trace time: 0
    where no kernel ran (the XLA twin names nothing and is recomputed
    whole) and until a gradient is taken."""
    named = jax.checkpoint_policies.save_only_these_names(FLASH_DOT_OUT,
                                                          FLASH_DOT_LSE)
    kept = {}
    registry.set_gauge(gauge, 0)

    def policy(prim, *avals, **params):
        keep = named(prim, *avals, **params)
        if keep:
            kept[params["name"]] = avals[0].size * avals[0].dtype.itemsize
            registry.set_gauge(gauge, sum(kept.values()))
        return keep

    return policy
