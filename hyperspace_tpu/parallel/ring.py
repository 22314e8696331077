"""Ring sequence parallelism for hyperbolic attention (SURVEY.md §5
"Long-context / sequence parallelism"; first-class per the rebuild plan).

Each device holds one shard of Q and one shard of K/V along the sequence
axis.  K/V shards rotate around the mesh axis with ``ppermute`` (one hop
per step — on TPU this rides the ICI ring), and every device folds each
incoming block into its flash-attention running state (max, denominator,
numerator) — the same online-softmax recurrence as
:func:`hyperspace_tpu.nn.attention.lorentz_attention_tiled`, with blocks
arriving over the network instead of from HBM.  After ``n`` hops every
device has seen the full sequence; the final row-rescale projects the
accumulated Lorentz-centroid numerator back to the hyperboloid.

Wrap with ``shard_map`` over a mesh axis (see ``ring_attention_sharded``).
Communication volume per device: 2 × (L/n) × D per hop, n hops — the
standard ring-attention cost, fully overlapped by XLA's async collectives
on real hardware.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hyperspace_tpu.manifolds import Lorentz, smath
from hyperspace_tpu.nn.attention import minkowski_gram


def _fold_block(q, kj, vj, c, beta, tau, carry, mask_j=None):
    """One online-softmax fold of KV block (kj, vj) into the carry;
    ``mask_j`` ([B, Lk_block] bool, batch-level key padding) drops padded
    keys — expanded here to align with logits of any rank."""
    m_run, l_run, s_run = carry
    gram = minkowski_gram(q, kj)
    logits = (2.0 / c + 2.0 * gram + beta) / tau
    if mask_j is not None:
        mj = mask_j.reshape(
            mask_j.shape[0], *([1] * (logits.ndim - 3)), 1, mask_j.shape[-1])
        logits = jnp.where(mj, logits, -jnp.inf)
    m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m_run), m_run - m_safe, -jnp.inf))
    p = jnp.exp(logits - m_safe[..., None])
    l_new = alpha * l_run + jnp.sum(p, axis=-1)
    s_new = alpha[..., None] * s_run + p @ vj
    return m_new, l_new, s_new


def ring_lorentz_attention(
    q: jax.Array,  # [..., Lq_local, D] this device's Q shard
    k: jax.Array,  # [..., Lk_local, D] this device's KV shard
    v: jax.Array,
    manifold: Lorentz,
    axis_name: str,
    *,
    beta: jax.Array | float = 0.0,
    tau: jax.Array | float = 1.0,
    k_mask: Optional[jax.Array] = None,  # [B, Lk_local] bool key padding
) -> jax.Array:
    """Per-device body of ring attention; call inside shard_map.

    Equivalent to :func:`lorentz_attention` over the gathered sequence
    (with ``mask`` broadcast from the batch-level key-padding mask when
    ``k_mask`` is given), without ever materializing it on one device.
    The mask shard rotates around the ring with its KV shard; the
    unmasked path carries no mask at all (no extra collective payload).
    """
    c = jnp.asarray(manifold.c, q.dtype)
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # constants must be marked varying over the ring axis or the fori_loop
    # carry types mismatch under shard_map's manual-axes checking
    m0 = jax.lax.pcast(jnp.full(q.shape[:-1], -jnp.inf, q.dtype), axis_name,
                       to="varying")
    l0 = jax.lax.pcast(jnp.zeros(q.shape[:-1], q.dtype), axis_name,
                       to="varying")
    s0 = jnp.zeros_like(q)

    def fold(carry, kvm):
        return _fold_block(q, kvm[0], kvm[1], c, beta, tau, carry,
                           mask_j=(kvm[2] if k_mask is not None else None))

    def body(i, state):
        kvm, carry = state
        # remat per hop: reverse-mode AD of the (scan-converted) ring
        # loop would otherwise SAVE each hop's [Lq_loc, Lk_loc] score
        # tile — O(L²/n) per device, exactly the memory the ring exists
        # to avoid.  checkpoint recomputes the tile from (q, kj) in the
        # backward (the flash-backward recipe), so residual memory stays
        # O(L·D) and long-context training holds in BOTH directions.
        # prevent_cse=False: under scan the CSE barriers are documented
        # unnecessary and would pad every hop with optimization barriers
        carry = jax.checkpoint(fold, prevent_cse=False)(carry, kvm)
        # rotate KV (+ mask) one hop around the ring (skipped data is
        # re-sent; the last hop's permute is dead code XLA removes when n
        # is static)
        kvm = jax.lax.ppermute(kvm, axis_name, perm)
        return kvm, carry

    kvm0 = (k, v) if k_mask is None else (k, v, k_mask)
    (_, (m_f, l_f, s_f)) = jax.lax.fori_loop(
        0, n, body, (kvm0, (m0, l0, s0)))
    s = s_f / smath.clamp_min(l_f, smath.min_norm(q.dtype))[..., None]
    sp = jnp.sum(s[..., 1:] * s[..., 1:], axis=-1, keepdims=True) - s[..., :1] * s[..., :1]
    nrm = smath.safe_sqrt(smath.clamp_min(-sp, smath.eps_for(q.dtype)))
    return s / (smath.sqrt_c(c) * nrm)


def ring_attention_sharded(
    q: jax.Array,  # [..., L, D] full arrays (sharded by the caller's specs)
    k: jax.Array,
    v: jax.Array,
    manifold: Lorentz,
    mesh: Mesh,
    axis: str = "seq",
    *,
    beta: jax.Array | float = 0.0,
    tau: jax.Array | float = 1.0,
    k_mask: Optional[jax.Array] = None,  # [B, L] bool key-padding mask
) -> jax.Array:
    """shard_map wrapper: shards the sequence axis over ``axis`` and runs
    the ring.  Batch/head axes stay replicated across the seq axis.
    ``k_mask`` is batch-level (same contract as the Ulysses wrapper);
    omitting it compiles the maskless ring — no mask ever rides the
    collectives."""
    seq_spec = P(*((None,) * (q.ndim - 2) + (axis, None)))

    if k_mask is None:
        @partial(shard_map, mesh=mesh,
                 in_specs=(seq_spec, seq_spec, seq_spec), out_specs=seq_spec)
        def run(q, k, v):
            return ring_lorentz_attention(
                q, k, v, manifold, axis, beta=beta, tau=tau)

        return run(q, k, v)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(None, axis)),
        out_specs=seq_spec,
    )
    def run(q, k, v, mk):
        return ring_lorentz_attention(
            q, k, v, manifold, axis, beta=beta, tau=tau, k_mask=mk)

    return run(q, k, v, k_mask)
