"""Ulysses (all-to-all) sequence parallelism for hyperbolic attention
(SURVEY.md §5 "Long-context / sequence parallelism" — the second of the
two first-class SP modes, complementing :mod:`hyperspace_tpu.parallel.ring`).

Layout: activations are sharded over the sequence axis between attention
calls (each device holds [B, H, L/n, D]).  Attention itself needs full
rows of the score matrix, so Ulysses trades the *sequence* sharding for a
*head* sharding exactly around the attention op with two ``all_to_all``
collectives:

    [B, H, L/n, D] --all_to_all(split H, concat L)--> [B, H/n, L, D]
        -> full-sequence Lorentz attention on H/n local heads
    [B, H/n, L, D] --all_to_all(split L, concat H)--> [B, H, L/n, D]

Communication: 2 × (B·H·L·D)/n per device per direction — constant in
sequence length per hop (vs ring's n hops), at the cost of requiring
H % n == 0.  On TPU the all_to_all rides the ICI torus; XLA overlaps it
with the surrounding compute where possible.

Both SP modes compute the same single-device attention math; since r04
the local op here is the N7 flash kernel
(:func:`hyperspace_tpu.kernels.attention.flash_attention` — flash in
both directions on TPU, dense twin elsewhere), so Ulysses long-context
memory stays per-block like the ring's.  Numerically interchangeable
with the ring and the dense form — the tests assert all three agree.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hyperspace_tpu.manifolds import Lorentz
from hyperspace_tpu.kernels.attention import flash_attention


def ulysses_lorentz_attention(
    q: jax.Array,  # [B, H, L_local, D] this device's sequence shard
    k: jax.Array,
    v: jax.Array,
    manifold: Lorentz,
    axis_name: str,
    *,
    beta: jax.Array | float = 0.0,
    tau: jax.Array | float = 1.0,
    k_mask: jax.Array | None = None,  # [B, L_local] bool key padding
) -> jax.Array:
    """Per-device body; call inside shard_map over ``axis_name``.

    Requires the head axis (dim 1) to be divisible by the axis size.
    """
    n = jax.lax.psum(1, axis_name)
    if q.shape[1] % n != 0:
        raise ValueError(
            f"Ulysses needs heads ({q.shape[1]}) divisible by axis size ({n})")
    # seq-sharded -> head-sharded: split heads, gather sequence
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name,
                  split_axis=1, concat_axis=2, tiled=True)
    qh, kh, vh = a2a(q), a2a(k), a2a(v)        # [B, H/n, L, D]
    mask = None
    if k_mask is not None:
        # the head-sharded view sees the FULL sequence of keys — gather
        # the key-padding mask and broadcast over heads/queries
        mk = jax.lax.all_gather(k_mask, axis_name, axis=-1, tiled=True)
        mask = mk[:, None, None, :]  # [B, 1, 1, L]
    # the local attention is the N7 flash kernel (r04: flash in BOTH
    # directions on TPU, dense twin elsewhere) — with head sharding the
    # per-device score working set is already H/n tiles, and flash keeps
    # it per-BLOCK instead of per-sequence, so Ulysses long-context holds
    # forward and backward like the ring does
    out = flash_attention(qh, kh, vh, manifold.c, beta=beta, tau=tau,
                          mask=mask)
    # head-sharded -> seq-sharded: split sequence, gather heads
    return jax.lax.all_to_all(out, axis_name=axis_name,
                              split_axis=2, concat_axis=1, tiled=True)


def ulysses_attention_sharded(
    q: jax.Array,  # [B, H, L, D] full arrays (sharded by the caller's specs)
    k: jax.Array,
    v: jax.Array,
    manifold: Lorentz,
    mesh: Mesh,
    axis: str = "seq",
    *,
    beta: jax.Array | float = 0.0,
    tau: jax.Array | float = 1.0,
    k_mask: jax.Array | None = None,  # [B, L] bool key-padding mask
) -> jax.Array:
    """shard_map wrapper: shards the sequence axis (dim 2) over ``axis``.
    Omitting ``k_mask`` compiles the maskless path (no mask all_gather)."""
    spec = P(None, None, axis, None)

    if k_mask is None:
        @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=spec)
        def run(q, k, v):
            return ulysses_lorentz_attention(q, k, v, manifold, axis,
                                             beta=beta, tau=tau)

        return run(q, k, v)

    @partial(shard_map, mesh=mesh,
             in_specs=(spec, spec, spec, P(None, axis)), out_specs=spec)
    def run(q, k, v, mk):
        return ulysses_lorentz_attention(q, k, v, manifold, axis,
                                         beta=beta, tau=tau, k_mask=mk)

    return run(q, k, v, k_mask)
