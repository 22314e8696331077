"""Row-sharded embedding tables: the TP/EP-like mode of SURVEY.md §2
("row-shard tables over ``model`` axis; shard_map + sparse gather for
lookups").

A [V, D] table too large for one chip is laid out P("model", None) —
each device owns a contiguous row range.  Lookup is a shard_map:

    every device gathers the requested rows it owns (others contribute
    zeros) and one ``psum`` over the model axis assembles full vectors.

Communication: one B×D all-reduce per lookup — independent of V, riding
ICI.  The VJP is the transpose: each device scatter-adds only the grad
rows it owns, with **no** cross-device traffic (the psum transposes to
an identity on the cotangent), so optimizer updates stay shard-local —
exactly the property that makes row sharding the right layout for
embedding training (the reference reaches the same place with NCCL
allgather/reduce-scatter pairs [INFERRED]).

The gather is exact under duplicate indices, and gradients under
duplicates accumulate (segment-combine), matching dense ``table[idx]``
semantics — asserted by tests/parallel/test_sharded_embed.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def table_sharding(mesh: Mesh, axis: str = "model") -> NamedSharding:
    """Rows over ``axis``, features replicated."""
    return NamedSharding(mesh, P(axis, None))


def shard_table(table: jax.Array, mesh: Mesh, axis: str = "model") -> jax.Array:
    """Place a [V, D] table row-sharded (V must divide by the axis size)."""
    if table.shape[0] % mesh.shape[axis]:
        raise ValueError(
            f"table rows {table.shape[0]} not divisible by "
            f"{axis}={mesh.shape[axis]}")
    return jax.device_put(table, table_sharding(mesh, axis))


def local_gather(table_local: jax.Array, idx: jax.Array, n_rows: int,
                 axis: str):
    """Per-device body: gather owned rows, zeros elsewhere, psum.

    Public so other ``shard_map`` programs over a row-sharded table can
    assemble replicated rows inside their own bodies — the serve
    engine's sharded k-NN (``serve/engine.py``) gathers its query rows
    this way before scanning the local shard.

    Index semantics match dense ``table[idx]``: negatives wrap
    (idx + V) and out-of-range clamps to the last row — without this a
    valid-for-dense negative index would silently gather zeros.
    """
    idx = jnp.where(idx < 0, idx + n_rows, idx)
    idx = jnp.clip(idx, 0, n_rows - 1)
    rows = table_local.shape[0]
    lo = jax.lax.axis_index(axis) * rows
    local = idx - lo
    valid = (local >= 0) & (local < rows)
    safe = jnp.clip(local, 0, rows - 1)
    out = jnp.where(valid[..., None], table_local[safe], 0.0)
    return jax.lax.psum(out, axis)


def sharded_gather(
    table: jax.Array,  # [V, D], laid out P(axis, None)
    idx: jax.Array,    # [...] int32 indices into V (replicated)
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:
    """``table[idx]`` over a row-sharded table; differentiable w.r.t. table."""
    run = shard_map(
        partial(local_gather, n_rows=table.shape[0], axis=axis),
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
    )
    return run(table, idx)
