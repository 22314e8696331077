"""Tensor-parallel sharding rules (SURVEY.md §2 parallelism inventory, TP row).

The reference has no explicit TP evidence; SURVEY's plan is "provide via
GSPMD sharding rules" — on TPU that is precisely a `NamedSharding` rule
over the parameter pytree, after which XLA inserts the all-gathers /
reduce-scatters onto ICI.  The rule here is the standard Megatron-style
column split for 2-D kernels: every dense kernel's *output-feature* axis is
sharded over the ``model`` mesh axis, biases and everything 1-D stay
replicated.  Activations between layers are left to GSPMD, which keeps the
feature axis sharded through elementwise chains and re-gathers only where a
contraction needs it.

Used by the minibatch mesh steps (`models/hvae.py`, `models/hybonet.py`,
`models/hgcn_sampled.py` `make_sharded_step`).  HGCN's node-sharded steps
replicate their state instead (`replicated_like`): their kernels are one
MXU tile wide, and every device holds a node shard of its own.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_names(path) -> list[str]:
    return [str(getattr(e, "key", getattr(e, "name", ""))) for e in path]


def tp_param_spec(path, leaf, axis: str = "model") -> P:
    """Partition spec for one parameter leaf under tensor parallelism:
    2-D dense kernels are column-sharded ``P(None, axis)``; scalars,
    biases, norms and manifold params (curvatures etc.) are replicated."""
    if "kernel" in _path_names(path) and getattr(leaf, "ndim", 0) == 2:
        return P(None, axis)
    return P()


def tp_param_shardings(params: Any, mesh: Mesh, axis: str = "model") -> Any:
    """Pytree of `NamedSharding`s for ``params`` under the TP rule.

    Degrades gracefully: if ``mesh`` has no ``axis`` (or it has size 1)
    everything is replicated, so callers can use one code path for
    dp-only, tp-only and dp×tp meshes.
    """
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        repl = NamedSharding(mesh, P())
        return jax.tree_util.tree_map(lambda _: repl, params)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, tp_param_spec(p, l, axis)), params)


def replicated_like(tree: Any, mesh: Mesh) -> Any:
    """Pytree of fully-replicated shardings matching ``tree``'s structure."""
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda _: repl, tree)


def state_shardings(state: Any, params: Any, mesh: Mesh,
                    axis: str = "model") -> Any:
    """Shardings for a whole train state, co-locating optimizer moments
    with their parameter shards (SURVEY.md §7 hard-part #4: Adam moments
    live in tangent spaces of moving points — their shards must sit with
    the parameter shards they transport).

    Optimizer states (optax) embed subtrees structurally mirroring
    ``params``, so a state leaf whose key-path *ends with* a parameter's
    full key-path (e.g. ``.0.mu.encoder.conv0.kernel`` vs
    ``encoder.conv0.kernel``) takes that parameter's TP spec; everything
    else (counts, PRNG keys, step counters) is replicated.
    """
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return replicated_like(state, mesh)

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    # longest-suffix-first so a param path that is itself a suffix of
    # another's can never shadow the longer match
    by_path = sorted(
        ((tuple(_path_names(p)), tp_param_spec(p, l, axis),
          getattr(l, "shape", ())) for p, l in flat),
        key=lambda kv: -len(kv[0]))

    def spec_for(path, leaf):
        names = tuple(_path_names(path))
        for ppath, spec, pshape in by_path:
            if len(names) >= len(ppath) and names[-len(ppath):] == ppath:
                # a state leaf only inherits the param's spec if its shape
                # is compatible — optax transforms may carry per-parameter
                # state of a different rank (e.g. scalars keyed by the
                # param name), which must fall back to replication
                return spec if getattr(leaf, "shape", ()) == pshape else P()
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, spec_for(p, l)), state)
