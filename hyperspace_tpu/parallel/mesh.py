"""Device-mesh construction and sharding helpers (SURVEY.md §2 N8).

The reference synchronizes gradients with NCCL all-reduce; here the same
role is played by GSPMD: arrays are placed with `NamedSharding`s over a
`jax.sharding.Mesh` and XLA compiles the `psum`s onto ICI (and onto DCN for
the host axis on multi-host meshes).  Axis conventions:

- ``data``  — batch/data parallelism (gradient all-reduce axis),
- ``model`` — tensor/embedding-row sharding,
- ``seq``   — sequence/context parallelism (ring attention),
- ``host``  — leading DCN axis on multi-host meshes (workload 5).

`jax.distributed.initialize` + a mesh spanning all hosts is the whole
multi-host story: Python never communicates across hosts, only XLA
collectives do (SURVEY.md §3.4).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    axes: dict[str, int] | None = None,
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a mesh from {axis_name: size}; -1 = "fill with the rest".

    Defaults to pure data parallelism over all local devices.  For
    multi-host, pass an explicit ``host`` axis first so it maps onto DCN
    (mesh-major order = slowest-varying = cross-host).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    known = int(np.prod([s for s in sizes if s != -1]))
    if -1 in sizes:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} != {n} devices")
    return Mesh(np.asarray(devices).reshape(sizes), tuple(names))


def multihost_mesh(axes: dict[str, int] | None = None) -> Mesh:
    """Mesh spanning all hosts: leading ``host`` axis over DCN, remaining
    axes over the local ICI topology (workload 5 [B])."""
    n_hosts = jax.process_count()
    per_host = jax.local_device_count()
    inner = axes or {"data": per_host}
    return make_mesh({"host": n_hosts, **inner})


def auto_mesh(multihost: bool = False, tp: int = 1) -> Optional[Mesh]:
    """Mesh selection shared by the CLI runners: the multi-host mesh when
    requested, a data(-×model) mesh over all local devices when there is
    more than one, else ``None`` (caller takes its single-device path)."""
    def warn_tp_dropped(n_avail):
        import warnings

        warnings.warn(
            f"auto_mesh: tp={tp} does not divide the {n_avail} available "
            "devices; falling back to pure data parallelism")

    if multihost:
        per_host = jax.local_device_count()
        if tp > 1 and per_host >= tp and per_host % tp == 0:
            # tp stays intra-host so its collectives ride ICI, not DCN
            return multihost_mesh({"data": per_host // tp, "model": tp})
        if tp > 1:
            warn_tp_dropped(per_host)
        return multihost_mesh()
    n = len(jax.devices())
    if n <= 1:
        return None
    if tp > 1 and n >= tp and n % tp == 0:
        return make_mesh({"data": n // tp, "model": tp})
    if tp > 1:
        warn_tp_dropped(n)
    return make_mesh({"data": n})


def model_mesh(shards: int = -1, *, devices: Optional[Sequence] = None
               ) -> Mesh:
    """A pure ``model``-axis mesh over ``shards`` devices (-1 = all) —
    the layout table-sharded *serving* uses (``serve/engine.py``;
    training meshes come from :func:`auto_mesh`).  ``shards`` larger
    than the device count, or 0, is an error — a silent clamp would
    quietly change the memory-per-chip story the caller sized for."""
    avail = list(devices if devices is not None else jax.local_devices())
    if shards == -1:
        shards = len(avail)
    if not 1 <= shards <= len(avail):
        raise ValueError(
            f"model_mesh: shards={shards} out of range [1, {len(avail)}] "
            "(-1 = all devices)")
    return make_mesh({"model": shards}, devices=avail[:shards])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading (batch) axis over every data-like mesh axis."""
    data_axes = tuple(a for a in ("host", "data") if a in mesh.axis_names)
    spec = (data_axes,) + (None,) * (ndim - 1)
    return NamedSharding(mesh, P(*spec))


def shard_batch(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Constrain an in-program value to batch sharding (GSPMD hint)."""
    return jax.lax.with_sharding_constraint(x, batch_sharding(mesh, x.ndim))


def data_extent(mesh: Mesh) -> int:
    """Total size of the data-like (batch-sharding) axes of ``mesh``."""
    return int(np.prod([mesh.shape[a] for a in ("host", "data")
                        if a in mesh.axis_names]))
