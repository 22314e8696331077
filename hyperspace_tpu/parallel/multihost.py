"""Multi-host runtime: process-group init, host↔global array movement,
and the restart-from-checkpoint failure-recovery drill helpers
(SURVEY.md §2 "Multi-host DP" [B], §3.4, §5 "Failure detection").

The reference reaches multi-host scale through an NCCL/MPI process group
[B]; here the whole story is:

1. every process calls :func:`initialize` (one line — JAX's distributed
   runtime does discovery over the coordinator, Gloo/ICI do transport),
2. a mesh from :func:`hyperspace_tpu.parallel.mesh.multihost_mesh` puts
   the ``host`` axis on DCN and inner axes on ICI,
3. jitted programs move data with :func:`host_local_to_global` and read
   results with :func:`fetch_replicated`; Python never touches the wire.

Failure model (SURVEY.md §5): XLA programs are fixed-topology, so there
is no mid-step elasticity — a lost host aborts the program and recovery
is **restart-from-checkpoint**: every process re-runs the same script,
:func:`initialize` re-forms the group, and
:func:`hyperspace_tpu.train.checkpoint.CheckpointManager.restore` resumes
from the last saved step.  ``tests/parallel/test_multihost.py`` drills
exactly this: kill one loopback process mid-run, restart both, assert
the resumed run matches an uninterrupted one.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np
from jax.experimental import multihost_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def initialize(
    coordinator: str,
    num_processes: int,
    process_id: int,
    *,
    local_device_count: Optional[int] = None,
) -> None:
    """Join the process group; call before any other JAX API.

    ``local_device_count`` forces N virtual CPU devices per process — the
    loopback test topology (SURVEY.md §4.6); leave None on real TPU hosts.
    """
    if local_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{local_device_count}").strip()
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_primary() -> bool:
    """True on process 0 — the ONE process that writes shared artifacts
    (checkpoint manifests, serving exports, trend records).  Per-host
    outputs (shard files, local logs) go to per-host paths instead;
    everything else is gated on this (the multiprocess-unsafe-io rule,
    docs/multihost.md)."""
    return jax.process_index() == 0


def process_row_range(
    num_rows: int,
    index: Optional[int] = None,
    count: Optional[int] = None,
) -> tuple[int, int]:
    """This process's contiguous row range of a globally-owned
    ``num_rows`` — near-equal split, same convention as
    ``host_table._shard_bounds`` so per-host table shards and per-host
    batch shards agree.  Ranges over all processes are disjoint and
    cover ``[0, num_rows)`` (tested)."""
    index = jax.process_index() if index is None else int(index)
    count = jax.process_count() if count is None else int(count)
    if not 0 <= index < count:
        raise ValueError(f"process {index} out of range [0, {count})")
    base, extra = divmod(int(num_rows), count)
    lo = index * base + min(index, extra)
    return lo, lo + base + (1 if index < extra else 0)


def local_batch_rows(x, index: Optional[int] = None,
                     count: Optional[int] = None):
    """THIS host's leading-axis shard of a host-identical global batch
    (every process computes the same batch deterministically and keeps
    only its own rows — no cross-host data movement)."""
    lo, hi = process_row_range(np.shape(x)[0], index, count)
    return x[lo:hi]


def assemble_global_batch(local, mesh: Mesh):
    """Batch-sharded global array from per-host local rows.

    The data-plane closer: each host hands in only the rows it owns
    (``local_batch_rows`` of a host-identical batch, or rows it alone
    assembled) and gets back one global array sharded over the mesh's
    data-like axes.  Single-process this is a plain ``device_put`` with
    batch sharding — identical wiring either way."""
    from hyperspace_tpu.parallel.mesh import batch_sharding

    def one(a):
        sh = batch_sharding(mesh, np.ndim(a))
        if jax.process_count() == 1:
            return jax.device_put(a, sh)
        return multihost_utils.host_local_array_to_global_array(
            a, mesh, sh.spec)

    return jax.tree_util.tree_map(one, local)


def local_batch_shards(batch):
    """Per-leaf ``local_batch_rows`` over a host-identical batch pytree,
    with the equal-shard check ``host_local_array_to_global_array``
    needs: every leading axis must divide evenly across processes —
    batch builders pad to a mesh multiple first
    (``hgcn.round_up_pairs``)."""
    count = jax.process_count()

    def check(a):
        n = np.shape(a)[0]
        if n % count:
            raise ValueError(
                f"batch rows {n} not divisible by {count} processes — "
                "pad the batch to a mesh multiple first")
        return local_batch_rows(a)

    return jax.tree_util.tree_map(check, batch)


def distribute_batch(batch, mesh: Mesh):
    """Host-identical global batch → batch-sharded global array, feeding
    only this host's row range (the per-host data plane: host→device
    traffic scales with 1/n_hosts)."""
    return assemble_global_batch(local_batch_shards(batch), mesh)


def host_local_to_global(x, mesh: Mesh, spec: P):
    """Assemble per-host shards into one global array (data loading path:
    each host feeds only its own batch shard; no host sees the full array)."""
    return multihost_utils.host_local_array_to_global_array(x, mesh, spec)


def global_to_host_local(x, mesh: Mesh, spec: P):
    """Inverse of :func:`host_local_to_global` (eval/debug path)."""
    return multihost_utils.global_array_to_host_local_array(x, mesh, spec)


def fetch_replicated(x) -> np.ndarray:
    """Host copy of a replicated global array (loss/metrics).

    Raises on sharded input — returning one shard of a batch-sharded
    array as if it were the full value would corrupt metrics silently.
    """
    if hasattr(x, "addressable_shards"):
        if not x.is_fully_replicated:
            raise ValueError(
                f"fetch_replicated on a sharded array ({x.sharding}); "
                "use global_to_host_local for sharded values")
        return np.asarray(jax.device_get(x.addressable_shards[0].data))
    return np.asarray(jax.device_get(x))


# sync() barrier ids must be unique per use on the coordination service;
# per-name call counters keep them so (processes must call sync with the
# same names in the same order — true of any barrier discipline).
_SYNC_SEQ: dict[str, int] = {}
_SYNC_TIMEOUT_MS = 300_000


def sync(name: str = "barrier") -> None:
    """Cross-host barrier (checkpoint commit points, export gating).

    A HOST-side barrier: returns once every process has arrived — the
    right primitive for file-commit points, where the guarded effect
    (shard files durable before the manifest) happens in host code, not
    on device.  Rides the distributed coordination service when the
    process group is up, so it works on every backend — including the
    CPU loopback topology, whose backend cannot execute cross-process
    device collectives (``sync_global_devices`` aborts there).  Falls
    back to ``sync_global_devices`` if there is no coordination client
    (a multi-process runtime brought up without
    ``jax.distributed.initialize``), and is a no-op single-process.
    """
    if jax.process_count() == 1:
        return
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        multihost_utils.sync_global_devices(name)
        return
    seq = _SYNC_SEQ.get(name, 0)
    _SYNC_SEQ[name] = seq + 1
    client.wait_at_barrier(f"hyperspace_sync:{name}:{seq}",
                           _SYNC_TIMEOUT_MS)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def assert_equal_across_hosts(x, msg: str = "") -> None:
    """Debug guard: all hosts must hold identical values (e.g. params
    after a DP step) — the multi-host analogue of a determinism check.

    Rides a device collective (``broadcast_one_to_all``), which the CPU
    loopback backend does not implement — the loopback harnesses
    (``benchmarks/mh_worker.py``) exchange content digests through the
    shared filesystem behind a :func:`sync` barrier instead."""
    multihost_utils.assert_equal(x, fail_message=msg)


def gather_metric_exports(registry=None) -> list:
    """Every process's raw metric export, on every process.

    The multihost half of ``telemetry/aggregate.py``: each process
    JSON-encodes its ``Registry.export()`` tuple, the encoded payloads
    ride one ``process_allgather`` (zero-padded uint8 rows — allgather
    needs equal shapes, so a length field travels alongside), and every
    process decodes all of them.  ``merge_exports`` of the result is the
    fleet view; on one process this degenerates to ``[export_state()]``
    with no collective issued, so the serve/train wiring is identical
    for world_size 1 and N (the ISSUE 17 shape contract).
    """
    from hyperspace_tpu.telemetry import aggregate

    if jax.process_count() == 1:
        return [aggregate.export_state(registry)]
    payload = aggregate.encode_bytes(aggregate.export_state(registry))
    n = np.int32(len(payload))
    lens = np.asarray(multihost_utils.process_allgather(n))
    width = int(lens.max())
    row = np.zeros((width,), dtype=np.uint8)
    row[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    rows = np.asarray(multihost_utils.process_allgather(row))
    return [
        aggregate.decode_bytes(rows[i, : int(lens[i])].tobytes())
        for i in range(rows.shape[0])
    ]
