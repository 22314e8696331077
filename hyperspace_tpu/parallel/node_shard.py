"""Node-sharded graph aggregation — the pod actually divides the work.

Sharding only the supervision pairs over a mesh leaves the full-graph
encoder (most of the step) replicated on every device.  This module
shards the *node dimension* — the TPU-native analogue of the reference
trainer's graph partitioning (SURVEY.md §2 N8, §7 hard-part #3):

- **Host-side partition** (:func:`partition_graph`): each of ``ndev``
  shards owns ``n_shard`` rows of the node table, rows
  [k·n_shard, (k+1)·n_shard) on shard k, and the edges whose receiver
  it owns.  Under the all-gather the shards are dealt blocks of 128
  nodes in turn (block b to shard b mod ndev: even edge counts whatever
  the node order); under a halo exchange they are contiguous node
  ranges (locality keeps the halo small).  Each shard gets its own
  receiver-local edge list, per-edge mean weights, and block-CSR plan,
  all padded to common static shapes.
- **Device-side aggregation** (:func:`node_sharded_aggregate`): a
  ``shard_map`` over every axis of the mesh, so each device holds a
  shard of its own and none repeats another's.  Each device all-gathers
  the [N, F] activations over ICI (the one collective; at bf16 this is
  ~N·F·2 bytes, ≪ the E·F gather it feeds), then runs *its shard's*
  gather + block-CSR segment-sum — E/ndev edges and N/ndev output rows
  per device.
- **Symmetric backward without cross-shard scatters**: for a symmetric
  edge list, dh[i] = Σ_{e: s_e=i} w_e·ḡ[r_e] re-indexes through the edge
  involution onto *receiver*-side edges (the nn/scatter.py identity), and
  every receiver-side edge of shard k lives on shard k.  So the backward
  is the same all-gather (of ḡ) + local planned segment-sum, with the
  reverse-edge weights ``w_bwd[e] = 1/deg[s_e]`` precomputed on host.
  No scatter ever crosses a shard boundary.

Mean aggregation uses the involution backward above (the bench- and
quality-default HGCN path).  Attention aggregation node-shards too —
receiver partitioning keeps its segment softmax shard-local, so
:func:`node_sharded_att_aggregate` runs it with plain autodiff
collectives (all-gather forward, psum-scatter backward) at a somewhat
worse constant than the mean path.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hyperspace_tpu.data import graphs as graph_data
from hyperspace_tpu.kernels.segment import build_csr_plan, csr_segment_sum
from hyperspace_tpu.telemetry import registry
from hyperspace_tpu.telemetry.trace import span

_BN = 128   # node-block rows (must match kernels.segment._BN tiling)
_BK = 512   # edge-chunk size (must match kernels.segment._BK)


class NodeShardedGraph(NamedTuple):
    """Device-resident node-sharded graph (pytree; statics in aux data).

    Per-edge arrays are [ndev, E_s] so a ``P(axes, None)`` sharding gives
    each device exactly its shard's slice; ``senders`` hold *table rows*
    (they index the all-gathered activations; the owner of row i is
    shard i // n_shard), ``recv`` holds *shard-local* receiver rows,
    ascending within each shard.  Table rows are graph ids under
    contiguous ranges; with ``block_interleave`` = b they are
    :func:`dealt_rows` of the graph ids, and the encoder hands its
    output back in graph order (:func:`graph_order`).

    When ``halo`` is set, ``senders`` instead hold *extended-local* ids
    into ``concat(h_local, halo_rows)`` and the exchange runs one of
    two schedules (``halo_kind``):

    - ``"a2a"``: one ``all_to_all`` over [ndev, H, F] send slots, every
      ordered pair padded to the global max H.  ONE collective — the
      schedule the XLA compiled-cost model prices lowest, because cost
      analysis charges every consumer of a buffer its FULL operand
      bytes, so multi-op schedules pay an accounting penalty per op.
    - ``"ppermute"``: one ``ppermute`` per kept ring distance
      d ∈ ``halo_dists``, each padded to its own max H_d
      (``halo_sizes``), slicing one gathered [ΣH_d, F] send buffer.
      Σ_d H_d ≪ ndev·H when hub-heavy pairs skew the per-pair maxima —
      the lowest TRUE interconnect volume — but the per-slice operand
      accounting above makes it measure worse in compiled bytes.

    ``partition_graph(halo="auto")`` picks the layout (or the plain
    all-gather) by ESTIMATED compiled bytes — the metric this
    environment can actually measure; on real multi-chip ICI the
    ppermute schedule's lower row volume may win and can be forced
    with ``halo="ppermute"``.
    """

    x: Any          # [N_pad, F] node features by table row, node-sharded
    senders: Any    # [ndev, E_s] int32 sender rows (table, or ext-local)
    recv: Any       # [ndev, E_s] int32 local receiver ids (sorted)
    w_fwd: Any      # [ndev, E_s] f32 forward mean weights (0 on padding)
    w_bwd: Any      # [ndev, E_s] f32 reverse-edge weights (0 on padding)
    plan: tuple     # 3 × [ndev, T] int32 padded block-CSR work items
    num_nodes: int  # static: real node count (< N_pad)
    n_shard: int    # static: nodes per shard (N_pad = n_shard · ndev)
    mesh: Any       # static: jax.sharding.Mesh
    axes: tuple     # static: mesh axis names the nodes shard over
    send_idx: Any = None     # [ndev, ndev, H] (a2a) | [ndev, ΣH_d] (ppermute)
    halo: bool = False       # static: exchange halo rows, not all-gather
    halo_kind: str = "a2a"   # static: "a2a" | "ppermute"
    halo_dists: tuple = ()   # static: kept ring distances (ppermute)
    halo_sizes: tuple = ()   # static: H_d per kept distance (ppermute)
    block_interleave: int = 0  # static: rows per dealt block; 0 = ranges


def _nsg_flatten(g: NodeShardedGraph):
    return ((g.x, g.senders, g.recv, g.w_fwd, g.w_bwd, g.plan, g.send_idx),
            (g.num_nodes, g.n_shard, g.mesh, g.axes, g.halo, g.halo_kind,
             g.halo_dists, g.halo_sizes, g.block_interleave))


def _nsg_unflatten(aux, leaves):
    x, s, r, wf, wb, plan, send_idx = leaves
    (num_nodes, n_shard, mesh, axes, halo, halo_kind, halo_dists,
     halo_sizes, block_interleave) = aux
    return NodeShardedGraph(x, s, r, wf, wb, plan, num_nodes, n_shard,
                            mesh, axes, send_idx, halo, halo_kind,
                            halo_dists, halo_sizes, block_interleave)


jax.tree_util.register_pytree_node(NodeShardedGraph, _nsg_flatten, _nsg_unflatten)


class HostPartition(NamedTuple):
    """Host-side (numpy) result of :func:`partition_graph`."""

    x: np.ndarray        # [N_pad, F] by table row
    senders: np.ndarray  # [ndev, E_s] table rows (extended-local if halo)
    recv: np.ndarray     # [ndev, E_s] local sorted
    w_fwd: np.ndarray    # [ndev, E_s]
    w_bwd: np.ndarray    # [ndev, E_s]
    plan: tuple          # 3 × [ndev, T]
    num_nodes: int
    n_shard: int
    send_idx: np.ndarray | None = None  # halo only (layout per halo_kind)
    halo: bool = False
    halo_kind: str = "a2a"
    halo_dists: tuple = ()   # kept ring distances (ppermute)
    halo_sizes: tuple = ()   # H_d per kept distance (ppermute)
    block_interleave: int = 0  # rows per dealt node block; 0 = ranges


def partition_graph(g: graph_data.Graph, ndev: int,
                    bn: int = _BN, bk: int = _BK,
                    halo: Any = "auto") -> HostPartition:
    """Partition a `prepare`-built symmetric graph into ``ndev`` node shards.

    Requires ``g`` built by ``data.graphs.prepare(symmetrize=True)`` (so
    the receiver-sorted layout, the masked degree, and the edge involution
    invariants hold — the backward identity needs every edge's reverse to
    exist).  Shard k owns table rows [k·n_shard, (k+1)·n_shard) and
    exactly the edges whose receiver it owns.  The exchange schedule is
    decided first, from the need sets of contiguous node ranges (row =
    graph id).  If a halo runs (forced, or ``"auto"``'s pick) the ranges
    stay.  Under the all-gather, blocks of ``bn`` nodes are dealt to the
    shards in turn (:func:`dealt_rows`; ``block_interleave`` = ``bn``),
    so a node order that puts the high-degree nodes first no longer
    loads one shard with most edges; the gauges describe the cut that
    runs.

    Plan padding: every shard's edge list ends with one full all-padding
    chunk, and plan rows are padded with (last block, last chunk,
    first=0) items — the padding chunk's values are zero, so the extra
    work items are exact no-ops in the kernel.
    """
    if g.rev_perm is None or g.deg is None:
        raise ValueError(
            "partition_graph needs a symmetric prepare()-built graph "
            "(rev_perm/deg missing)")
    n = g.num_nodes
    per_dev = -(-n // ndev)                 # ceil(n / ndev)
    n_shard = (-(-per_dev // bn)) * bn      # rounded up to whole node blocks
    n_pad = n_shard * ndev

    mask = np.asarray(g.edge_mask)
    s = np.asarray(g.senders)[mask]
    r = np.asarray(g.receivers)[mask]
    deg = np.maximum(np.asarray(g.deg), 1.0)
    # per-edge mean weights, by graph id before any relabelling: the
    # reverse edge (r, s) weighs 1/deg of ITS receiver, s — the backward
    # identity's w∘π without any cross-shard lookup
    w_r, w_s = 1.0 / deg[r], 1.0 / deg[s]
    bounds = np.searchsorted(r, np.arange(ndev + 1) * n_shard)

    # halo exchange (VERDICT r3 #6 / r4 #4): per-shard sender-row need
    # sets.  Under a locality ordering most referenced rows are local or
    # in a few neighbor shards, so exchanging exactly the needed rows
    # can beat the full [N, F] all-gather (~N_pad rows/device).  Two
    # schedules exist (NodeShardedGraph doc): the one-collective
    # ``all_to_all`` padded to the global per-pair max H, and the
    # per-ring-distance ``ppermute`` chain padded per distance.  The
    # backward needs the SAME rows of ḡ (the involution identity maps
    # it onto this shard's own edges), so one need-set serves both
    # directions.
    #
    # "auto" picks by ESTIMATED COMPILED BYTES (the metric
    # scripts/cost_scaling_probe.py asserts).  XLA's cost analysis
    # charges every consumer its full operand, so each schedule pays
    # accounting well beyond its wire volume (coefficients calibrated
    # against measured dp=16 compiled costs at 4096/F=16 and
    # 16384/F=128 — r05 docs/benchmarks.md "Halo exchange"):
    #   all-gather:  n_pad rows         (the gathered activation block)
    #   a2a:         ~4·ndev·H rows     (send gather + in + out +
    #                concat-consumer re-read)
    #   ppermute:    (2+n_dists)·ΣH_d   (each of the n_dists slices of
    #                the send buffer is charged the WHOLE buffer — the
    #                accounting that makes the lowest TRUE-volume
    #                schedule measure worst)
    # The gate is deliberately conservative toward the all-gather: a
    # halo schedule must win by construction (strong block structure,
    # e.g. the ring-of-cliques / strongly-communitied DC-SBM shapes),
    # not by a modeling coin-flip.
    # identity/type check, not ==: the int 1 equals True but would take
    # neither string branch below and build a broken partition
    if not (halo is False or halo is True
            or halo in ("auto", "a2a", "ppermute")):
        raise ValueError(
            f"halo={halo!r}: want False, True, 'auto', 'a2a' or "
            "'ppermute' (a typo here would silently measure the "
            "auto-gated schedule instead of the forced one)")
    use_halo = False
    halo_kind = "a2a"
    send_idx = None
    halo_dists: tuple = ()
    halo_sizes: tuple = ()
    need = _need_sets(s, bounds, n_shard)
    if halo is not False and ndev > 1:
        # per-distance max receive count: at distance d, shard k
        # receives need[k][(k - d) % ndev] and sends need[(k+d)%ndev][k]
        h_d = {}
        for d in range(1, ndev):
            m = max(len(need[(k + d) % ndev][k]) for k in range(ndev))
            if m:
                h_d[d] = -(-m // 8) * 8
        h_max = max(h_d.values(), default=1)
        sum_h = sum(h_d.values())
        est = {
            False: n_shard * ndev,
            "a2a": 4 * ndev * h_max,
            "ppermute": (2 + len(h_d)) * sum_h,
        }
        if not h_d:
            # no cross-shard edges at all: there is nothing to exchange
            # — a "halo" here would build zero-distance ppermute chains
            # (empty concatenate) or all-zero a2a slots; the aggregation
            # is purely local either way, so stay on the gather-free
            # default even when a halo was forced
            use_halo = False
        elif halo in ("a2a", "ppermute", True):
            use_halo = True
            halo_kind = "a2a" if halo is True else halo
        else:  # "auto"
            best = min(est, key=est.get)
            use_halo = best is not False
            halo_kind = best if use_halo else "a2a"
        if use_halo and halo_kind == "a2a":
            send_idx = np.zeros((ndev, ndev, h_max), np.int32)
            for k in range(ndev):
                for j in range(ndev):
                    rows = need[j][k]          # what j needs FROM k
                    send_idx[k, j, :len(rows)] = rows - k * n_shard
        if use_halo and halo_kind == "ppermute":
            halo_dists = tuple(sorted(h_d))
            halo_sizes = tuple(h_d[d] for d in halo_dists)
            send_idx = np.zeros((ndev, sum(halo_sizes)), np.int32)
            col = 0
            for d, hd in zip(halo_dists, halo_sizes):
                for k in range(ndev):
                    rows = need[(k + d) % ndev][k]   # what (k+d) needs FROM k
                    send_idx[k, col:col + len(rows)] = rows - k * n_shard
                col += hd
        if use_halo:
            # extended-local ids.  a2a: halo rows land as [ndev, H]
            # (sender-major), so owner j's block for shard k starts at
            # n_shard + j·H.  ppermute: rows land concatenated in
            # distance order, owner j's block at
            # n_shard + Σ_{d' < dist(k, j)} H_{d'} (same for every k).
            if halo_kind == "ppermute":
                off_d = {}
                acc = n_shard
                for d, hd in zip(halo_dists, halo_sizes):
                    off_d[d] = acc
                    acc += hd
            ext = np.zeros(len(s), np.int32)
            for k in range(ndev):
                lo, hi = bounds[k], bounds[k + 1]
                sk = s[lo:hi]
                owner = sk // n_shard
                local = owner == k
                ext[lo:hi][local] = sk[local] - k * n_shard
                for j in np.unique(owner):
                    j = int(j)
                    if j == k:
                        continue
                    sel = owner == j
                    if halo_kind == "a2a":
                        base = n_shard + j * h_max
                    else:
                        base = off_d[(k - j) % ndev]
                    ext[lo:hi][sel] = base + np.searchsorted(need[k][j],
                                                             sk[sel])

    # the cut that runs.  Under the all-gather every shard reads the
    # whole table, so which nodes a shard owns costs the exchange
    # nothing: deal blocks of ``bn`` nodes to the shards in turn, which
    # spreads the high-degree nodes a locality order puts first over
    # all of them and evens out the edges.  A halo, forced or chosen,
    # keeps the contiguous ranges: locality is what keeps it small.
    forced = halo is True or halo in ("a2a", "ppermute")
    deal = bn if ndev > 1 and not use_halo and not forced else 0
    x = np.zeros((n_pad, g.x.shape[1]), np.float32)
    if deal:
        x[dealt_rows(np.arange(n), n_shard, ndev, bn)] = g.x
        s = dealt_rows(s, n_shard, ndev, bn)
        r = dealt_rows(r, n_shard, ndev, bn)
        # group the edges by shard; within one the rows keep the
        # receivers' order, so the local receivers stay sorted
        perm = np.argsort((r // n_shard).astype(np.int16), kind="stable")
        s, r, w_r, w_s = s[perm], r[perm], w_r[perm], w_s[perm]
        bounds = np.searchsorted(r, np.arange(ndev + 1) * n_shard)
        need = _need_sets(s, bounds, n_shard)
    else:
        x[:n] = g.x
    if use_halo:
        s = ext

    counts = np.diff(bounds)
    # every shard ends with ≥ one full all-padding chunk so padded plan
    # items always have an inert chunk to point at
    e_s = (-(-max(int(counts.max()), 1) // bk)) * bk + bk
    senders = np.zeros((ndev, e_s), np.int32)  # padding edges carry w = 0
    recv = np.full((ndev, e_s), n_shard - 1, np.int32)
    w_fwd = np.zeros((ndev, e_s), np.float32)
    w_bwd = np.zeros((ndev, e_s), np.float32)
    plans = []
    for k in range(ndev):
        lo, hi = bounds[k], bounds[k + 1]
        m = hi - lo
        senders[k, :m] = s[lo:hi]
        recv[k, :m] = r[lo:hi] - k * n_shard
        w_fwd[k, :m] = w_r[lo:hi]
        w_bwd[k, :m] = w_s[lo:hi]
        plans.append(build_csr_plan(recv[k], n_shard, bn, bk))

    t_max = max(p.block.shape[0] for p in plans)
    nb, nchunks = n_shard // bn, e_s // bk
    plan = tuple(np.full((ndev, t_max), fill, np.int32)
                 for fill in (nb - 1, nchunks - 1, 0))
    for k, p in enumerate(plans):
        t = p.block.shape[0]
        plan[0][k, :t] = p.block
        plan[1][k, :t] = p.chunk
        plan[2][k, :t] = p.first
    _record_partition(counts, e_s, n_shard, need, use_halo, halo_kind,
                      send_idx, halo_sizes, deal)
    return HostPartition(x, senders, recv, w_fwd, w_bwd, plan, n, n_shard,
                         send_idx, use_halo, halo_kind, halo_dists,
                         halo_sizes, deal)


def _need_sets(s, bounds, n_shard):
    """``need[k][j]``: the rows of shard j that shard k's senders
    (``s[bounds[k]:bounds[k + 1]]``, table rows) name, each once,
    ascending; empty for j = k."""
    ndev = len(bounds) - 1
    need = [[np.zeros(0, np.int64)] * ndev for _ in range(ndev)]
    for k in range(ndev if ndev > 1 else 0):
        rows = np.unique(s[bounds[k]:bounds[k + 1]])
        cuts = np.searchsorted(rows, np.arange(ndev + 1) * n_shard)
        for j in range(ndev):
            if j != k:
                need[k][j] = rows[cuts[j]:cuts[j + 1]]
    return need


def dealt_rows(ids, n_shard: int, ndev: int, bn: int = _BN):
    """Table rows of graph ids when blocks of ``bn`` nodes are dealt to
    ``ndev`` shards in turn: block b is shard (b mod ndev)'s
    (b div ndev)-th block, and shard k holds rows [k·n_shard,
    (k+1)·n_shard).  Integer arithmetic, for numpy and jax arrays."""
    b = ids // bn
    return (b % ndev) * n_shard + (b // ndev) * bn + ids % bn


# the exchange schedule as the gauge ``node_shard/schedule`` codes it
SCHEDULE_CODES = {"all-gather": 0, "a2a": 1, "ppermute": 2}


def _record_partition(counts, e_s, n_shard, need, use_halo, halo_kind,
                      send_idx, halo_sizes, deal) -> None:
    """The partition's shape as gauges (docs/observability.md): how many
    shards it cut, the rows a shard needs from the others
    (``need[k][j]``), the rows the chosen schedule moves to it, how
    evenly the edges fell, how much of the edge arrays is padding and
    whether node blocks were dealt (their rows) or ranges cut (0).
    Rows are counted per layer and pass: the forward exchanges ``h``,
    the backward the same rows of its cotangent."""
    ndev = len(need)
    need = [sum(len(rows) for rows in of_k) for of_k in need]
    if not use_halo:
        moved = n_shard * (ndev - 1)
    elif halo_kind == "a2a":
        moved = send_idx.shape[2] * (ndev - 1)
    else:
        moved = int(sum(halo_sizes))
    for name, value in (
            ("shards", ndev),
            ("halo_rows_need_max", max(need)),
            ("halo_rows_need_sum", sum(need)),
            # every schedule pads each shard's delivery to the same size
            ("halo_rows_moved_max", moved),
            ("halo_rows_moved_sum", moved * ndev),
            ("edges_max", int(counts.max())),
            ("edges_min", int(counts.min())),
            ("edge_pad_share", 1.0 - float(counts.sum()) / (ndev * e_s)),
            ("block_interleave", deal),
            ("schedule", SCHEDULE_CODES[halo_kind if use_halo
                                        else "all-gather"])):
        registry.set_gauge("node_shard/" + name, value)


def graph_order(a, g: NodeShardedGraph):
    """A node-sharded table ``a`` ([N_pad, ...] by table row) in graph
    order: under dealt blocks the inverse of :func:`dealt_rows`, a
    reshape and transpose of whole blocks (no gather); ``a`` itself
    under contiguous ranges."""
    b = g.block_interleave
    if not b:
        return a
    ndev = a.shape[0] // g.n_shard
    return (a.reshape(ndev, g.n_shard // b, b, *a.shape[1:])
            .swapaxes(0, 1).reshape(a.shape))


def graph_shardings(g: NodeShardedGraph) -> NodeShardedGraph:
    """Sharding pytree matching ``g`` (for jit in_shardings) — the aux
    statics are copied from ``g`` so the tree structures are identical."""
    sh = NamedSharding(g.mesh, P(g.axes, None))
    return NodeShardedGraph(sh, sh, sh, sh, sh, (sh, sh, sh),
                            g.num_nodes, g.n_shard, g.mesh, g.axes,
                            None if g.send_idx is None else sh,
                            g.halo, g.halo_kind, g.halo_dists,
                            g.halo_sizes, g.block_interleave)


def to_device_sharded(hp: HostPartition, mesh: Mesh) -> NodeShardedGraph:
    """Place a :class:`HostPartition` on ``mesh`` as a NodeShardedGraph,
    one shard a device over every axis of the mesh."""
    axes = tuple(mesh.axis_names)
    if hp.senders.shape[0] != mesh.size:
        raise ValueError(
            f"partition has {hp.senders.shape[0]} shards but the mesh "
            f"{dict(mesh.shape)} has {mesh.size} devices")
    sh = NamedSharding(mesh, P(axes, None))
    put = lambda a: jax.device_put(jnp.asarray(a), sh)
    return NodeShardedGraph(
        x=put(hp.x), senders=put(hp.senders), recv=put(hp.recv),
        w_fwd=put(hp.w_fwd), w_bwd=put(hp.w_bwd),
        plan=tuple(put(a) for a in hp.plan),
        num_nodes=hp.num_nodes, n_shard=hp.n_shard, mesh=mesh, axes=axes,
        send_idx=None if hp.send_idx is None else put(hp.send_idx),
        halo=hp.halo, halo_kind=hp.halo_kind,
        halo_dists=tuple(hp.halo_dists),
        halo_sizes=tuple(hp.halo_sizes),
        block_interleave=hp.block_interleave)


def shard_graph(g: graph_data.Graph, mesh: Mesh,
                halo: Any = "auto") -> NodeShardedGraph:
    """partition_graph + to_device_sharded in one call: one shard a
    device of ``mesh``, whatever its axes are named."""
    with span("partition"):
        return to_device_sharded(partition_graph(g, mesh.size, halo=halo),
                                 mesh)


# --- the sharded aggregation --------------------------------------------------


def _local_segsum(msgs, recv, pb, pc, pf, n_shard):
    """Per-shard sorted segment-sum: block-CSR kernel on TPU, XLA sorted
    scatter elsewhere — same dispatch contract as nn/scatter.py."""
    return csr_segment_sum(msgs, recv, (pb, pc, pf), n_shard)


def _mesh_extent(mesh, axes) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _halo_rows(vals_l, si_l, axes, kind, dists, sizes, ndev):
    """The halo collective (NodeShardedGraph doc), either kind.

    ``"a2a"``: one gather of [ndev, H, F] send slots + one
    ``all_to_all``; received rows land sender-major — [ndev·H, F].
    ``"ppermute"``: one gather of the [ΣH_d, F] concatenated send rows,
    then one ``ppermute`` per kept distance over its slice; received
    rows land in distance order.  Both match the extended-local id
    layout ``partition_graph`` wrote for that kind.
    """
    if kind == "a2a":
        sendbuf = vals_l[si_l]                 # [ndev, H, F]
        halo = jax.lax.all_to_all(sendbuf, axes, split_axis=0,
                                  concat_axis=0, tiled=False)
        return halo.reshape(-1, vals_l.shape[-1])
    ax = axes[0] if len(axes) == 1 else axes
    sendbuf = vals_l[si_l]                     # [ΣH_d, F] — one gather
    col = 0
    parts = []
    for d, hd in zip(dists, sizes):
        perm = [(i, (i + d) % ndev) for i in range(ndev)]
        parts.append(jax.lax.ppermute(sendbuf[col:col + hd], ax, perm))
        col += hd
    return jnp.concatenate(parts, axis=0)


def _exchange(vals_l, si_l, axes, kind, dists, sizes, ndev):
    """The rows this shard's ``senders`` index, from its own block
    ``vals_l``: the all-gathered table without ``si_l``, else
    ``concat(vals_l, halo rows)``.  Every schedule's collective and its
    send gather run under the scope ``halo_exchange``, in the forward
    and (the involution backward calls this again) in the backward."""
    with jax.named_scope("halo_exchange"):
        if si_l is None:
            return jax.lax.all_gather(vals_l, axes, axis=0, tiled=True)
        halo = _halo_rows(vals_l, si_l, axes, kind, dists, sizes, ndev)
        return jnp.concatenate([vals_l, halo], axis=0)


def _gather_aggregate(mesh, axes, n_shard, h, w, senders, recv, pb, pc, pf,
                      send_idx=None, kind="a2a", dists=(), sizes=()):
    """Collective + local planned aggregation of this shard's edges.

    Default: all_gather(h) over the node-sharding axes, then gather the
    sender rows locally.  With ``send_idx`` (halo mode): each shard
    sends exactly the rows its peers reference — one ``ppermute`` per
    kept ring distance (:func:`_halo_rows`) — and indexes
    ``concat(h_local, halo)``: 2·Σ_d H_d rows of interconnect traffic
    instead of ~N_pad.  Used for forward (w = w_fwd) and, via the edge
    involution, for backward (h = ḡ, w = w_bwd) — same need sets both
    directions.
    """
    spec = P(axes, None)
    ndev = _mesh_extent(mesh, axes)

    def body(h_l, w_l, s_l, r_l, pb_l, pc_l, pf_l, si_l=None):
        table = _exchange(h_l, None if si_l is None else si_l[0], axes,
                          kind, dists, sizes, ndev)
        msgs = w_l[0][:, None] * table[s_l[0]]
        return _local_segsum(msgs, r_l[0], pb_l[0], pc_l[0], pf_l[0],
                             n_shard)

    args = (h, w, senders, recv, pb, pc, pf)
    if send_idx is not None:
        args += (send_idx,)
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * len(args), out_specs=spec, check_vma=False,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _nsagg(mesh, axes, n_shard, halo_cfg, h, w_fwd, w_bwd, senders, recv,
           pb, pc, pf, send_idx):
    """out[r] = Σ_{e: recv_e = r} w_e · h[senders_e], node-sharded."""
    return _gather_aggregate(mesh, axes, n_shard, h, w_fwd,
                             senders, recv, pb, pc, pf, send_idx,
                             *halo_cfg)


def _nsagg_fwd(mesh, axes, n_shard, halo_cfg, h, w_fwd, w_bwd, senders,
               recv, pb, pc, pf, send_idx):
    out = _gather_aggregate(mesh, axes, n_shard, h, w_fwd,
                            senders, recv, pb, pc, pf, send_idx,
                            *halo_cfg)
    return out, (w_bwd, senders, recv, pb, pc, pf, send_idx)


def _nsagg_bwd(mesh, axes, n_shard, halo_cfg, res, g):
    w_bwd, senders, recv, pb, pc, pf, send_idx = res
    # dh[i] = Σ_{e: s_e = i} w_e ḡ[r_e]  =  Σ_{e: r_e = i} w_{π(e)} ḡ[s_e]
    # — the nn/scatter.py involution identity, which lands every term on
    # the shard that owns node i; so the backward is the same collective-
    # plus-local-CSR program as the forward with (ḡ, w_bwd) in place of
    # (h, w_fwd).  Weights are static (mean aggregation): no dw.
    dh = _gather_aggregate(mesh, axes, n_shard, g, w_bwd,
                           senders, recv, pb, pc, pf, send_idx,
                           *halo_cfg)
    return dh, None, None, None, None, None, None, None, None


_nsagg.defvjp(_nsagg_fwd, _nsagg_bwd)


def node_sharded_aggregate(h: jax.Array, g: NodeShardedGraph,
                           agg_dtype: Optional[Any] = None) -> jax.Array:
    """Mean-aggregate ``h`` over ``g``'s edges, node-sharded over
    ``g.axes``; returns [N_pad, F] in ``h``'s dtype (f32 accumulation).

    ``agg_dtype`` (e.g. bf16) casts the activations *before* the
    collective — halving the ICI bytes as well as the edge-gather HBM
    traffic, same contract as HGCConv's ``agg_dtype``.
    """
    out_dt = h.dtype
    if agg_dtype is not None:
        h = h.astype(agg_dtype)
    w_f = g.w_fwd.astype(h.dtype)
    w_b = g.w_bwd.astype(h.dtype)
    out = _nsagg(g.mesh, g.axes, g.n_shard,
                 (g.halo_kind, g.halo_dists, g.halo_sizes),
                 h, w_f, w_b, g.senders, g.recv, *g.plan,
                 g.send_idx if g.halo else None)
    return out.astype(out_dt)


def node_sharded_att_aggregate(
    h: jax.Array,        # [N_pad, F] node values (node-sharded)
    alpha_s: jax.Array,  # [N_pad] per-node sender attention scores
    alpha_r: jax.Array,  # [N_pad] per-node receiver attention scores
    g: NodeShardedGraph,
    agg_dtype: Optional[Any] = None,
    negative_slope: float = 0.2,
) -> jax.Array:
    """GAT-style segment-softmax aggregation, node-sharded.

    Receiver partitioning makes the softmax shard-local: every edge of a
    receiver lives on the shard that owns it, so the per-receiver
    max/sum run on local sorted segment ops.  Cross-shard reads are two
    all-gathers (h and the [N] sender-score vector); the backward is
    plain autodiff — all_gather transposes to psum_scatter and the edge
    gather to a per-shard scatter-add, so per-device work still scales
    ~1/ndev (with a worse constant than the mean path's involution
    backward; mean aggregation remains the optimized default).
    """
    out_dt = h.dtype
    mesh, axes, n_shard = g.mesh, g.axes, g.n_shard

    def _weights_and_agg(a_se, ar_l, r, mask, hs):
        from hyperspace_tpu.nn.gcn import bounded_att_logits

        # bounded-logit softmax (nn/gcn.py): exp is range-safe without a
        # per-receiver max pass — and stays numerically equivalent to the
        # single-device planned path (the equivalence tests rely on it)
        logits = bounded_att_logits(a_se + ar_l[r], negative_slope)
        w = jnp.where(mask, jnp.exp(logits), 0.0)
        if agg_dtype is not None:  # num and den see identically-rounded w
            hs = hs.astype(agg_dtype)
            w = w.astype(agg_dtype)
        acc_dt = jnp.promote_types(hs.dtype, jnp.float32)
        den = jax.ops.segment_sum(w.astype(acc_dt), r, n_shard,
                                  indices_are_sorted=True)
        num = jax.ops.segment_sum((w[:, None] * hs).astype(acc_dt), r,
                                  n_shard, indices_are_sorted=True)
        return (num / jnp.maximum(den, 1e-15)[:, None])

    def body(h_l, as_l, ar_l, senders, recv, w_f):
        with jax.named_scope("halo_exchange"):
            h_full = jax.lax.all_gather(h_l, axes, axis=0, tiled=True)
            as_full = jax.lax.all_gather(as_l, axes, axis=0, tiled=True)
        s = senders[0]
        mask = w_f[0] > 0  # static edge-validity mask (padding has w=0)
        return _weights_and_agg(as_full[s], ar_l, recv[0], mask, h_full[s])

    def body_halo(h_l, as_l, ar_l, senders, recv, w_f, si_l):
        # halo layout (g.halo): senders are extended-local ids; α_s rides
        # as an extra feature column so the per-distance ppermutes serve
        # both the messages and the sender scores.  Plain autodiff: each
        # ppermute transposes to the reverse permutation + a local
        # scatter-add.
        s = senders[0]
        mask = w_f[0] > 0
        ha_l = jnp.concatenate([h_l, as_l[:, None].astype(h_l.dtype)], 1)
        picked = _exchange(ha_l, si_l[0], axes, g.halo_kind, g.halo_dists,
                           g.halo_sizes, _mesh_extent(mesh, axes))[s]
        return _weights_and_agg(picked[:, -1], ar_l, recv[0], mask,
                                picked[:, :-1])

    spec = P(axes, None)
    vec = P(axes)
    if g.halo:
        out = shard_map(
            body_halo, mesh=mesh,
            in_specs=(spec, vec, vec, spec, spec, spec, spec),
            out_specs=spec, check_vma=False,
        )(h, alpha_s, alpha_r, g.senders, g.recv, g.w_fwd, g.send_idx)
    else:
        out = shard_map(
            body, mesh=mesh,
            in_specs=(spec, vec, vec, spec, spec, spec),
            out_specs=spec, check_vma=False,
        )(h, alpha_s, alpha_r, g.senders, g.recv, g.w_fwd)
    return out.astype(out_dt)


def pad_node_array(a: np.ndarray, n_pad: int, fill=0) -> np.ndarray:
    """Pad a per-node host array to the sharded node count ``n_pad``."""
    a = np.asarray(a)
    out = np.full((n_pad,) + a.shape[1:], fill, a.dtype)
    out[: a.shape[0]] = a
    return out
