"""Graph datasets for HGCN: Cora / ogbn-arxiv loaders + synthetic fallbacks.

Reference workload 2 (BASELINE.json configs[1]): hyperbolic GCN on
Cora / ogbn-arxiv in the Lorentz model — the north-star benchmark
(SURVEY.md §0, §3.2).

TPU constraint (SURVEY.md §7 hard-part #3): XLA wants static shapes, so the
edge list is **padded to a bucket size** and carried with a boolean mask;
aggregation is masked ``segment_sum`` over receivers, never ragged ops.

This environment has no network access, so the loaders read standard
on-disk formats when present (Planetoid ``cora.content``/``cora.cites``;
OGB's extracted csv layout) and otherwise synthesize structurally similar
graphs: a noisy hierarchy (trees embed well in hyperbolic space, so link
prediction ROC-AUC is a meaningful quality signal — the same reason the
reference's workloads are hierarchy-shaped) with community-correlated
features for node classification.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from hyperspace_tpu.telemetry.trace import importing, span


@dataclasses.dataclass
class Graph:
    """A static-shape graph: padded edge list + masks.

    ``senders``/``receivers`` hold one direction per stored edge; callers
    that need symmetric message passing should build the graph through
    :func:`prepare` which symmetrizes and adds self-loops before padding.
    """

    x: np.ndarray  # [N, F] float32 node features
    senders: np.ndarray  # [E_pad] int32
    receivers: np.ndarray  # [E_pad] int32, sorted ascending (see prepare)
    edge_mask: np.ndarray  # [E_pad] bool (False = padding)
    num_nodes: int
    rev_perm: np.ndarray | None = None  # [E_pad] int32 edge -> reverse edge
    deg: np.ndarray | None = None  # [N] float32 masked in-degree (static)
    csr_plan: tuple | None = None  # kernels.segment.CsrPlan work items
    cluster_split: Any | None = None  # kernels.cluster.ClusterSplit (mean agg)
    labels: np.ndarray | None = None  # [N] int32
    num_classes: int = 0
    train_mask: np.ndarray | None = None  # [N] bool (node tasks)
    val_mask: np.ndarray | None = None
    test_mask: np.ndarray | None = None

    @property
    def num_edges(self) -> int:
        return int(self.edge_mask.sum())


class DeviceGraph(NamedTuple):
    """Device-resident graph arrays, one pytree leaf per field.

    The single argument models/layers take for message passing; built once
    per graph with :func:`to_device`.  Optional fields are ``None`` when
    the graph was not built by :func:`prepare` (consumers then fall back
    to plain masked segment ops).
    """

    x: "jax.Array"                      # [N, F]
    senders: "jax.Array"                # [E] int32
    receivers: "jax.Array"              # [E] int32 sorted
    edge_mask: "jax.Array"              # [E] bool
    num_nodes: int                      # static (python int)
    rev_perm: Optional["jax.Array"] = None   # [E] int32 involution
    deg: Optional["jax.Array"] = None        # [N] f32 masked in-degree
    plan: Optional[tuple] = None             # 3 × [T] int32 CSR work items
    cluster: Any = None                      # nn.scatter.ClusterAgg (mean agg)


# num_nodes must stay a static (hashable) field across jit boundaries, so
# DeviceGraph is registered with num_nodes as auxiliary pytree data.
def _dg_flatten(g: DeviceGraph):
    return (g.x, g.senders, g.receivers, g.edge_mask, g.rev_perm, g.deg,
            g.plan, g.cluster), g.num_nodes


def _dg_unflatten(num_nodes, leaves):
    x, s, r, m, rp, deg, plan, cluster = leaves
    return DeviceGraph(x, s, r, m, num_nodes, rp, deg, plan, cluster)


jax.tree_util.register_pytree_node(DeviceGraph, _dg_flatten, _dg_unflatten)


def to_device(g: Graph) -> DeviceGraph:
    """Put a host :class:`Graph` on device as a :class:`DeviceGraph`."""
    info = {}
    with span("place", info):
        cluster = None
        if g.cluster_split is not None:
            from hyperspace_tpu.nn.scatter import ClusterAgg

            cluster = ClusterAgg.from_host(g.cluster_split)
        dg = DeviceGraph(
            x=jnp.asarray(g.x),
            senders=jnp.asarray(g.senders),
            receivers=jnp.asarray(g.receivers),
            edge_mask=jnp.asarray(g.edge_mask),
            num_nodes=g.num_nodes,
            rev_perm=None if g.rev_perm is None else jnp.asarray(g.rev_perm),
            deg=None if g.deg is None else jnp.asarray(g.deg),
            plan=None if g.csr_plan is None
            else tuple(jnp.asarray(a) for a in g.csr_plan),
            cluster=cluster,
        )
        info["bytes"] = sum(
            int(a.nbytes) for a in jax.tree_util.tree_leaves(dg))
    return dg


@dataclasses.dataclass
class LinkSplit:
    """Edge split for link prediction (SURVEY.md §3.2 LP head).

    ``graph`` contains only the *training* edges (message passing must not
    see held-out edges).  val/test arrays are [K, 2] (u, v) pairs.
    """

    graph: Graph
    train_pos: np.ndarray
    val_pos: np.ndarray
    val_neg: np.ndarray
    test_pos: np.ndarray
    test_neg: np.ndarray


def _pad_to(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def _prepare_edges_numpy(edges, num_nodes, *, symmetrize=True,
                         self_loops=True, pad_multiple=1024):
    """Numpy edge-layout pipeline: the fallback for :func:`prepare` and
    the parity oracle for ``native.prepare_edges`` (tests/data).

    Returns (senders, receivers, mask, rev_perm, deg); ``rev_perm`` is
    None unless ``symmetrize``.
    """
    e = np.asarray(edges, np.int64)
    if symmetrize and len(e):
        e = np.concatenate([e, e[:, ::-1]], axis=0)
    if self_loops:
        loops = np.stack([np.arange(num_nodes)] * 2, axis=1)
        e = np.concatenate([e, loops], axis=0) if len(e) else loops
    # dedupe + sort by (receiver, sender) via flat receiver-major keys
    key = e[:, 1] * num_nodes + e[:, 0]
    e = e[np.unique(key, return_index=True)[1]]
    e_pad = _pad_to(max(len(e), 1), pad_multiple)
    senders = np.full(e_pad, num_nodes - 1, np.int32)
    receivers = np.full(e_pad, num_nodes - 1, np.int32)
    mask = np.zeros(e_pad, bool)
    senders[: len(e)] = e[:, 0]
    receivers[: len(e)] = e[:, 1]
    mask[: len(e)] = True

    rev_perm = None
    if symmetrize:
        # reverse of (s, r) has key s·N + r; keys are sorted, so
        # searchsorted gives its index.  Padding maps to itself.
        keys_sorted = e[:, 1] * num_nodes + e[:, 0]
        rev_perm = np.arange(e_pad, dtype=np.int32)
        rev_perm[: len(e)] = np.searchsorted(
            keys_sorted, e[:, 0] * num_nodes + e[:, 1]).astype(np.int32)
    deg = np.bincount(receivers[mask], minlength=num_nodes).astype(np.float32)
    return senders, receivers, mask, rev_perm, deg


def _check_edge_range(edges, num_nodes: int) -> None:
    """Raise IndexError on out-of-range ids BEFORE any native path runs
    (the C++ pipelines do no bounds checks — a bad id would silently
    corrupt memory or segfault instead of raising)."""
    e = np.asarray(edges)
    if len(e) and (e.min() < 0 or e.max() >= num_nodes):
        raise IndexError(
            f"edge ids out of range [0, {num_nodes}): min {e.min()}, "
            f"max {e.max()}")


# raw-edge-count gate for cache="auto" (data/prep_cache.py): below this
# the host prep is cheaper than hashing + disk IO, and unit-test graphs
# must never touch the on-disk cache
_CACHE_AUTO_MIN_EDGES = 200_000


def cluster_min_pair_for(use_att: bool) -> int:
    """The mode-dependent cluster-pair density threshold — ONE home for
    the r05 sweep result (docs/benchmarks.md "Per-mode cluster
    threshold"): mean aggregation wins at 256, attention at 128 (the
    in-tile attention kernels save enough [E]-stream per clustered edge
    that sparser pairs still pay).  Re-sweeps update this function only.
    """
    return 128 if use_att else 256


def prepare(
    edges: np.ndarray,
    num_nodes: int,
    x: np.ndarray,
    *,
    symmetrize: bool = True,
    self_loops: bool = True,
    pad_multiple: int = 1024,
    cluster: str | bool = "auto",
    cluster_min_pair: int = 256,
    cache: Any = "auto",
    **node_fields,
) -> Graph:
    """Symmetrize, add self-loops, dedupe, sort by receiver, pad.

    TPU layout decisions (SURVEY.md §2 "padding/bucketing needed on TPU"
    and §7 hard-part #3):

    - Edges are **sorted by (receiver, sender)** so every aggregation
      scatter runs XLA's sorted fast path (~2.3× at arxiv scale).
    - ``rev_perm`` maps each edge to its reverse (self-loops and padding
      map to themselves), letting the aggregation VJP scatter sorted too
      (see nn/scatter.py).  Requires ``symmetrize=True``; otherwise left
      ``None`` and consumers fall back to plain segment ops.
    - Padding edges are (N−1, N−1) with ``edge_mask`` False — the max key
      keeps the receiver order sorted; weight 0 keeps them inert.
    - ``deg`` (masked in-degree) and ``csr_plan`` (the block-CSR work-item
      schedule for :func:`hyperspace_tpu.kernels.segment.csr_segment_sum`)
      are static per graph, so they are computed here once instead of per
      training step.
    - The whole edge layout (everything above plus the cluster split) is
      a pure function of (edges, num_nodes, knobs), so it is served from
      the persistent :mod:`hyperspace_tpu.data.prep_cache` when ``cache``
      allows — ``"auto"`` caches big graphs only; pass ``True``/a
      ``PrepCache`` to force, ``False`` to disable.  ``x`` and the node
      fields ride outside the cache (they don't shape the edge layout).
    """
    _check_edge_range(edges, num_nodes)
    from hyperspace_tpu.data import prep_cache

    e_arr = np.asarray(edges)
    pc = prep_cache.resolve(
        cache, auto_ok=len(e_arr) >= _CACHE_AUTO_MIN_EDGES)
    build = lambda: _build_edge_layout(
        e_arr, num_nodes, symmetrize=symmetrize, self_loops=self_loops,
        pad_multiple=pad_multiple, cluster=cluster,
        cluster_min_pair=cluster_min_pair)
    if pc is not None:
        layout = pc.get_or_build(
            "edge-layout",
            (e_arr.astype(np.int64, copy=False), num_nodes, symmetrize,
             self_loops, pad_multiple, str(cluster), cluster_min_pair),
            build)
    else:
        layout = build()

    return Graph(
        x=np.asarray(x, np.float32),
        num_nodes=num_nodes,
        **layout,
        **node_fields,
    )


def _build_edge_layout(edges, num_nodes, *, symmetrize, self_loops,
                       pad_multiple, cluster, cluster_min_pair) -> dict:
    """The cacheable core of :func:`prepare`: every edge-derived artifact
    as a dict of Graph field values (no x/labels/masks)."""
    senders = receivers = mask = rev_perm = deg = None
    try:  # native C++ pipeline; _prepare_edges_numpy is the oracle
        from hyperspace_tpu.data import native

        senders, receivers, mask, rev_perm, deg = native.prepare_edges(
            np.asarray(edges, np.int32), num_nodes, symmetrize=symmetrize,
            self_loops=self_loops, pad_multiple=pad_multiple)
        if not symmetrize:
            rev_perm = None
    except ImportError:  # no C++ compiler installed (data/native.py)
        pass
    if senders is None:
        senders, receivers, mask, rev_perm, deg = _prepare_edges_numpy(
            edges, num_nodes, symmetrize=symmetrize, self_loops=self_loops,
            pad_multiple=pad_multiple)

    from hyperspace_tpu.kernels.segment import build_csr_plan

    # cluster-pair split (kernels/cluster.py): avoids the [E, F] message
    # round-trip for block-dense edges.  "auto" builds it only at scales
    # where the aggregation is actually HBM-bound (the one-time host sort
    # is wasted on toy graphs, and small graphs fit the plain path fine).
    # ``cluster_min_pair``: the (rb, sb)-pair density threshold.  The
    # r05 same-session sweep (docs/benchmarks.md) found the best value
    # is MODE-dependent: 256 for mean aggregation (0.1288 vs 0.1314 s
    # at 128) but 128 for attention (0.2771 vs 0.2898 s) — the in-tile
    # attention kernels save enough [E]-stream per clustered edge that
    # sparser pairs still pay; callers that know attention will run
    # pass 128 (cli.train, run_hgcn_bench use_att).
    split = None
    n_real = int(mask.sum())
    if cluster is True or (cluster == "auto" and n_real >= 200_000):
        if symmetrize:  # the involution backward needs a symmetric set
            from hyperspace_tpu.kernels.cluster import build_cluster_split

            split = build_cluster_split(senders, receivers, mask, deg,
                                        num_nodes, rev_perm=rev_perm,
                                        min_pair_edges=cluster_min_pair)

    return dict(
        senders=senders,
        receivers=receivers,
        edge_mask=mask,
        rev_perm=rev_perm,
        deg=deg,
        csr_plan=tuple(build_csr_plan(receivers, num_nodes)),
        cluster_split=split,
    )


# --- link-prediction split ----------------------------------------------------


def split_edges(
    edges: np.ndarray,
    num_nodes: int,
    x: np.ndarray,
    *,
    val_frac: float = 0.05,
    test_frac: float = 0.10,
    seed: int = 0,
    pad_multiple: int = 1024,
    cluster_min_pair: int = 256,
    cache: Any = "auto",
    **node_fields,
) -> LinkSplit:
    """Hold out edges for LP eval; message passing uses only train edges.

    Negatives are uniform non-edges, the Chami et al. 2019 protocol whose
    ROC-AUC is the [B] quality target.  The host split (canonicalized
    permutation + rejection-sampled negatives) is deterministic in
    (edges, num_nodes, fracs, seed), so it caches persistently alongside
    the prepared graph's edge layout (``cache`` — see :func:`prepare`).
    """
    e = np.asarray(edges, np.int64)

    def build() -> dict:
        # the WHOLE host split lives inside the cached builder — the
        # O(E log E) canonicalize/sort/dedup/permutation is most of the
        # cost at arxiv scale, so a cache hit must skip it too, not just
        # the negative sampling
        rng = np.random.default_rng(seed)
        # undirected canonical form for splitting
        canon = np.sort(e, axis=1)
        canon = canon[np.unique(canon[:, 0] * num_nodes + canon[:, 1],
                                return_index=True)[1]]
        perm = rng.permutation(len(canon))
        n_val = int(len(canon) * val_frac)
        n_test = int(len(canon) * test_frac)
        val_pos = canon[perm[:n_val]]
        test_pos = canon[perm[n_val : n_val + n_test]]
        train_pos = canon[perm[n_val + n_test :]]

        def sample_neg(k: int) -> np.ndarray:
            try:  # native rejection sampler (arxiv-scale edge sets)
                from hyperspace_tpu.data import native

                neg = native.sample_negative_edges(
                    canon, num_nodes, k, seed=int(rng.integers(2**31)))
                if len(neg) == k:
                    return neg.astype(np.int64)
            except ImportError:  # no C++ compiler (data/native.py)
                pass
            edge_set = {(int(u), int(v)) for u, v in canon}
            out = []
            while len(out) < k:
                cand = rng.integers(0, num_nodes,
                                    size=(2 * (k - len(out)) + 16, 2))
                for u, v in cand:
                    if u == v:
                        continue
                    a, b = (int(u), int(v)) if u < v else (int(v), int(u))
                    if (a, b) in edge_set:
                        continue
                    out.append((a, b))
                    if len(out) == k:
                        break
            return np.asarray(out, np.int64)

        return dict(
            train_pos=train_pos.astype(np.int32),
            val_pos=val_pos.astype(np.int32),
            val_neg=sample_neg(len(val_pos)).astype(np.int32),
            test_pos=test_pos.astype(np.int32),
            test_neg=sample_neg(len(test_pos)).astype(np.int32),
        )

    from hyperspace_tpu.data import prep_cache

    pc = prep_cache.resolve(cache, auto_ok=len(e) >= _CACHE_AUTO_MIN_EDGES)
    if pc is not None:
        arrs = pc.get_or_build(
            "lp-split", (e, num_nodes, val_frac, test_frac, seed), build)
    else:
        arrs = build()
    g = prepare(
        arrs["train_pos"], num_nodes, x, pad_multiple=pad_multiple,
        cluster_min_pair=cluster_min_pair, cache=cache, **node_fields
    )
    return LinkSplit(graph=g, **arrs)


# --- on-disk loaders ----------------------------------------------------------


def load_cora(root: str):
    """Planetoid raw format: ``cora.content`` + ``cora.cites``.

    Returns (edges [E,2], x [N,F], labels [N], num_classes).
    """
    content = os.path.join(root, "cora.content")
    cites = os.path.join(root, "cora.cites")
    ids, feats, labels, label_ids = {}, [], [], {}
    with open(content) as f:
        for line in f:
            parts = line.strip().split()
            ids[parts[0]] = len(ids)
            feats.append([float(t) for t in parts[1:-1]])
            lab = parts[-1]
            label_ids.setdefault(lab, len(label_ids))
            labels.append(label_ids[lab])
    edges = []
    with open(cites) as f:
        for line in f:
            a, b = line.strip().split()
            if a in ids and b in ids:
                edges.append((ids[a], ids[b]))
    return (
        np.asarray(edges, np.int64),
        np.asarray(feats, np.float32),
        np.asarray(labels, np.int32),
        len(label_ids),
    )


def _read_csv(path: str, dtype):
    """Fast csv matrix read: pandas C engine when available (an order of
    magnitude faster at arxiv scale — node-feat.csv is ~21.7 M floats),
    np.loadtxt as the no-pandas fallback."""
    info = {"file": os.path.basename(path)}
    with span("read_csv", info):
        try:
            with importing("pandas"):
                import pandas as pd

            a = pd.read_csv(path, header=None, dtype=dtype).to_numpy()
        except ImportError:
            a = np.loadtxt(path, delimiter=",", dtype=dtype)
        info.update(bytes=os.path.getsize(path), rows=int(a.shape[0]))
    return a


# the OGB node-property datasets :func:`load_graph` reads from disk, each
# with its published shape.  ``ogbn-mag-cites`` is ogbn-mag's
# paper-cites-paper relation alone: the homogeneous projection its plain
# GCN / GraphSAGE baselines train on (Hu et al. 2020, arxiv 2005.00687)
OGB_SHAPES = {
    "ogbn-arxiv": dict(num_nodes=169_343, num_edges=1_166_243,
                       feat_dim=128, num_classes=40),
    "ogbn-mag-cites": dict(num_nodes=736_389, num_edges=5_416_271,
                           feat_dim=128, num_classes=349),
}


def load_ogb_csv(root: str):
    """OGB extracted-csv layout (``raw/edge.csv``, ``raw/node-feat.csv``,
    ``raw/node-label.csv``), whichever dataset of :data:`OGB_SHAPES` it
    holds."""
    raw = os.path.join(root, "raw")
    edges = _read_csv(os.path.join(raw, "edge.csv"), np.int64)
    x = np.ascontiguousarray(
        _read_csv(os.path.join(raw, "node-feat.csv"), np.float32))
    labels = _read_csv(os.path.join(raw, "node-label.csv"), np.int64)
    return edges, x, labels.astype(np.int32).reshape(-1), int(labels.max()) + 1


# from this many values on, a csv is formatted by several processes (the
# published ogbn-mag feature matrix is 94 M floats, 100 s in one; the
# arxiv shape's 21.7 M stay in one)
_PARALLEL_CSV_MIN_VALUES = 32_000_000
# one worker of :func:`_write_csv_parallel`: argv = chunk.npy, part.csv.
# A plain interpreter that imports numpy and pandas alone: it never
# re-imports the caller's ``__main__`` (as a spawned pool would) and
# never touches an accelerator the caller holds
_CSV_WORKER = (
    "import sys, numpy, pandas; "
    "pandas.DataFrame(numpy.load(sys.argv[1])).to_csv("
    "sys.argv[2], header=False, index=False, float_format='%.6g')")


def _write_csv_parallel(path: str, a: np.ndarray, workers: int) -> None:
    """The bytes pandas writes for ``a`` whole, from ``workers`` row
    chunks each formatted by a process of its own (a row's text does not
    depend on its neighbours) and joined in order."""
    import shutil
    import subprocess
    import sys
    import tempfile

    with tempfile.TemporaryDirectory(dir=os.path.dirname(path)) as tmp:
        procs = []
        try:
            for i, chunk in enumerate(np.array_split(a, workers)):
                src = os.path.join(tmp, f"{i}.npy")
                np.save(src, chunk)
                procs.append(subprocess.Popen(
                    [sys.executable, "-c", _CSV_WORKER, src,
                     os.path.join(tmp, f"{i}.csv")]))
            with open(path, "wb") as out:
                for i, proc in enumerate(procs):
                    if proc.wait() != 0:
                        raise RuntimeError(
                            f"csv worker {i} of {path} exited "
                            f"{proc.returncode}")
                    with open(os.path.join(tmp, f"{i}.csv"), "rb") as part:
                        shutil.copyfileobj(part, out)
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()


def write_ogb_csv_layout(root: str, edges: np.ndarray, x: np.ndarray,
                         labels: np.ndarray) -> None:
    """Write a graph to the OGB extracted-csv layout ``load_ogb_csv``
    reads (``raw/{edge,node-feat,node-label}.csv``) — the disk end of the
    disk → load → prepare → train pipeline."""
    raw = os.path.join(root, "raw")
    os.makedirs(raw, exist_ok=True)

    def _write(path, a, fmt):
        try:  # pandas C writer: ~10x np.savetxt on the 21.7M-float feat
            import pandas as pd

            workers = min(8, os.cpu_count() or 1)
            if a.size >= _PARALLEL_CSV_MIN_VALUES and workers > 1:
                _write_csv_parallel(path, a, workers)
                return
            pd.DataFrame(a).to_csv(path, header=False, index=False,
                                   float_format="%.6g")
        except ImportError:
            np.savetxt(path, a, fmt=fmt, delimiter=",")

    _write(os.path.join(raw, "edge.csv"), np.asarray(edges, np.int64), "%d")
    _write(os.path.join(raw, "node-feat.csv"), np.asarray(x, np.float32),
           "%.6g")
    _write(os.path.join(raw, "node-label.csv"),
           np.asarray(labels, np.int64).reshape(-1, 1), "%d")


# --- synthetic fallbacks ------------------------------------------------------


def synthetic_hierarchy(
    num_nodes: int = 1024,
    branching: int = 3,
    feat_dim: int = 32,
    ancestor_hops: int = 3,
    extra_edge_frac: float = 0.02,
    num_classes: int = 4,
    seed: int = 0,
):
    """A noisy hierarchy with community-correlated features.

    Structure: a ``branching``-ary tree over all nodes, **plus ancestor
    edges up to ``ancestor_hops`` levels** (a truncated transitive closure)
    and a few random cross edges.  The ancestor edges make the graph
    structurally redundant: every held-out link has parallel 2-hop paths
    (child—grandparent—parent), so link prediction from message passing is
    well-posed — a pure tree would disconnect under edge removal and cap
    ROC-AUC near chance.  Hierarchies have strong negative curvature, so
    hyperbolic models fit them well — the signal the integration tests
    assert (SURVEY.md §4.7).

    Class = top-level subtree; features = class prototype + noise + a depth
    coordinate.  Returns (edges [E,2], x [N,F], labels [N], num_classes).
    """
    rng = np.random.default_rng(seed)
    parent = np.zeros(num_nodes, np.int64)
    parent[1:] = (np.arange(1, num_nodes) - 1) // branching
    edges = []
    for i in range(1, num_nodes):
        anc = i
        for _ in range(max(1, ancestor_hops)):
            anc = int(parent[anc])
            edges.append((i, anc))
            if anc == 0:
                break
    n_extra = int(num_nodes * extra_edge_frac)
    for _ in range(n_extra):
        u, v = rng.integers(0, num_nodes, 2)
        if u != v:
            edges.append((int(u), int(v)))
    edges = np.asarray(edges, np.int64)

    # class of a node = which depth-1 subtree it falls under
    depth = np.zeros(num_nodes, np.int64)
    top = np.zeros(num_nodes, np.int64)
    for i in range(1, num_nodes):
        depth[i] = depth[parent[i]] + 1
        top[i] = i if depth[i] == 1 else top[parent[i]]
    labels = (top % num_classes).astype(np.int32)
    labels[0] = 0

    protos = rng.normal(size=(num_classes, feat_dim)).astype(np.float32)
    x = protos[labels] + 0.4 * rng.normal(size=(num_nodes, feat_dim)).astype(np.float32)
    x[:, 0] = depth / max(depth.max(), 1)
    return edges, x, labels, num_classes


def community_power_law_graph(
    num_nodes: int = 169_343,
    num_edges: int = 1_166_243,
    num_classes: int = 40,
    feat_dim: int = 128,
    gamma: float = 2.6,
    p_in: float = 0.72,
    p_sub: float = 0.55,
    sub_size: int = 400,
    triadic_frac: float = 0.15,
    seed: int = 0,
):
    """Community-structured power-law graph at citation-network statistics.

    The uniform-random edge majority of :func:`synthetic_hierarchy` is
    *unclusterable by construction* — adversarial to the BFS-locality /
    cluster-pair levers real citation graphs reward (VERDICT r3 #3).
    This generator produces the structure those levers were built for,
    with ogbn-arxiv-like shape statistics:

    - **degree-corrected SBM**: node degrees follow a truncated power law
      (exponent ``gamma``, arxiv's in-degree tail fits ~2.5–3); both edge
      endpoints are degree-weighted, so hubs emerge.
    - **communities**: ``num_classes`` groups with power-law sizes; a
      ``p_in`` fraction of edges stay inside the sender's community
      (arxiv's label assortativity ~0.65–0.8 depending on measure).
      Class label = community; features = community prototype + noise
      (same recipe as :func:`synthetic_hierarchy`).
    - **hierarchical sub-communities**: citation topics cluster down to
      research-group scale, not just field scale — within a community,
      a ``p_sub`` fraction of its internal edges stay inside the
      sender's ~``sub_size``-node sub-community.  This is the level the
      BFS locality reorder converts into (receiver-block × sender-block)
      density for the cluster-pair kernel.
    - **triadic closure**: ``triadic_frac`` of edges connect two
      neighbors of a shared node, lifting the clustering coefficient
      from the SBM's near-zero toward citation-graph levels.

    Returns (edges [E, 2] directed with E == ``num_edges`` exactly,
    x [N, F], labels [N], num_classes).
    """
    rng = np.random.default_rng(seed)
    # truncated power-law degree propensities (inverse-transform Pareto)
    u = rng.random(num_nodes)
    prop = np.minimum(u ** (-1.0 / (gamma - 1.0)), num_nodes ** 0.5)
    prop /= prop.sum()
    # power-law community sizes via Dirichlet over a decaying base measure
    base = (1.0 / np.arange(1, num_classes + 1)) ** 0.8
    sizes = rng.dirichlet(base * num_classes)
    comm = rng.choice(num_classes, size=num_nodes, p=sizes)

    # sub-communities: chunk each community's member list into
    # ~sub_size-node groups (globally-unique sub ids)
    sub = np.zeros(num_nodes, np.int64)
    next_sub = 0
    for c in range(num_classes):
        members = np.flatnonzero(comm == c)
        n_sub = max(1, len(members) // sub_size)
        sub[members] = next_sub + rng.integers(0, n_sub, len(members))
        next_sub += n_sub

    n_base = int(num_edges * (1.0 - triadic_frac))
    senders = rng.choice(num_nodes, size=n_base, p=prop)
    receivers = np.empty(n_base, np.int64)
    r_scope = rng.random(n_base)
    in_comm = r_scope < p_in
    in_sub = r_scope < p_in * p_sub
    out_idx = np.flatnonzero(~in_comm)
    receivers[out_idx] = rng.choice(num_nodes, size=len(out_idx), p=prop)

    def _fill_grouped(group_of, take_mask):
        """Degree-weighted receiver draw within the sender's group."""
        take = np.flatnonzero(take_mask)
        if len(take) == 0:
            return
        gids = group_of[senders[take]]
        order = np.argsort(gids, kind="stable")
        take = take[order]
        gids = gids[order]
        starts = np.flatnonzero(np.r_[True, gids[1:] != gids[:-1]])
        ends = np.r_[starts[1:], len(gids)]
        for st, en in zip(starts, ends):
            members = np.flatnonzero(group_of == gids[st])
            pc = prop[members] / prop[members].sum()
            receivers[take[st:en]] = members[
                rng.choice(len(members), size=en - st, p=pc)]

    _fill_grouped(sub, in_sub)
    _fill_grouped(comm, in_comm & ~in_sub)
    edges = np.stack([senders, receivers], axis=1)
    edges = edges[edges[:, 0] != edges[:, 1]]

    # triadic closure: connect two neighbors of a shared pivot — pair
    # the receivers of two edges that share a sender (adjacent in sender
    # order).  Pivots are drawn among the pairs that do close a triangle,
    # so the published edge count is met exactly
    n_tri = num_edges - len(edges)
    if n_tri > 0:
        by_sender = edges[np.argsort(edges[:, 0], kind="stable")]
        a, b = by_sender[:-1], by_sender[1:]
        closes = np.flatnonzero((a[:, 0] == b[:, 0]) & (a[:, 1] != b[:, 1]))
        if not len(closes):
            raise ValueError(
                "community_power_law_graph: no two edges share a sender, "
                "so no triangle can be closed — raise num_edges or set "
                "triadic_frac=0")
        pivots = rng.choice(closes, size=n_tri)
        tri = np.stack([a[pivots, 1], b[pivots, 1]], axis=1)
        edges = np.concatenate([edges, tri], axis=0)

    protos = rng.normal(size=(num_classes, feat_dim)).astype(np.float32)
    labels = comm.astype(np.int32)
    x = protos[labels] + 0.4 * rng.normal(
        size=(num_nodes, feat_dim)).astype(np.float32)
    return edges.astype(np.int64), x, labels, num_classes


def ensure_ogb_scale_dataset(root: str, name: str, seed: int = 0,
                             **graph_kw) -> str:
    """Materialize :func:`community_power_law_graph` at the published
    shape of the OGB dataset ``name`` (:data:`OGB_SHAPES`) on disk, in
    the extracted-csv layout that ``load_graph(name, root)`` reads
    (~200 MB at the ogbn-arxiv shape, ~0.9 GB at ogbn-mag's citation
    relation; generated once per ``root``).  Returns ``root``.
    ``graph_kw`` go to the generator over the published counts: tests
    shrink the graph with them, everything else runs the shape as
    published.

    The stand-in for the real download wherever there is no network:
    the trainer then runs at the real size through the real disk →
    ``load_ogb_csv`` → ``prepare`` pipeline (``source: "disk"``)
    instead of on :func:`load_graph`'s small synthetic hierarchy.
    """
    import shutil

    root = os.path.abspath(root)
    if not os.path.exists(os.path.join(root, "raw", "edge.csv")):
        # write into a temp sibling and rename whole: an interrupted
        # generation must not leave a half-written tree that the
        # edge.csv existence sentinel would treat as complete
        with span("make_dataset", {"dataset": name}):
            tmp = root + ".tmp"
            shutil.rmtree(tmp, ignore_errors=True)
            edges, x, labels, _ = community_power_law_graph(
                seed=seed, **{**OGB_SHAPES[name], **graph_kw})
            write_ogb_csv_layout(tmp, edges, x, labels)
            os.makedirs(os.path.dirname(root), exist_ok=True)
            shutil.rmtree(root, ignore_errors=True)
            os.replace(tmp, root)
    return root


def ensure_arxiv_scale_dataset(root: str | None = None, seed: int = 0,
                               **graph_kw) -> str:
    """:func:`ensure_ogb_scale_dataset` for ``ogbn-arxiv`` (169,343
    nodes, 1,166,243 directed edges, 128 features, 40 classes); the
    default ``root`` is ``<repo>/.cache/arxiv-synth``."""
    if root is None:
        root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                            ".cache", "arxiv-synth")
    return ensure_ogb_scale_dataset(root, "ogbn-arxiv", seed, **graph_kw)


def ensure_magcite_scale_dataset(root: str, seed: int = 0,
                                 **graph_kw) -> str:
    """:func:`ensure_ogb_scale_dataset` for ``ogbn-mag-cites`` (736,389
    paper nodes, 5,416,271 directed citation edges, 128 features, 349
    venue classes): 4.35 times ogbn-arxiv's nodes, the graph of
    ``configs/hgcn_magcite_lp.yaml``, sized for a four-chip host."""
    return ensure_ogb_scale_dataset(root, "ogbn-mag-cites", seed, **graph_kw)


def node_split_masks(num_nodes: int, train_frac=0.6, val_frac=0.2, seed: int = 0):
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_nodes)
    n_tr = int(num_nodes * train_frac)
    n_va = int(num_nodes * val_frac)
    tr = np.zeros(num_nodes, bool)
    va = np.zeros(num_nodes, bool)
    te = np.zeros(num_nodes, bool)
    tr[perm[:n_tr]] = True
    va[perm[n_tr : n_tr + n_va]] = True
    te[perm[n_tr + n_va :]] = True
    return tr, va, te


def load_graph(name: str, root: str | None = None, **synth_kw):
    """Dispatch: the dataset's files under ``root`` if they exist, else
    a SMALL synthetic hierarchy that stands in for tests and demos —
    2,048 nodes for ``cora``, 16,384 for the OGB datasets
    (:data:`OGB_SHAPES`) — same feature and class counts as the named
    dataset, NOT its size (for a published OGB shape without a
    download, write :func:`ensure_ogb_scale_dataset` and pass its root).

    Returns (edges, x, labels, num_classes, source) where source is
    "disk" or "synthetic"; callers record it, with the node and edge
    counts, in the run manifest and result (cli/train.py).
    """
    info = {"dataset": name}
    with span("load_graph", info):
        out = _load_graph(name, root, synth_kw)
        info["source"] = out[-1]
    return out


def _load_graph(name: str, root, synth_kw: dict):
    if root is not None:
        if name == "cora" and os.path.exists(os.path.join(root, "cora.content")):
            return (*load_cora(root), "disk")
        if name in OGB_SHAPES and os.path.exists(
            os.path.join(root, "raw", "edge.csv")
        ):
            return (*load_ogb_csv(root), "disk")
    defaults = {"cora": dict(num_nodes=2048, feat_dim=64, num_classes=7),
                **{k: dict(num_nodes=16384, feat_dim=v["feat_dim"],
                           num_classes=v["num_classes"])
                   for k, v in OGB_SHAPES.items()}}
    kw = {**defaults.get(name, {}), **synth_kw}
    return (*synthetic_hierarchy(**kw), "synthetic")


# --- locality reordering ------------------------------------------------------


def locality_order(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """BFS relabeling that clusters neighborhoods into contiguous id
    ranges.

    Returns ``order`` with ``order[rank] = old_id``: BFS from the
    highest-degree node of each component (high-degree seeds keep hub
    neighborhoods contiguous).  Real citation graphs arrive with
    essentially random ids; after this relabeling their community
    structure becomes (receiver-block × sender-block) locality, which is
    what the cluster-pair SpMM kernel (kernels/cluster.py) converts into
    VMEM-tile reuse.  The relabeling is a graph isomorphism — quality
    metrics are unaffected, only the memory layout changes.

    Dispatches to the native C++ BFS (``data/_native/localorder.cc``,
    47× at arxiv scale: 1.14 s → 24 ms) when the toolchain is
    available; the pure-Python deque walk below is the fallback and the
    parity oracle.
    """
    e = np.asarray(edges)
    # validate HERE so native and fallback paths fail identically (the
    # C++ walk would OOB-write silently; the python walk would wrap
    # negative ids)
    _check_edge_range(e, num_nodes)
    try:
        from hyperspace_tpu.data import native

        return native.locality_order(np.asarray(e, np.int32), num_nodes)
    except ImportError:  # no C++ compiler installed (data/native.py)
        return _locality_order_python(e, num_nodes)


def _locality_order_python(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """Pure-Python BFS fallback and parity oracle for locality_order."""
    from collections import deque

    e = np.asarray(edges, np.int64)
    e = np.concatenate([e, e[:, ::-1]], axis=0)
    e = e[np.argsort(e[:, 0], kind="stable")]
    indptr = np.searchsorted(e[:, 0], np.arange(num_nodes + 1))
    nbr = e[:, 1]
    deg = np.diff(indptr)
    seeds = np.argsort(-deg, kind="stable")
    visited = np.zeros(num_nodes, bool)
    out = np.empty(num_nodes, np.int64)
    pos = 0
    si = 0
    q = deque()
    while pos < num_nodes:
        while si < num_nodes and visited[seeds[si]]:
            si += 1
        root = seeds[si]
        visited[root] = True
        q.append(root)
        while q:
            u = q.popleft()
            out[pos] = u
            pos += 1
            for v in nbr[indptr[u] : indptr[u + 1]]:
                if not visited[v]:
                    visited[v] = True
                    q.append(v)
    return out


def _lpa_sweeps(snd: np.ndarray, rcv: np.ndarray, num_nodes: int,
                sweeps: int, rng) -> np.ndarray:
    """Semi-asynchronous label propagation over a symmetric edge list.

    Each sweep computes every node's majority neighbor label (ties break
    to the smaller label) but applies it to a random HALF of the nodes —
    synchronous LPA on community graphs oscillates on near-bipartite
    motifs and strands ~40% of nodes as singletons (measured); the half
    update converges instead.  Vectorized: two lexsorts + run-length
    counts per sweep, O(E log E).
    """
    lab = np.arange(num_nodes, dtype=np.int64)
    for _ in range(sweeps):
        nl = lab[snd]
        o = np.lexsort((nl, rcv))
        r_s, l_s = rcv[o], nl[o]
        new_pair = np.r_[True, (r_s[1:] != r_s[:-1]) | (l_s[1:] != l_s[:-1])]
        starts = np.flatnonzero(new_pair)
        counts = np.diff(np.r_[starts, len(r_s)])
        pr, pl = r_s[starts], l_s[starts]
        ordp = np.lexsort((-counts, pr))
        firsts = np.flatnonzero(np.r_[True, pr[ordp][1:] != pr[ordp][:-1]])
        upd_r, upd_l = pr[ordp][firsts], pl[ordp][firsts]
        m = rng.random(len(upd_r)) < 0.5
        lab2 = lab.copy()
        lab2[upd_r[m]] = upd_l[m]
        lab = lab2
    return lab


def community_order(edges: np.ndarray, num_nodes: int,
                    sweeps: int = 16, split_rounds: int = 2,
                    split_above: int = 1024, seed: int = 0) -> np.ndarray:
    """Community-clustered relabeling: LPA groups + BFS-rank interleave.

    :func:`locality_order`'s plain BFS mixes communities at every
    frontier expansion — on a community-structured power-law graph at
    arxiv scale it recovers only ~21% block-clusterable edges where the
    planted-partition oracle reaches ~41%.  This ordering first detects
    communities with semi-async label propagation (giant labels get
    re-clustered on their internal subgraph), then orders nodes by
    (community's first BFS rank, BFS rank): communities become
    contiguous id ranges, adjacent communities stay near each other, and
    within a community the BFS rank preserves neighborhood locality —
    measured ~31% clusterable on the same graph (docs/benchmarks.md
    r04).  Pure host-side numpy, ~20 s at arxiv scale (one-time prep,
    amortized over the whole training run).  Like the BFS order this is
    a graph isomorphism: only the memory layout changes.
    """
    e = np.asarray(edges, np.int64)
    _check_edge_range(e, num_nodes)
    rng = np.random.default_rng(seed)
    sym = np.concatenate([e, e[:, ::-1]], axis=0)
    snd, rcv = sym[:, 0], sym[:, 1]
    lab = _lpa_sweeps(snd, rcv, num_nodes, sweeps, rng)
    for _ in range(split_rounds):
        szmap = np.bincount(lab)
        big = szmap[lab] > split_above
        keep = big[snd] & big[rcv] & (lab[snd] == lab[rcv])
        if not keep.sum():
            break
        sub = _lpa_sweeps(snd[keep], rcv[keep], num_nodes, max(sweeps - 6, 4),
                          rng)
        lab = np.where(big, lab.max() + 1 + sub, lab)
    bfs = locality_order(e, num_nodes)
    rank = np.empty(num_nodes, np.int64)
    rank[bfs] = np.arange(num_nodes)
    minr = np.full(int(lab.max()) + 1, num_nodes, np.int64)
    np.minimum.at(minr, lab, rank)
    return np.lexsort((rank, minr[lab]))


def apply_locality_order(edges: np.ndarray, x: np.ndarray,
                         labels: Optional[np.ndarray] = None,
                         method: str = "bfs", cache: Any = "auto"):
    """Relabel a loaded graph with :func:`locality_order` (``method=
    "bfs"``) or :func:`community_order` (``method="community"`` — better
    block density on community-structured graphs, costlier host prep).

    Returns (edges, x, labels, order) with node ``order[rank]`` renamed
    to ``rank``; pass the result straight to :func:`prepare` /
    :func:`split_edges`.  The order array is deterministic in (edges, n,
    method), so it caches persistently (``cache`` — see :func:`prepare`;
    the community order is ~20 s of host work at arxiv scale).
    """
    n = x.shape[0]
    if method not in ("community", "bfs"):
        raise ValueError(f"unknown reorder method {method!r}")
    from hyperspace_tpu.data import prep_cache

    e_arr = np.asarray(edges, np.int64)
    pc = prep_cache.resolve(
        cache, auto_ok=len(e_arr) >= _CACHE_AUTO_MIN_EDGES)
    build = lambda: (community_order(e_arr, n) if method == "community"
                     else locality_order(e_arr, n))
    if pc is not None:
        order = pc.get_or_build("local-order", (e_arr, n, method), build)
    else:
        order = build()
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    new_edges = rank[np.asarray(edges, np.int64)]
    new_x = np.asarray(x)[order]
    new_labels = None if labels is None else np.asarray(labels)[order]
    return new_edges, new_x, new_labels, order
