"""HGCN — hyperbolic graph convolutional network (reference workload 2).

BASELINE.json configs[1]: HGCN on Cora / ogbn-arxiv, **Lorentz model**; the
north-star metric is samples/sec/chip and matching test ROC-AUC
(SURVEY.md §0, §3.2, §6).

Model shape (Chami et al. NeurIPS 2019):

    features --exp0--> manifold --[HGCConv × L]--> embeddings z
    LP head: FermiDirac(d²(z_u, z_v)) → BCE → ROC-AUC
    NC head: hyperbolic MLR → CE → accuracy/F1

The whole step — forward over the full padded graph, loss, grad, Adam
update — is one jitted XLA program.  Full-graph training is the natural
TPU formulation for graphs of Cora/arxiv scale: the [N, d] node tensor and
the padded edge list are static shapes resident in HBM, and every layer is
one MXU matmul plus masked segment ops (SURVEY.md §7 hard-part #3).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from hyperspace_tpu.telemetry.trace import importing, span

with importing("flax"):
    import flax.linen as nn
with importing("optax"):
    import optax

from hyperspace_tpu import precision as precision_lib
from hyperspace_tpu.data import graphs as graph_data
from hyperspace_tpu.nn.decoders import FermiDiracDecoder
from hyperspace_tpu.nn.gcn import HGCConv, from_tangent0_coords, make_manifold
from hyperspace_tpu.nn.mlr import LorentzMLR, HypMLR
from hyperspace_tpu.utils import metrics as metrics_lib


@dataclasses.dataclass(frozen=True)
class HGCNConfig:
    feat_dim: int = 32
    hidden_dims: Sequence[int] = (64, 16)
    kind: str = "lorentz"  # BASELINE.json: Lorentz model for workload 2
    c: float = 1.0
    learn_c: bool = False
    use_att: bool = False
    dropout: float = 0.0
    num_classes: int = 0  # NC head only when > 0
    lr: float = 1e-2
    weight_decay: float = 5e-4
    # >0: clip the global gradient norm before adamw.  The attention
    # arm's measured failure mode (docs/benchmarks.md convergence §2) is
    # a collapse to the degenerate logits-0 solution driven by early
    # gradient spikes; clipping at ~1.0 removes the cliff (regression-
    # tested in tests/models/test_stability.py).  0 disables.
    clip_norm: float = 0.0
    neg_per_pos: int = 1  # LP negatives sampled per positive per step
    dtype: Any = jnp.float32
    # edge-message dtype for neighbor aggregation (None = dtype); bf16
    # halves the dominant HBM traffic while the kernel accumulates f32
    agg_dtype: Any = None
    # dtype of the LP decoder's pair-distance pass during TRAINING
    # (None = dtype); eval always scores in full precision.  bf16 halves
    # the bytes of the 1.9 M-pair gathers, elementwise passes and
    # cotangent rows.
    decoder_dtype: Any = None
    # rematerialize each conv layer in the backward pass (jax.checkpoint):
    # trades an extra forward per layer for not storing its residuals.
    # Measured at arxiv-like shapes the peak temp is a single pass's
    # [E, F] working set, not the residuals, so this only pays off for
    # DEEP stacks (many layers) or very wide features; off by default.
    remat: bool = False
    # mixed-precision policy (hyperspace_tpu/precision.py): "bf16" maps
    # onto this model's quality-validated bf16 lanes — agg_dtype (edge
    # messages) and decoder_dtype (training pair-distance pass) — while
    # the encoder compute, every manifold op and all reductions stay
    # f32 (the docs/benchmarks.md quality-anchor config).  Explicit
    # agg_dtype/decoder_dtype always win over the policy mapping.
    precision: str = "f32"

    def resolved_agg_dtype(self):
        """agg_dtype as executed: the explicit field, else the policy's
        compute dtype when mixed, else None (= dtype)."""
        pol = precision_lib.get_policy(self.precision)
        if self.agg_dtype is not None:
            return self.agg_dtype
        return pol.compute if pol.mixed else None

    def resolved_decoder_dtype(self):
        """decoder_dtype as executed (same resolution rule)."""
        pol = precision_lib.get_policy(self.precision)
        if self.decoder_dtype is not None:
            return self.decoder_dtype
        return pol.compute if pol.mixed else None


class HGCNEncoder(nn.Module):
    """Feature lift (exp0) + stacked HGCConv layers over a DeviceGraph."""

    cfg: HGCNConfig

    @nn.compact
    def __call__(self, g: graph_data.DeviceGraph, *, deterministic=True):
        cfg = self.cfg
        m0 = make_manifold(cfg.kind, cfg.c)
        # Euclidean features are origin-tangent coordinates; lift to the
        # manifold (SURVEY.md §3.2 "embed: expmap₀(features)").
        h = from_tangent0_coords(m0, g.x.astype(cfg.dtype))
        c_prev = cfg.c
        for i, d in enumerate(cfg.hidden_dims):
            is_last = i == len(cfg.hidden_dims) - 1
            conv = HGCConv(
                features=d,
                kind=cfg.kind,
                c_in=c_prev,
                c_out=cfg.c,
                learn_c=cfg.learn_c,
                use_att=cfg.use_att,
                dropout_rate=cfg.dropout,
                activation=(lambda v: v) if is_last else nn.relu,
                agg_dtype=cfg.resolved_agg_dtype(),
                name=f"conv{i}",
            )
            if cfg.remat:
                # re-run the layer's forward during the backward instead
                # of keeping its [N, F] / [E, F] intermediates live — the
                # HBM lever for beyond-arxiv graphs.  Static curvature
                # only: the remat'd callable must return arrays, so the
                # output manifold is reconstructed outside.
                if cfg.learn_c:
                    raise ValueError("remat=True requires learn_c=False "
                                     "(the remat boundary returns arrays)")

                def run_conv(mdl, hh):
                    out, _ = mdl(hh, g, deterministic=deterministic)
                    return out

                h = nn.remat(run_conv)(conv, h)
                m = make_manifold(cfg.kind, cfg.c)
            else:
                h, m = conv(h, g, deterministic=deterministic)
            c_prev = m.c
        return _graph_order(h, g), m  # points on the final layer's manifold


class HGCNLinkPred(nn.Module):
    """Encoder + Fermi–Dirac decoder; returns edge logits."""

    cfg: HGCNConfig

    @nn.compact
    def __call__(self, g: graph_data.DeviceGraph, pairs, *, deterministic=True,
                 sorted_vjp=False):
        z, m = HGCNEncoder(self.cfg, name="encoder")(
            g, deterministic=deterministic
        )
        ddt = self.cfg.resolved_decoder_dtype()
        if ddt is not None and not deterministic:
            z = z.astype(ddt)  # train only; eval full-prec
        with jax.named_scope("pair_dist"):
            u, v = pairs[:, 0], pairs[:, 1]
            if sorted_vjp:
                # the same forward; the backward sums the cotangent rows
                # with one block-CSR kernel call in place of XLA's two
                # scatter-adds.  A Pallas call, so only where no mesh
                # partitions the step (_lp_step_impl)
                from hyperspace_tpu.nn.edge_dist import pair_sqdist

                sq = pair_sqdist(z, m.c, u, v, self.cfg.kind)
            else:
                sq = m.sqdist(z[u], z[v])
        return FermiDiracDecoder(name="decoder")(sq.astype(self.cfg.dtype))

    @nn.compact
    def split_pair_logits(self, g: graph_data.DeviceGraph, pos, neg, *,
                          deterministic=True):
        """``(pos_logits, neg_logits)`` with ONE encoder pass and NO
        concatenation of the two pair batches — the form the sharded LP
        step takes on a multi-axis mesh (see ``_lp_step_impl``): it
        gathers the two batches separately and combines scalars
        only."""
        z, m = HGCNEncoder(self.cfg, name="encoder")(
            g, deterministic=deterministic
        )
        ddt = self.cfg.resolved_decoder_dtype()
        if ddt is not None and not deterministic:
            z = z.astype(ddt)  # train only; eval full-prec
        dec = FermiDiracDecoder(name="decoder")
        with jax.named_scope("pair_dist"):
            sq_p = m.sqdist(z[pos[:, 0]], z[pos[:, 1]])
            sq_n = m.sqdist(z[neg[:, 0]], z[neg[:, 1]])
        return (dec(sq_p.astype(self.cfg.dtype)),
                dec(sq_n.astype(self.cfg.dtype)))


class HGCNNodeClf(nn.Module):
    """Encoder + hyperbolic MLR head; returns per-node class logits."""

    cfg: HGCNConfig

    @nn.compact
    def __call__(self, g: graph_data.DeviceGraph, *, deterministic=True):
        z, m = HGCNEncoder(self.cfg, name="encoder")(
            g, deterministic=deterministic
        )
        if self.cfg.kind == "euclidean":  # flat control: plain linear head
            return nn.Dense(self.cfg.num_classes, name="head")(z)
        head = LorentzMLR if self.cfg.kind == "lorentz" else HypMLR
        return head(self.cfg.num_classes, m, name="head")(z)


# --- training ----------------------------------------------------------------


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    key: jax.Array
    step: jax.Array


def make_optimizer(cfg: HGCNConfig) -> optax.GradientTransformation:
    # the clip stage is always present (inf = no-op) so the opt_state
    # pytree structure is identical across clip_norm settings — a
    # checkpoint written with clipping on restores with it off and
    # vice versa (orbax restore is structure-strict)
    max_norm = cfg.clip_norm if cfg.clip_norm > 0.0 else float("inf")
    return optax.chain(optax.clip_by_global_norm(max_norm),
                       optax.adamw(cfg.lr, weight_decay=cfg.weight_decay))


def _device_graph(g: graph_data.Graph) -> graph_data.DeviceGraph:
    return graph_data.to_device(g)


# ---- link prediction ----


def _param_count(params) -> int:
    return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))


def init_lp(cfg: HGCNConfig, g: graph_data.Graph, seed: int = 0):
    info = {"model": "hgcn_lp"}
    with span("init", info):
        model = HGCNLinkPred(cfg)
        key = jax.random.PRNGKey(seed)
        k_init, key = jax.random.split(key)
        # flax draws a parameter from its path and its shape, and the only
        # shape the graph gives is the feature width: initialise over a
        # two-node graph of that width.  The eager forward of ``model.init``
        # over ``g`` itself would put the whole graph on one device, which
        # a graph made for a mesh does not fit
        dg = _device_graph(graph_data.prepare(
            np.array([[0, 1]]), 2, np.zeros((2, g.x.shape[1]), np.float32),
            cache=False))
        dummy_pairs = jnp.zeros((2, 2), jnp.int32)
        params = model.init({"params": k_init}, dg, dummy_pairs)["params"]
        opt = make_optimizer(cfg)
        state = TrainState(params, opt.init(params), key,
                           jnp.zeros((), jnp.int32))
        info["params"] = _param_count(params)
    return model, opt, state


def _bce_pos_neg(pos_logit, neg_logit, w_pos=None):
    """Mean BCE of positives (label 1, optionally weighted) and negatives
    (label 0) from sums alone — no concatenate of the two batches."""
    with jax.named_scope("loss"):
        bce_pos = optax.sigmoid_binary_cross_entropy(
            pos_logit, jnp.ones_like(pos_logit))
        bce_neg = optax.sigmoid_binary_cross_entropy(
            neg_logit, jnp.zeros_like(neg_logit))
        if w_pos is None:
            n_pos = pos_logit.shape[0]
        else:
            bce_pos, n_pos = bce_pos * w_pos, jnp.sum(w_pos)
        return ((jnp.sum(bce_pos) + jnp.sum(bce_neg))
                / (n_pos + neg_logit.shape[0]))


def _apply_grads(opt, state: TrainState, grads, key) -> TrainState:
    """Clip + AdamW + apply: the LP steps' shared tail."""
    with jax.named_scope("optimizer"):
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
    return TrainState(params, opt_state, key, state.step + 1)


def _lp_step_impl(model, opt, num_nodes, state, g, train_pos, constrain=None,
                  split_pairs=False):
    """Shared LP step body: sample negatives on device, BCE on pos+neg
    logits.  ``constrain`` (optional) pins the supervision batch's sharding
    (GSPMD hint).  Without one no mesh partitions the step, and the
    decoder's pair distances take the VJP that sums their cotangent rows
    with a Pallas kernel (`nn.edge_dist.pair_sqdist`); under a mesh Mosaic
    kernels cannot be partitioned and the pair batch is sharded while
    ``dz`` is not, so the mesh steps keep XLA's scatter-add."""
    n_neg = train_pos.shape[0] * model.cfg.neg_per_pos
    with jax.named_scope("negatives"):
        key, k_neg, k_drop = jax.random.split(state.key, 3)
        neg = jax.random.randint(k_neg, (n_neg, 2), 0, num_nodes)

    def loss_fn(params):
        if constrain is not None and split_pairs:
            # multi-axis-mesh form: NO concatenate anywhere near the
            # constrained batch.  An earlier jax's GSPMD miscompiled
            # `concatenate` when any operand — or any downstream
            # consumer, via backward sharding propagation — carried a
            # with_sharding_constraint over a proper subset of a
            # multi-axis mesh's axes (P(("data",), None) on a dp×tp
            # mesh), so under such a mesh the step gathers pos and neg
            # separately (one encoder pass, no pair concat) and
            # combines scalar sums.  The installed jax (0.9.0) compiles
            # the reduced repro correctly (tests/parallel/
            # test_node_sharded.py::
            # test_gspmd_concat_under_subset_constraint); this branch
            # stays until a benchmark cell has compared the two forms
            # on the chip.  Single-axis (dp-only) meshes keep the
            # historical form below, unchanged.
            pos_logit, neg_logit = model.apply(
                {"params": params}, g,
                constrain(train_pos), constrain(neg),
                deterministic=False, rngs={"dropout": k_drop},
                method=HGCNLinkPred.split_pair_logits,
            )
            return _bce_pos_neg(pos_logit, neg_logit)
        tp, ng = train_pos, neg
        if constrain is not None:
            tp, ng = constrain(tp), constrain(ng)
        pairs = jnp.concatenate([tp, ng], axis=0)
        logits = model.apply(
            {"params": params}, g, pairs,
            deterministic=False, rngs={"dropout": k_drop},
            sorted_vjp=constrain is None,
        )
        with jax.named_scope("loss"):
            labels = jnp.concatenate(
                [jnp.ones(train_pos.shape[0]), jnp.zeros(n_neg)]
            ).astype(logits.dtype)
            return jnp.mean(
                optax.sigmoid_binary_cross_entropy(logits, labels))

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return _apply_grads(opt, state, grads, key), loss


@partial(jax.jit, static_argnames=("model", "opt", "num_nodes"), donate_argnames=("state",))
def train_step_lp(
    model: HGCNLinkPred,
    opt,
    num_nodes: int,
    state: TrainState,
    g: graph_data.DeviceGraph,
    train_pos: jax.Array,  # [P, 2]
):
    """One LP step: sample negatives on device, BCE on pos+neg logits."""
    return _lp_step_impl(model, opt, num_nodes, state, g, train_pos)


def _concat_hazard(mesh) -> bool:
    """True when ``mesh`` has a non-trivial axis outside the
    batch-sharding ("host"/"data") set — the mesh shape under which an
    earlier jax's GSPMD miscompiled a constrained ``concatenate``
    (``_lp_step_impl``'s split_pairs rationale; fixed in the installed
    jax, the split form stays until measured).  The hazard needed a
    constraint over a proper subset of the axes; the node-sharded step
    constrains its pairs over all of them, so there it no longer
    applies."""
    return any(int(mesh.shape[a]) > 1 for a in mesh.axis_names
               if a not in ("host", "data"))


def round_up_pairs(pairs: np.ndarray, mesh) -> np.ndarray:
    """Resize a [P, 2] supervision batch to a multiple of the mesh's
    data-axis extent (GSPMD needs the sharded axis divisible).  Repeats
    the leading edges cyclically — a negligible reweighting of a batch
    that already covers every positive edge each step."""
    from hyperspace_tpu.parallel.mesh import data_extent

    d = data_extent(mesh)
    n = -(-pairs.shape[0] // d) * d
    return np.resize(np.asarray(pairs), (n, 2))


def make_node_sharded_step_lp(
    model: HGCNLinkPred,
    opt,
    num_nodes: int,
    mesh,
    state: TrainState,
    split: graph_data.LinkSplit,
    halo="auto",  # forwarded to partition_graph ("a2a"/"ppermute" force
    # that exchange schedule, False forces the all-gather, "auto" picks
    # by estimated compiled bytes — parallel/node_shard.py doc)
):
    """LP train step whose encoder work divides across ``mesh``.

    The graph is node-sharded over every axis of the mesh, one shard a
    device (`parallel/node_shard`): the [N, F] activations, every matmul
    row, and each shard's slice of the edge aggregation live on one
    device; the only collective in the encoder is an [N, F] exchange per
    layer per direction riding ICI.  Per-device FLOPs and HBM bytes
    scale ~1/ndev (asserted by tests/parallel/test_node_sharded.py's
    compiled-cost check).  The supervision batch (positives + sampled
    negatives) arrives sharded over the batch axes (``host``/``data``)
    and is resharded inside the step over the same axes as the nodes,
    so XLA inserts the gradient all-reduce over every device.
    Parameters and optimizer moments are replicated: HGCN's widest
    kernel is one MXU tile, and splitting it over a ``model`` axis
    would only repeat the graph work on that axis's devices.

    Mean aggregation uses the involution backward (no cross-shard
    scatter); attention works too — the receiver partition keeps its
    segment softmax shard-local (`parallel.node_shard.
    node_sharded_att_aggregate`, autodiff collectives).  Returns
    ``(step, placed_state, placed_graph)``; call as
    ``state, loss = step(state, nsg, train_pos)``; ``state`` is donated.
    """
    from hyperspace_tpu.parallel.mesh import batch_sharding, replicated
    from hyperspace_tpu.parallel.node_shard import graph_shardings, shard_graph
    from hyperspace_tpu.parallel.tp import replicated_like

    nsg = shard_graph(split.graph, mesh, halo=halo)
    state_sh = replicated_like(state, mesh)
    bsh = batch_sharding(mesh, ndim=2)
    # pairs over every device, as the nodes: a row count that is a
    # multiple of the batch axes but not of the device count shards
    # unevenly, which the constraint allows
    psh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(nsg.axes, None))
    constrain = lambda x: jax.lax.with_sharding_constraint(x, psh)

    step = jax.jit(
        partial(_lp_step_impl, model, opt, num_nodes, constrain=constrain,
                split_pairs=_concat_hazard(mesh)),
        # pairs arrive BATCH-SHARDED (not replicated): the multi-process
        # data plane feeds a global array each host assembled from only
        # its own row range (multihost.distribute_batch); uncommitted
        # single-process arrays get placed the same way
        in_shardings=(state_sh, graph_shardings(nsg), bsh),
        out_shardings=(state_sh, replicated(mesh)),
        donate_argnums=(0,),
    )
    return step, jax.device_put(state, state_sh), nsg


def make_node_sharded_step_nc(
    model: HGCNNodeClf,
    opt,
    mesh,
    state: TrainState,
    g: graph_data.Graph,
    halo="auto",
):
    """NC twin of `make_node_sharded_step_lp`: node-sharded encoder over
    every axis of the mesh, with labels/train-mask padded to the sharded
    node count, the per-node cross-entropy terms sharded over the same
    axes and the state replicated.  Returns
    ``(step, placed_state, placed_graph, labels, train_mask)``.
    """
    from hyperspace_tpu.parallel.mesh import replicated
    from hyperspace_tpu.parallel.node_shard import (
        graph_shardings,
        pad_node_array,
        shard_graph,
    )
    from hyperspace_tpu.parallel.tp import replicated_like

    nsg = shard_graph(g, mesh, halo=halo)
    n_pad = nsg.x.shape[0]
    labels = jnp.asarray(pad_node_array(g.labels, n_pad, 0))
    train_mask = jnp.asarray(pad_node_array(g.train_mask, n_pad, False))
    state_sh = replicated_like(state, mesh)
    nsh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(nsg.axes))
    constrain = lambda x: jax.lax.with_sharding_constraint(x, nsh)

    step = jax.jit(
        partial(_nc_step_impl, model, opt, constrain=constrain),
        in_shardings=(state_sh, graph_shardings(nsg),
                      replicated(mesh), replicated(mesh)),
        out_shardings=(state_sh, replicated(mesh)),
        donate_argnums=(0,),
    )
    return step, jax.device_put(state, state_sh), nsg, labels, train_mask


@partial(jax.jit, static_argnames=("model",))
def eval_scores_lp(model: HGCNLinkPred, params, g: graph_data.DeviceGraph, pairs):
    return model.apply({"params": params}, g, pairs)


def evaluate_lp(model, params, split: graph_data.LinkSplit, which: str = "test",
                ga: graph_data.DeviceGraph | None = None) -> dict:
    """LP ROC-AUC; pass ``ga`` to reuse an already-transferred DeviceGraph."""
    ga = _device_graph(split.graph) if ga is None else ga
    pos = jnp.asarray(getattr(split, f"{which}_pos"))
    neg = jnp.asarray(getattr(split, f"{which}_neg"))
    s_pos = np.asarray(eval_scores_lp(model, params, ga, pos))
    s_neg = np.asarray(eval_scores_lp(model, params, ga, neg))
    return {"roc_auc": metrics_lib.roc_auc(s_pos, s_neg)}


def train_lp(
    cfg: HGCNConfig,
    split: graph_data.LinkSplit,
    steps: int = 200,
    seed: int = 0,
    log_every: int = 0,
) -> tuple[Any, Any, list]:
    """Full LP training loop; returns (model, params, history)."""
    model, opt, state = init_lp(cfg, split.graph, seed)
    ga = _device_graph(split.graph)
    train_pos = jnp.asarray(split.train_pos)
    history = []
    for i in range(steps):
        state, loss = train_step_lp(model, opt, split.graph.num_nodes, state, ga, train_pos)
        if log_every and (i + 1) % log_every == 0:
            ev = evaluate_lp(model, state.params, split, "val", ga=ga)
            history.append({"step": i + 1, "loss": float(loss), **ev})
    return model, state.params, history


# ---- node classification ----


def init_nc(cfg: HGCNConfig, g: graph_data.Graph, seed: int = 0):
    info = {"model": "hgcn_nc"}
    with span("init", info):
        model = HGCNNodeClf(cfg)
        key = jax.random.PRNGKey(seed)
        k_init, key = jax.random.split(key)
        dg = _device_graph(g)
        params = model.init({"params": k_init}, dg)["params"]
        opt = make_optimizer(cfg)
        state = TrainState(params, opt.init(params), key,
                           jnp.zeros((), jnp.int32))
        info["params"] = _param_count(params)
    return model, opt, state


def _nc_step_impl(model, opt, state, g, labels, train_mask, constrain=None):
    """Shared NC step body; ``constrain`` optionally pins the per-node
    loss terms' sharding (data-parallel over the node axis)."""
    key, k_drop = jax.random.split(state.key)

    def loss_fn(params):
        logits = model.apply(
            {"params": params}, g,
            deterministic=False, rngs={"dropout": k_drop},
        )
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        if constrain is not None:
            ce = constrain(ce)
        w = train_mask.astype(ce.dtype)
        return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return _apply_grads(opt, state, grads, key), loss


@partial(jax.jit, static_argnames=("model", "opt"), donate_argnames=("state",))
def train_step_nc(
    model: HGCNNodeClf,
    opt,
    state: TrainState,
    g: graph_data.DeviceGraph,
    labels: jax.Array,  # [N] int32
    train_mask: jax.Array,  # [N] bool
):
    return _nc_step_impl(model, opt, state, g, labels, train_mask)


@partial(jax.jit, static_argnames=("model",))
def eval_logits_nc(model: HGCNNodeClf, params, g: graph_data.DeviceGraph):
    return model.apply({"params": params}, g)


def evaluate_nc(model: HGCNNodeClf, params, g: graph_data.Graph,
                ga: graph_data.DeviceGraph | None = None) -> dict:
    """NC metrics; pass ``ga`` to reuse an already-transferred DeviceGraph
    (the [N, F] feature tensor is ~90 MB at arxiv scale)."""
    logits = np.asarray(eval_logits_nc(
        model, params, _device_graph(g) if ga is None else ga))
    return {
        "val_acc": metrics_lib.accuracy(logits, g.labels, g.val_mask),
        "test_acc": metrics_lib.accuracy(logits, g.labels, g.test_mask),
        "test_f1": metrics_lib.f1_macro(
            logits, g.labels, model.cfg.num_classes, g.test_mask),
    }


def train_nc(
    cfg: HGCNConfig,
    g: graph_data.Graph,
    steps: int = 200,
    seed: int = 0,
) -> tuple[Any, Any, dict]:
    model, opt, state = init_nc(cfg, g, seed)
    ga = _device_graph(g)
    labels = jnp.asarray(g.labels)
    tr = jnp.asarray(g.train_mask)
    for _ in range(steps):
        state, loss = train_step_nc(model, opt, state, ga, labels, tr)
    res = {"loss": float(loss), **evaluate_nc(model, state.params, g, ga=ga)}
    return model, state.params, res


def _graph_order(z, g):
    """The encoder's rows in graph order, where the pairs, the sampled
    negatives and the labels name nodes.  Only a node-sharded graph whose
    shards were dealt node blocks holds them otherwise
    (``parallel/node_shard.py``); decided at trace time."""
    if not getattr(g, "block_interleave", 0):
        return z
    from hyperspace_tpu.parallel.node_shard import graph_order

    return graph_order(z, g)
