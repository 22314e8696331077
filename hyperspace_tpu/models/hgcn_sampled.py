"""Neighbor-sampled minibatch HGCN training (GraphSAGE-style fanouts).

Full-graph HGCN training (models/hgcn.py) holds every [N, F] layer
activation per step — the right trade at ogbn-arxiv scale, but the
per-step footprint grows with the graph, and its "samples/s" counts
every node each step.  This module is the complementary training mode
the reference family ships alongside full-graph trainers [INFERRED —
SURVEY.md §1a "models" layer]: fixed-fanout neighbor sampling with
**static block shapes**, where one step supervises exactly
``batch_size`` labeled seed nodes.

TPU-first design (what makes this NOT a translation of a CPU sampler
loop):

- **No scatter, no segment ops, no edge lists on device.**  A batch is a
  pyramid of dense index blocks — seeds ``[B]``, their sampled neighbors
  ``[B, f1]``, the neighbors' neighbors ``[B, f1, f2]`` — so every
  aggregation is a plain ``mean`` over a trailing axis of an MXU-shaped
  tensor.  The irregular work (adjacency walk, uniform draws) happens in
  the native C++ sampler (`data/_native/sampler.cc`) on the host, where
  it belongs.
- **Unbiased estimator of the full-graph operator.**  The full-graph
  layer aggregates with self-loop-inclusive mean weights
  ``(h_self + Σ_nbrs h) / (1 + n_nbrs)``; the sampled layer computes
  ``(h_self + (n_nbrs / f) · Σ_{f samples} h) / (1 + n_nbrs)`` whose
  expectation over the sampler's uniform draws is exactly the full sum.
  Nodes whose degree ≤ the fanout are reconstructed near-exactly;
  isolated nodes reduce to ``h_self``.
- **Parameter-tree compatibility.**  Layer/param names mirror
  ``HGCNEncoder``/``HGCNNodeClf`` (``encoder/conv{i}/kernel`` …,
  ``head``), so parameters trained with sampled minibatches evaluate
  with the exact full-graph model (`hgcn.evaluate_nc`) — tested in
  tests/models/test_hgcn_sampled.py.

Mean aggregation only: attention weights over a sampled multiset would
estimate a different (renormalized) operator than the full-graph
segment softmax, so ``use_att=True`` is rejected rather than silently
diverging.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from hyperspace_tpu.models import hgcn
from hyperspace_tpu.nn.gcn import (
    from_tangent0_coords,
    make_manifold,
    tangent0_coords,
)
from hyperspace_tpu.nn.mlr import HypMLR, LorentzMLR


@dataclasses.dataclass(frozen=True)
class SampledConfig:
    base: hgcn.HGCNConfig
    # fanouts[l] = neighbors sampled per node at pyramid level l; length
    # must equal len(base.hidden_dims) (one sampling level per conv)
    fanouts: Sequence[int] = (10, 10)
    batch_size: int = 512

    def __post_init__(self):
        if len(self.fanouts) != len(self.base.hidden_dims):
            raise ValueError(
                f"need one fanout per conv layer: {self.fanouts} vs "
                f"hidden_dims {self.base.hidden_dims}")
        if self.base.use_att:
            raise ValueError(
                "sampled HGCN is mean-aggregation only (a sampled softmax "
                "estimates a different operator than the full-graph one)")


class SampledHGCConv(nn.Module):
    """One conv layer on a dense (self, sampled-neighbors) block.

    Same math as ``nn.gcn.HGCConv`` — tangent-0 matmul, mean
    aggregation, activation, expmap at the (optionally learned) output
    curvature — with identical param names/shapes, so trees transfer."""

    features: int
    kind: str = "lorentz"
    c_in: float = 1.0
    c_out: float = 1.0
    learn_c: bool = False
    use_bias: bool = True
    activation: Any = nn.relu
    dropout_rate: float = 0.0
    kernel_init: Any = nn.initializers.glorot_uniform()

    @nn.compact
    def __call__(self, x_self, x_nbr, n_nbrs, *, deterministic=True):
        # x_self [..., amb]; x_nbr [..., f, amb]; n_nbrs [...] true degree
        m_in = make_manifold(self.kind, self.c_in)
        if self.learn_c:
            init = float(np.log(np.expm1(self.c_out)))
            c_raw = self.param("c_raw", nn.initializers.constant(init), ())
            c_out = nn.softplus(c_raw)
        else:
            c_out = self.c_out
        m_out = make_manifold(self.kind, c_out)

        v_self = tangent0_coords(m_in, x_self)
        v_nbr = tangent0_coords(m_in, x_nbr)
        kernel = self.param("kernel", self.kernel_init,
                            (v_self.shape[-1], self.features), v_self.dtype)
        h_self = v_self @ kernel
        h_nbr = v_nbr @ kernel
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), v_self.dtype)
            h_self = h_self + bias
            h_nbr = h_nbr + bias
        if self.dropout_rate > 0.0:
            # h_self and h_nbr get INDEPENDENT masks, so a node that
            # appears both as itself and as a sampled neighbor (or is
            # drawn multiple times with replacement) sees different masks
            # than the full-graph layer's single per-node dropout: with
            # dropout>0 the sampled step is therefore not an unbiased
            # estimator of the full-graph training operator (standard
            # minibatch-GNN behavior; eval/deterministic paths agree).
            drop = nn.Dropout(self.dropout_rate)
            h_self = drop(h_self, deterministic=deterministic)
            h_nbr = drop(h_nbr, deterministic=deterministic)

        # E[agg] = the full-graph self-loop-inclusive mean (module doc)
        f = x_nbr.shape[-2]
        n = n_nbrs.astype(h_self.dtype)[..., None]
        agg = (h_self + (n / f) * jnp.sum(h_nbr, axis=-2)) / (1.0 + n)
        return from_tangent0_coords(m_out, self.activation(agg)), m_out


class SampledEncoder(nn.Module):
    """Feature lift + stacked SampledHGCConv over the index pyramid."""

    cfg: hgcn.HGCNConfig

    @nn.compact
    def __call__(self, levels, n_nbrs, *, deterministic=True):
        # levels[l]: [B, f1, .., fl, F0] raw features; n_nbrs[l] degrees
        cfg = self.cfg
        m0 = make_manifold(cfg.kind, cfg.c)
        pts = [from_tangent0_coords(m0, x.astype(cfg.dtype)) for x in levels]
        c_prev = cfg.c
        m = m0
        for i, d in enumerate(cfg.hidden_dims):
            is_last = i == len(cfg.hidden_dims) - 1
            conv = SampledHGCConv(
                features=d,
                kind=cfg.kind,
                c_in=c_prev,
                c_out=cfg.c,
                learn_c=cfg.learn_c,
                dropout_rate=cfg.dropout,
                activation=(lambda v: v) if is_last else nn.relu,
                name=f"conv{i}",
            )
            new_pts = []
            for l in range(len(pts) - 1):
                out, m = conv(pts[l], pts[l + 1], n_nbrs[l],
                              deterministic=deterministic)
                new_pts.append(out)
            pts = new_pts  # every call shares the layer's params, so the
            c_prev = m.c   # manifold from the last call is THE layer output
        return pts[0], m


class SampledHGCNNodeClf(nn.Module):
    """Sampled encoder + the same MLR head as ``HGCNNodeClf``."""

    cfg: hgcn.HGCNConfig

    @nn.compact
    def __call__(self, levels, n_nbrs, *, deterministic=True):
        z, m = SampledEncoder(self.cfg, name="encoder")(
            levels, n_nbrs, deterministic=deterministic)
        if self.cfg.kind == "euclidean":
            return nn.Dense(self.cfg.num_classes, name="head")(z)
        head = LorentzMLR if self.cfg.kind == "lorentz" else HypMLR
        return head(self.cfg.num_classes, m, name="head")(z)


class SampledHGCNLinkPred(nn.Module):
    """Sampled encoder + the same Fermi–Dirac decoder as ``HGCNLinkPred``.

    The seed vector is four aligned [P] chunks — (u_pos, v_pos, u_neg,
    v_neg) — so the pyramid encodes all endpoints in one pass; logits
    come from pairwise squared distances within chunks.  Param tree
    matches ``HGCNLinkPred`` (``encoder/...`` + ``decoder/{r, t_raw}``),
    so `hgcn.evaluate_lp` scores sampled-trained params directly."""

    cfg: hgcn.HGCNConfig

    @nn.compact
    def __call__(self, levels, n_nbrs, *, deterministic=True):
        from hyperspace_tpu.nn.decoders import FermiDiracDecoder

        z, m = SampledEncoder(self.cfg, name="encoder")(
            levels, n_nbrs, deterministic=deterministic)
        ddt = self.cfg.resolved_decoder_dtype()
        if ddt is not None and not deterministic:
            z = z.astype(ddt)
        p = z.shape[0] // 4
        sq_pos = m.sqdist(z[:p], z[p : 2 * p])
        sq_neg = m.sqdist(z[2 * p : 3 * p], z[3 * p :])
        dec = FermiDiracDecoder(name="decoder")
        return (dec(sq_pos.astype(self.cfg.dtype)),
                dec(sq_neg.astype(self.cfg.dtype)))


# --- host-side batch planning -------------------------------------------------


def build_adjacency(edges: np.ndarray, num_nodes: int):
    """Undirected CSR (indptr int64 [N+1], indices int32) for the sampler.

    Self-loops are NOT added — the sampled layer handles the self term
    explicitly (module doc), mirroring how ``data.graphs.prepare`` owns
    the self-loop for the full-graph path."""
    e = np.asarray(edges, np.int64)
    e = e[e[:, 0] != e[:, 1]] if len(e) else e.reshape(0, 2)
    both = np.concatenate([e, e[:, ::-1]]) if len(e) else e
    # dedupe like graphs.prepare does: duplicate rows or both orientations
    # in the input must not inflate degrees, or the sampled estimator
    # targets a different operator than the full-graph eval model
    key = both[:, 0] * num_nodes + both[:, 1] if len(both) else both[:, :0]
    s = both[np.unique(key, return_index=True)[1]] if len(both) else \
        np.zeros((0, 2), np.int64)
    indptr = np.searchsorted(s[:, 0], np.arange(num_nodes + 1)).astype(np.int64)
    return indptr, s[:, 1].astype(np.int32)


def _sample(indptr, indices, seeds, fanout, seed):
    try:
        from hyperspace_tpu.data import native

        return native.sample_neighbors(indptr, indices, seeds, fanout, seed)
    except ImportError:  # no C++ compiler installed (data/native.py)
        from hyperspace_tpu.data.native import sample_neighbors_numpy

        return sample_neighbors_numpy(indptr, indices, seeds, fanout, seed)


class SampledBatches(NamedTuple):
    """S planned minibatches, device-resident (one pyramid per step)."""

    ids: tuple      # level l: [S, B, f1, .., fl] int32
    # [S, B] int32 seed labels (NC); None for LP batches, where
    # positives/negatives are positional in the seed chunks
    labels: Any


def _mix64(x: int) -> int:
    """Host-side splitmix64 finalizer (one round) over a python int."""
    m = (1 << 64) - 1
    x = (x + 0x9E3779B97F4A7C15) & m
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & m
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & m
    return x ^ (x >> 31)


def _build_pyramid(cfg: SampledConfig, indptr, indices, seeds, seed: int):
    """Fanout levels over per-step seed rows ([S, B] → [S, B, f1], ...).

    The ONE sampler-driving loop both planners share (same per-level
    seed derivation — NC and LP pyramids must never diverge).  ONE
    native-sampler call per level over all steps' seeds flattened — the
    per-(step, level) python loop was the planner's bottleneck, and the
    overlap pipeline (:class:`SampledBatchStream`) needs planning far
    cheaper than the device step.  The per-call seed is splitmix64-
    hashed first: the sampler computes ``splitmix64(seed ^ cell)``, so
    raw small-integer call seeds would correlate calls' RNG streams
    (ADVICE r3); within a call every (step, row, draw) is a distinct
    cell, so one call per level is at least as decorrelated as the old
    per-step calls."""
    levels = [seeds]
    for li, f in enumerate(cfg.fanouts):
        prev = levels[-1]
        nxt = _sample(indptr, indices, prev.ravel(), f,
                      seed=_mix64(seed * 1_000_003 + li))
        levels.append(nxt.reshape(prev.shape + (f,)))
    return levels


def _plan_nc_chunk(cfg: SampledConfig, indptr, indices, train_nodes,
                   labels, steps: int, chunk_seed: int):
    """Numpy core of one NC chunk: (levels, labels) for ``steps`` steps."""
    rng = np.random.default_rng(chunk_seed)
    seeds = rng.choice(train_nodes,
                       size=(steps, cfg.batch_size)).astype(np.int32)
    levels = _build_pyramid(cfg, indptr, indices, seeds, chunk_seed)
    return levels, np.asarray(labels, np.int32)[seeds]


def _plan_lp_chunk(cfg: SampledConfig, indptr, indices, train_pos,
                   num_nodes: int, steps: int, chunk_seed: int):
    """Numpy core of one LP chunk: (levels, None)."""
    rng = np.random.default_rng(chunk_seed)
    p = cfg.batch_size
    rows = rng.integers(0, len(train_pos), (steps, p))
    pos = train_pos[rows]                                    # [S, P, 2]
    neg = rng.integers(0, num_nodes, (steps, p, 2))
    seeds = np.concatenate(
        [pos[..., 0], pos[..., 1], neg[..., 0], neg[..., 1]],
        axis=1).astype(np.int32)                             # [S, 4P]
    return _build_pyramid(cfg, indptr, indices, seeds, chunk_seed), None


def plan_batches(cfg: SampledConfig, edges: np.ndarray, labels: np.ndarray,
                 train_mask: np.ndarray, num_nodes: int, steps: int,
                 seed: int = 0) -> tuple[SampledBatches, jax.Array]:
    """Draw ``steps`` seed batches + their fanout pyramids on the host.

    Returns the device-resident batches and the ``[N]`` true-degree
    array the steps gather their estimator weights from."""
    indptr, indices = build_adjacency(edges, num_nodes)
    train_nodes = np.flatnonzero(np.asarray(train_mask))
    levels, lab = _plan_nc_chunk(cfg, indptr, indices, train_nodes, labels,
                                 steps, seed)
    deg = (indptr[1:] - indptr[:-1]).astype(np.float32)
    return (SampledBatches(tuple(jnp.asarray(l) for l in levels),
                           jnp.asarray(lab)),
            jnp.asarray(deg))


def plan_lp_batches(cfg: SampledConfig, train_pos: np.ndarray,
                    num_nodes: int, steps: int,
                    seed: int = 0) -> tuple[SampledBatches, jax.Array]:
    """LP pyramids: per step, ``batch_size`` positive pairs drawn from
    ``train_pos`` and as many uniform-random negative pairs; the seed
    vector is the four aligned endpoint chunks (u⁺, v⁺, u⁻, v⁻).
    ``labels`` is None — positives/negatives are positional.

    Message passing samples over the TRAIN edges only (``train_pos`` is
    both the supervision set and the adjacency), matching the full-graph
    LP protocol where ``split_edges`` builds the encoder graph from
    train edges — held-out val/test edges must never leak into the
    neighborhood aggregation."""
    indptr, indices = build_adjacency(np.asarray(train_pos), num_nodes)
    levels, _ = _plan_lp_chunk(cfg, indptr, indices, np.asarray(train_pos),
                               num_nodes, steps, seed)
    deg = (indptr[1:] - indptr[:-1]).astype(np.float32)
    return (SampledBatches(tuple(jnp.asarray(l) for l in levels), None),
            jnp.asarray(deg))


class SampledBatchStream:
    """Background-planned, double-buffered minibatch pyramids.

    VERDICT r3 #5: the r03 trainer pre-planned ``plan_steps`` pyramids
    once and recycled them modulo on long runs.  This stream plans a
    FRESH chunk of ``chunk_steps`` pyramids in a background thread while
    the device trains on the current one, transfers it (``device_put``
    happens in the worker, so the host→device copy overlaps training
    too) and hands it over through a bounded queue (``depth`` chunks of
    look-ahead; the put blocks when full, bounding host memory).  Every
    chunk uses a splitmix64-derived seed, so a run of any length never
    sees a repeated batch.  ``plan_steps`` keeps its r03 meaning as the
    device-resident footprint cap — it is now the chunk size, not the
    total variety.

    The planner cores are the SAME functions the one-shot planners use
    (`_plan_nc_chunk` / `_plan_lp_chunk`); only the per-chunk seed
    derivation differs (splitmix64 of (seed, chunk index)).  The
    thread/queue machinery itself is the generic
    :class:`hyperspace_tpu.data.prefetch.HostPrefetcher` (this stream is
    the pipeline it was factored out of); this class owns only the
    planning and the chunk-seed sequence.
    """

    def __init__(self, cfg: SampledConfig, task: str, *, num_nodes: int,
                 edges=None, labels=None, train_mask=None, train_pos=None,
                 chunk_steps: int = 64, depth: int = 2, seed: int = 0,
                 start_chunk: int = 0):
        from hyperspace_tpu.data.prefetch import HostPrefetcher

        self.cfg = cfg
        self.task = task
        self.chunk_steps = int(chunk_steps)
        self._seed = int(seed)
        self._num_nodes = int(num_nodes)
        if task == "nc":
            self._indptr, self._indices = build_adjacency(edges, num_nodes)
            self._train_nodes = np.flatnonzero(np.asarray(train_mask))
            self._labels = np.asarray(labels, np.int32)
        elif task == "lp":
            self._train_pos = np.asarray(train_pos)
            self._indptr, self._indices = build_adjacency(self._train_pos,
                                                          num_nodes)
        else:
            raise ValueError(f"unknown task {task!r}")
        self.deg = jnp.asarray(
            (self._indptr[1:] - self._indptr[:-1]).astype(np.float32))
        # resume support (ADVICE r04): a run restored at step R passes
        # start_chunk = ceil(R / chunk_steps) — see train/loop.resume_chunk
        # (NOT floor: floor would re-serve the partially-consumed boundary
        # chunk's first R%cs rows, the batch-replay bug) — so the chunk
        # sequence CONTINUES instead of replaying consumed chunks; the
        # "never a repeated batch" guarantee holds across restarts
        self._prefetch = HostPrefetcher(self._make_chunk, depth=depth,
                                        start=int(start_chunk))

    def _plan(self, chunk: int):
        cs = _mix64((self._seed << 20) ^ chunk)
        if self.task == "nc":
            return _plan_nc_chunk(self.cfg, self._indptr, self._indices,
                                  self._train_nodes, self._labels,
                                  self.chunk_steps, cs)
        return _plan_lp_chunk(self.cfg, self._indptr, self._indices,
                              self._train_pos, self._num_nodes,
                              self.chunk_steps, cs)

    def _make_chunk(self, chunk: int) -> SampledBatches:
        # device_put in the prefetch worker: the host→device copy of
        # chunk i+1 overlaps the device's training on chunk i
        levels, lab = self._plan(chunk)
        return SampledBatches(
            tuple(jax.device_put(l) for l in levels),
            None if lab is None else jax.device_put(lab))

    def next(self) -> SampledBatches:
        """Block until the next fresh chunk of pyramids is ready.

        Re-raises any exception the planner thread hit (the run fails
        with the real traceback instead of hanging on an empty queue).
        """
        return self._prefetch.next()

    def close(self):
        self._prefetch.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# --- training ----------------------------------------------------------------


def init_sampled_nc(cfg: SampledConfig, feat_dim: int, seed: int = 0):
    """Model + optimizer + TrainState (same tree as ``hgcn.init_nc``)."""
    model = SampledHGCNNodeClf(cfg.base)
    key = jax.random.PRNGKey(seed)
    k_init, key = jax.random.split(key)
    b = cfg.batch_size
    dummy_levels, shape = [], (b,)
    for f in (None,) + tuple(cfg.fanouts):
        if f is not None:
            shape = shape + (f,)
        dummy_levels.append(jnp.zeros(shape + (feat_dim,), jnp.float32))
    dummy_nn = [jnp.ones(l.shape[:-1], jnp.float32)
                for l in dummy_levels[:-1]]
    params = model.init(k_init, dummy_levels, dummy_nn)["params"]
    opt = hgcn.make_optimizer(cfg.base)
    return model, opt, hgcn.TrainState(params, opt.init(params), key,
                                       jnp.zeros((), jnp.int32))


def init_sampled_lp(cfg: SampledConfig, feat_dim: int, seed: int = 0):
    """LP model + optimizer + TrainState (same tree as ``hgcn.init_lp``)."""
    model = SampledHGCNLinkPred(cfg.base)
    key = jax.random.PRNGKey(seed)
    k_init, key = jax.random.split(key)
    dummy_levels, shape = [], (4 * cfg.batch_size,)
    for f in (None,) + tuple(cfg.fanouts):
        if f is not None:
            shape = shape + (f,)
        dummy_levels.append(jnp.zeros(shape + (feat_dim,), jnp.float32))
    dummy_nn = [jnp.ones(l.shape[:-1], jnp.float32)
                for l in dummy_levels[:-1]]
    params = model.init(k_init, dummy_levels, dummy_nn)["params"]
    opt = hgcn.make_optimizer(cfg.base)
    return model, opt, hgcn.TrainState(params, opt.init(params), key,
                                       jnp.zeros((), jnp.int32))


def _lp_row_step(model, opt, state, x_table, deg, ids, constrain=None):
    """One LP minibatch step on a single pyramid row (un-jitted body)."""
    if constrain is not None:
        ids = [constrain(a) for a in ids]
    levels = [x_table[a] for a in ids]
    n_nbrs = [deg[a] for a in ids[:-1]]
    key, k_drop = jax.random.split(state.key)

    def loss_fn(params):
        pos_logit, neg_logit = model.apply(
            {"params": params}, levels, n_nbrs,
            deterministic=False, rngs={"dropout": k_drop})
        bce_pos = optax.sigmoid_binary_cross_entropy(
            pos_logit, jnp.ones_like(pos_logit))
        bce_neg = optax.sigmoid_binary_cross_entropy(
            neg_logit, jnp.zeros_like(neg_logit))
        return ((jnp.sum(bce_pos) + jnp.sum(bce_neg))
                / (pos_logit.shape[0] + neg_logit.shape[0]))

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    updates, opt_state = opt.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return hgcn.TrainState(params, opt_state, key, state.step + 1), loss


@partial(jax.jit, static_argnames=("model", "opt"), donate_argnames=("state",))
def train_step_sampled_lp(
    model: SampledHGCNLinkPred,
    opt,
    state: hgcn.TrainState,
    x_table: jax.Array,
    deg: jax.Array,
    batches: SampledBatches,
):
    """One sampled LP step; consumes pyramid ``state.step % S``.

    Supervises ``batch_size`` positive pairs (+ as many negatives)."""
    return _sampled_lp_impl(model, opt, state, x_table, deg, batches)


@partial(jax.jit, static_argnames=("model", "opt"), donate_argnames=("state",))
def train_epoch_sampled_lp(
    model: SampledHGCNLinkPred,
    opt,
    state: hgcn.TrainState,
    x_table: jax.Array,
    deg: jax.Array,
    batches: SampledBatches,
):
    """All S planned LP minibatches as one `lax.scan` program."""

    def body(st, ids):
        return _lp_row_step(model, opt, st, x_table, deg, list(ids))

    return jax.lax.scan(body, state, tuple(batches.ids))


def _row_step(model, opt, state, x_table, deg, ids, labels, constrain=None):
    """One minibatch step on a single pyramid row (un-jitted body)."""
    if constrain is not None:  # GSPMD hint: shard the batch axis
        ids = [constrain(a) for a in ids]
        labels = constrain(labels)
    levels = [x_table[a] for a in ids]
    n_nbrs = [deg[a] for a in ids[:-1]]
    key, k_drop = jax.random.split(state.key)

    def loss_fn(params):
        logits = model.apply({"params": params}, levels, n_nbrs,
                             deterministic=False, rngs={"dropout": k_drop})
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    updates, opt_state = opt.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return hgcn.TrainState(params, opt_state, key, state.step + 1), loss


def _take_row(state, batches: SampledBatches):
    """Row ``state.step % S`` of the plan (the one modulo-indexed
    selection both the NC and LP steps use)."""
    s = batches.ids[0].shape[0]
    i = state.step % s
    take = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    labels = None if batches.labels is None else take(batches.labels)
    return [take(a) for a in batches.ids], labels


def _sampled_impl(model, opt, state, x_table, deg, batches, constrain=None):
    ids, labels = _take_row(state, batches)
    return _row_step(model, opt, state, x_table, deg, ids, labels, constrain)


@partial(jax.jit, static_argnames=("model", "opt"), donate_argnames=("state",))
def train_step_sampled_nc(
    model: SampledHGCNNodeClf,
    opt,
    state: hgcn.TrainState,
    x_table: jax.Array,   # [N, F0] raw features, device-resident
    deg: jax.Array,       # [N] true degrees
    batches: SampledBatches,
):
    """One minibatch step; consumes pyramid ``state.step % S``.

    Supervises exactly ``batch_size`` seed nodes — the honest
    "samples/step" unit of the sampled trainer."""
    return _sampled_impl(model, opt, state, x_table, deg, batches)


@partial(jax.jit, static_argnames=("model", "opt"), donate_argnames=("state",))
def train_epoch_sampled_nc(
    model: SampledHGCNNodeClf,
    opt,
    state: hgcn.TrainState,
    x_table: jax.Array,
    deg: jax.Array,
    batches: SampledBatches,
):
    """All S planned minibatches as ONE XLA program (`lax.scan` over the
    pyramid rows, front to back — identical trajectory to S calls of
    :func:`train_step_sampled_nc` from ``state.step % S == 0``).  The
    per-step device work is a handful of small dense ops, so the scan's
    dispatch amortization is worth ~the same factor it buys the Poincaré
    workload (docs/benchmarks.md r03b)."""

    def body(st, row):
        ids, labels = row
        return _row_step(model, opt, st, x_table, deg, list(ids), labels)

    return jax.lax.scan(body, state, (tuple(batches.ids), batches.labels))


def _sampled_lp_impl(model, opt, state, x_table, deg, batches,
                     constrain=None):
    ids, _ = _take_row(state, batches)
    return _lp_row_step(model, opt, state, x_table, deg, ids, constrain)


def _make_sharded(impl, model, opt, mesh, state: hgcn.TrainState,
                  x_table, deg, batches: SampledBatches):
    """Shared DP builder: the pyramid's batch axis shards across the
    data-like axes (XLA inserts the gradient all-reduce — SURVEY.md §2
    N8); features/degrees/plan are placed replicated once.  Returns
    ``(step, placed_state, placed_data)``; call as ``state, loss =
    step(state, *placed_data)``.  The pyramid's leading batch axis (B
    for NC, 4·batch_size for LP) must divide by the mesh's data extent."""
    from hyperspace_tpu.parallel.mesh import (
        data_extent,
        replicated,
        shard_batch,
    )
    from hyperspace_tpu.parallel.tp import state_shardings

    d = data_extent(mesh)
    if batches.ids[0].shape[1] % d:
        raise ValueError(
            f"pyramid batch axis {batches.ids[0].shape[1]} not divisible "
            f"by the mesh's data extent {d}")
    state_sh = state_shardings(state, state.params, mesh)
    repl = replicated(mesh)
    step = jax.jit(
        partial(impl, model, opt, constrain=partial(shard_batch, mesh=mesh)),
        in_shardings=(state_sh, repl, repl, repl),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,),
    )
    data = (jax.device_put(x_table, repl), jax.device_put(deg, repl),
            jax.tree_util.tree_map(lambda a: jax.device_put(a, repl),
                                   batches))
    return step, jax.device_put(state, state_sh), data


def make_sharded_step(model, opt, mesh, state: hgcn.TrainState,
                      x_table, deg, batches: SampledBatches):
    """Data-parallel sampled NC step over ``mesh`` (see _make_sharded)."""
    return _make_sharded(_sampled_impl, model, opt, mesh, state, x_table,
                         deg, batches)


def make_sharded_lp_step(model, opt, mesh, state: hgcn.TrainState,
                         x_table, deg, batches: SampledBatches):
    """Data-parallel sampled LP step over ``mesh`` (see _make_sharded)."""
    return _make_sharded(_sampled_lp_impl, model, opt, mesh, state,
                         x_table, deg, batches)
