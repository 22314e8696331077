"""Poincaré embeddings (Nickel & Kiela 2017) — reference workload 1.

An embedding table on the curvature-c ball, trained so that ancestors are
close to their descendants: for a positive pair (u, v) and k sampled
negatives n₁..n_k,

    loss = -log [ exp(-d(u,v)) / (exp(-d(u,v)) + Σ exp(-d(u,nᵢ))) ].

Everything per-step — negative sampling, gather, distance matrix, loss,
gradient, Riemannian update — is one XLA program (the BASELINE.json single
compiled-train-step requirement).  Negatives are drawn on device with
``jax.random`` so the host feeds only the static closure array once.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from hyperspace_tpu.manifolds import PoincareBall
from hyperspace_tpu.optim.radam import RAdamState, riemannian_adam
from hyperspace_tpu.optim.rsgd import riemannian_sgd


@dataclasses.dataclass(frozen=True)
class PoincareEmbedConfig:
    num_nodes: int = 0
    dim: int = 10  # BASELINE.json configs[0]: 10-dim ball
    c: float = 1.0
    lr: float = 0.3
    neg_samples: int = 10
    batch_size: int = 512
    burnin_steps: int = 100
    burnin_factor: float = 0.01
    init_scale: float = 1e-3
    dtype: Any = jnp.float32
    # "rsgd" (Nickel & Kiela) or "radam" (Bécigneul & Ganea transported
    # moments) — both run inside the same single XLA-compiled train step
    optimizer: str = "rsgd"
    # sparse=True uses train_step_sparse: only the rows a batch touches are
    # gathered, updated and scattered back (SURVEY.md §7 hard-part #2) —
    # O(B·(2+K)·d) update work instead of O(N·d)
    sparse: bool = False
    # negative sampling policy for the DENSE step paths:
    #   "uniform" (default, bit-identical to the pre-mining build) draws
    #   neg_samples ids uniformly per row;
    #   "mined" draws a shared candidate pool of mine_pool ids uniformly,
    #   then keeps each row's neg_samples NEAREST pool members (sampled
    #   hard-negative mining) via the fused scan-top-k kernel
    #   (kernels/scan_topk.py; XLA twin on CPU) — the mining distances
    #   are stop_gradient'ed, so only the loss's own distance terms
    #   train.  Collisions with the row's u/v are masked by the loss as
    #   before.  The host-planned sparse paths keep uniform draws (their
    #   negatives are planned before the embeddings exist).
    neg_mode: str = "uniform"
    # candidate-pool size for neg_mode="mined" (0 = max(4*neg_samples, 64))
    mine_pool: int = 0
    # mixed-precision policy (hyperspace_tpu/precision.py).  This
    # workload is ALL boundary-sensitive math: the table is a master
    # parameter (policy: f32), and the per-step compute is the ball
    # distance + Riemannian update (policy: boundary/param, f32), so
    # "bf16" is bit-identical to "f32" here BY DESIGN — regression-
    # tested, because a bf16 cast creeping into this step is exactly the
    # failure the policy exists to prevent.  The workload's bf16 win
    # lives in the serving scan (serve/engine precision="bf16").
    precision: str = "f32"


class TrainState(NamedTuple):
    table: jax.Array  # [N, d] points on the ball
    opt_state: Any
    key: jax.Array
    step: jax.Array


def init_table(cfg: PoincareEmbedConfig, key: jax.Array) -> jax.Array:
    """Uniform init in a tiny ball around the origin (N&K 2017 init)."""
    u = jax.random.uniform(
        key, (cfg.num_nodes, cfg.dim), cfg.dtype, -cfg.init_scale, cfg.init_scale
    )
    return u


def make_optimizer(cfg: PoincareEmbedConfig):
    ball = PoincareBall(cfg.c)
    if cfg.optimizer == "radam":
        # burn-in as a schedule (radam has no native burn-in knob)
        lr = cfg.lr
        if cfg.burnin_steps > 0:
            factor, steps = cfg.burnin_factor, cfg.burnin_steps
            lr = lambda n: cfg.lr * jnp.where(n < steps, factor, 1.0)
        return riemannian_adam(lr, tags=ball)
    if cfg.optimizer != "rsgd":
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    return riemannian_sgd(
        cfg.lr,
        tags=ball,  # single-leaf param tree: the whole table is on the ball
        burnin_steps=cfg.burnin_steps,
        burnin_factor=cfg.burnin_factor,
    )


def _ranking_loss(u, cv, u_idx, v_idx, neg_idx, c):
    """-log softmax(-d)[positive]: u [B, d] against cv [B, 1+K, d]
    (column 0 = the positive v), with sampled negatives that collide with
    the positive v or the query u itself masked out -- otherwise ~K/N of
    rows get a log(2) loss floor and a gradient pushing the true ancestor
    away.  (Collisions with *other* ancestors of u remain, as in standard
    on-the-fly sampled-softmax training.)  The one loss body every step
    variant (dense / sparse / planned / packed) shares."""
    ball = PoincareBall(c)
    d = ball.dist(u[:, None, :], cv)
    logits = -d
    collide = (neg_idx == v_idx[:, None]) | (neg_idx == u_idx[:, None])
    mask = jnp.concatenate(
        [jnp.zeros_like(v_idx[:, None], bool), collide], axis=1)
    logits = jnp.where(mask, -jnp.inf, logits)
    return jnp.mean(jax.nn.logsumexp(logits, axis=1) - logits[:, 0])


def loss_fn(
    table: jax.Array,
    u_idx: jax.Array,
    v_idx: jax.Array,
    neg_idx: jax.Array,
    c,
) -> jax.Array:
    """Batch loss. u_idx, v_idx: [B]; neg_idx: [B, K]."""
    u = table[u_idx]  # [B, d]
    cand = jnp.concatenate([v_idx[:, None], neg_idx], axis=1)  # [B, 1+K]
    return _ranking_loss(u, table[cand], u_idx, v_idx, neg_idx, c)


def _mine_negatives(cfg: PoincareEmbedConfig, table: jax.Array,
                    u_idx: jax.Array, k_neg: jax.Array) -> jax.Array:
    """Sampled hard-negative mining (``neg_mode="mined"``): draw a
    shared uniform candidate pool, keep each row's ``neg_samples``
    nearest pool members under the ball metric — one fused scan-top-k
    over the pool slab (kernels/scan_topk.py), no [B, pool] distance
    matrix in HBM on the kernel path.  Everything is stop_gradient'ed:
    mining picks indices, the loss computes its own distances."""
    from hyperspace_tpu.kernels import scan_topk as fused_kernel

    pool = cfg.mine_pool or max(4 * cfg.neg_samples, 64)
    pool_idx = jax.random.randint(k_neg, (pool,), 0, cfg.num_nodes)
    tbl = jax.lax.stop_gradient(table)
    _, sel = fused_kernel.scan_topk(
        tbl[pool_idx], tbl[u_idx], jnp.zeros_like(u_idx), 0,
        spec=("poincare", cfg.c), k=cfg.neg_samples, n=pool,
        exclude_self=False)
    # sel slots are pool positions (always valid: neg_samples <= pool)
    return pool_idx[sel]                                  # [B, K]


def _check_neg_mode(cfg: PoincareEmbedConfig, *, dense: bool):
    if cfg.neg_mode not in ("uniform", "mined"):
        raise ValueError(
            f"neg_mode must be 'uniform' or 'mined'; got {cfg.neg_mode!r}")
    if cfg.neg_mode == "mined":
        if not dense:
            raise ValueError(
                "neg_mode='mined' needs the dense step paths (mining "
                "reads the live table; the host-planned sparse paths "
                "draw their negatives before the embeddings exist) — "
                "drop sparse=true or neg_mode")
        if not 0 < cfg.neg_samples <= (cfg.mine_pool
                                       or max(4 * cfg.neg_samples, 64)):
            raise ValueError(
                f"mine_pool={cfg.mine_pool} must hold at least "
                f"neg_samples={cfg.neg_samples} candidates")
        # mining has NO two-stage fallback (it IS the fused kernel), so
        # the kernel's hard caps must fail here, at config time, with a
        # config-shaped message — not mid-training from inside jit
        from hyperspace_tpu.kernels import scan_topk as fused_kernel

        if not fused_kernel.supports(("poincare", cfg.c),
                                     k=cfg.neg_samples, dim=cfg.dim):
            raise ValueError(
                f"neg_mode='mined' mines through the fused scan-top-k "
                f"kernel, which caps neg_samples at "
                f"{fused_kernel.FUSED_MAX_K} and dim at "
                f"{fused_kernel.FUSED_MAX_DIM}; got neg_samples="
                f"{cfg.neg_samples}, dim={cfg.dim} — lower them or "
                "drop neg_mode")


def _dense_step_body(
    cfg: PoincareEmbedConfig,
    opt,
    state: TrainState,
    pairs: jax.Array,
) -> tuple[TrainState, jax.Array]:
    """Un-jitted dense step body: device-side batch + negative sampling
    (uniform, or sampled hard-negative mining under ``neg_mode="mined"``
    — :func:`_mine_negatives`), loss, grad, whole-table Riemannian
    update.  Shared verbatim by :func:`train_step` (one dispatch per
    step) and :func:`train_epoch_scan` (one dispatch per epoch) so the
    two trajectories are the same computation."""
    # trace-time and free: direct train_step/train_epoch_scan callers
    # (bench, tests) get the same config-shaped errors make_train_step
    # raises — a bad mined config must never surface kernel internals
    _check_neg_mode(cfg, dense=True)
    key, k_batch, k_neg = jax.random.split(state.key, 3)
    num_pairs = pairs.shape[0]
    rows = jax.random.randint(k_batch, (cfg.batch_size,), 0, num_pairs)
    batch = pairs[rows]  # [B, 2]
    u_idx, v_idx = batch[:, 0], batch[:, 1]
    if cfg.neg_mode == "mined":
        neg_idx = _mine_negatives(cfg, state.table, u_idx, k_neg)
    else:
        neg_idx = jax.random.randint(
            k_neg, (cfg.batch_size, cfg.neg_samples), 0, cfg.num_nodes
        )
    loss, grads = jax.value_and_grad(loss_fn)(state.table, u_idx, v_idx, neg_idx, cfg.c)
    updates, opt_state = opt.update(grads, state.opt_state, state.table)
    table = optax.apply_updates(state.table, updates)
    return TrainState(table, opt_state, key, state.step + 1), loss


@partial(jax.jit, static_argnames=("cfg", "opt"), donate_argnames=("state",))
def train_step(
    cfg: PoincareEmbedConfig,
    opt,
    state: TrainState,
    pairs: jax.Array,  # [P, 2] the full closure, resident on device
) -> tuple[TrainState, jax.Array]:
    return _dense_step_body(cfg, opt, state, pairs)


@partial(jax.jit, static_argnames=("cfg", "opt", "steps"),
         donate_argnames=("state",))
def train_epoch_scan(
    cfg: PoincareEmbedConfig,
    opt,
    state: TrainState,
    pairs: jax.Array,  # [P, 2] the full closure, resident on device
    steps: int,
) -> tuple[TrainState, jax.Array]:
    """``steps`` dense steps as ONE XLA program (`lax.scan` over the step
    body).  At WordNet scale the per-step device work is ~tens of µs of
    compute on a [66 k, 10] table, so an epoch of separate dispatches is
    dominated by launch latency; scanning the epoch removes all but one
    dispatch.  Bitwise the same trajectory as ``steps`` calls of
    :func:`train_step` (same body, same PRNG stream).  Returns the final
    state and the [steps] per-step losses."""

    def body(st, _):
        return _dense_step_body(cfg, opt, st, pairs)

    return jax.lax.scan(body, state, None, length=steps)


@partial(jax.jit, static_argnames=("cfg", "opt"), donate_argnames=("state",))
def train_step_sparse(
    cfg: PoincareEmbedConfig,
    opt,
    state: TrainState,
    pairs: jax.Array,  # [P, 2] the full closure, resident on device
) -> tuple[TrainState, jax.Array]:
    """Sparse-row variant of `train_step` (SURVEY.md §7 hard-part #2).

    The dense step differentiates a gather into a full [N, d] cotangent and
    expmaps the whole table; fine at WordNet scale, ruinous for arxiv-scale
    tables.  Here the batch's unique touched rows (≤ B·(2+K), static shape)
    are gathered, the loss is computed on the gathered sub-table, and only
    those rows are updated and scattered back — update work is O(B·(2+K)·d)
    regardless of N.  TPU mechanics: `jnp.unique(..., size=...)` keeps the
    shape static; sentinel-padded slots point one past the table, gather
    clips them (their gradient is identically zero) and the final scatter
    uses ``mode="drop"`` so they never write back.

    Optimizer-state semantics for stateful optimizers (radam): moment rows
    are gathered/updated/scattered with the same index set — untouched rows
    keep stale moments ("lazy" sparse Adam, geoopt's
    SparseRiemannianAdam/torch SparseAdam semantics), while bias correction
    uses the global step count.  For rsgd the sparse step is mathematically
    identical to the dense one (untouched rows: expmap(x, 0) = x).
    """
    # a mined config reaching the sparse step directly would otherwise
    # silently train on uniform negatives — reject like make_train_step
    _check_neg_mode(cfg, dense=False)
    key, k_batch, k_neg = jax.random.split(state.key, 3)
    num_pairs = pairs.shape[0]
    rows_sel = jax.random.randint(k_batch, (cfg.batch_size,), 0, num_pairs)
    batch = pairs[rows_sel]  # [B, 2]
    u_idx, v_idx = batch[:, 0], batch[:, 1]
    neg_idx = jax.random.randint(
        k_neg, (cfg.batch_size, cfg.neg_samples), 0, cfg.num_nodes
    )

    b = cfg.batch_size
    all_idx = jnp.concatenate([u_idx, v_idx, neg_idx.reshape(-1)])
    # return_inverse gives every slot mapping in the one unique call — the
    # r02 version re-derived them with three searchsorted passes
    uniq, inv = jnp.unique(all_idx, size=all_idx.shape[0],
                           fill_value=cfg.num_nodes, return_inverse=True)
    rows = state.table[jnp.minimum(uniq, cfg.num_nodes - 1)]  # [U, d]

    def sub_loss(rows):
        cand_slots = jnp.concatenate(
            [inv[b : 2 * b, None], inv[2 * b :].reshape(b, -1)], axis=1)
        return _ranking_loss(rows[inv[:b]], rows[cand_slots],
                             u_idx, v_idx, neg_idx, cfg.c)

    loss, g_rows = jax.value_and_grad(sub_loss)(rows)

    # run the optimizer transform on the gathered rows; gather/scatter any
    # per-row optimizer state (radam moments) with the same index set
    opt_state = state.opt_state
    if isinstance(opt_state, RAdamState):
        row_state = RAdamState(
            count=opt_state.count,
            mu=opt_state.mu[jnp.minimum(uniq, cfg.num_nodes - 1)],
            nu=opt_state.nu[jnp.minimum(uniq, cfg.num_nodes - 1)],
        )
        updates, row_state = opt.update(g_rows, row_state, rows)
        # explicit casts: under x64 the bias-corrected moments come back
        # f64; scattering them into the f32 state arrays must not rely on
        # implicit (and soon-to-be-removed) scatter dtype promotion
        new_opt_state = RAdamState(
            count=row_state.count,
            mu=opt_state.mu.at[uniq].set(
                row_state.mu.astype(opt_state.mu.dtype), mode="drop"),
            nu=opt_state.nu.at[uniq].set(
                row_state.nu.astype(opt_state.nu.dtype), mode="drop"),
        )
    else:  # stateless-per-row (rsgd: count only)
        updates, new_opt_state = opt.update(g_rows, opt_state, rows)
    new_rows = optax.apply_updates(rows, updates)
    table = state.table.at[uniq].set(
        new_rows.astype(state.table.dtype), mode="drop")
    return TrainState(table, new_opt_state, key, state.step + 1), loss


def make_train_step(cfg: PoincareEmbedConfig):
    """The configured step function: ``f(cfg, opt, state, pairs)``."""
    _check_neg_mode(cfg, dense=not cfg.sparse)
    return train_step_sparse if cfg.sparse else train_step


# --- host-planned sparse updates (VERDICT r2 next #2) -------------------------
#
# `train_step_sparse` pays a device-side sort (jnp.unique) every step —
# measured 3.6x slower than the dense step on TPU at WordNet scale, because
# the table work it saves is smaller than the sort latency it adds.  The
# planned variant moves ALL index preparation to the host, amortized over a
# chunk of steps:
#
# - batches + negatives are drawn on host (numpy, vectorized over the chunk);
# - each step's flat index multiset is argsorted ONCE on host, yielding:
#   uniq (sorted unique rows, sentinel-padded), inv_map (flat position →
#   slot), order (occurrences sorted by row), seg_sorted (their slots,
#   ascending);
# - on device the step is: one sorted gather of touched rows (+ their radam
#   moment rows), the batch loss through `_dedup_gather` — whose custom VJP
#   routes every cotangent through gathers and one SORTED segment-sum (no
#   unsorted scatter anywhere in autodiff) — the optimizer on the [U, d]
#   sub-table, and three sorted scatter-sets (table, mu, nu) with
#   ``mode="drop"`` for the sentinel rows.
#
# No device sort, no searchsorted, no unsorted scatter: update work is
# O(B·(2+K)·d) + the sorted-scatter latency, independent of N.


class SparsePlan(NamedTuple):
    """Device-resident plan for S planned-sparse steps (host-built).

    U = B·(2+K) flat index slots per step; all arrays static-shaped.
    """

    u_idx: jax.Array       # [S, B]
    v_idx: jax.Array       # [S, B]
    neg_idx: jax.Array     # [S, B, K]
    uniq: jax.Array        # [S, U] sorted unique rows, sentinel = num_nodes
    inv_map: jax.Array     # [S, U] flat position -> slot in uniq
    order: jax.Array       # [S, U] occurrences argsorted by row id
    seg_sorted: jax.Array  # [S, U] = inv_map[order] (ascending)


def plan_arrays_np(cfg: PoincareEmbedConfig, u_idx, v_idx, neg_idx):
    """The numpy planning pass behind :func:`plan_from_indices` —
    returns the seven plan arrays as HOST numpy (the host-resident
    trainer keeps them on host to union/remap before any transfer)."""
    import numpy as np

    steps = u_idx.shape[0]
    u_idx = np.asarray(u_idx, np.int32)
    v_idx = np.asarray(v_idx, np.int32)
    neg_idx = np.asarray(neg_idx, np.int32)
    flat = np.concatenate(
        [u_idx, v_idx, neg_idx.reshape(steps, -1)], axis=1)   # [S, U]
    order = np.argsort(flat, axis=1, kind="stable").astype(np.int32)
    sorted_ids = np.take_along_axis(flat, order, axis=1)
    # slot boundaries: a new unique row wherever the sorted id changes
    new_seg = np.ones_like(sorted_ids, bool)
    new_seg[:, 1:] = sorted_ids[:, 1:] != sorted_ids[:, :-1]
    seg_sorted = (np.cumsum(new_seg, axis=1) - 1).astype(np.int32)
    u_slots = flat.shape[1]
    uniq = np.full((steps, u_slots), cfg.num_nodes, np.int32)
    s_grid, _ = np.nonzero(new_seg)
    uniq[s_grid, seg_sorted[new_seg]] = sorted_ids[new_seg]
    inv_map = np.empty_like(seg_sorted)
    np.put_along_axis(inv_map, order, seg_sorted, axis=1)
    return u_idx, v_idx, neg_idx, uniq, inv_map, order, seg_sorted


def plan_from_indices(cfg: PoincareEmbedConfig, u_idx, v_idx,
                      neg_idx) -> SparsePlan:
    """Build the per-step index plans for explicit [S, B] / [S, B, K]
    batches — one vectorized numpy pass, ~milliseconds per epoch-chunk."""
    return SparsePlan(*(jnp.asarray(a) for a in
                        plan_arrays_np(cfg, u_idx, v_idx, neg_idx)))


def plan_sparse_steps(cfg: PoincareEmbedConfig, pairs, steps: int,
                      seed: int = 0) -> SparsePlan:
    """Draw ``steps`` batches + negatives on host and plan their indices."""
    import numpy as np

    _check_neg_mode(cfg, dense=False)
    rng = np.random.default_rng(seed)
    pairs = np.asarray(pairs)
    b, k = cfg.batch_size, cfg.neg_samples
    batch = pairs[rng.integers(0, len(pairs), (steps, b))]    # [S, B, 2]
    neg_idx = rng.integers(0, cfg.num_nodes, (steps, b, k))
    return plan_from_indices(cfg, batch[..., 0], batch[..., 1], neg_idx)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dedup_gather(rows, inv_map, order, seg_sorted, num_slots: int):
    """rows[inv_map] whose VJP never scatters: the cotangent is permuted
    into row-sorted occurrence order (a gather) and combined per slot with
    a SORTED segment-sum."""
    return rows[inv_map]


def _dg_fwd(rows, inv_map, order, seg_sorted, num_slots):
    return rows[inv_map], (inv_map, order, seg_sorted)


def _dg_bwd(num_slots, res, g):
    inv_map, order, seg_sorted = res
    acc_dt = jnp.promote_types(g.dtype, jnp.float32)
    d_rows = jax.ops.segment_sum(
        g[order].astype(acc_dt), seg_sorted, num_slots,
        indices_are_sorted=True).astype(g.dtype)
    return d_rows, None, None, None


_dedup_gather.defvjp(_dg_fwd, _dg_bwd)


@partial(jax.jit, static_argnames=("cfg", "opt"), donate_argnames=("state",))
def train_step_sparse_planned(
    cfg: PoincareEmbedConfig,
    opt,
    state: TrainState,
    plan: SparsePlan,
) -> tuple[TrainState, jax.Array]:
    """One planned-sparse step; consumes plan row ``state.step % S``.

    Mathematically identical to the dense step on the planned batch
    (duplicate cotangents are summed per row before the expmap), with the
    same lazy-moment radam semantics as `train_step_sparse`.
    """
    s = plan.u_idx.shape[0]
    i = state.step % s
    take = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    u_idx, v_idx, neg_idx, uniq, inv_map, order, seg_sorted = (
        take(a) for a in plan)
    b = cfg.batch_size
    n_slots = uniq.shape[0]
    safe_uniq = jnp.minimum(uniq, cfg.num_nodes - 1)
    rows = state.table[safe_uniq]  # [U, d] sorted gather

    def sub_loss(rows):
        flat = _dedup_gather(rows, inv_map, order, seg_sorted, n_slots)
        cv = jnp.concatenate(
            [flat[b : 2 * b, None],
             flat[2 * b :].reshape(b, -1, rows.shape[-1])], axis=1)
        return _ranking_loss(flat[:b], cv, u_idx, v_idx, neg_idx, cfg.c)

    loss, g_rows = jax.value_and_grad(sub_loss)(rows)

    opt_state = state.opt_state
    if isinstance(opt_state, RAdamState):
        row_state = RAdamState(
            count=opt_state.count,
            mu=opt_state.mu[safe_uniq],
            nu=opt_state.nu[safe_uniq],
        )
        updates, row_state = opt.update(g_rows, row_state, rows)
        new_opt_state = RAdamState(
            count=row_state.count,
            mu=opt_state.mu.at[uniq].set(
                row_state.mu.astype(opt_state.mu.dtype),
                mode="drop", indices_are_sorted=True),
            nu=opt_state.nu.at[uniq].set(
                row_state.nu.astype(opt_state.nu.dtype),
                mode="drop", indices_are_sorted=True),
        )
    else:
        updates, new_opt_state = opt.update(g_rows, opt_state, rows)
    new_rows = optax.apply_updates(rows, updates)
    table = state.table.at[uniq].set(
        new_rows.astype(state.table.dtype),
        mode="drop", indices_are_sorted=True)
    return TrainState(table, new_opt_state, key_after(state.key),
                      state.step + 1), loss


def key_after(key: jax.Array) -> jax.Array:
    """Advance the state PRNG key (planned steps draw nothing on device,
    but the key must still move so dense/sparse states stay interchangeable)."""
    return jax.random.split(key, 1)[0]


# --- packed planned state: one gather + ONE scatter per step ------------------
#
# On-chip breakdown at 598 k rows (docs/benchmarks.md sparse section): the
# planned radam step spent ~2.6 ms of its 4.7 ms in its three sorted
# scatter-sets (table, mu, nu) — each scatter pays the serialization
# latency once.  Packing the table and both moment tables side-by-side as
# one [N, 3d] array (a layout private to the planned path; `unpack_state`
# restores the standard TrainState) turns the update into ONE [U, 3d]
# gather and ONE sorted scatter-set, which is what lets the sparse path
# finally beat the dense step at arxiv-scale tables.


class PackedState(NamedTuple):
    packed: jax.Array  # [N, d] (rsgd) or [N, 2d+1] (radam: table|mu|nu-scalar)
    aux: Any           # non-row optimizer state (counts)
    key: jax.Array
    step: jax.Array


def pack_state(cfg: PoincareEmbedConfig, state: TrainState) -> PackedState:
    if isinstance(state.opt_state, RAdamState):
        packed = jnp.concatenate(
            [state.table, state.opt_state.mu, state.opt_state.nu], axis=1)
        aux = state.opt_state.count
    else:
        packed = state.table
        aux = state.opt_state
    return PackedState(packed, aux, state.key, state.step)


def unpack_state(cfg: PoincareEmbedConfig, p: PackedState) -> TrainState:
    d = cfg.dim
    if p.packed.shape[1] > d:  # radam rows: table | mu | nu (nu is [*, 1])
        table = p.packed[:, :d]
        opt_state = RAdamState(count=p.aux, mu=p.packed[:, d : 2 * d],
                               nu=p.packed[:, 2 * d :])
    else:
        table, opt_state = p.packed, p.aux
    return TrainState(table, opt_state, p.key, p.step)


def _packed_row_body(
    cfg: PoincareEmbedConfig,
    opt,
    state: PackedState,
    row: SparsePlan,  # single-step slices: [B], [B], [B, K], [U] ×4
    sorted_indices: bool = True,
) -> tuple[PackedState, jax.Array]:
    """Un-jitted packed-planned step body on one plan row; shared by
    :func:`train_step_planned_packed` and :func:`train_epoch_planned_packed`
    (``sorted_indices=True`` — per-step uniq rows are ascending) and by
    :func:`train_epoch_planned_hosted` (``False`` — the host-resident
    trainer remaps rows to device hot-cache SLOTS, which are arbitrary
    after the first eviction; same math, the scatter just loses its
    sortedness hint)."""
    u_idx, v_idx, neg_idx, uniq, inv_map, order, seg_sorted = row
    b, d = cfg.batch_size, cfg.dim
    n_slots = uniq.shape[0]
    safe_uniq = jnp.minimum(uniq, cfg.num_nodes - 1)
    all_rows = state.packed[safe_uniq]        # ONE gather, [U, d or 3d]
    rows = all_rows[:, :d]

    def sub_loss(rows):
        flat = _dedup_gather(rows, inv_map, order, seg_sorted, n_slots)
        cv = jnp.concatenate(
            [flat[b : 2 * b, None], flat[2 * b :].reshape(b, -1, d)], axis=1)
        return _ranking_loss(flat[:b], cv, u_idx, v_idx, neg_idx, cfg.c)

    loss, g_rows = jax.value_and_grad(sub_loss)(rows)

    if all_rows.shape[1] > d:  # radam: moments ride in the packed rows
        row_state = RAdamState(count=state.aux, mu=all_rows[:, d : 2 * d],
                               nu=all_rows[:, 2 * d :])
        updates, row_state = opt.update(g_rows, row_state, rows)
        new_all = jnp.concatenate(
            [optax.apply_updates(rows, updates),
             row_state.mu.astype(all_rows.dtype),
             row_state.nu.astype(all_rows.dtype)], axis=1)
        aux = row_state.count
    else:
        updates, aux = opt.update(g_rows, state.aux, rows)
        new_all = optax.apply_updates(rows, updates)
    packed = state.packed.at[uniq].set(
        new_all.astype(state.packed.dtype),
        mode="drop", indices_are_sorted=sorted_indices)  # ONE scatter
    return PackedState(packed, aux, key_after(state.key), state.step + 1), loss


@partial(jax.jit, static_argnames=("cfg", "opt"), donate_argnames=("state",))
def train_step_planned_packed(
    cfg: PoincareEmbedConfig,
    opt,
    state: PackedState,
    plan: SparsePlan,
) -> tuple[PackedState, jax.Array]:
    """`train_step_sparse_planned` on a :class:`PackedState` — identical
    math, one row gather and one sorted scatter-set regardless of the
    optimizer's moment count.  Consumes plan row ``state.step % S``."""
    s = plan.u_idx.shape[0]
    i = state.step % s
    take = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    row = SparsePlan(*(take(a) for a in plan))
    return _packed_row_body(cfg, opt, state, row)


@partial(jax.jit, static_argnames=("cfg", "opt"), donate_argnames=("state",))
def train_epoch_planned_packed(
    cfg: PoincareEmbedConfig,
    opt,
    state: PackedState,
    plan: SparsePlan,
) -> tuple[PackedState, jax.Array]:
    """All S planned steps as ONE XLA program: `lax.scan` over the plan
    rows in order.  Identical trajectory to S calls of
    :func:`train_step_planned_packed` when ``state.step % S == 0`` at
    entry (the single-step variant picks rows by ``step % S``, the scan
    consumes them front to back).  Returns the final state and the [S]
    per-step losses."""

    def body(st, row):
        return _packed_row_body(cfg, opt, st, row)

    return jax.lax.scan(body, state, plan)


@partial(jax.jit, static_argnames=("cfg", "opt"), donate_argnames=("state",))
def train_epoch_planned_hosted(
    cfg: PoincareEmbedConfig,
    opt,
    state: PackedState,
    plan: SparsePlan,
) -> tuple[PackedState, jax.Array]:
    """:func:`train_epoch_planned_packed` for the host-resident trainer
    (train/host_embed.py): ``state.packed`` is the device HOT-ROW CACHE
    (``[C, W]``, ``parallel/host_table.DeviceHotCache``) and the plan's
    ``uniq`` rows are remapped to cache slots — arbitrary order, so the
    scatter drops its sortedness hint; ``cfg.num_nodes`` must be the
    cache capacity C (the remapped sentinel).  Mathematically the same
    per-row computation as the in-HBM program — the host path is
    bitwise-identical to it on small tables (tested)."""

    def body(st, row):
        return _packed_row_body(cfg, opt, st, row, sorted_indices=False)

    return jax.lax.scan(body, state, plan)


def init_state(cfg: PoincareEmbedConfig, seed: int = 0) -> tuple[TrainState, optax.GradientTransformation]:
    """Build the initial state *and* its matching optimizer.

    Returned together so opt_state and the transformation can never be
    constructed from diverging configs.
    """
    from hyperspace_tpu import precision as precision_mod

    precision_mod.get_policy(cfg.precision)  # validate the name early
    key = jax.random.PRNGKey(seed)
    k_init, key = jax.random.split(key)
    table = init_table(cfg, k_init)
    opt = make_optimizer(cfg)
    return TrainState(table, opt.init(table), key, jnp.zeros((), jnp.int32)), opt


# --- evaluation: MAP and mean rank over the closure (SURVEY.md §3.5) ----------


@jax.jit
def _rank_chunk(table: jax.Array, u_idx: jax.Array, v_idx: jax.Array, c):
    """For each pair (u, v): rank of v among all nodes by distance from u."""
    from hyperspace_tpu.kernels.distmat import pdist

    u = table[u_idx]  # [B, d]
    # fused [B, N] distance tile (kernels/distmat.py — one Gram matmul +
    # rank-1 broadcasts per tile, no [B, N, d] difference tensor); the
    # XLA twin == PoincareBall.dist pairwise, parity-tested
    d_all = pdist(u, table, c, manifold="poincare")  # [B, N]
    d_pos = jnp.take_along_axis(d_all, v_idx[:, None], axis=1)  # [B, 1]
    # rank = #nodes strictly closer than v (excluding u itself and v)
    closer = (d_all < d_pos).astype(jnp.int32)
    closer = closer.at[jnp.arange(u_idx.shape[0]), u_idx].set(0)
    closer = closer.at[jnp.arange(u_idx.shape[0]), v_idx].set(0)
    return jnp.sum(closer, axis=1) + 1  # 1-based rank


def evaluate(table: jax.Array, pairs, c, batch: int = 1024) -> dict:
    """Mean rank and MAP of ground-truth ancestors, ranking all N nodes.

    Chunked distance matrix (SURVEY.md §3.5) — N×B blocks stream through the
    device; nothing materializes N×N.
    """
    import numpy as np

    pairs = np.asarray(pairs)
    ranks = []
    for s in range(0, len(pairs), batch):
        chunk_pairs = pairs[s : s + batch]
        r = _rank_chunk(
            table, jnp.asarray(chunk_pairs[:, 0]), jnp.asarray(chunk_pairs[:, 1]), c
        )
        ranks.append(np.asarray(r))
    ranks = np.concatenate(ranks)

    # N&K protocol: rank each ancestor v against *non-ancestor* nodes only
    # ("filtered"): sorting u's unfiltered ranks, the i-th has exactly i other
    # positives above it, so its filtered rank is r_i - i and the precision at
    # its position is (i+1)/r_i.
    by_u: dict[int, list[int]] = {}
    for (u, v), r in zip(pairs, ranks):
        by_u.setdefault(int(u), []).append(int(r))
    aps, filtered_ranks = [], []
    for u, rs in by_u.items():
        rs = sorted(rs)
        aps.append(np.mean([(i + 1) / max(r, i + 1) for i, r in enumerate(rs)]))
        filtered_ranks.extend(max(r - i, 1) for i, r in enumerate(rs))
    return {
        "mean_rank": float(np.mean(filtered_ranks)),
        "map": float(np.mean(aps)),
    }
