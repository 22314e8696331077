"""Host-resident planned-sparse training for beyond-HBM embedding
tables (ROADMAP item 3 — the training half; ``parallel/host_table.py``
holds the table/cache machinery, ``serve/engine.py`` the int8 serve
lane).

The in-HBM planned-packed trainer (models/poincare_embed.py) keeps the
whole ``[N, W]`` packed table (embeddings | optimizer moments) device-
resident; this runner keeps it in HOST memory and visits the device
with only each chunk's working set:

1. **Plan on host** (prefetched): draw ``chunk_steps`` batches +
   negatives, build the per-step sparse plans
   (``poincare_embed.plan_arrays_np``), and union the steps' unique
   rows into the chunk's touched-id set — all numpy, overlapped with
   the previous chunk's device work via ``data/prefetch.HostPrefetcher``.
2. **Hot-row gather**: ``DeviceHotCache.ensure`` uploads only the
   rows not already device-resident (one bucketed transfer + scatter);
   rows hot across chunks never cross the link again.
3. **Run the chunk** as ONE dispatch:
   ``train_epoch_planned_hosted`` — the packed-planned scan program
   with every plan ``uniq`` remapped to CACHE SLOTS (sentinel → C),
   updating the cache in place (donated).
4. **Write back at the chunk boundary**: fetch the touched rows and
   scatter them into the host master, so the master is current before
   the next chunk's gather.

**Equivalence contract.**  The default (synchronous gather) path is
**bitwise-identical** to the in-HBM planned-packed trainer fed the same
per-chunk plans (:func:`run_planned_inhbm`; tested): remapping rows to
slots changes gather/scatter indices, never values, and the per-row
optimizer math has no cross-row coupling.  ``gather_ahead=True``
overlaps upcoming chunks' row gathers with the current chunk's
compute; a row evicted from the cache and re-touched can then be read
STALE, bounded by the prefetch look-ahead: the worker runs up to
``prefetch_depth + 1`` chunks ahead of the consumer's write-back
(depth queued + one in flight), so the staleness bound is
``prefetch_depth + 1`` chunks (default 3) — a bounded-staleness trade
(the classic async parameter-server relaxation), documented and
opt-in.  Rows that stay CACHED are always current (the cache is
updated in place), so at ``hot_rows >= N`` the overlap mode is exact
again.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from hyperspace_tpu.data.prefetch import HostPrefetcher
from hyperspace_tpu.models import poincare_embed as pe
from hyperspace_tpu.parallel.host_table import DeviceHotCache, HostEmbedTable
from hyperspace_tpu.telemetry import registry as _telem
from hyperspace_tpu.telemetry import trace as _trace
from hyperspace_tpu.telemetry.trace import span as _span
from hyperspace_tpu.train.telemetry import StepPhases

DEFAULT_CHUNK_STEPS = 8

# largest table the CLI will materialize back onto the device for the
# closing eval (`HostPlannedTrainer.to_state`) — past this the whole
# point of the host-resident path is that the table does NOT fit, so
# eval is skipped and the sharded master save is the run's product
EVAL_MAX_ROWS = 1 << 21


def auto_hot_rows(cfg: pe.PoincareEmbedConfig, chunk_steps: int) -> int:
    """Default cache capacity: the chunk's worst-case working set
    (every id distinct), capped at the table — small tables fit whole."""
    worst = int(chunk_steps) * cfg.batch_size * (2 + cfg.neg_samples)
    return min(cfg.num_nodes, worst)


def chunk_plan_np(cfg: pe.PoincareEmbedConfig, pairs: np.ndarray,
                  steps: int, seed: int, chunk_index: int):
    """Host-drawn batches + sparse plans for chunk ``chunk_index`` —
    deterministic in ``(cfg, pairs, steps, seed, chunk_index)``, so the
    host-resident and in-HBM trainers consume IDENTICAL plans (the
    bitwise contract's precondition)."""
    rng = np.random.default_rng((int(seed), int(chunk_index)))
    b, k = cfg.batch_size, cfg.neg_samples
    batch = pairs[rng.integers(0, len(pairs), (steps, b))]    # [S, B, 2]
    neg = rng.integers(0, cfg.num_nodes, (steps, b, k))
    return pe.plan_arrays_np(cfg, batch[..., 0], batch[..., 1], neg)


def _chunk_sizes(steps: int, chunk_steps: int) -> list[int]:
    sizes = [chunk_steps] * (steps // chunk_steps)
    if steps % chunk_steps:
        sizes.append(steps % chunk_steps)  # one ragged tail chunk
    return sizes


class HostPlannedTrainer:
    """Drives the per-chunk protocol above over one host master table.

    ``master`` holds PACKED rows (``pack_state`` layout: table alone
    for rsgd, table | mu | nu for radam); ``aux``/``key``/``step`` are
    the packed state's non-row leaves.  Build from a live
    :class:`~hyperspace_tpu.models.poincare_embed.TrainState` with
    :meth:`from_state` (small/medium tables), or hand a pre-built
    sharded master directly (the 10M-row bench path).
    """

    def __init__(self, cfg: pe.PoincareEmbedConfig, opt,
                 master: HostEmbedTable, aux, key, step=0, *,
                 chunk_steps: int = DEFAULT_CHUNK_STEPS,
                 hot_rows: int = 0, seed: int = 0,
                 gather_ahead: bool = False, prefetch_depth: int = 2,
                 profile: bool = False, phases: StepPhases = None):
        if master.num_rows != cfg.num_nodes:
            raise ValueError(
                f"master has {master.num_rows} rows; cfg.num_nodes is "
                f"{cfg.num_nodes}")
        pe._check_neg_mode(cfg, dense=False)
        self.cfg, self.opt, self.master = cfg, opt, master
        self.aux, self.key = aux, jnp.asarray(key)
        self.step = jnp.asarray(step, jnp.int32)
        self.chunk_steps = int(chunk_steps)
        if self.chunk_steps < 1:
            raise ValueError(f"chunk_steps must be >= 1; got {chunk_steps}")
        self.hot_rows = int(hot_rows) or auto_hot_rows(cfg, self.chunk_steps)
        self.seed = int(seed)
        self.gather_ahead = bool(gather_ahead)
        self.prefetch_depth = int(prefetch_depth)
        # per-chunk phase timers (train/telemetry.py): data_wait /
        # host_gather / device_step / write_back histograms; profile=
        # makes device_step block on the chunk's output (honest
        # execution window — the CLI's profile_steps= flag)
        self.phases = phases or StepPhases(profile=profile)
        self.cache = DeviceHotCache(master, self.hot_rows)
        # ONE local config per capacity: the chunk program's static
        # num_nodes is the cache size C (remapped sentinel = C), so
        # every chunk shares one executable per plan shape
        self._cfg_local = dataclasses.replace(
            cfg, num_nodes=self.cache.capacity)

    @classmethod
    def from_state(cls, cfg: pe.PoincareEmbedConfig, opt,
                   state: pe.TrainState, *, shards: int = 1,
                   **kw) -> "HostPlannedTrainer":
        """Pack a live TrainState's rows into a host master (row-
        sharded ``shards`` ways) — the entry for tables that still fit
        on one device; big tables build the master directly."""
        p = pe.pack_state(cfg, state)
        master = HostEmbedTable.from_array(np.asarray(p.packed), shards)
        return cls(cfg, opt, master, p.aux, p.key, p.step, **kw)

    # --- the per-chunk protocol ----------------------------------------------

    def _make_chunk(self, chunk_index: int, steps: int):
        """Prefetcher body: plan + union on host; under ``gather_ahead``
        also the (possibly stale, bounded by the look-ahead) row gather."""
        plan = chunk_plan_np(self.cfg, self._pairs, steps, self.seed,
                             chunk_index)
        uniq = plan[3]
        chunk_ids = np.unique(uniq)
        chunk_ids = chunk_ids[chunk_ids < self.cfg.num_nodes]
        rows = self.master.gather(chunk_ids) if self.gather_ahead else None
        return plan, chunk_ids, rows

    def _run_chunk(self, item) -> np.ndarray:
        plan, chunk_ids, pre_rows = item
        cap = self.cache.capacity
        with self.phases.phase("host_gather"):
            if pre_rows is None:
                slots = self.cache.ensure(chunk_ids)
            else:
                slots = self.cache.ensure_with_rows(
                    chunk_ids, pre_rows, np.ones(len(chunk_ids), bool))
        u_idx, v_idx, neg_idx, uniq, inv_map, order, seg = plan
        # remap global rows -> cache slots; the sentinel (num_nodes)
        # becomes the local sentinel C (gather clamps, scatter drops)
        pos = np.minimum(np.searchsorted(chunk_ids, uniq),
                         max(len(chunk_ids) - 1, 0))
        local_uniq = np.where(uniq >= self.cfg.num_nodes, cap,
                              slots[pos]).astype(np.int32)
        dev_plan = pe.SparsePlan(*(jnp.asarray(a) for a in (
            u_idx, v_idx, neg_idx, local_uniq, inv_map, order, seg)))
        pstate = pe.PackedState(self.cache.array, self.aux, self.key,
                                self.step)
        # device_step: in profile mode the phase blocks on the updated
        # cache (the chunk's donated output) before closing, so the
        # window is execution, not enqueue; default mode adds no sync
        with self.phases.phase("device_step", lambda: out.packed):
            with _span("host_chunk_dispatch"):
                out, losses = pe.train_epoch_planned_hosted(
                    self._cfg_local, self.opt, pstate, dev_plan)
        self.cache.array = out.packed
        self.aux, self.key, self.step = out.aux, out.key, out.step
        # chunk-boundary write-back: the master is current before the
        # next chunk's gather (and before any eviction could drop the
        # only fresh copy)
        with self.phases.phase("write_back"):
            self.master.write_back(chunk_ids, self.cache.fetch(slots))
        _telem.inc("host_table/chunks")
        return np.asarray(losses)

    def run(self, pairs, steps: int) -> np.ndarray:
        """Train ``steps`` steps in chunks; returns the [steps] losses.

        Plans are built (and under ``gather_ahead`` rows gathered) in a
        background :class:`HostPrefetcher` thread, ``prefetch_depth``
        chunks ahead of the device."""
        self._pairs = np.asarray(pairs)
        sizes = _chunk_sizes(int(steps), self.chunk_steps)
        if not sizes:
            return np.zeros((0,), np.float32)
        losses = []
        startup_open = _trace.startup_open()
        with HostPrefetcher(
                lambda i: self._make_chunk(i, sizes[i]),
                depth=self.prefetch_depth) as pf:
            for _ in sizes:
                # data_wait: blocking on the prefetcher — near zero
                # while the planner keeps ahead of the device
                with self.phases.phase("data_wait"):
                    item = pf.next()
                losses.append(self._run_chunk(item))
                if startup_open:
                    # this trainer has no run_loop: the process's
                    # start-up timeline ends at its first chunk's return
                    _trace.close_startup()
                    startup_open = False
        return np.concatenate(losses)

    def to_state(self) -> pe.TrainState:
        """Materialize the master back into a device TrainState — the
        small-table eval/export path only (a beyond-HBM table must stay
        on host; use the master directly)."""
        host = self.master.to_array()
        packed = jnp.asarray(host)  # hyperlint: disable=full-table-materialization — documented small-table eval/export exit; beyond-HBM callers keep the master host-resident
        return pe.unpack_state(self.cfg, pe.PackedState(
            packed, self.aux, self.key, self.step))


def run_planned_inhbm(cfg: pe.PoincareEmbedConfig, opt,
                      state: pe.TrainState, pairs, steps: int, *,
                      chunk_steps: int = DEFAULT_CHUNK_STEPS,
                      seed: int = 0) -> tuple[pe.TrainState, np.ndarray]:
    """The in-HBM reference: the SAME per-chunk plans
    (:func:`chunk_plan_np`) through the packed-planned device program
    over the full resident table — the bitwise baseline the host-
    resident path is tested against, and the bench's in-HBM step-time
    leg."""
    pairs = np.asarray(pairs)
    p = pe.pack_state(cfg, state)
    losses = []
    for ci, s in enumerate(_chunk_sizes(int(steps), int(chunk_steps))):
        plan = pe.SparsePlan(*(jnp.asarray(a) for a in chunk_plan_np(
            cfg, pairs, s, seed, ci)))
        p, chunk_losses = pe.train_epoch_planned_packed(cfg, opt, p, plan)
        losses.append(np.asarray(chunk_losses))
    return pe.unpack_state(cfg, p), np.concatenate(losses)
