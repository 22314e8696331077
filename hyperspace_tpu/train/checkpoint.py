"""Checkpoint / resume on orbax (SURVEY.md §5 "Checkpoint / resume").

Saves the full training state pytree — params, optimizer state (for
Riemannian Adam that includes the tangent moments *and* the step count
whose base points are the saved params themselves), PRNG key, step, and
any learned curvatures, since they all live inside the state pytree.

Restore applies an optional ``project`` function (manifold re-projection):
checkpoints written in one dtype and restored in another can drift off the
constraint surface, and re-projection is idempotent for clean restores
(SURVEY.md §5: "restore re-projects params onto their manifolds").

Async by default: `keep_period`-style retention is delegated to orbax's
CheckpointManager options.  The recovery model is restart-from-checkpoint
(XLA programs are fixed-topology; SURVEY.md §5 "Failure detection").
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from hyperspace_tpu.telemetry.trace import importing

with importing("orbax.checkpoint"):
    import orbax.checkpoint as ocp


class CheckpointManager:
    """Thin orbax wrapper pinned to this framework's conventions."""

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 3,
        save_interval_steps: int = 1,
        async_save: bool = True,
        save_retries: int = 2,
        retry_backoff_s: float = 0.05,
    ):
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        # transient-IO retry policy around save (docs/resilience.md):
        # save_retries EXTRA attempts, exponential backoff from
        # retry_backoff_s — always a bounded loop, never sleep-forever
        self._save_retries = max(int(save_retries), 0)
        self._retry_backoff_s = float(retry_backoff_s)
        self._clean_orphans()
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps,
            enable_async_checkpointing=async_save,
        )
        self._mgr = ocp.CheckpointManager(self._dir, options=options)

    def _clean_orphans(self) -> None:
        """Remove save debris a crashed process left behind: orbax
        staging dirs (``…orbax-checkpoint-tmp…``) and all-digit step
        dirs that fail the commit test.  A crash between staging write
        and the commit rename leaks exactly these shapes FOREVER (the
        retention policy only rotates committed steps), and an
        uncommitted dir shadows the resume scan's candidate list every
        restart.  Runs at init — before this manager has any save in
        flight; the single-writer assumption (one manager owns a
        checkpoint dir, as everywhere in this module) makes that safe.
        Cleanups are counted (``ckpt/orphans_cleaned``) and logged."""
        import shutil

        try:
            entries = os.listdir(self._dir)
        except OSError:
            return
        orphans = []
        for name in entries:
            path = os.path.join(self._dir, name)
            if "orbax-checkpoint-tmp" in name:
                orphans.append(path)
            elif (name.isdigit() and os.path.isdir(path)
                    and not _step_dir_committed(path)):
                orphans.append(path)
        if not orphans:
            return
        from hyperspace_tpu.telemetry import registry as telem

        cleaned = 0
        for path in orphans:
            try:
                if os.path.isdir(path):
                    shutil.rmtree(path)
                else:
                    os.remove(path)
                cleaned += 1
            except OSError as e:
                print(f"[ckpt] failed to clean orphan {path}: {e}",
                      flush=True)
        if cleaned:
            telem.inc("ckpt/orphans_cleaned", cleaned)
            print(f"[ckpt] cleaned {cleaned} orphaned staging "
                  f"dir(s) under {self._dir} (crash between staging "
                  "write and commit rename)", flush=True)

    def _fault_point(self, step: int) -> None:
        """The ``ckpt.save`` fault site (resilience/faults.py): chaos
        tests inject a transient IOError (absorbed by the retry loop),
        latency, or ``crash_staged`` — which materializes the exact
        on-disk debris a process killed between staging write and
        commit rename leaves (an uncommitted step dir + a staging dir),
        then raises InjectedCrash (NOT retried: a kill is not a
        transient)."""
        from hyperspace_tpu.resilience import faults

        spec = faults.due("ckpt.save")
        if spec is None:
            return
        if spec.kind == "latency":
            import time

            time.sleep(spec.ms / 1e3)
        elif spec.kind == "ioerror":
            raise faults.InjectedIOError("injected IOError at ckpt.save")
        elif spec.kind == "crash_staged":
            partial = os.path.join(self._dir, str(int(step)))
            os.makedirs(os.path.join(
                partial, "tmp.orbax-checkpoint-tmp-0"), exist_ok=True)
            os.makedirs(os.path.join(
                self._dir, f"{int(step)}.orbax-checkpoint-tmp-0"),
                exist_ok=True)
            raise faults.InjectedCrash(
                "injected crash between staging write and commit rename")

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        """Maybe-save (interval-gated); returns True if a save started.

        ``force=True`` bypasses the interval gate — used for the final
        step of a run, which must always land on disk regardless of
        where it falls in the save cadence.  Started saves bump
        ``ckpt/saves`` and accumulate the BLOCKING portion (orbax's
        synchronous device→host copy; the disk write is async) into
        ``ckpt/save_s`` — the number that says how much step time
        checkpointing steals (docs/observability.md).

        Transient ``OSError`` s (a flaky filesystem; the injected
        ``ckpt.save`` ioerror fault) are retried up to ``save_retries``
        extra attempts with exponential backoff (``ckpt/save_retries``
        counts them); past the budget the last error propagates —
        bounded by construction, per the ``unbounded-retry`` lint."""
        import time

        from hyperspace_tpu.resilience import faults
        from hyperspace_tpu.telemetry import registry as telem
        from hyperspace_tpu.telemetry.trace import default_tracer

        t0 = time.perf_counter()
        if not (force or self._mgr.should_save(step)):
            return False  # interval-gated skip: no copy, no fault point
        # snapshot the pytree BEFORE handing it to orbax: the async
        # machinery's device→host copy is NOT reliably complete when
        # save() returns (observed on this image's orbax 0.7.0 / CPU:
        # a donated stepper's next dispatch reuses the buffers and a
        # MID-RUN checkpoint silently holds a LATER step's content —
        # exactly the corruption a rollback target must never have).
        # One device copy per STARTED save; interval-gated skips above
        # pay nothing.
        state = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a).copy(), state)
        for attempt in range(self._save_retries + 1):
            try:
                if faults.active():
                    self._fault_point(step)
                started = self._mgr.save(
                    step, args=ocp.args.StandardSave(state), force=force)
                break
            except OSError as e:
                if attempt >= self._save_retries:
                    raise
                telem.inc("ckpt/save_retries")
                delay = self._retry_backoff_s * (2 ** attempt)
                print(f"[ckpt] save step {step} attempt {attempt + 1} "
                      f"failed ({e}); retrying in {delay:.3g}s",
                      flush=True)
                time.sleep(delay)
        t1 = time.perf_counter()
        if started:
            # counter and span recorded together, and ONLY for saves
            # that actually started — an interval-gated skip is a no-op
            # in both metrics, so ckpt/saves and span/ckpt_save_n agree
            telem.inc("ckpt/saves")
            telem.inc("ckpt/save_s", t1 - t0)
            # the distribution behind the sum: a single slow save (a
            # cold filesystem, a huge state) shows in ckpt/save_ms p99
            # where the counter only shows a bigger total
            telem.observe("ckpt/save_ms", (t1 - t0) * 1e3)
            tracer = default_tracer()
            if tracer.enabled:
                tracer.record_span("ckpt_save", t0, t1,
                                   args={"step": int(step)})
        return started

    def restore(
        self,
        state_like: Any,
        *,
        step: Optional[int] = None,
        project: Optional[Callable[[Any], Any]] = None,
    ) -> tuple[Any, int]:
        """Restore (state, step); ``state_like`` supplies structure/shapes.

        With ``step=None`` the target is :meth:`latest_committed_step`,
        NOT orbax's ``latest_step()`` — orbax trusts any all-digit dir,
        including an interrupted save's empty one, and restoring that
        would crash (or worse, desync from the resume-offset accounting
        ``peek_latest_step`` derived from the committed step)."""
        step = self.latest_committed_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self._dir}")
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(state_like))
        if project is not None:
            restored = project(restored)
        return restored, step

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def latest_committed_step(self) -> Optional[int]:
        """Newest step dir that passes the commit test (the SAME scan
        ``peek_latest_step`` runs) — the restore target and the CLI's
        resume-offset source must agree on which step is real, or an
        interrupted save desyncs stream accounting from the restored
        step (ADVICE r5)."""
        return _latest_committed_step(self._dir)

    def wait(self):
        """Block until async saves land (call before process exit).

        Once everything is on disk, the ``ckpt/bytes`` gauge is set to
        the directory's total size — bytes are only meaningful after
        the async writes commit, so this is the one place to count.
        The recursive size walk only runs while a telemetry run has the
        tracer enabled; the default (telemetry off) pays nothing."""
        self._mgr.wait_until_finished()
        from hyperspace_tpu.telemetry.trace import default_tracer

        if default_tracer().enabled:
            from hyperspace_tpu.telemetry import registry as telem

            telem.set_gauge("ckpt/bytes", dir_bytes(self._dir))

    def close(self):
        self._mgr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.wait()
        self.close()


def restore_params_only(directory: str, *, step: Optional[int] = None
                        ) -> tuple[Any, int]:
    """Restore a checkpoint's raw state pytree WITHOUT constructing
    optimizer state — the serving-export path (``serve/artifact.py``).

    With ``step=None`` the target is the newest COMMITTED step (the same
    scan :meth:`CheckpointManager.latest_committed_step` runs, so an
    interrupted save's uncommitted dir is never trusted).  The restore
    goes through orbax's template-free ``StandardRestore``: the caller
    needs NO ``state_like`` pytree, hence no optimizer/model objects —
    NamedTuple states come back as plain dicts keyed by field name
    (``tree["table"]``, ``tree["params"]["c_raw"]``, ...).  Returns
    ``(tree, step)``.  Raises ``FileNotFoundError`` when no committed
    checkpoint exists under ``directory``.
    """
    directory = os.path.abspath(directory)
    if step is None:
        step = _latest_committed_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no committed checkpoint under {directory}")
    elif not _step_dir_committed(os.path.join(directory, str(int(step)))):
        # the never-trust-an-uncommitted-dir rule holds for pinned steps
        # too — an interrupted save must not become a serving artifact
        raise FileNotFoundError(
            f"step {step} under {directory} is missing or uncommitted")
    mgr = ocp.CheckpointManager(directory)
    try:
        tree = mgr.restore(step, args=ocp.args.StandardRestore())
    finally:
        mgr.close()
    return tree, step


def dir_bytes(directory: str) -> int:
    """Total bytes on disk under ``directory`` (0 on any OS error).

    The per-file try/except is load-bearing, not defensive boilerplate:
    this walks the checkpoint dir WHILE the async save thread is
    renaming staging dirs and the retention policy is deleting old
    steps, so a file listed by ``os.walk`` can be gone (or mid-rename)
    by the time ``getsize`` stats it — ``FileNotFoundError`` (and any
    other ``OSError``) skips that file instead of sinking the gauge.
    """
    total = 0
    try:
        for root, _dirs, files in os.walk(directory):
            for name in files:
                try:
                    total += os.path.getsize(os.path.join(root, name))
                except OSError:  # incl. FileNotFoundError: deleted mid-scan
                    pass
    except OSError:
        pass
    return total


def _step_dir_committed(path: str) -> bool:
    """Whether a candidate step dir holds a COMMITTED save, judged the
    way orbax's ``latest_step()`` would: orbax writes into a
    ``…orbax-checkpoint-tmp…`` staging name and renames on commit, so an
    interrupted save leaves either no all-digit dir at all or an
    empty/partial one.  Structural test first (non-empty, no staging
    markers inside — orbax's own ``is_checkpoint_finalized`` passes an
    EMPTY dir, which is exactly the interrupted-save shape to reject),
    then orbax's finalization check on top when the installed version
    exposes it."""
    try:
        entries = os.listdir(path)
    except OSError:
        return False
    if not entries or any("orbax-checkpoint-tmp" in e for e in entries):
        return False
    try:
        from orbax.checkpoint import utils as ocp_utils

        return bool(ocp_utils.is_checkpoint_finalized(path))
    except Exception:  # noqa: BLE001 — version drift: structural verdict
        return True


def _latest_committed_step(directory: str) -> Optional[int]:
    """Newest all-digit step dir under ``directory`` that passes
    :func:`_step_dir_committed` — the ONE scan behind both
    ``peek_latest_step`` (resume-offset accounting) and
    ``CheckpointManager.latest_committed_step`` (restore target), so the
    two can never disagree on which step is real."""
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    for s in sorted((int(n) for n in names if n.isdigit()), reverse=True):
        if _step_dir_committed(os.path.join(directory, str(s))):
            return s
    return None


def peek_latest_step(directory: str) -> int:
    """Latest COMMITTED checkpoint step under ``directory``, 0 if none —
    WITHOUT opening a full manager (no async machinery, nothing created
    on disk).  Used by the CLI to derive resume offsets (e.g. the
    sampled stream's starting chunk) before the training loop restores.

    Candidate all-digit dirs are validated with the same commit test
    orbax's ``latest_step()`` applies (ADVICE r5): after an interrupted
    save the newest dir can be uncommitted, and trusting it would derive
    ``start_chunk`` from a newer step than the one the loop actually
    restores — chunks skipped, consumed-batch accounting drifting from
    the restored step.  Uncommitted candidates are skipped in favor of
    the next older committed one."""
    step = _latest_committed_step(os.path.abspath(directory))
    return 0 if step is None else step


def reproject_params(tags, params):
    """Build a ``project`` fn argument from a manifold tag tree: re-projects
    every manifold-tagged leaf, passes Euclidean leaves through."""
    from hyperspace_tpu.optim.tags import map_tagged

    def apply(tree):
        return map_tagged(
            lambda t, p: p if t is None else t.proj(p), tags, tree)

    return apply
