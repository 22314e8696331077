"""Train-plane step-phase telemetry: where a train step's time goes.

The serve plane decomposes a request into stages
(``telemetry/spans.py``); this module is the train-plane mirror for
the host-resident chunk protocol (``train/host_embed.py``) and the
chunked loop (``train/loop.py``).  Each chunk decomposes into the
:data:`PHASES`:

- ``data_wait`` — blocking on the :class:`~hyperspace_tpu.data.
  prefetch.HostPrefetcher` for the next chunk's plans (near zero when
  the prefetcher keeps ahead; the planner is the bottleneck when not),
- ``host_gather`` — ``DeviceHotCache.ensure``: the host→device
  transfer of the chunk's cold rows,
- ``device_step`` — the chunk's one fused dispatch.  Dispatch is async
  enqueue; in ``profile`` mode the phase blocks on the chunk's output
  (``jax.block_until_ready``) before closing, so the window times
  EXECUTION.  Off (the default), it times enqueue only and the wait
  surfaces in the next write_back/fetch — the production loop never
  pays an extra sync for telemetry,
- ``write_back`` — fetching the touched cache rows and scattering them
  into the host master.

Each phase observes a ``train/phase/<name>_ms`` registry histogram
(docs/observability.md "Train-plane phases"), so a training job with
``metrics_out=`` exposes its phase decomposition in the same
Prometheus families the serve plane does — and the multihost
aggregation hook (``parallel/multihost.gather_metric_exports``) merges
them across processes unchanged.

Each phase is also a trace span (``telemetry/trace.py``): with the
tracer on, the phases show as named ranges in the Chrome dump and in
any captured device profile.

Host-table cache effectiveness (hit/miss/evict counters and the
``host_table/cache_hit_rate`` gauge) ticks inside
``parallel/host_table.py`` itself; compile events come from
``telemetry.registry.install_jax_monitoring_hook`` (``jax/recompiles``,
``jax/compile_s``), which the package's import arms.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional

from hyperspace_tpu.telemetry import registry as telem
from hyperspace_tpu.telemetry.trace import span

# chunk-phase order: consecutive phases of one chunk never overlap, so
# their bounds are monotone in this order (tested)
PHASES = ("data_wait", "host_gather", "device_step", "write_back")


class StepPhases:
    """Per-chunk phase timers (module docstring).

    ``profile=True`` makes the ``device_step`` phase block on its
    output before closing (honest execution window — the bench/debug
    mode the CLI's ``profile_steps=`` flag arms).  The last chunk's
    readings stay on :attr:`last` (ms) and :attr:`last_bounds` (raw
    perf_counter pairs) for assertions and log records."""

    def __init__(self, profile: bool = False):
        self.profile = bool(profile)
        self.last: dict[str, float] = {}
        self.last_bounds: dict[str, tuple] = {}

    @contextlib.contextmanager
    def phase(self, name: str, block: Optional[Callable] = None):
        """Time one phase.  ``block`` is a thunk returning the device
        value(s) the phase produced — called (and blocked on) only in
        ``profile`` mode, AFTER the body, so late-bound locals are
        fine: ``with phases.phase("device_step", lambda: out.packed):``
        """
        t0 = time.perf_counter()
        try:
            with span(name):
                yield
                if self.profile and block is not None:
                    import jax

                    jax.block_until_ready(block())
        finally:
            t1 = time.perf_counter()
            self.last[name] = (t1 - t0) * 1e3
            self.last_bounds[name] = (t0, t1)
            # the phase histogram family (one per PHASES member):
            # telemetry-catalog: train/phase/data_wait_ms
            # telemetry-catalog: train/phase/host_gather_ms
            # telemetry-catalog: train/phase/device_step_ms
            # telemetry-catalog: train/phase/write_back_ms
            telem.observe(f"train/phase/{name}_ms", (t1 - t0) * 1e3)
