"""Run-wide telemetry: trace spans, counter registry, numerical health.

The observability layer the chunked-dispatch loop (PR 1) made necessary:
K steps vanish into one ``lax.scan`` dispatch, prefetch and prep-cache
activity happens on background threads, and the only run artifact is a
JSONL of losses.  This package adds, with zero per-step host sync and
~zero cost when disabled (the default):

- :mod:`trace` — nested host wall-clock spans (``span("dispatch")``)
  aggregated into ``span/*`` JSONL fields per log boundary, plus a
  Chrome/Perfetto ``trace_events`` dump (``trace_out=`` on the CLI);
- :mod:`registry` — process-wide named counters/gauges (prep-cache
  hit/miss, prefetch stalls/queue depth, dispatches, recompiles via
  ``jax.monitoring``, checkpoint saves/seconds/bytes), snapshotted as
  ``ctr/*`` into every log record and a final ``telemetry_summary``;
- :mod:`histogram` — streaming latency histograms (``observe(name,
  ms)``: fixed log buckets, ~5% quantile error, mergeable snapshots),
  surfaced as ``hist/*`` entries (count/sum/min/max/p50..p99) in the
  same snapshots — the p50/p95/p99 layer the serve SLOs stand on;
- :mod:`health` — on-device hyperbolic numerical-health stats (ball
  boundary margin, hyperboloid constraint residual, nonfinite counts),
  sampled every ``health_every=`` chunks and threshold-checked.

Catalog + reading guide: docs/observability.md.
"""

import contextlib

# the tracer first: its import opens the process's start-up timeline,
# so that jax's import, if this package is what brings it in, has a span
from hyperspace_tpu.telemetry.trace import (  # noqa: F401
    Tracer,
    default_tracer,
    importing,
    span,
)

with importing("jax"):
    import jax  # noqa: F401

from hyperspace_tpu.telemetry.health import (  # noqa: F401
    HealthMonitor,
    health_stats,
    make_health_fn,
)


@contextlib.contextmanager
def cli_session(telemetry: bool, trace_out, *, stream=None):
    """The CLI entry points' shared telemetry bracket (train and serve).

    Enables span recording up front (BEFORE the workload, so host prep
    lands in the trace; jax's compile events are counted from the
    package's import on), and in a ``finally`` ends the process's
    start-up timeline if the workload has not (a run that got to no
    loop, or crashed on its way there: its set-up spans still lead the
    dump) and dumps the Chrome trace — a crashed run must still produce
    its trace, and an OSError from the dump must never mask the
    exception this block may be unwinding — then disables recording.
    ``stream`` is where the dump notices print (train: stdout, serve:
    stderr — serve's stdout is a strict response stream)."""
    from hyperspace_tpu.telemetry import trace as _trace

    if telemetry or trace_out:
        _trace.enable(keep_events=bool(trace_out))
    try:
        yield
    finally:
        _trace.close_startup()
        if trace_out:
            try:
                n = default_tracer().dump_chrome_trace(trace_out)
                print(f"[telemetry] {n} trace events -> {trace_out}",
                      file=stream, flush=True)
            except OSError as e:
                print(f"[telemetry] trace dump failed: {e!r}",
                      file=stream, flush=True)
        if telemetry or trace_out:
            _trace.disable()
from hyperspace_tpu.telemetry.exposition import (  # noqa: F401
    MetricsFileWriter,
    render_prometheus,
    sanitize_name,
)
from hyperspace_tpu.telemetry.histogram import (  # noqa: F401
    Histogram,
    HistogramSnapshot,
)
from hyperspace_tpu.telemetry.window import SloWindow  # noqa: F401
from hyperspace_tpu.telemetry.registry import (  # noqa: F401
    Registry,
    default_registry,
    install_jax_monitoring_hook,
    observe,
)
