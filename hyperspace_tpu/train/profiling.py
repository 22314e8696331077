"""Profiling & timing harness (SURVEY.md §5 "Tracing/profiling").

- ``benchmark_step``: wall-clock a jitted step with warmup +
  ``block_until_ready`` — the number the benchmark suite reports.
- ``trace``: context manager around ``jax.profiler`` producing an XPlane/
  Perfetto trace directory for TPU runs.
- ``compiled_cost``: XLA's own FLOP/bytes estimate for a jitted function —
  per-kernel cost visibility without hardware counters.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable

import jax


def benchmark_step(
    fn: Callable[[], Any],
    *,
    warmup: int = 3,
    iters: int = 20,
) -> dict:
    """Time ``fn()`` (must return jax arrays); returns seconds statistics.

    ``warmup=0`` is legal (an intentionally-cold first iteration —
    compile time lands in ``max_s``): the warmup barrier only runs when
    a warmup call produced something to wait on.
    """
    out = None
    for _ in range(warmup):
        out = fn()
    if out is not None:
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    n = len(times)
    return {
        "mean_s": sum(times) / n,
        "p50_s": times[n // 2],
        "min_s": times[0],
        "max_s": times[-1],
        "iters": n,
    }


@contextlib.contextmanager
def trace(log_dir: str):
    """jax.profiler trace (view with TensorBoard/Perfetto/xprof)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict — {} when the
    backend reports none (the call returns None there)."""
    return dict(compiled.cost_analysis() or {})


def compiled_cost(fn: Callable, *args, **kwargs) -> dict:
    """flops / bytes-accessed of the XLA executable for fn(*args) —
    the two keys every roofline consumer wants (absent where the
    backend offers no analysis)."""
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    cost = cost_analysis_dict(compiled)
    return {k: cost[k] for k in ("flops", "bytes accessed") if k in cost}
