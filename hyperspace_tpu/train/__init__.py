"""L4 train runtime (SURVEY.md §1b): chunked-dispatch loop,
checkpointing, metrics, profiling."""

from hyperspace_tpu.telemetry.trace import importing

# (the checkpoint module brings orbax in, for a run with no ckpt_dir too:
# the span says what that costs)
with importing(__name__):
    from hyperspace_tpu.train.checkpoint import CheckpointManager  # noqa: F401
    from hyperspace_tpu.train.logging import MetricsLogger  # noqa: F401
    from hyperspace_tpu.train.loop import (  # noqa: F401
        make_chunked_stepper,
        run_loop,
    )
    from hyperspace_tpu.train.profiling import benchmark_step  # noqa: F401
