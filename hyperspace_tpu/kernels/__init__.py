"""Pallas TPU kernel layer — TPU-native equivalents of the reference's
CUDA kernels N1-N7 (SURVEY.md §2 native inventory).

Every op in this package:

- runs as a **Pallas (Mosaic) kernel** when the default backend is TPU;
- runs its **pure-JAX twin** (the manifold-math oracle) on CPU/GPU;
- can be forced with ``HYPERSPACE_KERNELS={auto,pallas,interpret,xla}``
  (``interpret`` = Pallas interpreter on CPU, used by the parity tests);
- differentiates through the twin via ``custom_vjp`` (rematerializing
  backward — the TPU-idiomatic FLOPs-for-HBM trade).
"""

from hyperspace_tpu.telemetry.trace import importing

# (this is where jax.experimental.pallas comes in)
with importing(__name__):
    from hyperspace_tpu.kernels._support import mode
    from hyperspace_tpu.kernels.distmat import lorentz_pdist, poincare_pdist
    from hyperspace_tpu.kernels.attention import (flash_attention,
                                                  flash_dot_attention)
    from hyperspace_tpu.kernels.hyplinear import hyp_linear
    from hyperspace_tpu.kernels.mlr import hyp_mlr
    # the fused scan-top-k lives at hyperspace_tpu.kernels.scan_topk
    # (module-level API: scan_topk / scan_topk_cand / supports /
    # fused_tile_rows) — NOT re-exported here: the entry point shares the
    # module's name, and a function attribute would shadow the submodule
    from hyperspace_tpu.kernels import scan_topk  # noqa: F401 — submodule export
    from hyperspace_tpu.kernels.pointwise import (
        expmap,
        expmap0,
        logmap,
        logmap0,
        mobius_add,
        mobius_scalar_mul,
        ptransp,
    )

__all__ = [
    "mode",
    "mobius_add",
    "mobius_scalar_mul",
    "expmap",
    "logmap",
    "expmap0",
    "logmap0",
    "ptransp",
    "poincare_pdist",
    "lorentz_pdist",
    "hyp_mlr",
    "hyp_linear",
    "flash_attention",
    "flash_dot_attention",
    "scan_topk",
]
