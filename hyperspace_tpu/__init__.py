"""hyperspace_tpu — a TPU-native Riemannian-geometry deep-learning framework.

A from-scratch JAX/XLA/Pallas rebuild of the capabilities of the reference
``fbad/hyperspace`` framework (CUDA + NCCL; see /root/repo/SURVEY.md for the
evidence map): hyperbolic manifold math (Poincaré ball + Lorentz model, plus
Sphere/Euclidean/Product for mixed-curvature spaces), Riemannian SGD/Adam as
single XLA-compiled train steps, Pallas TPU kernels for the hot primitives,
and GSPMD sharding over a device mesh in place of NCCL all-reduce.

Layer map (SURVEY.md §1b):
  manifolds/  L0 pure-JAX manifold math (curvature is a traced value)
  kernels/    L1 Pallas TPU kernels + pure-JAX twins (fallback & test oracle)
  optim/      L2 Riemannian SGD / Adam (optax-style transforms)
  nn/         L3 hyperbolic layers (HypLinear, LorentzLinear, attention, ...)
  train/      L4 jitted train loop, Mesh/GSPMD sharding, checkpointing
  models/     L5 the five reference workloads
  data/       loaders (WordNet closure, graphs, MNIST, text)
  serve/      inference: frozen serving artifacts + batched query engine
"""

__version__ = "0.1.0"

# first: importing the tracer opens the process's start-up timeline
# (telemetry/trace.py)
from hyperspace_tpu.telemetry import trace as _trace

with _trace.importing("hyperspace_tpu.manifolds"):
    from hyperspace_tpu.manifolds import (  # noqa: F401
        Euclidean,
        Lorentz,
        PoincareBall,
        Product,
        Sphere,
    )

# jax is in by now: its trace / lower / compile events are counted, and
# are spans of the start-up timeline, from here on
from hyperspace_tpu.telemetry import registry as _registry  # noqa: E402

_registry.install_jax_monitoring_hook()
