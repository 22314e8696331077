"""The two refusals that keep a number off the wrong hardware.  They sit
in a module of their own so that a CPU test of the harness can lift them
(and only a test does)."""

from __future__ import annotations


class Refused(RuntimeError):
    """The run may not produce a result."""


def require_chip(chips: int) -> dict:
    """The device as JAX reports it; refuses anything but ``chips`` TPU
    devices of a kind with published peaks."""
    import jax

    from benchmark.peaks import device_peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found platform {devs[0].platform!r}, not 'tpu'")
    if len(devs) != chips:
        raise Refused(f"{len(devs)} device(s) where the cell asks for "
                      f"{chips}")
    try:
        device_peaks(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e.args[0])) from None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_pallas() -> None:
    """Refuses a program whose kernels would run as anything but the
    Pallas kernels (an XLA twin, the interpreter)."""
    from hyperspace_tpu.kernels import _support

    if _support.mode() != "pallas":
        raise Refused(f"kernel mode {_support.mode()!r}: the benchmark "
                      "measures the Pallas kernels and nothing in their "
                      "place")
