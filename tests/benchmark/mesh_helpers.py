"""Shared by the CPU tests of the four-chip cell: the suite's virtual
devices cut to four, so that ``auto_mesh`` picks the mesh it picks on a
four-chip host (``data=2 x model=2``).  The cell's two calibrated
faults are ``benchmark/mesh_faults.py``'s."""

from __future__ import annotations

from tests.benchmark import helpers

CELL = "hgcn_magcite_lp.mesh4"


def four_devices(monkeypatch, real=None):
    import jax

    real = real or jax.devices
    if len(real()) < 4:
        import pytest

        pytest.skip("needs four virtual devices")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:4])


def lift_refusals(monkeypatch):
    """``helpers.lift_refusals`` with four devices shown, not one."""
    import jax

    real = jax.devices
    helpers.lift_refusals(monkeypatch)
    four_devices(monkeypatch, real)
