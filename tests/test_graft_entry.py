"""__graft_entry__ device acquisition: the platform is whatever
``JAX_PLATFORMS`` resolves to, never a fallback.

- a fresh process asks for its n CPU devices before the backend comes
  up (``jax_num_cpu_devices``);
- a process whose backend is already up is served from it, untouched;
- too few devices is an error, not a switch to another platform.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_ensure_devices_too_few_raises():
    """A backend that is already up has a fixed device count: asking
    for more raises (naming the fix: a fresh process) instead of
    falling back to some other platform or clearing backends."""
    import jax
    import pytest

    import __graft_entry__ as g

    before = jax.devices()
    with pytest.raises(RuntimeError, match="fresh process"):
        g._ensure_devices(len(before) + 1)
    assert jax.devices() == before  # backend untouched


def test_ensure_devices_cpu_fresh_process():
    """A fresh process with JAX_PLATFORMS=cpu gets its n virtual CPU
    devices without XLA_FLAGS pre-set."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g\n"
         "d = g._ensure_devices(4)\n"
         "assert len(d) == 4 and d[0].platform == 'cpu', d\n"
         "print('CPU_OK', len(d))"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "CPU_OK 4" in proc.stdout


def test_ensure_devices_in_process():
    """In the test process (8 virtual CPU devices already up) the
    devices come from the existing backend, which stays as it was."""
    import jax

    import __graft_entry__ as g

    if len(jax.devices()) < 4:
        import pytest

        pytest.skip("needs 4 virtual devices")
    before = jax.devices()
    d = g._ensure_devices(4)
    assert len(d) == 4 and all(x.platform == "cpu" for x in d)
    assert jax.devices() == before  # backend untouched


def test_dryrun_bounded_timeout_emits_parseable_artifact(capsys):
    """A dryrun that outruns its budget emits a parseable
    budget_exhausted record (bench.py's sentinel shape, so bench_trend
    and any tail parser read it) and returns False — never a silent
    rc=124 loss."""
    import json
    import time

    import __graft_entry__ as g

    exits = []
    ok = g.run_dryrun_bounded(4, 0.2, _dryrun=lambda n: time.sleep(5),
                              _exit=exits.append)
    assert ok is False
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "budget_exhausted"
    assert rec["detail"]["lane"] == "dryrun_multichip"
    assert rec["detail"]["budget_s"] == 0.2
    assert exits == []  # SIGALRM path won; the watchdog never fired


def test_dryrun_bounded_success_emits_nothing(capsys):
    """A run that finishes inside the budget is transparent: no
    sentinel line, True back, the alarm disarmed."""
    import signal

    import __graft_entry__ as g

    ran = []
    ok = g.run_dryrun_bounded(4, 30.0, _dryrun=ran.append)
    assert ok is True and ran == [4]
    assert "budget_exhausted" not in capsys.readouterr().out
    # the deadline alarm was restored (no timer left pending)
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


def test_dryrun_budget_env_and_escape_hatch(monkeypatch):
    """MULTICHIP_BUDGET_S feeds the default; <= 0 runs unbounded."""
    import __graft_entry__ as g

    ran = []
    monkeypatch.setenv("MULTICHIP_BUDGET_S", "0")
    assert g.run_dryrun_bounded(4, _dryrun=ran.append) is True
    assert ran == [4]  # unbounded escape hatch still runs the lane
