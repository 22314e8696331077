"""No chip, no run: the harness as the driver starts it ends with a code
other than 0 and prints no result line, in seconds."""

import os
import shutil
import subprocess
import sys
import time

import pytest

from tests.benchmark.helpers import REPO

ARGS = ["--workload", "hgcn_att_arxiv_lp.fullgraph", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("HYPERSPACE_KERNELS", None)
    env.update(env_extra or {})
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, "benchmark/run.py"] + ARGS, cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=120)
    return p, time.monotonic() - t0


def test_a_cpu_is_refused_in_seconds():
    p, took = _run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr
    assert took < 60


def test_an_unknown_workload_is_refused():
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "no.such", "--seed", "1", "--seconds", "1"],
                       cwd=REPO, capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_the_benchmark_alone_is_refused(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has no program to measure."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, "tests", "benchmark"),
                    tmp_path / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p, _ = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_kernels_other_than_pallas_are_refused(mode, monkeypatch):
    from benchmark import guards

    monkeypatch.setenv("HYPERSPACE_KERNELS", mode)
    with pytest.raises(guards.Refused):
        guards.require_pallas()


def test_a_device_without_peaks_is_refused():
    from benchmark import guards

    with pytest.raises(guards.Refused):  # the test host's CPU
        guards.require_chip(1)
