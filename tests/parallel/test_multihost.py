"""Multi-host DP over loopback processes (SURVEY.md §4.6) and the
kill-one-host → restart-from-checkpoint fault drill (SURVEY.md §5
"Failure detection / elastic recovery").

Spawns real OS processes each running tests/parallel/_mh_worker.py with
``jax.distributed`` over 127.0.0.1 (2 processes × 2 virtual CPU devices
= a 2×2 host×data mesh), so the cross-process collective path — the
TPU-native stand-in for the reference's NCCL group — is exercised for
real, not simulated.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "_mh_worker.py")

# real OS-process spawns + distributed init: inherently slow (>1 min total)
pytestmark = pytest.mark.slow


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # workers set their own device count
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    extra = env.get("PYTHONPATH")  # no empty entry (= cwd) when unset
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root] + (extra.split(os.pathsep) if extra else []))
    return env


def _launch(pid, nprocs, port, workdir, *extra):
    return subprocess.Popen(
        [sys.executable, _WORKER, "--pid", str(pid), "--nprocs", str(nprocs),
         "--port", str(port), "--workdir", str(workdir), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_env())


def _run_group(nprocs, workdir, *extra, timeout=240):
    """Run an nprocs group to completion; return pid-0's RESULT dict."""
    port = _free_port()
    procs = [_launch(p, nprocs, port, workdir, *extra) for p in range(nprocs)]
    outs = []
    try:
        for pr in procs:
            out, _ = pr.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for pr in procs:
            pr.kill()
        raise AssertionError(
            "multihost group timed out\n" + "\n".join(outs))
    for pr, out in zip(procs, outs):
        assert pr.returncode == 0, f"worker failed:\n{out}"
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                return json.loads(line[len("RESULT "):])
    raise AssertionError("no RESULT line\n" + "\n".join(outs))


@pytest.fixture(scope="module")
def ref_result(tmp_path_factory):
    """Uninterrupted 2-process run — the drill's ground truth."""
    wd = tmp_path_factory.mktemp("mh_ref")
    return _run_group(2, wd, "--steps", "6", "--ckpt-every", "2")


def test_two_process_dp_trains(ref_result):
    assert ref_result["devices"] == 4  # 2 procs × 2 virtual devices
    assert ref_result["loss"] < 1.0    # descended from ~14 at w=0
    assert np.all(np.isfinite(ref_result["params"]))


def test_single_process_matches_two_process(ref_result, tmp_path):
    res1 = _run_group(1, tmp_path, "--steps", "6", "--ckpt-every", "2")
    np.testing.assert_allclose(res1["params"], ref_result["params"],
                               rtol=1e-5, atol=1e-6)


def test_kill_one_host_restart_from_checkpoint(ref_result, tmp_path):
    """The SURVEY.md §5 recovery model, end to end: process 1 dies after
    the step-4 checkpoint commits; the survivor is torn down (the cluster
    manager's job); both restart with --resume and must reproduce the
    uninterrupted run exactly."""
    port = _free_port()
    procs = [_launch(p, 2, port, tmp_path, "--steps", "6", "--ckpt-every",
                     "2", "--crash-at", "4") for p in range(2)]
    try:
        out1, _ = procs[1].communicate(timeout=240)
    except subprocess.TimeoutExpired:
        for pr in procs:
            pr.kill()
            pr.wait()
        raise AssertionError("victim hung instead of crashing")
    assert procs[1].returncode == 7, f"victim did not crash as planned:\n{out1}"
    # survivor hangs on the next collective — failure detection kills it
    try:
        procs[0].communicate(timeout=10)
    except subprocess.TimeoutExpired:
        procs[0].kill()
        procs[0].wait()

    resumed = _run_group(2, tmp_path, "--steps", "6", "--ckpt-every", "2",
                         "--resume")
    np.testing.assert_allclose(resumed["params"], ref_result["params"],
                               rtol=1e-6, atol=1e-7)


def test_two_process_hgcn_sharded_step(tmp_path):
    """The north-star workload's mesh step (make_node_sharded_step_lp, fed
    through multihost.distribute_batch) trains over a real 2-process
    host×data mesh — the encoder's exchange and the gradient all-reduce
    cross the process boundary inside XLA."""
    res = _run_group(2, tmp_path, "--steps", "5", "--hgcn")
    assert res["devices"] == 4
    losses = res["losses"]
    assert len(losses) == 5 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
