"""Tier-1 multi-process smoke: a REAL 2-process × 2-virtual-device
``jax.distributed`` group over loopback, fast enough for every CI run
(one bounded group launch; the long kill/restart fault drill stays in
``test_multihost.py`` behind the ``slow`` marker).

Runs ``hyperspace_tpu.benchmarks.mh_worker --task pipeline`` once and
asserts the full pod story against its RESULT: group formation, the
per-host data plane (each process's addressable shards of the
assembled global batch hold exactly its owned rows — verified inside
the workers), bit-identical replicas across processes (digest exchange
behind a coordination barrier), the per-host-owned table checkpoint
with its process-0 manifest commit, and the process-0-gated artifact
export — then restores the 2-host checkpoint and loads the artifact
in THIS single process, closing the elastic-restore loop.
"""

import hashlib
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER_MOD = "hyperspace_tpu.benchmarks.mh_worker"


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
        [sys.executable, "-m", _WORKER_MOD, "--pid", str(pid),
         "--nprocs", str(nprocs), "--port", str(port),
         "--workdir", str(workdir), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_env())


def _run_group(nprocs, workdir, *extra, timeout=180):
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
def smoke(tmp_path_factory):
    """ONE 2-process pipeline run shared by every assertion below —
    the launch (not the checks) is the expensive part."""
    wd = tmp_path_factory.mktemp("mh_smoke")
    return _run_group(2, wd, "--task", "pipeline", "--steps", "3")


@pytest.mark.flaky  # a loaded CI host can starve the subprocess launch
def test_two_process_group_trains(smoke):
    assert smoke["processes"] == 2
    assert smoke["devices"] == 2  # per-process local devices
    losses = smoke["losses"]
    assert len(losses) == 3 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # descended


def test_data_plane_owns_disjoint_rows(smoke):
    """Each process assembled the global batch from only its own rows
    (asserted shard-by-shard inside the workers; the RESULT reports
    process 0's view)."""
    plane = smoke["data_plane"]
    assert plane["local_rows"] == [0, plane["batch_rows"] // 2]
    assert plane["local_shards"] == 2


def test_per_host_checkpoint_commits_and_restores_elastically(smoke):
    """The 2-host checkpoint (one shard item per host + process-0
    manifest) restores in THIS 1-process context, bit-identical to the
    table the workers trained."""
    from hyperspace_tpu.parallel import host_table as HT

    names = set(os.listdir(smoke["ckpt_dir"]))
    assert {"shard_00000.npy", "shard_00001.npy", HT.MANIFEST} <= names
    t = HT.HostEmbedTable.load_sharded(smoke["ckpt_dir"], shards=1)
    assert t.num_rows == smoke["num_rows"]
    sha = hashlib.sha256(
        np.ascontiguousarray(t.to_array()).tobytes()).hexdigest()
    assert sha == smoke["table_sha"]
    # per-host read path: process 0's owned range, read directly
    lo, hi = smoke["owned_rows_p0"]
    rows = HT.load_rows(smoke["ckpt_dir"], lo, hi)
    np.testing.assert_array_equal(rows, t.to_array()[lo:hi])


def test_export_is_single_committed_artifact(smoke):
    """Process-0-gated export: one committed artifact, loadable here,
    with the fingerprint every process agreed on."""
    from hyperspace_tpu.serve.artifact import is_committed, load_artifact

    assert is_committed(smoke["export_dir"])
    art = load_artifact(smoke["export_dir"])
    assert art.fingerprint == smoke["fingerprint"]
    assert art.table.shape[0] == smoke["num_rows"]
