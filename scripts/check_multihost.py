#!/usr/bin/env python
"""Smoke lint: the pod train→checkpoint→restore→export→serve loop,
with a REAL 2-process ``jax.distributed`` group over loopback.

Launches ``hyperspace_tpu.benchmarks.mh_worker --task pipeline`` as a
2-process × 2-virtual-device group (the per-host data plane, the
digest-exchange replica consistency check, the per-host-owned table
checkpoint and the process-0-gated export all run inside the workers),
then closes the elastic loop in THIS single process.  Asserted (exit 1
on any miss):

- both workers exit 0 and process 0 prints one parseable RESULT line
  with finite, descending losses;
- the 2-host checkpoint (one ``.npy`` shard per host + process-0
  manifest) restores here at 1 process, bit-identical to the table the
  fleet trained (``table_sha`` match) — restore across a DIFFERENT
  process count than wrote it;
- ``load_rows`` of process 0's owned range matches the restored slice
  (the per-host partial-read path);
- the exported artifact is committed, loads here, and its fingerprint
  matches what every worker verified;
- re-exporting the RESTORED table from this single process reproduces
  the SAME fingerprint — a pod run and a single-host run yield
  interchangeable serving artifacts;
- ``QueryEngine.from_artifact`` answers a top-k query over it.

Run by ``tests/parallel/test_check_multihost_script.py`` inside the
suite (mirroring ``check_serve_artifact.py``), so a pod-loop
regression fails the build.
"""

from __future__ import annotations

import hashlib
import json
import os
import socket
import subprocess
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable as a plain script from anywhere (the package is not installed)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_WORKER_MOD = "hyperspace_tpu.benchmarks.mh_worker"
NPROCS = 2
STEPS = 3
K = 5


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # workers set their own device count
    extra = env.get("PYTHONPATH")  # no empty entry (= cwd) when unset
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + (extra.split(os.pathsep) if extra else []))
    return env


def run_group(workdir: str, *extra: str, nprocs: int = NPROCS,
              timeout: int = 180):
    """Run an nprocs worker group to completion; return (rc_fail_text,
    RESULT dict) — exactly one of the two is None."""
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, "-m", _WORKER_MOD, "--pid", str(p),
         "--nprocs", str(nprocs), "--port", str(port),
         "--workdir", workdir, *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_env()) for p in range(nprocs)]
    outs = []
    try:
        for pr in procs:
            out, _ = pr.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for pr in procs:
            pr.kill()
            pr.wait()
        return "GROUP TIMED OUT\n" + "\n".join(outs), None
    for pr, out in zip(procs, outs):
        if pr.returncode != 0:
            return (f"WORKER rc={pr.returncode}:\n{out}", None)
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                return None, json.loads(line[len("RESULT "):])
    return "NO RESULT LINE\n" + "\n".join(outs), None


def _sha(a) -> str:
    import numpy as np

    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def main(out_dir: str | None = None) -> int:
    import numpy as np

    tmp = None
    if out_dir is None:
        tmp = tempfile.TemporaryDirectory()
        out_dir = tmp.name
    try:
        fail, res = run_group(out_dir, "--task", "pipeline",
                              "--steps", str(STEPS))
        if fail is not None:
            print(fail)
            return 1
        losses = res["losses"]
        if (res["processes"] != NPROCS or len(losses) != STEPS
                or not np.all(np.isfinite(losses))
                or not losses[-1] < losses[0]):
            print(f"FLEET DID NOT TRAIN: {res}")
            return 1

        from hyperspace_tpu.parallel import host_table as HT

        # elastic restore: the 2-host checkpoint, read at 1 process
        t = HT.HostEmbedTable.load_sharded(res["ckpt_dir"], shards=1)
        arr = t.to_array()
        if _sha(arr) != res["table_sha"]:
            print(f"RESTORE NOT BITWISE: restored sha {_sha(arr)} != "
                  f"fleet table sha {res['table_sha']}")
            return 1
        lo, hi = res["owned_rows_p0"]
        rows = HT.load_rows(res["ckpt_dir"], lo, hi)
        if rows.tobytes() != arr[lo:hi].tobytes():
            print(f"PER-HOST READ PATH DIVERGES on rows [{lo}, {hi})")
            return 1

        from hyperspace_tpu.serve import QueryEngine
        from hyperspace_tpu.serve.artifact import (export_artifact,
                                                   is_committed,
                                                   load_artifact)

        if not is_committed(res["export_dir"]):
            print(f"EXPORT NOT COMMITTED: {res['export_dir']}")
            return 1
        art = load_artifact(res["export_dir"])
        if art.fingerprint != res["fingerprint"]:
            print(f"ARTIFACT FINGERPRINT {art.fingerprint} != fleet's "
                  f"{res['fingerprint']}")
            return 1

        # export parity: the restored table, exported HERE at 1
        # process, must fingerprint identically to the pod's export
        solo_dir = os.path.join(out_dir, "artifact_solo")
        solo = export_artifact(solo_dir, arr, art.manifold_spec,
                               model_config=art.model_config,
                               overwrite=True)
        if solo.fingerprint != art.fingerprint:
            print(f"EXPORT PARITY BROKEN: single-process re-export "
                  f"fingerprint {solo.fingerprint} != pod export "
                  f"{art.fingerprint}")
            return 1

        eng = QueryEngine.from_artifact(art)
        ids, dists = (np.asarray(a) for a in
                      eng.topk_neighbors([0, 1], K))
        if ids.shape != (2, K) or not np.all(np.isfinite(dists)):
            print(f"SERVE QUERY BROKEN: ids {ids.shape}, dists "
                  f"finite={np.all(np.isfinite(dists))}")
            return 1

        print(f"check_multihost OK: {NPROCS} processes trained "
              f"{STEPS} steps (loss {losses[0]:.4f} -> "
              f"{losses[-1]:.4f}), 2-host checkpoint restored at 1 "
              f"process bitwise, export parity "
              f"{art.fingerprint[:12]}, top-{K} query served")
        return 0
    finally:
        if tmp is not None:
            tmp.cleanup()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))
