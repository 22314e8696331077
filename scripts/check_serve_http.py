#!/usr/bin/env python
"""Smoke lint: the HTTP front door round trip, as a real subprocess.

export → ``serve-http`` with ``prewarm=1`` on an ephemeral port →
healthz → stats → same-bucket queries → stats → score → a malformed
request → SIGTERM drain.  Asserted (exit 1 on any miss):

- exactly one response per request (none dropped, none duplicated);
- with ``prewarm=1`` the bucket ladder is compiled BEFORE the
  listeners open, so ``jax/recompiles`` is FLAT from the **first**
  request — the stats endpoint is read before any topk, and again
  after them (docs/serving.md "Warm starts"; before PR 13 this script
  could only assert flatness across same-bucket repeats AFTER a
  warmup request);
- the served top-k matches a live engine on the same table bit-for-bit;
- a malformed request answers 400 with a typed kind and the server
  keeps serving;
- SIGTERM exits 0 with the drain notice + latency summary on stderr —
  the stdin loop's drain contract, through the socket path.

Run by ``tests/serve/test_check_http_script.py`` inside the suite,
mirroring ``check_serve_artifact.py``, so a front-door regression fails
the build.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable as a plain script from anywhere (the package is not installed)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N, D, C = 123, 8, 1.1
LISTEN_DEADLINE_S = 120.0  # first-launch jax import dominates
K = 5


def build_table():
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.manifolds import PoincareBall

    v = 0.5 * jax.random.normal(jax.random.PRNGKey(7), (N, D), jnp.float32)
    return PoincareBall(C).expmap0(v)


class _StderrPump:
    """Drain the server's stderr on a thread so (a) the LISTEN
    deadline is actually enforced — a blocking ``readline`` on a
    wedged-but-silent server would wait forever, the exact unbounded
    shape the dryrun satellite exists to kill — and (b) the full
    stream stays collectable for the drain-notice assertions after the
    process exits."""

    def __init__(self, proc):
        self._q: queue.Queue = queue.Queue()
        self.lines: list[str] = []
        self._t = threading.Thread(target=self._pump, args=(proc,),
                                   daemon=True)
        self._t.start()

    def _pump(self, proc) -> None:
        for line in proc.stderr:
            self.lines.append(line)
            self._q.put(line)

    def next_line(self, timeout: float):
        """The next stderr line, or None after ``timeout`` seconds."""
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    def text(self) -> str:
        self._t.join(timeout=10)
        return "".join(self.lines)


def _wait_for_port(proc, pump: _StderrPump) -> tuple[str, int]:
    """Parse the '[serve-http] listening on HOST:PORT' stderr line,
    HARD-bounded at LISTEN_DEADLINE_S — a server that wedges before
    announcing fails loudly instead of hanging the suite."""
    deadline = time.monotonic() + LISTEN_DEADLINE_S
    while time.monotonic() < deadline:
        line = pump.next_line(timeout=0.25)
        if line is None:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server died rc={proc.returncode} before "
                    f"listening:\n{pump.text()[-800:]}")
            continue
        line = line.strip()
        if "listening on" in line:
            hostport = line.rsplit(" ", 1)[-1]
            host, _, port = hostport.rpartition(":")
            return host, int(port)
    raise RuntimeError("no listening line within the deadline")


def _post(host: str, port: int, path: str, payload,
          raw: bytes | None = None):
    """(status, parsed body) over one fresh connection."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        body = raw if raw is not None else json.dumps(payload).encode()
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


def _get(host: str, port: int, path: str):
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


def main(out_dir: str | None = None) -> int:
    import numpy as np

    from hyperspace_tpu.serve import (QueryEngine, RequestBatcher,
                                      export_artifact)

    table = np.asarray(build_table())
    spec = ("poincare", C)
    tmp = None
    if out_dir is None:
        tmp = tempfile.TemporaryDirectory()
        out_dir = os.path.join(tmp.name, "artifact")
    proc = None
    try:
        export_artifact(out_dir, table, spec, model_config={"c": C},
                        overwrite=True)
        # the reference runs the SAME bucketed program the server runs
        # (3 ids pad to min_bucket): XLA:CPU vectorizes a [3, D] and an
        # [8, D] batch differently, so the unpadded engine call differs
        # from the served answer in the last ulp of some rows
        live = RequestBatcher(QueryEngine(table, spec))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "hyperspace_tpu.cli.serve",
             "serve-http", f"artifact={out_dir}", "port=0",
             "host=127.0.0.1", "max_wait_us=1000", "telemetry=1",
             "prewarm=1", f"k={K}"],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        pump = _StderrPump(proc)
        host, port = _wait_for_port(proc, pump)

        sent = answered = 0

        status, health = _get(host, port, "/healthz")
        sent += 1
        answered += 1
        if status != 200 or health.get("ok") is not True:
            print(f"HEALTHZ BROKEN: {status} {health}")
            return 1

        # recompile count BEFORE any topk: prewarm=1 compiled the whole
        # ladder before the listener opened, so the FIRST real request
        # must find its executable warm (stats itself compiles nothing)
        status, stats0 = _post(host, port, "/v1/stats", {})
        sent += 1
        answered += 1
        if status != 200 or stats0.get("prewarmed", 0) <= 0:
            print(f"PREWARM DID NOT RUN: {status} {stats0.get('prewarmed')}")
            return 1

        ids0 = [0, 1, 2]
        status, first = _post(host, port, "/v1/topk",
                              {"ids": ids0, "k": K})
        sent += 1
        answered += 1
        if status != 200:
            print(f"FIRST QUERY FAILED: {status} {first}")
            return 1
        li, ld = (np.asarray(a) for a in live.topk(ids0, K))
        if not np.array_equal(li, np.asarray(first["neighbors"])):
            print(f"SERVED NEIGHBORS DIFFER from live engine:\n"
                  f"{li}\nvs\n{first['neighbors']}")
            return 1
        if not np.array_equal(
                ld.astype(np.float32).view(np.uint32),
                np.asarray(first["dists"],
                           np.float32).view(np.uint32)):
            print("SERVED DISTANCES not bit-identical to live engine")
            return 1

        status, stats1 = _post(host, port, "/v1/stats", {})
        sent += 1
        answered += 1
        for qids in ([3, 4, 5], [10, 11, 12], [20, 21, 22]):
            status, r = _post(host, port, "/v1/topk",
                              {"ids": qids, "k": K})
            sent += 1
            answered += 1
            if status != 200 or len(r["neighbors"]) != len(qids):
                print(f"QUERY {qids} FAILED: {status} {r}")
                return 1
        status, stats2 = _post(host, port, "/v1/stats", {})
        sent += 1
        answered += 1
        if stats2["recompiles"] != stats1["recompiles"]:
            print(f"RECOMPILES NOT FLAT across same-bucket requests: "
                  f"{stats1['recompiles']} -> {stats2['recompiles']}")
            return 1
        # the prewarm contract: flat from the FIRST request, not merely
        # across repeats after a warmup — the pre-first-query reading
        # equals the post-queries reading
        if stats2["recompiles"] != stats0["recompiles"]:
            print(f"RECOMPILES NOT FLAT FROM THE FIRST REQUEST despite "
                  f"prewarm=1: {stats0['recompiles']} -> "
                  f"{stats2['recompiles']}")
            return 1

        status, r = _post(host, port, "/v1/score",
                          {"u": [0, 1], "v": [2, 3]})
        sent += 1
        answered += 1
        if status != 200 or len(r["scores"]) != 2:
            print(f"SCORE FAILED: {status} {r}")
            return 1

        # a malformed request answers a typed 400 and the server lives
        status, r = _post(host, port, "/v1/topk", None,
                          raw=b"this is not json")
        sent += 1
        answered += 1
        if status != 400 or r["error"]["kind"] != "parse":
            print(f"MALFORMED REQUEST mishandled: {status} {r}")
            return 1
        status, r = _post(host, port, "/v1/topk", {"ids": [0], "k": K})
        sent += 1
        answered += 1
        if status != 200:
            print(f"SERVER DID NOT SURVIVE a malformed request: {status}")
            return 1

        if sent != answered:
            print(f"RESPONSE COUNT DRIFT: sent {sent}, answered "
                  f"{answered}")
            return 1

        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            print("DRAIN HUNG: SIGTERM did not stop the server in 60 s")
            return 1
        err = pump.text()
        if proc.returncode != 0:
            print(f"DRAIN EXIT CODE {proc.returncode}; stderr:\n{err}")
            return 1
        if "drained" not in err or "latency e2e_ms" not in err:
            print(f"DRAIN NOTICE / latency summary missing; stderr:\n"
                  f"{err}")
            return 1
        print(f"serve-http round trip OK: {sent} requests, {answered} "
              f"responses, recompiles flat at {stats2['recompiles']}, "
              "drained clean")
        return 0
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        if tmp is not None:
            tmp.cleanup()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))
