#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # the four-chip path and its comparison

Drives the two front doors through their normal command lines, at the
published width of workloads the repo supports, and checks what comes
out.  This parent never imports ``jax`` or ``hyperspace_tpu``: every
phase is a child process, one after the other, so exactly one process
holds the chip at a time.  It neither sets nor clears ``JAX_PLATFORMS``
(``JAX_PLATFORMS=cpu python chip_smoke.py`` is the quick negative
test), all children share one compile cache (where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<repo>/.cache/jax_compile``),
and all data is generated from ``SEED`` inside the run.

One chip, phases in order (any failure ends the run, nothing is carried
past it):

0. **probe** — a child prints the platform, device kind and count jax
   finds.  Anything but ``tpu`` ends the run within seconds.
1. **trainer** — ``cli.train hgcn --yaml configs/hgcn_arxiv_lp.yaml`` on
   a generated graph of the published ogbn-arxiv shape (169,343 nodes,
   1,166,243 edges, 128 features, 40 classes) read from disk.
2. **trainer_att** — the same trainer's attention arm, a few steps.
3. **server** — ``cli.train poincare --yaml configs/poincare_wordnet.yaml``
   on a WordNet-noun-scale closure (66,430 nodes / 323,847 pairs),
   ``cli.serve export``, then ``cli.serve serve-http`` answering top-k
   batches over HTTP, checked against a float64 numpy top-k over the
   exported table; then once more with ``scan_mode=fused``.

``--chips 4`` runs only the four-chip path and what it is compared
with: phase 1's command on all four chips (auto-mesh → node-sharded
step) and the same command restricted to one chip — both with the
bfloat16 lanes of the yaml set to float32 (see ``MESH_LANES``).

Every phase prints one JSON line; the LAST line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device
as a child's run manifest reports it.  Exit 0 only when every phase
passed on a TPU with the Pallas kernels — there is no option that lets
the script pass without one.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
BUDGET_S = 1150.0  # the whole run, children included, ends inside 1200 s
# ids-up-to-ties and distance tolerances of the served answers against
# the float64 reference (f32 distances on points in the unit ball)
TIE_TOL = 1e-5
DIST_TOL = 1e-4
# the four-chip loss curve against the one-chip one: the tolerance
# tests/parallel/test_node_sharded.py holds the same comparison to
LOSS_RTOL, LOSS_ATOL = 1e-4, 1e-5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at.  ``FULL`` is the only size the script
    itself uses; the tests pass a tiny one to rehearse the command
    lines on the CPU."""

    graph: dict            # community_power_law_graph kwargs ({} = arxiv)
    nodes: int             # node count the trainer must report
    edges: int
    mean_steps: int
    att_steps: int
    att_log_every: int
    tree: tuple            # synthetic_tree (depth, branching)
    tree_nodes: int
    embed_steps: int
    embed_overrides: tuple  # extra cli.train poincare overrides
    query_sizes: tuple     # ids per POST /v1/topk
    k: int
    mesh_steps: int        # --chips 4: steps of both runs
    mesh_log_every: int


FULL = Sizes(graph={}, nodes=169_343, edges=1_166_243, mean_steps=150,
             att_steps=30, att_log_every=10, tree=(5, 9), tree_nodes=66_430,
             embed_steps=300, embed_overrides=(),
             query_sizes=(1, 7, 64, 300), k=10, mesh_steps=40,
             mesh_log_every=10)


class PhaseFailed(Exception):
    """A phase did not pass; the message says why."""


# --- running children ---------------------------------------------------------


class Runner:
    """Starts children one at a time under one wall-clock budget, keeps
    their output under ``out_dir``, and leaves none running."""

    def __init__(self, work_dir: str, out_dir: str, budget_s: float):
        self.work_dir, self.out_dir = work_dir, out_dir
        self._deadline = time.monotonic() + budget_s
        self._live: list[subprocess.Popen] = []
        self.seconds: dict[str, float] = {}  # child name -> wall seconds

    def remaining(self) -> float:
        return self._deadline - time.monotonic()

    def start(self, name: str, argv: list[str],
              env: dict | None = None) -> subprocess.Popen:
        """Start a child, its output going to ``out_dir``; ``run`` waits
        for it, the server phase talks to it while it runs."""
        if self.remaining() <= 0:
            raise PhaseFailed(f"{name}: the run's time budget is spent")
        out = open(os.path.join(self.out_dir, f"{name}.out"), "w")
        err = open(os.path.join(self.out_dir, f"{name}.err"), "w")
        try:
            proc = subprocess.Popen(
                argv, cwd=ROOT, env=env, stdout=out, stderr=err,
                start_new_session=True)  # its own group: killed whole
        finally:
            out.close()
            err.close()
        self._live.append(proc)
        return proc

    def run(self, name: str, argv: list[str], *, timeout: float,
            env: dict | None = None) -> tuple[str, str]:
        """Run one child to its end; returns (stdout, stderr).  A
        non-zero exit, or a child still running at ``timeout``, fails
        the phase."""
        t0 = time.monotonic()
        proc = self.start(name, argv, env)
        try:
            rc = proc.wait(timeout=max(min(timeout, self.remaining()), 1.0))
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise PhaseFailed(
                f"{name}: still running after its time limit") from None
        self.seconds[name] = round(time.monotonic() - t0, 1)
        out, err = self.output(name)
        if rc != 0:
            raise PhaseFailed(f"{name}: exit code {rc}\n{_tail(err)}")
        return out, err

    def output(self, name: str) -> tuple[str, str]:
        texts = []
        for ext in ("out", "err"):
            with open(os.path.join(self.out_dir, f"{name}.{ext}"),
                      errors="replace") as f:
                texts.append(f.read())
        return texts[0], texts[1]

    def stop(self, proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass  # it ended between the poll and the kill
            proc.wait()

    def stop_all(self) -> None:
        for proc in self._live:
            self.stop(proc)


def _tail(text: str, n: int = 1500) -> str:
    return text[-n:]


def _py(*args: str) -> list[str]:
    return [sys.executable, *args]


def _last_json_line(text: str, what: str) -> dict:
    lines = [ln for ln in text.strip().splitlines() if ln.strip()]
    if not lines:
        raise PhaseFailed(f"{what}: printed nothing")
    try:
        doc = json.loads(lines[-1])
    except json.JSONDecodeError:
        raise PhaseFailed(
            f"{what}: last line is not JSON: {lines[-1][:200]!r}") from None
    if not isinstance(doc, dict):
        raise PhaseFailed(f"{what}: last line is not a JSON object")
    return doc


def read_jsonl(path: str) -> list[dict]:
    try:
        with open(path) as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    except (OSError, json.JSONDecodeError) as e:
        raise PhaseFailed(f"cannot read the run's log {path}: {e}") from None


# --- checks (pure: fed by the children's records; the tests feed them
# recorded ones) -----------------------------------------------------------------


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def manifest_of(records: list[dict]) -> dict:
    for rec in records:
        if rec.get("event") == "run_manifest":
            return rec
    raise PhaseFailed("the run's log has no run_manifest record")


def summary_of(records: list[dict]) -> dict:
    for rec in reversed(records):
        if rec.get("event") == "telemetry_summary":
            return rec
    raise PhaseFailed("the run's log has no telemetry_summary record")


def device_of(manifest: dict) -> dict:
    """The contract's device object, as the child's manifest says."""
    return {"platform": manifest.get("backend"),
            "kind": manifest.get("device_kind"),
            "count": manifest.get("device_count")}


def check_manifest(manifest: dict, chips: int) -> list[str]:
    """Problems with where a child ran: it must say a TPU, the Pallas
    kernels, and the device count this run was asked for."""
    bad = []
    if manifest.get("backend") != "tpu":
        bad.append(f"platform is {manifest.get('backend')!r}, not 'tpu'")
    if manifest.get("kernel_mode") != "pallas":
        bad.append(f"kernel mode is {manifest.get('kernel_mode')!r}, "
                   "not 'pallas'")
    if manifest.get("device_count") != chips:
        bad.append(f"{manifest.get('device_count')} device(s), "
                   f"wanted {chips}")
    return bad


def losses_of(records: list[dict]) -> list[tuple[int, float]]:
    return [(rec["step"], rec["loss"]) for rec in records
            if "step" in rec and "loss" in rec and "event" not in rec]


def check_trainer(result: dict, records: list[dict], *, chips: int,
                  nodes: int, edges: int, need_auc: bool,
                  need_decrease: bool = True) -> list[str]:
    """Problems with one trainer child, from its result line and log."""
    manifest = manifest_of(records)
    bad = check_manifest(manifest, chips)
    if result.get("source") != "disk":
        bad.append(f"data source is {result.get('source')!r}, not 'disk'")
    if result.get("num_nodes") != nodes or result.get("num_edges") != edges:
        bad.append(f"graph is {result.get('num_nodes')} nodes / "
                   f"{result.get('num_edges')} edges, wanted {nodes} / "
                   f"{edges}")
    data = manifest.get("data") or {}
    if data.get("source") != result.get("source") \
            or data.get("num_nodes") != result.get("num_nodes"):
        bad.append("the manifest's data record disagrees with the result")
    losses = losses_of(records)
    if len(losses) < 2:
        bad.append(f"{len(losses)} logged loss(es), wanted at least 2")
    if not all(_finite(v) for _, v in losses) \
            or not _finite(result.get("loss")):
        bad.append(f"non-finite loss: logged {losses}, final "
                   f"{result.get('loss')}")
    elif need_decrease and len(losses) >= 2 \
            and not losses[-1][1] < losses[0][1]:
        bad.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    if need_auc:
        auc = result.get("roc_auc")
        if not _finite(auc) or not auc > 0.5:
            bad.append(f"test ROC-AUC is {auc!r}, wanted finite and > 0.5")
    return bad


def poincare_dist64(u: np.ndarray, v: np.ndarray, c: float) -> np.ndarray:
    """Closed-form Poincaré distances [B, N] in float64 — the plain
    reference, independent of the code under test."""
    u = np.asarray(u, np.float64)
    v = np.asarray(v, np.float64)
    diff2 = np.sum((u[:, None, :] - v[None, :, :]) ** 2, axis=-1)
    den = ((1.0 - c * np.sum(u * u, axis=-1))[:, None]
           * (1.0 - c * np.sum(v * v, axis=-1))[None, :])
    return np.arccosh(1.0 + 2.0 * c * diff2 / den) / math.sqrt(c)


def check_topk(table: np.ndarray, c: float, ids: list[int], k: int,
               neighbors, dists) -> list[str]:
    """Problems with one served top-k answer against the reference:
    every served neighbour must be the reference's at that rank up to
    ties within ``TIE_TOL``, with its served distance within
    ``DIST_TOL`` (self excluded, as the server does by default)."""
    got_i = np.asarray(neighbors)
    got_d = np.asarray(dists, np.float64)
    if got_i.shape != (len(ids), k) or got_d.shape != (len(ids), k):
        return [f"answer shapes {got_i.shape} / {got_d.shape}, wanted "
                f"{(len(ids), k)}"]
    if not np.all(np.isfinite(got_d)):
        return ["non-finite served distance"]
    if got_i.min() < 0 or got_i.max() >= len(table):
        return [f"served id out of range [0, {len(table)})"]
    ref = poincare_dist64(table[np.asarray(ids)], table, c)
    ref[np.arange(len(ids)), np.asarray(ids)] = np.inf
    ref_sorted = np.sort(ref, axis=1)[:, :k]
    ref_of_got = np.take_along_axis(ref, got_i, axis=1)
    bad = []
    rank_err = np.abs(ref_of_got - ref_sorted)
    if rank_err.max() > TIE_TOL:
        q, j = np.unravel_index(np.argmax(rank_err), rank_err.shape)
        bad.append(
            f"query id {ids[q]} rank {j}: served id {got_i[q, j]} lies "
            f"at {ref_of_got[q, j]:.8f}, the reference's rank {j} at "
            f"{ref_sorted[q, j]:.8f}")
    dist_err = np.abs(got_d - ref_of_got)
    if dist_err.max() > DIST_TOL:
        q, j = np.unravel_index(np.argmax(dist_err), dist_err.shape)
        bad.append(
            f"query id {ids[q]} rank {j}: served distance "
            f"{got_d[q, j]:.8f}, reference {ref_of_got[q, j]:.8f}")
    if any(len(set(row)) != k for row in got_i.tolist()):
        bad.append("a served neighbour list repeats an id")
    return bad


def check_server(health: dict, stats_first: dict,
                 stats_last: dict) -> list[str]:
    """Problems with where and how the server ran, from its own
    /healthz and /v1/stats payloads."""
    bad = []
    if health.get("ok") is not True:
        bad.append(f"healthz not ok: {health}")
    for name, doc in (("healthz", health), ("stats", stats_last)):
        if doc.get("backend") != "tpu":
            bad.append(f"{name}: platform is {doc.get('backend')!r}, "
                       "not 'tpu'")
        if doc.get("kernel_mode") != "pallas":
            bad.append(f"{name}: kernel mode is "
                       f"{doc.get('kernel_mode')!r}, not 'pallas'")
    if not stats_first.get("prewarmed"):
        bad.append("prewarm=1 warmed nothing")
    if stats_first.get("recompiles") != stats_last.get("recompiles"):
        bad.append(f"recompiles moved {stats_first.get('recompiles')} -> "
                   f"{stats_last.get('recompiles')} while serving")
    return bad


def check_loss_curves(mesh: list[tuple[int, float]],
                      one: list[tuple[int, float]]) -> list[str]:
    if [s for s, _ in mesh] != [s for s, _ in one] or not mesh:
        return [f"loss logged at steps {[s for s, _ in mesh]} vs "
                f"{[s for s, _ in one]}"]
    bad = []
    for (step, a), (_, b) in zip(mesh, one):
        if not (_finite(a) and _finite(b)):
            bad.append(f"step {step}: non-finite loss {a} / {b}")
        elif abs(a - b) > LOSS_ATOL + LOSS_RTOL * abs(b):
            bad.append(f"step {step}: four-chip loss {a:.8f} vs one-chip "
                       f"{b:.8f} (rel {abs(a - b) / abs(b):.2e})")
    return bad


def memory_of(records: list[dict]) -> list[dict]:
    """Each device's allocator reading at the end of training, while
    the state and its data were alive (the run's telemetry_summary)."""
    return summary_of(records).get("device_memory") or []


def check_memory(mesh_mem: list[dict], one_mem: list[dict]) -> list[str]:
    """Bytes in use per device at the end of training, the four-chip
    run against the one-chip run's device: every one below it, none
    above twice another — the graph is divided, not parked on one
    device.  (The allocator's PEAK also counts the eager parameter
    initialisation, which runs the whole graph on device 0 in both
    runs, so it cannot tell the two apart.)"""
    four = [m.get("bytes_in_use") for m in mesh_mem]
    one = [m.get("bytes_in_use") for m in one_mem]
    if len(four) != 4 or len(one) != 1 \
            or not all(_finite(b) and b > 0 for b in four + one):
        return [f"memory statistics missing: {four} / {one}"]
    bad = []
    if max(four) >= one[0]:
        bad.append(f"a device of four holds {max(four)} bytes, the "
                   f"one-chip run's {one[0]}")
    if max(four) > 2 * min(four):
        bad.append(f"bytes in use per device {four}: one holds more than "
                   "twice another")
    return bad


# --- phases ---------------------------------------------------------------------
#
# A phase runs its children and returns ``(manifest, problems)``.  A
# child that does not exit 0 (or a record that cannot be read) raises
# PhaseFailed on the spot; what the checks find comes back as the
# problem list, and ``main`` fails the run on the first non-empty one.


def _report(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase,
                      "seconds": round(time.monotonic() - t0, 1),
                      **fields}), flush=True)


def _compile_fields(summary: dict) -> dict:
    return {"compile_s": round(summary.get("ctr/jax/compile_s", 0.0), 2),
            "cache_hits": int(summary.get("ctr/jax/compile_cache_hit", 0)),
            "cache_misses": int(summary.get("ctr/jax/compile_cache_miss",
                                            0))}


PROBE = ("import json, jax; d = jax.devices(); "
         "print(json.dumps({'platform': d[0].platform, "
         "'kind': d[0].device_kind, 'count': len(d)}))")


def phase_probe(rn: Runner, name: str = "probe",
                env: dict | None = None) -> dict:
    """What jax finds, as a child prints it: platform, kind, count."""
    t0 = time.monotonic()
    out, _ = rn.run(name, _py("-c", PROBE), timeout=180, env=env)
    dev = _last_json_line(out, name)
    _report(name, t0, **dev)
    return dev


def check_probe(dev: dict, chips: int) -> list[str]:
    if dev.get("platform") != "tpu":
        return [f"jax found platform {dev.get('platform')!r}, not 'tpu'"]
    if dev.get("count") != chips:
        return [f"{dev.get('count')} device(s) where this run wants "
                f"{chips} (the option --chips says how many)"]
    return []


def graph_dataset(rn: Runner, sz: Sizes) -> str:
    """The arxiv-shape graph in OGB csv layout, generated once per run
    by the repo's own generator (a child: this parent stays off jax)."""
    root = os.path.join(rn.work_dir, "arxiv")
    code = ("import sys, json; "
            "from hyperspace_tpu.data import graphs as G; "
            "G.ensure_arxiv_scale_dataset(sys.argv[1], seed=int(sys.argv[2]),"
            " **json.loads(sys.argv[3]))")
    rn.run("graph_data", _py("-c", code, root, str(SEED),
                             json.dumps(sz.graph)), timeout=400)
    return root


def run_trainer(rn: Runner, name: str, data_root: str, steps: int,
                *extra: str, env: dict | None = None
                ) -> tuple[dict, list[dict]]:
    """One ``cli.train hgcn`` child: its result line and its log."""
    log = os.path.join(rn.out_dir, f"{name}.jsonl")
    out, _ = rn.run(name, _py(
        "-m", "hyperspace_tpu.cli.train", "hgcn", "--yaml",
        os.path.join("configs", "hgcn_arxiv_lp.yaml"),
        f"data_root={data_root}", f"steps={steps}", f"seed={SEED}",
        "telemetry=1", f"log={log}",
        f"ckpt_dir={os.path.join(rn.work_dir, name, 'ckpt')}", *extra),
        timeout=800, env=env)
    return _last_json_line(out, name), read_jsonl(log)


def step_ms(records: list[dict]) -> float | None:
    """Wall milliseconds per step between the first and last logged
    loss — each log boundary fetches the loss, so the steps before it
    have finished.  None with fewer than two records."""
    logged = [(rec["step"], rec["ts"]) for rec in records
              if "step" in rec and "loss" in rec and "event" not in rec]
    if len(logged) < 2 or logged[-1][0] == logged[0][0]:
        return None
    return round((logged[-1][1] - logged[0][1])
                 / (logged[-1][0] - logged[0][0]) * 1e3, 2)


def phase_trainer(rn: Runner, sz: Sizes, data_root: str, chips: int,
                  *, att: bool) -> tuple[dict, list[str]]:
    name = "trainer_att" if att else "trainer"
    t0 = time.monotonic()
    extra = (("use_att=true", "lr=0.003", "clip_norm=1.0",
              f"eval_every={sz.att_log_every}") if att else ())
    result, records = run_trainer(
        rn, name, data_root, sz.att_steps if att else sz.mean_steps, *extra)
    manifest = manifest_of(records)
    _report(name, t0, **_compile_fields(summary_of(records)),
            platform=manifest.get("backend"),
            kernel_mode=manifest.get("kernel_mode"),
            source=result.get("source"), num_nodes=result.get("num_nodes"),
            num_edges=result.get("num_edges"), steps=result.get("steps"),
            step_ms=step_ms(records), losses=losses_of(records),
            roc_auc=result.get("roc_auc"),
            bytes_in_use=[m.get("bytes_in_use")
                          for m in memory_of(records)])
    return manifest, check_trainer(result, records, chips=chips,
                                   nodes=sz.nodes, edges=sz.edges,
                                   need_auc=not att)


def _http(port: int, method: str, path: str, payload=None) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        body = None if payload is None else json.dumps(payload).encode()
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read().decode())
    except (OSError, http.client.HTTPException,
            json.JSONDecodeError) as e:
        raise PhaseFailed(f"{method} {path}: {e!r}") from None
    finally:
        conn.close()
    if resp.status != 200:
        raise PhaseFailed(f"{method} {path}: HTTP {resp.status} {doc}")
    return doc


def _wait_listening(rn: Runner, name: str, proc: subprocess.Popen,
                    limit_s: float) -> int:
    """The port from the server's '[serve-http] listening on HOST:PORT'
    stderr line."""
    end = time.monotonic() + min(limit_s, rn.remaining())
    while time.monotonic() < end:
        _, err = rn.output(name)
        for line in err.splitlines():
            if "listening on" in line:
                return int(line.rsplit(":", 1)[-1])
        if proc.poll() is not None:
            raise PhaseFailed(f"{name}: server ended (exit code "
                              f"{proc.returncode}) before listening\n"
                              f"{_tail(err)}")
        time.sleep(0.25)
    raise PhaseFailed(f"{name}: no listening line within its time limit")


def serve_and_query(rn: Runner, name: str, artifact: str,
                    batches: list[list[int]], k: int,
                    *extra: str) -> dict:
    """One ``cli.serve serve-http`` child: its answers to ``batches``,
    its /healthz and first/last /v1/stats payloads, and its log.
    SIGTERM must drain it to exit code 0."""
    log = os.path.join(rn.out_dir, f"{name}.jsonl")
    proc = rn.start(name, _py(
        "-m", "hyperspace_tpu.cli.serve", "serve-http",
        f"artifact={artifact}", "port=0", "prewarm=1", f"k={k}",
        f"log={log}", *extra))
    try:
        port = _wait_listening(rn, name, proc, 400)
        got = {"health": _http(port, "GET", "/healthz"),
               "stats_first": _http(port, "POST", "/v1/stats", {})}
        got["answers"] = [
            _http(port, "POST", "/v1/topk", {"ids": ids, "k": k})
            for ids in batches]
        got["stats_last"] = _http(port, "POST", "/v1/stats", {})
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=max(min(120, rn.remaining()), 1.0))
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{name}: SIGTERM did not drain the server "
                              "in its time limit") from None
    finally:
        rn.stop(proc)
    if rc != 0:
        raise PhaseFailed(f"{name}: drain exit code {rc}\n"
                          f"{_tail(rn.output(name)[1])}")
    got["records"] = read_jsonl(log)
    return got


def phase_server(rn: Runner, sz: Sizes, chips: int
                 ) -> tuple[dict, list[str]]:
    t0 = time.monotonic()
    tsv = os.path.join(rn.work_dir, "closure.tsv")
    code = ("import sys; from hyperspace_tpu.data import wordnet as W; "
            "W.write_closure_tsv(sys.argv[1], W.synthetic_tree("
            "int(sys.argv[2]), int(sys.argv[3])))")
    rn.run("closure_data", _py("-c", code, tsv, *map(str, sz.tree)),
           timeout=200)

    ckpt = os.path.join(rn.work_dir, "embed", "ckpt")
    log = os.path.join(rn.out_dir, "embed_train.jsonl")
    out, _ = rn.run("embed_train", _py(
        "-m", "hyperspace_tpu.cli.train", "poincare", "--yaml",
        os.path.join("configs", "poincare_wordnet.yaml"),
        f"data_root={tsv}", f"steps={sz.embed_steps}", f"seed={SEED}",
        "telemetry=1", f"log={log}", f"ckpt_dir={ckpt}",
        *sz.embed_overrides), timeout=600)
    result = _last_json_line(out, "embed_train")
    records = read_jsonl(log)
    bad = [f"embed_train: {b}"
           for b in check_manifest(manifest_of(records), chips)]
    if result.get("source") != "disk" \
            or result.get("num_nodes") != sz.tree_nodes:
        bad.append(f"embed_train: embedded {result.get('num_nodes')} nodes "
                   f"from {result.get('source')!r}, wanted {sz.tree_nodes} "
                   "from 'disk'")
    values = [v for _, v in losses_of(records)] + [
        result.get(key) for key in ("loss", "mean_rank", "map")]
    if not all(_finite(v) for v in values):
        bad.append(f"embed_train: non-finite loss or eval metric: {values}")

    artifact = os.path.join(rn.work_dir, "artifact")
    rn.run("export", _py(
        "-m", "hyperspace_tpu.cli.serve", "export",
        f"ckpt={ckpt}", f"out={artifact}",
        "workload=poincare", "c=1.0"), timeout=300)
    try:
        table = np.load(os.path.join(artifact, "table.npy"))
        with open(os.path.join(artifact, "artifact.json")) as f:
            c = float(json.load(f)["model_config"]["c"])
    except (OSError, ValueError, KeyError) as e:
        raise PhaseFailed(f"export: cannot read the artifact: {e!r}") \
            from None
    if table.shape[0] != sz.tree_nodes or not np.all(np.isfinite(table)):
        raise PhaseFailed(f"export: table {table.shape}, all finite: "
                          f"{bool(np.all(np.isfinite(table)))}")

    rng = np.random.default_rng(SEED)
    batches = [sorted(rng.choice(len(table), size=b, replace=False).tolist())
               for b in sz.query_sizes]
    served, compiles, mismatches = {}, {}, []
    for mode, extra in (("two_stage", ()), ("fused", ("scan_mode=fused",))):
        name = f"serve_{mode}"
        got = serve_and_query(rn, name, artifact, batches, sz.k, *extra)
        manifest = manifest_of(got["records"])
        compiles[mode] = _compile_fields(summary_of(got["records"]))
        for ids, ans in zip(batches, got["answers"]):
            mismatches += [f"{name}: {b}" for b in check_topk(
                table, c, ids, sz.k, ans.get("neighbors"), ans.get("dists"))]
        bad += [f"{name}: {b}" for b in check_manifest(manifest, chips)
                + check_server(got["health"], got["stats_first"],
                               got["stats_last"])]
        served[mode] = [ans.get("neighbors") for ans in got["answers"]]
    if served["fused"] != served["two_stage"]:
        mismatches.append("serve_fused: ids differ from the default scan "
                          "mode's on the same queries")
    _report("server", t0, **_compile_fields(summary_of(records)),
            serve_compile=compiles["two_stage"],
            fused_compile=compiles["fused"],
            platform=manifest.get("backend"),
            kernel_mode=manifest.get("kernel_mode"),
            num_nodes=result.get("num_nodes"), steps=result.get("steps"),
            loss=result.get("loss"), mean_rank=result.get("mean_rank"),
            queries=int(sum(sz.query_sizes)), scan_modes=list(served),
            answers_match_reference=not mismatches,
            child_seconds={k: rn.seconds.get(k) for k in (
                "closure_data", "embed_train", "export")})
    return manifest, bad + mismatches


# the TPU runtime's own variables for giving a process one chip of a host
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}


# Both runs of the comparison keep the message and decoder lanes in
# float32, as the CPU test of the same comparison does: the yaml's
# bfloat16 lanes agree at the first step and then drift apart on ANY
# two programs that round in different places (Adam's first updates
# are ±lr whatever the gradient's size, so a sign flipped by rounding
# moves a weight by 2·lr), which says nothing about the partition.
MESH_LANES = ("agg_dtype=float32", "decoder_dtype=float32")


def phase_mesh(rn: Runner, sz: Sizes, data_root: str
               ) -> tuple[dict, list[str]]:
    """Phase 1's command on all four chips (auto-mesh → node-sharded
    step) against the same command, config and seed on one of them."""
    t0 = time.monotonic()
    extra = (*MESH_LANES, f"eval_every={sz.mesh_log_every}")
    result4, records4 = run_trainer(rn, "mesh_four", data_root,
                                    sz.mesh_steps, *extra)
    manifest = manifest_of(records4)
    # said at once: kept even if the comparison run cannot be made
    _report("mesh_four", t0, **_compile_fields(summary_of(records4)),
            devices=manifest.get("device_count"),
            kernel_mode=manifest.get("kernel_mode"),
            step_ms=step_ms(records4), losses=losses_of(records4),
            memory=memory_of(records4))
    one_env = dict(os.environ, **ONE_CHIP_ENV)
    bad = [f"probe_one_chip: {b}" for b in check_probe(
        phase_probe(rn, name="probe_one_chip", env=one_env), 1)]
    if bad:
        return manifest, bad
    result1, records1 = run_trainer(rn, "mesh_one", data_root,
                                    sz.mesh_steps, *extra, env=one_env)
    mem4, mem1 = memory_of(records4), memory_of(records1)
    _report("mesh", t0,
            four=_compile_fields(summary_of(records4)),
            one=_compile_fields(summary_of(records1)),
            devices=manifest.get("device_count"),
            kernel_mode=manifest.get("kernel_mode"),
            step_ms_four=step_ms(records4), step_ms_one=step_ms(records1),
            losses_four=losses_of(records4), losses_one=losses_of(records1),
            memory_four=mem4, memory_one=mem1)
    common = dict(nodes=sz.nodes, edges=sz.edges, need_auc=False,
                  need_decrease=False)
    return manifest, (
        check_trainer(result4, records4, chips=4, **common)
        + check_trainer(result1, records1, chips=1, **common)
        + check_memory(mem4, mem1)
        + check_loss_curves(losses_of(records4), losses_of(records1)))


# --- the run --------------------------------------------------------------------


def _must_be_empty(phase: str, problems: list[str]) -> None:
    if problems:
        raise PhaseFailed(f"{phase}: " + "; ".join(problems))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path and the one-chip "
                         "run it is compared with (default 1: phases 0-3)")
    args = ap.parse_args(argv)

    work_dir = os.path.join(ROOT, ".cache", "chip_smoke")
    out_dir = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    for d in (work_dir, out_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    rn = Runner(work_dir, out_dir, BUDGET_S)
    device = None
    ok = False
    try:
        device = phase_probe(rn)
        _must_be_empty("probe", check_probe(device, args.chips))
        data_root = graph_dataset(rn, FULL)
        if args.chips == 4:
            phases = [("mesh", lambda: phase_mesh(rn, FULL, data_root))]
        else:
            phases = [
                ("trainer", lambda: phase_trainer(rn, FULL, data_root, 1,
                                                  att=False)),
                ("trainer_att", lambda: phase_trainer(rn, FULL, data_root,
                                                      1, att=True)),
                ("server", lambda: phase_server(rn, FULL, 1))]
        for name, phase in phases:
            manifest, bad = phase()
            device = device_of(manifest)
            _must_be_empty(name, bad)
        ok = True
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr, flush=True)
    finally:
        rn.stop_all()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
