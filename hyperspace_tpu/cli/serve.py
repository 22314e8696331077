"""Serving entry point: ``python -m hyperspace_tpu.cli.serve``.

Three modes, same ``key=value`` override grammar as the train CLI:

    # freeze the newest committed checkpoint step into a serving artifact
    python -m hyperspace_tpu.cli.serve export \
        ckpt=runs/poincare/ckpt out=runs/poincare/artifact \
        workload=poincare c=1.0

    # one-shot queries (tests, smoke checks): prints one JSON line
    python -m hyperspace_tpu.cli.serve query artifact=runs/poincare/artifact \
        ids=0,1,2 k=5
    python -m hyperspace_tpu.cli.serve query artifact=... u=0,1 v=2,3 prob=1

    # stdin/JSONL loop: one request per line, one JSON response per line
    python -m hyperspace_tpu.cli.serve serve artifact=... telemetry=1

    # asyncio HTTP front door with continuous batching (port=0 =
    # ephemeral; the bound port is announced on stderr)
    python -m hyperspace_tpu.cli.serve serve-http artifact=... \
        port=8080 max_wait_us=2000 queue_max=64 deadline_ms=50

    # shard the table across the chips (mesh=-1 = all local devices)
    python -m hyperspace_tpu.cli.serve serve artifact=... mesh=-1

Loop-mode requests:

    {"op": "topk",   "ids": [0, 1, 2], "k": 5}
    {"op": "score",  "u": [0, 1], "v": [2, 3], "prob": true}
    {"op": "upsert", "ids": [7, 120], "rows": [[...], [...]]}
    {"op": "delete", "ids": [3]}
    {"op": "stats"}

``upsert``/``delete`` need ``live=1`` (the artifact's engine is wrapped
in a :class:`~hyperspace_tpu.serve.delta.LiveQueryEngine`; ``delta_cap=``
/ ``compact_at=`` size the delta segment — docs/serving.md "Live index
and rollover"); against a frozen engine they answer a ``validation``
error.

Responses mirror the request (``neighbors``/``dists``, ``scores``, or
the counter snapshot); a failed line yields ``{"error": {"kind": ...,
"message": ...}}`` with a machine-readable kind (``parse`` /
``validation`` / ``deadline_exceeded`` / ``overloaded`` / ``internal``
— docs/serving.md "Error taxonomy") and the loop continues — a
malformed line must never take the server down, and no line is ever
silently dropped.  ``deadline_ms=``/``queue_max=`` arm per-request
deadlines and bounded-queue admission control with a degradation
ladder; SIGTERM drains gracefully (docs/resilience.md).
Telemetry wiring matches the train CLI: ``telemetry=1`` installs the
recompile hook and prints a closing summary line to stderr,
``trace_out=`` dumps the host spans (each batch runs under a ``query``
span) as Chrome ``trace_events`` JSON in a ``finally``.  The serve loop
additionally prints a one-line ``serve/e2e_ms`` latency summary (count,
p50/p95/p99 — docs/observability.md "Histograms") to stderr on exit and
alongside every ``stats`` response.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import sys

import numpy as np

from hyperspace_tpu.cli.train import _json_safe, apply_overrides
from hyperspace_tpu.telemetry import trace as _trace


@dataclasses.dataclass
class ServeConfig:
    # shared
    artifact: str | None = None   # artifact dir (query/serve)
    telemetry: bool = False
    trace_out: str | None = None
    # export
    ckpt: str | None = None       # CheckpointManager dir
    out: str | None = None        # artifact dir to write
    workload: str = "poincare"    # poincare | lorentz | product
    # curvature the run TRAINED with — required for poincare/lorentz
    # export (not recoverable from the checkpoint; no silent default)
    c: str | None = None
    factors: str = ""             # product factor layout JSON [[kind, dim], ...]
    step: int = -1                # checkpoint step (-1 = newest committed)
    overwrite: bool = False
    # export: build an IVF index (hyperbolic k-means; serve/index.py)
    # into the artifact.  index=1 with ncells=0 picks ~sqrt(N) cells;
    # ncells=K alone also implies index=1.
    index: bool = False
    ncells: int = 0
    # export: also ship a packed scan lane (int4 | pq) in the artifact
    # (serve/artifact.py QuantPayload — pq freezes the trained
    # codebooks so every replica ranks through the same centers)
    quant: str = ""
    # query / serve
    k: int = 10
    ids: str = ""                 # comma-separated query ids (one-shot topk)
    u: str = ""                   # comma-separated endpoints (one-shot score)
    v: str = ""
    prob: bool = False            # score as Fermi–Dirac link probability
    fd_r: float = 2.0
    fd_t: float = 1.0
    min_bucket: int = 8
    max_bucket: int = 1024
    cache_size: int = 65536
    chunk_rows: int = 0           # 0 = auto from the tile budget
    # devices on the mesh's `model` axis to row-shard the table over:
    # 0 = single-device (no mesh), -1 = all local devices, N = first N.
    # A 1-device mesh runs the single-device program (bit-compatible).
    mesh: int = 0
    # two_stage | carry | fused (fused = the Pallas scan-top-k kernel,
    # rank-identical answers; docs/serving.md, docs/kernels.md)
    scan_mode: str = "two_stage"
    # table-scan precision: f32 (default, bit-identical) | bf16 (scan a
    # bf16 table copy, rescore candidates in f32 — docs/precision.md) |
    # int8 (per-row symmetric quantized scan copy at a quarter of the
    # table bytes, same f32 rescore — docs/serving.md "Quantized scan
    # lane") | int4 (two nibbles per byte + f16 scale, ~1/6 the bytes) |
    # pq (product-quantized codes + hyperbolic-aware codebooks, wider
    # over-fetch — docs/serving.md "Sub-int8 lanes"; an artifact
    # exported with a matching quant payload serves its shipped
    # codes/codebooks instead of re-packing)
    precision: str = "f32"
    # IVF probing (query/serve): cells probed per query.  0 = exact
    # scan; needs an artifact exported with an index.  nprobe >= ncells
    # or a sub-threshold table fall back to the exact program
    # (docs/serving.md "Approximate retrieval").
    nprobe: int = 0
    # --- live mutable index (serve/delta.py; docs/serving.md "Live
    # index and rollover") ----------------------------------------------
    # live=1 wraps the engine in a LiveQueryEngine: upsert/delete ops
    # (stdin loop) and POST /v1/upsert | /v1/delete (front door) mutate
    # through a delta segment with tombstone masking; frozen serving
    # (the default) rejects mutations with a validation error.
    # Incompatible with scan_mode=fused (no tombstone lane).
    live: bool = False
    # delta-segment capacity in rows (static shape — the merged query
    # path compiles once per bucket whatever the mutation rate)
    delta_cap: int = 1024
    # background-compaction trigger: occupancy fraction of delta_cap at
    # which a compaction thread folds the segment into a rebuilt base
    compact_at: float = 0.75
    # --- overload safety (docs/resilience.md) --------------------------
    # default per-request deadline in ms (0 = none); a request's own
    # "deadline_ms" field overrides.  Expired requests answer
    # error.kind=deadline_exceeded — never dispatched late, never
    # silently dropped.
    deadline_ms: float = 0.0
    # bounded admission queue: > N concurrent requests shed with
    # error.kind=overloaded, and queue pressure drives the degradation
    # ladder (nprobe steps toward 1, then cache-only).  0 = off.
    queue_max: int = 0
    # fault injection (resilience/faults.py), e.g.
    # chaos=serve.dispatch:latency:ms=50:times=3
    chaos: str | None = None
    chaos_seed: int = 0
    # --- HTTP front door (serve-http mode; docs/serving.md) ------------
    # bind address + port (0 = ephemeral; the bound port is announced
    # as "[serve-http] listening on HOST:PORT" on stderr)
    host: str = "127.0.0.1"
    port: int = 0
    # continuous-batching max wait: a pending bucket that has not
    # exactly filled a power-of-two rung flushes after this many µs
    max_wait_us: float = 2000.0
    # --- compile-time control (docs/serving.md "Warm starts") ----------
    # persistent XLA compilation cache (hyperspace_tpu/compile_cache.py):
    # default ON — where JAX_COMPILATION_CACHE_DIR says if that is set
    # (no other directory is then accepted), else at
    # <repo>/.cache/jax_compile unless this flag or
    # HYPERSPACE_COMPILE_CACHE names a path; 0 disables.  A serve
    # restart then deserializes its executables instead of re-compiling
    # the whole bucket ladder.
    compile_cache_dir: str | None = None
    # startup bucket prewarm: compile the configured bucket ladder
    # (× the IVF degradation-ladder widths) BEFORE serving traffic —
    # serve mode warms before reading stdin, serve-http before the
    # listeners open, so the first real request on every bucket is warm.
    # 0 (default) = off; 1 = warm k= (the config's k); a comma list
    # ("5,10") warms those k values.
    prewarm: str = "0"
    # --- observability plane (docs/observability.md "Live metrics,
    # access log, and the flight recorder") ----------------------------
    # serve-session JSONL (train-CLI record shapes): a run_manifest
    # first record and a closing telemetry_summary, so read_jsonl
    # tooling works on serve sessions too
    log: str | None = None
    # structured JSONL access log: one line per serve request —
    # request_id, route, buckets, collator flush id, queue-wait/
    # dispatch/e2e ms, cache hits, degrade level, taxonomy outcome
    access_log: str | None = None
    # rolling SLO window (telemetry/window.py): p50/p95/p99 + shed/
    # deadline/error rates over the last N seconds from histogram ring
    # deltas, surfaced in stats responses, /metrics, and the exit
    # summary.  0 disables.
    window_s: float = 60.0
    # latency-aware degradation signal: with queue_max>0 and a window,
    # a windowed e2e p99 past this many ms drives the ladder down even
    # without queue pressure.  0 (default) = queue-depth-only.
    slo_ms: float = 0.0
    # flight recorder (serve/access.py): keep a bounded ring of recent
    # access records and dump a timestamped incident JSONL here on
    # typed-error bursts, degrade transitions, and SIGTERM drain
    incident_dir: str | None = None
    # span-level pipeline tracing (telemetry/spans.py): every request
    # decomposes into queue_wait / collate_wait / dispatch /
    # device_compute / rescore / serialize stages — per-stage
    # histograms on /metrics, stage breakdowns in the access log, and
    # full span trees on failed/slow requests and incident dumps.
    # Adds a device sync per dispatch (docs/observability.md "Spans").
    trace: bool = False
    # slow-query JSONL: with slo_ms>0, a request past the SLO writes
    # its full access record + span tree here (implies trace=1)
    slow_log: str | None = None
    # --- multi-tenant serving (serve-http only; serve/registry.py,
    # docs/serving.md "Multi-tenant front door") -----------------------
    # tenant roster: a JSON list (inline, or a path to a .json file) of
    # {"name", "artifact", "weight"?, "queue_max"?, "deadline_ms"?,
    # "slo_ms"?, "precision"?, "nprobe"?} objects — each tenant gets
    # its own engine + batcher + degradation ladder + SLO window behind
    # the ONE front door; unlisted knobs inherit this config's values.
    # The FIRST tenant is the default route (requests without a
    # "tenant" field).  Mutually exclusive with artifact= and live=1.
    tenants: str | None = None
    # engine-paging budget in MiB of device table bytes (0 = unlimited):
    # past it, idle tenants' engines are dropped (the artifact stays
    # the host master) and rebuilt on demand, prewarmed off the hot path
    device_budget_mb: float = 0.0


def _ids(s: str, name: str) -> list[int]:
    try:
        out = [int(t) for t in s.split(",") if t.strip() != ""]
    except ValueError:
        raise SystemExit(f"{name}={s!r}: want comma-separated integers")
    if not out:
        raise SystemExit(f"{name}= is required (comma-separated ids)")
    return out


def _build(cfg: ServeConfig):
    """(engine, batcher) from the committed artifact."""
    from hyperspace_tpu.serve import (QueryEngine, RequestBatcher,
                                      load_artifact)

    if not cfg.artifact:
        raise SystemExit("artifact= is required for query/serve modes")
    mesh = None
    if cfg.mesh:
        from hyperspace_tpu.parallel.mesh import model_mesh

        try:
            mesh = model_mesh(cfg.mesh)
        except ValueError as e:
            raise SystemExit(f"mesh={cfg.mesh}: {e}") from None
    art = load_artifact(cfg.artifact)
    try:
        eng = QueryEngine.from_artifact(art, chunk_rows=cfg.chunk_rows,
                                        mesh=mesh, scan_mode=cfg.scan_mode,
                                        precision=cfg.precision,
                                        nprobe=cfg.nprobe)
        if cfg.live:
            # mutable serving: the artifact table becomes the host
            # master (a writable copy — the mmapped artifact stays
            # pristine) and the frozen engine becomes the base under a
            # delta segment (serve/delta.py)
            from hyperspace_tpu.parallel.host_table import HostEmbedTable
            from hyperspace_tpu.serve.delta import LiveQueryEngine

            master = HostEmbedTable.from_array(
                np.array(art.table, np.float32))
            eng = LiveQueryEngine(eng, master, capacity=cfg.delta_cap,
                                  compact_at=cfg.compact_at)
    except ValueError as e:  # bad scan_mode/chunk_rows/precision/nprobe
        raise SystemExit(str(e)) from None
    # --- observability plane (ServeConfig docstrings): window, access
    # log, flight recorder — all optional, wired into the batcher so
    # every serving surface (stdin loop, one-shot query, front door)
    # carries the same records
    window = recorder = alog = sink = slow = slow_sink = None
    if cfg.window_s < 0:
        raise SystemExit(f"window_s must be >= 0; got {cfg.window_s}")
    if cfg.window_s:
        from hyperspace_tpu.telemetry.window import SloWindow

        window = SloWindow(cfg.window_s)
    if cfg.trace or cfg.slow_log:
        # slow_log= needs span trees to attach, so it implies trace=
        from hyperspace_tpu.telemetry import spans

        spans.enable()
    try:
        if cfg.incident_dir:
            from hyperspace_tpu.serve.access import FlightRecorder

            recorder = FlightRecorder(cfg.incident_dir)
        if cfg.access_log or recorder is not None:
            from hyperspace_tpu.serve.access import AccessLog

            alog = AccessLog(cfg.access_log, recorder=recorder)
            sink = alog.emit
        if cfg.slow_log:
            from hyperspace_tpu.serve.access import AccessLog

            slow = AccessLog(cfg.slow_log)
            slow_sink = slow.emit
    except OSError as e:  # uncreatable/unwritable path is a usage error
        raise SystemExit(f"observability path: {e}") from None
    try:
        batcher = RequestBatcher(eng, min_bucket=cfg.min_bucket,
                                 max_bucket=cfg.max_bucket,
                                 cache_size=cfg.cache_size,
                                 queue_max=cfg.queue_max,
                                 deadline_ms=cfg.deadline_ms,
                                 window=window, slo_ms=cfg.slo_ms,
                                 access_sink=sink, recorder=recorder,
                                 slow_sink=slow_sink)
    except ValueError as e:  # bad queue_max/deadline_ms/slo_ms
        raise SystemExit(str(e)) from None
    batcher.access_log = alog  # closed by the serve-session bracket
    batcher.slow_log = slow
    return eng, batcher


def _build_registry(cfg: ServeConfig, prewarm_ks: list[int]):
    """The serve-http multi-tenant path: ``tenants=`` (inline JSON or a
    path to a JSON file) → a fully-built
    :class:`~hyperspace_tpu.serve.registry.EngineRegistry`.  Per-tenant
    fields override the shared config's serving knobs; malformed
    rosters are usage errors before any engine builds."""
    from hyperspace_tpu.serve.registry import EngineRegistry

    if cfg.artifact:
        raise SystemExit("tenants= and artifact= are mutually exclusive "
                         "(each tenant names its own artifact)")
    if cfg.live:
        raise SystemExit("tenants= does not support live=1 yet (the "
                         "delta segment is per-engine state that "
                         "engine paging would drop)")
    text = cfg.tenants
    if text and os.path.exists(text):
        try:
            with open(text, "r", encoding="utf-8") as f:
                text = f.read()
        except OSError as e:
            raise SystemExit(f"tenants={cfg.tenants}: {e}") from None
    try:
        roster = json.loads(text or "")
    except json.JSONDecodeError as e:
        raise SystemExit(
            f"tenants= wants a JSON list (inline or a file path): {e}"
        ) from None
    if (not isinstance(roster, list) or not roster
            or not all(isinstance(t, dict) for t in roster)):
        raise SystemExit(
            "tenants= wants a non-empty JSON list of tenant objects")
    reg = EngineRegistry(device_budget_mb=cfg.device_budget_mb,
                         max_wait_us=cfg.max_wait_us,
                         prewarm_ks=prewarm_ks)
    try:
        for t in roster:
            name, artifact = t.get("name"), t.get("artifact")
            if not (isinstance(name, str) and name
                    and isinstance(artifact, str) and artifact):
                raise SystemExit(
                    f"tenant entry {t!r}: wants string \"name\" and "
                    "\"artifact\" fields")
            unknown = set(t) - {"name", "artifact", "weight",
                                "queue_max", "deadline_ms", "slo_ms",
                                "precision", "nprobe"}
            if unknown:
                raise SystemExit(
                    f"tenant {name!r}: unknown field(s) "
                    f"{sorted(unknown)}")
            reg.add_tenant(
                name, artifact,
                weight=float(t.get("weight", 1.0)),
                window_s=cfg.window_s,
                engine_kw=dict(
                    chunk_rows=cfg.chunk_rows,
                    scan_mode=cfg.scan_mode,
                    precision=t.get("precision", cfg.precision),
                    nprobe=int(t.get("nprobe", cfg.nprobe))),
                batcher_kw=dict(
                    min_bucket=cfg.min_bucket,
                    max_bucket=cfg.max_bucket,
                    cache_size=cfg.cache_size,
                    queue_max=int(t.get("queue_max", cfg.queue_max)),
                    deadline_ms=float(t.get("deadline_ms",
                                            cfg.deadline_ms)),
                    slo_ms=float(t.get("slo_ms", cfg.slo_ms))))
    except (ValueError, TypeError, OSError) as e:
        # bad artifact / duplicate name / bad knob values: usage errors
        raise SystemExit(f"tenants=: {e}") from None
    return reg


def _prewarm_ks(cfg: ServeConfig) -> list[int]:
    """The ``prewarm=`` flag parsed into the k values to warm ([] = off;
    docstring on the ServeConfig field).  Malformed values are clean
    usage errors — a typo'd prewarm silently serving cold would defeat
    the flag's whole point."""
    v = cfg.prewarm.strip().lower()
    if v in ("", "0", "false", "no", "off"):
        return []
    if v in ("1", "true", "yes", "on"):
        return [cfg.k]
    try:
        ks = [int(t) for t in v.split(",") if t.strip()]
    except ValueError:
        raise SystemExit(
            f"prewarm={cfg.prewarm!r}: want 0/1 or a comma-separated "
            "list of k values to warm") from None
    if not ks or any(k < 1 for k in ks):
        raise SystemExit(
            f"prewarm={cfg.prewarm!r}: k values must be >= 1")
    return ks


def _run_prewarm(batcher, ks: list[int]) -> None:
    """Warm the ladder and announce it on stderr (diagnostics — stdout
    stays the response stream).  Invalid ks for this table (k past the
    row count) are usage errors, same class as a bad query k."""
    if not ks:
        return
    try:
        info = batcher.prewarm(ks)
    except ValueError as e:
        raise SystemExit(f"prewarm: {e}") from None
    try:
        print(f"[serve] prewarmed {info['programs']} program(s) over "
              f"buckets {info['buckets']} ks {info['ks']} in "
              f"{info['seconds']:.2f}s", file=sys.stderr, flush=True)
    except (OSError, ValueError):
        pass  # closed stderr: announcement loss only


def run_export(cfg: ServeConfig) -> dict:
    from hyperspace_tpu.serve import export_from_checkpoint

    if not (cfg.ckpt and cfg.out):
        raise SystemExit("export needs ckpt= and out=")
    model_config: dict = {}
    if cfg.workload in ("poincare", "lorentz"):
        if cfg.c is None:
            raise SystemExit(
                f"export workload={cfg.workload} requires c= (the "
                "curvature the run trained with — a wrong default would "
                "freeze the wrong metric into the artifact)")
        try:
            model_config["c"] = float(cfg.c)
        except ValueError:
            raise SystemExit(f"c={cfg.c!r}: want a float") from None
    elif cfg.factors:
        try:
            model_config["factors"] = json.loads(cfg.factors)
        except json.JSONDecodeError as e:
            raise SystemExit(
                f"factors={cfg.factors!r}: want JSON [[kind, dim], ...] "
                f"({e})") from None
    index_ncells = None
    if cfg.index or cfg.ncells:
        if cfg.ncells < 0:
            raise SystemExit(f"ncells={cfg.ncells}: want 0 (auto) or >= 2")
        index_ncells = cfg.ncells or -1  # <= 0 = auto (~sqrt(N))
    if cfg.quant and cfg.quant not in ("int4", "pq"):
        raise SystemExit(f"quant={cfg.quant!r}: want int4 or pq")
    try:
        art = export_from_checkpoint(
            cfg.ckpt, cfg.out, workload=cfg.workload,
            model_config=model_config,
            step=None if cfg.step < 0 else cfg.step,
            overwrite=cfg.overwrite, index_ncells=index_ncells,
            quant_lane=cfg.quant or None)
    except ValueError as e:  # bad ncells for the table size: usage
        raise SystemExit(str(e)) from None
    out = {"mode": "export", "out": cfg.out, "workload": cfg.workload,
           "num_nodes": art.num_nodes, "dim": art.dim, "step": art.step,
           "fingerprint": art.fingerprint}
    if art.index is not None:
        out["index"] = {"ncells": art.index.ncells,
                        "max_cell": art.index.max_cell,
                        "fingerprint": art.index.fingerprint}
    if art.quant is not None:
        out["quant"] = {"lane": art.quant.lane,
                        "fingerprint": art.quant.fingerprint}
    return out


def run_query(cfg: ServeConfig) -> dict:
    from hyperspace_tpu.serve.errors import ServeError

    _eng, batcher = _build(cfg)
    _trace.close_startup()  # built: the one query is no part of start-up
    # request-shaped ValueErrors (k out of range, IVF probe capacity /
    # under-fill) and the typed serve errors (deadline/overload) are
    # usage errors in one-shot mode: clean exit, no traceback — the
    # serve loop answers the same errors per line
    try:
        if cfg.u or cfg.v:
            scores = batcher.score(_ids(cfg.u, "u"), _ids(cfg.v, "v"),
                                   prob=cfg.prob, fd_r=cfg.fd_r,
                                   fd_t=cfg.fd_t)
            return {"mode": "query", "scores": scores.tolist()}
        ids = _ids(cfg.ids, "ids")
        idx, dist = batcher.topk(ids, cfg.k)
    except (ValueError, ServeError) as e:
        raise SystemExit(str(e)) from None
    return {"mode": "query", "ids": ids, "k": cfg.k,
            "neighbors": idx.tolist(), "dists": dist.tolist()}


def _latency_line(baseline: dict | None = None) -> str:
    """One-line ``serve/e2e_ms`` summary (count + p50/p95/p99) from the
    latency histogram — printed to STDERR on serve-loop exit and per
    ``stats`` request (stdout stays strictly one response per line).
    With a ``baseline`` (a registry ``mark()`` from serve-loop start)
    the distribution is the delta over THIS session, not the process
    lifetime — an earlier in-process run's requests never inflate it."""
    from hyperspace_tpu.telemetry import registry as telem

    snap = telem.default_registry().snapshot(baseline=baseline)
    lat = snap.get("hist/serve/e2e_ms")
    if not lat or not lat.get("count"):
        return "[serve] latency e2e_ms: no requests"
    return ("[serve] latency e2e_ms count=%d p50=%.3f p95=%.3f p99=%.3f"
            % (lat["count"], lat["p50"], lat["p95"], lat["p99"]))


def _print_latency_stderr(baseline: dict | None = None) -> None:
    """Print the latency one-liner to stderr, OUTSIDE the request
    try-block and shielded: a consumer closing our stderr mid-serve
    (BrokenPipeError, or ValueError on a closed file) is a diagnostics
    loss, never a served-request failure or a loop exit."""
    try:
        print(_latency_line(baseline), file=sys.stderr, flush=True)
    except (OSError, ValueError):
        pass


def _window_line(batcher) -> str | None:
    """One-line rolling-window SLO summary (telemetry/window.py) — the
    'latency NOW' complement of the cumulative ``_latency_line``; None
    when no window is armed."""
    w = getattr(batcher, "window", None)
    if w is None:
        return None
    rep = w.report()
    e = rep.get("e2e_ms")
    if not e:
        return "[serve] window: no requests in the current window"
    return ("[serve] window %.1fs e2e_ms count=%d p50=%.3f p95=%.3f "
            "p99=%.3f qps=%.2f shed/s=%.2f err/s=%.2f"
            % (rep["window_s"], e["count"], e["p50"], e["p95"],
               e["p99"], rep["rate_qps"], rep["shed_rate"],
               rep["error_rate"]))


def _print_window_stderr(batcher) -> None:
    line = _window_line(batcher)
    if line is None:
        return
    try:
        print(line, file=sys.stderr, flush=True)
    except (OSError, ValueError):
        pass


@contextlib.contextmanager
def _serve_session(cfg: ServeConfig, batcher):
    """The serve modes' observability bracket: with ``log=``, write the
    train-CLI record shapes — a ``run_manifest`` FIRST record (the full
    ServeConfig as executed + device/backend identity) and a closing
    ``telemetry_summary`` scoped to this session by a registry mark —
    so ``read_jsonl`` tooling reads serve sessions exactly like train
    runs; always closes the access log on the way out.  The process's
    first session also writes the ``startup`` event before its summary
    (process start to server ready by span, docs/observability.md
    "Start-up timeline").  Yields the session mark (the latency
    one-liners' baseline)."""
    from hyperspace_tpu.telemetry import registry as telem

    mark = telem.default_registry().mark()
    ends_startup = _trace.startup_open()
    logger = None
    try:
        if cfg.log:
            from hyperspace_tpu.train.logging import MetricsLogger
            from hyperspace_tpu.train.loop import run_manifest

            try:
                logger = MetricsLogger(cfg.log, stdout=False)
            except OSError as e:
                # same usage-error mapping as access_log=/incident_dir=
                # (and the access log opened by _build still closes —
                # this raise unwinds through the finally below)
                raise SystemExit(f"log={cfg.log}: {e}") from None
            logger.event("run_manifest", **run_manifest(cfg))
        yield mark
    finally:
        if logger is not None:
            # summary must land even when the loop died — the session's
            # counters matter most in a post-mortem (train-loop rule)
            startup = _trace.startup_fields() if ends_startup else {}
            if startup:  # {}: it died before it was ready
                logger.event("startup", **startup)
            logger.event("telemetry_summary",
                         **telem.default_registry().snapshot(
                             "ctr/", baseline=mark))
            logger.close()
        alog = getattr(batcher, "access_log", None)
        if alog is not None:
            alog.close()
        slow = getattr(batcher, "slow_log", None)
        if slow is not None:
            slow.close()
        if cfg.trace or cfg.slow_log:
            # span enablement is process-global (_build turned it on):
            # an in-process caller (tests) must not inherit it
            from hyperspace_tpu.telemetry import spans

            spans.disable()


def _json_bool(req: dict, key: str, default: bool) -> bool:
    """Strict JSON boolean: the string \"false\" must be an error, not
    truthy — same reject-don't-coerce policy as the id/k validation."""
    v = req.get(key, default)
    if not isinstance(v, bool):
        raise ValueError(
            f"{key} must be a JSON boolean, got {type(v).__name__}")
    return v


def _req_deadline(req: dict):
    """Validate the optional per-request ``deadline_ms`` field (strict:
    a positive JSON number, not a bool/string) — None means "use the
    server's default"."""
    v = req.get("deadline_ms")
    if v is None:
        return None
    if isinstance(v, bool) or not isinstance(v, (int, float)) or v <= 0:
        raise ValueError(
            f"deadline_ms must be a positive number, got {v!r}")
    return float(v)


def _req_id(req: dict) -> str | None:
    """The optional per-request ``request_id`` (strict: a string) —
    the stdin loop's analog of the HTTP ``X-Request-Id`` header.  When
    present it is threaded into the lifecycle/access log AND echoed in
    the response line, so a client can join its requests to answers
    over the one shared stdout stream."""
    v = req.get("request_id")
    if v is None:
        return None
    if not isinstance(v, str) or not v:
        raise ValueError(
            f"request_id must be a non-empty string, got {v!r}")
    return v


def _handle(batcher, req: dict, entered=None) -> dict:
    """One request; ``entered`` (a 1-element list) is set True the
    moment a batcher entry is invoked — past that point the batcher
    owns the access log, before it the loop's error path must emit the
    record itself (the HTTP server's ``entered`` contract)."""
    op = req.get("op")
    rid = _req_id(req)
    echo = {} if rid is None else {"request_id": rid}
    if op == "topk":
        # k passes through raw: the batcher rejects non-integers rather
        # than truncating (a float k must be a client error, not k-1)
        ids, k = req["ids"], req.get("k", 10)
        exclude_self = _json_bool(req, "exclude_self", True)
        deadline_ms = _req_deadline(req)
        if entered is not None:
            entered[0] = True
        idx, dist = batcher.topk(ids, k, exclude_self=exclude_self,
                                 deadline_ms=deadline_ms, request_id=rid)
        return {"neighbors": idx.tolist(), "dists": dist.tolist(), **echo}
    if op == "score":
        u, v = req["u"], req["v"]
        prob = _json_bool(req, "prob", False)
        fd_r = float(req.get("fd_r", 2.0))
        fd_t = float(req.get("fd_t", 1.0))
        deadline_ms = _req_deadline(req)
        if entered is not None:
            entered[0] = True
        scores = batcher.score(u, v, prob=prob, fd_r=fd_r, fd_t=fd_t,
                               deadline_ms=deadline_ms, request_id=rid)
        return {"scores": scores.tolist(), **echo}
    if op == "upsert":
        ids, rows = req.get("ids"), req.get("rows")
        deadline_ms = _req_deadline(req)
        if entered is not None:
            entered[0] = True
        return {**batcher.upsert(ids, rows, deadline_ms=deadline_ms,
                                 request_id=rid), **echo}
    if op == "delete":
        deadline_ms = _req_deadline(req)
        if entered is not None:
            entered[0] = True
        return {**batcher.delete(req.get("ids"), deadline_ms=deadline_ms,
                                 request_id=rid), **echo}
    if op == "stats":
        # stats echoes too: a pipelined client must be able to join
        # EVERY answered line, scrape ops included
        return {**batcher.stats(), **echo}
    raise ValueError(
        f"unknown op {op!r} (want topk|score|upsert|delete|stats)")


def _loop_access(batcher, req, outcome: str) -> None:
    """Access-log a loop failure that never reached the batcher — the
    HTTP server's ``_serve_access`` analog for the stdin surface
    (parse errors, non-object lines, unknown ops, missing/malformed
    pre-dispatch fields).  The batcher emits for everything past its
    entry, so this covers exactly the complement: no double lines,
    and a malformed-line storm still feeds ``serve/errors``, the
    window's error rate, and the flight recorder's burst detector."""
    op = "none"
    rid = None
    if isinstance(req, dict):
        if isinstance(req.get("op"), str):
            op = req["op"]
        v = req.get("request_id")
        if isinstance(v, str) and v:
            rid = v
    batcher.emit_synthetic_access(op, request_id=rid, outcome=outcome)


def _echo_error_rid(resp: dict, req) -> dict:
    """Echo a well-formed ``request_id`` on ERROR responses too — a
    client pipelining requests over the one stdout stream must be able
    to join failures to requests, not only successes."""
    if isinstance(req, dict):
        rid = req.get("request_id")
        if isinstance(rid, str) and rid:
            return {**resp, "request_id": rid}
    return resp


def run_serve(cfg: ServeConfig, *, stdin=None, stdout=None) -> dict:
    """The JSONL loop; returns the closing stats dict (also printed to
    stderr when telemetry is on).  ``stdin``/``stdout`` injectable for
    tests.

    Error taxonomy (docs/serving.md): every failed line answers
    ``{"error": {"kind": ..., "message": ...}}`` with a machine-readable
    kind — ``parse`` (not JSON), ``validation`` (bad request),
    ``deadline_exceeded``, ``overloaded``, ``internal``.  Every read
    line gets exactly one response line; none is silently dropped.

    SIGTERM triggers **graceful drain**: stop admitting new lines,
    finish the in-flight request, print the drain notice + latency
    summary to stderr, and return the closing stats normally.  A real
    (fileno-backed) stdin is read through a select-polling raw reader
    (:func:`_poll_lines`) so an IDLE server drains within one poll
    interval too — a handler that only ran at the next protocol event
    would make a silent client block shutdown forever.  (From a
    non-main thread, where signal handlers cannot install, the loop
    simply runs without drain support; injected test streams without a
    fileno drain at line boundaries.)"""
    import signal
    import threading

    from hyperspace_tpu.serve.errors import ServeError, error_response
    from hyperspace_tpu.telemetry import registry as telem

    stdin = sys.stdin if stdin is None else stdin
    stdout = sys.stdout if stdout is None else stdout
    _eng, batcher = _build(cfg)
    # warm the ladder BEFORE the first line is read — the first real
    # request on every bucket must be warm (docs/serving.md "Warm
    # starts"); with the persistent cache on, warming a restarted
    # server is deserialization, not compilation
    _run_prewarm(batcher, _prewarm_ks(cfg))
    served = 0
    draining = threading.Event()
    prev_handler = None
    try:
        prev_handler = signal.signal(signal.SIGTERM,
                                     lambda _s, _f: draining.set())
    except ValueError:
        pass  # not the main thread: no drain hook, loop still serves
    # session bracket: log= parity records + access-log close; the
    # yielded mark is the latency one-liners' baseline (the
    # distribution of THIS serve loop, not the whole process)
    session = _serve_session(cfg, batcher)
    session_mark = session.__enter__()
    # built and prewarmed, no line read yet: the server is ready and the
    # process's start-up timeline ends, so every request runs the closed
    # span path (allocation-free with tracing off)
    _trace.close_startup()
    try:
        for line in _line_source(stdin, draining):
            if draining.is_set():
                break  # stop admitting; the prior request already flushed
            line = line.strip()
            if not line:
                continue
            is_stats = False
            req = None
            entered = [False]  # past a batcher entry, it owns the log
            try:
                try:
                    req = json.loads(line)
                except json.JSONDecodeError as e:
                    raise _ParseError(str(e)) from None
                if not isinstance(req, dict):
                    raise ValueError(
                        f"request must be a JSON object, "
                        f"got {type(req).__name__}")
                resp = _handle(batcher, req, entered)
                served += 1
                is_stats = req.get("op") == "stats"
            except _ParseError as e:
                resp = {"error": {"kind": "parse", "message": str(e)}}
                _loop_access(batcher, req, "parse")
            except (ServeError, ValueError, KeyError, TypeError,
                    OverflowError, OSError) as e:
                # OverflowError: numpy raises it for ints past the cast
                # width; belt-and-braces with the batcher's range check.
                # OSError: a per-request IO failure (incl. the injected
                # serve.dispatch ioerror chaos fault) answers
                # error.kind=internal and the loop keeps serving — one
                # request's IO trouble must not kill the server.
                # error_response maps ServeError kinds
                # (deadline_exceeded/overloaded), the stdlib validation
                # classes, and everything else (-> internal) onto the
                # taxonomy
                resp = error_response(e)
                if not entered[0]:
                    # the failure never reached the batcher: the loop
                    # must write the access record itself
                    _loop_access(batcher, req, resp["error"]["kind"])
            if "error" in resp:
                resp = _echo_error_rid(resp, req)
            print(json.dumps(_json_safe(resp)), file=stdout, flush=True)
            if is_stats:
                # the latency one-liner rides on stderr beside the stats
                # response — stdout stays one response per line
                _print_latency_stderr(session_mark)
                _print_window_stderr(batcher)
    finally:
        if prev_handler is not None:
            signal.signal(signal.SIGTERM, prev_handler)
        if draining.is_set():
            try:
                print(f"[serve] drained: SIGTERM — stopped admitting, "
                      f"{served} request(s) served", file=sys.stderr,
                      flush=True)
            except (OSError, ValueError):
                pass  # diagnostics never sink the drain
            if batcher.recorder is not None:
                # SIGTERM is a flight-recorder trigger on the stdin
                # path too — shutdown leaves the same evidence the
                # front door's drain does (wait: the process exits next)
                batcher.recorder.dump("sigterm_drain", _cls="drain",
                                      wait=True)
        # the closing summary must survive an engine-level crash — the
        # accumulated distribution matters most in a post-mortem
        _print_latency_stderr(session_mark)
        _print_window_stderr(batcher)
        session.__exit__(None, None, None)
    return {"mode": "serve", "served": served,
            "drained": draining.is_set(), **batcher.stats()}


def run_serve_http(cfg: ServeConfig, *, ready=None) -> dict:
    """The asyncio HTTP front door (serve/server.py): concurrent
    ``POST /v1/topk`` / ``/v1/score`` / ``/v1/upsert`` /
    ``/v1/delete`` / ``/v1/stats`` + ``POST /admin/rollover`` + ``GET
    /healthz`` over the continuous-batching collator; SIGTERM drains
    exactly like the stdin loop (in-flight answered, new connections
    refused, latency summary on stderr).  ``ready(host, port)`` is
    called once the listener is bound — the default announces the port
    on stderr as a parseable ``[serve-http] listening on HOST:PORT``
    line (port=0 binds an ephemeral port).  ``/admin/rollover`` is
    armed with a builder that replays this config against the posted
    ``target`` artifact path (serve/rollover.py: the standby is built
    and prewarmed off-loop, the flip is health-gated and atomic)."""
    import asyncio

    from hyperspace_tpu.serve.server import run_front_door

    if cfg.max_wait_us < 0:  # usage error BEFORE the artifact load pays
        raise SystemExit(
            f"max_wait_us must be >= 0; got {cfg.max_wait_us}")
    prewarm_ks = _prewarm_ks(cfg)  # parse errors before the build pays

    def announce(host, port):
        # prewarmed and bound, no connection taken yet: the server is
        # ready and the process's start-up timeline ends (run_serve)
        _trace.close_startup()
        try:
            print(f"[serve-http] listening on {host}:{port}",
                  file=sys.stderr, flush=True)
        except (OSError, ValueError):
            pass  # hyperlint: disable=swallow-base-exception — closed stderr: announcement loss only
        if ready is not None:
            ready(host, port)

    if cfg.tenants:
        # multi-tenant front door (serve/registry.py): one engine/
        # batcher/ladder stack per roster entry, weighted-fair dispatch
        # on the one shared executor, engine paging under the budget
        registry = _build_registry(cfg, prewarm_ks)
        with _serve_session(cfg, registry.default.batcher):
            try:
                result = asyncio.run(run_front_door(
                    registry=registry, host=cfg.host, port=cfg.port,
                    max_wait_us=cfg.max_wait_us, ready=announce,
                    prewarm_ks=prewarm_ks))
            except ValueError as e:  # prewarm k out of range
                raise SystemExit(f"prewarm: {e}") from None
            except OSError as e:
                raise SystemExit(
                    f"serve-http: cannot bind {cfg.host}:{cfg.port} "
                    f"— {e}") from None
        return {"mode": "serve_http", **result,
                "tenants": registry.stats()}
    _eng, batcher = _build(cfg)

    def rebuild(target: str):
        # SystemExit (how _build reports a bad artifact) would escape the
        # connection task uncaught — re-raise as the ValueError the front
        # door's error taxonomy maps to a 400 validation response.
        try:
            return _build(dataclasses.replace(cfg, artifact=target))[1]
        except SystemExit as e:
            raise ValueError(str(e)) from None

    with _serve_session(cfg, batcher):
        try:
            result = asyncio.run(run_front_door(
                batcher, host=cfg.host, port=cfg.port,
                max_wait_us=cfg.max_wait_us, ready=announce,
                prewarm_ks=prewarm_ks, rollover_builder=rebuild))
        except ValueError as e:  # prewarm k out of range for this table
            raise SystemExit(f"prewarm: {e}") from None
        except OSError as e:  # bind failure (port in use, bad host): usage
            raise SystemExit(
                f"serve-http: cannot bind {cfg.host}:{cfg.port} — {e}"
            ) from None
        _print_window_stderr(batcher)
    return {"mode": "serve_http", **result, **batcher.stats()}


class _ParseError(Exception):
    """Internal marker: the line was not JSON at all (kind=parse)."""


def _poll_lines(fd: int, draining):
    """Line iterator over a raw fd with a drain check every poll tick.

    A plain ``for line in sys.stdin`` blocks in ``readline`` — and
    PEP 475 retries the read after a signal handler runs, so a SIGTERM
    to an IDLE server would never drain until the client's next line.
    Reading the raw fd under a short ``select`` timeout bounds the
    drain latency at one tick; buffering by hand (rather than through
    the TextIO layer) avoids the classic select-vs-buffered-reader
    stall where a burst of lines sits unread in the text buffer while
    select waits on the drained fd."""
    import select

    buf = b""
    while True:
        nl = buf.find(b"\n")
        if nl >= 0:
            line, buf = buf[:nl + 1], buf[nl + 1:]
            yield line.decode("utf-8", errors="replace")
            continue
        if draining.is_set():
            return
        ready, _, _ = select.select([fd], [], [], 0.25)
        if not ready:
            continue
        chunk = os.read(fd, 65536)
        if not chunk:  # EOF; a trailing unterminated line still serves
            if buf:
                yield buf.decode("utf-8", errors="replace")
            return
        buf += chunk


def _line_source(stdin, draining):
    """The serve loop's line iterator: the polling raw-fd reader for
    real streams, plain iteration for injected test streams (StringIO
    and generators have no usable fileno — they drain at line
    boundaries instead)."""
    try:
        fd = stdin.fileno()
    except (AttributeError, OSError, ValueError, io.UnsupportedOperation):
        return iter(stdin)
    return _poll_lines(fd, draining)


MODES = {"export": run_export, "query": run_query, "serve": run_serve,
         "serve-http": run_serve_http}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="hyperspace_tpu.cli.serve",
        description="Export serving artifacts and answer embedding queries.")
    ap.add_argument("mode", choices=sorted(MODES))
    ap.add_argument("overrides", nargs="*",
                    help="key=value overrides (ServeConfig fields)")
    args = ap.parse_args(argv)

    kv = {}
    for p in args.overrides:
        if "=" not in p:
            raise SystemExit(f"expected key=value, got {p!r}")
        k, v = p.split("=", 1)
        kv[k] = v
    cfg = apply_overrides(ServeConfig(), kv)

    from hyperspace_tpu import compile_cache
    from hyperspace_tpu.resilience import faults as _faults
    from hyperspace_tpu.telemetry import cli_session

    try:
        # BEFORE the engine builds: every bucket executable (and the
        # prewarm pass) should come from / land in the persistent cache
        compile_cache.activate(cfg.compile_cache_dir)
    except ValueError as e:  # unusable cache dir is a usage error
        raise SystemExit(str(e)) from None
    # (the serve stats' `recompiles` field is a CONTRACT number, flat
    # once warm, honest with telemetry=0 and the cache disabled: the
    # package's import armed the jax.monitoring hook that counts it)
    try:
        chaos_armed = _faults.install_chaos(cfg.chaos, cfg.chaos_seed)
    except ValueError as e:  # malformed chaos= grammar is a usage error
        raise SystemExit(str(e)) from None
    try:
        # stream=stderr: in serve mode stdout is the response stream
        with cli_session(cfg.telemetry, cfg.trace_out, stream=sys.stderr):
            result = MODES[args.mode](cfg)
        if chaos_armed:
            result["chaos"] = _faults.stats()
    finally:
        if chaos_armed:
            # process-global registry: an in-process caller (tests)
            # must never inherit this run's faults
            _faults.clear()
        if cfg.telemetry:
            from hyperspace_tpu.telemetry import registry as telem

            print(json.dumps({"telemetry_summary":
                              telem.snapshot("ctr/")}),
                  file=sys.stderr, flush=True)
    # serve mode's stdout is the response stream (one line per request,
    # strictly) and serve-http's responses ride the sockets; both
    # modes' closing stats are diagnostics and go to stderr
    print(json.dumps(_json_safe(result)),
          file=(sys.stderr if args.mode in ("serve", "serve-http")
                else sys.stdout))
    return 0


if __name__ == "__main__":
    sys.exit(main())
