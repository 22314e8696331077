"""Persistent on-disk cache for expensive graph preprocessing.

Host graph prep is the slow, deterministic prefix of every big-graph run:
edge layout (symmetrize/dedupe/sort/pad + reverse involution + block-CSR
plan), the cluster-pair split (one host sort over ~2.4 M edges), the
community/BFS locality order (~20 s at arxiv scale), and the LP edge
split.  All of it is a pure function of (input arrays, knobs, code), so
repeat runs — and the bench's realistic disk-graph legs, which rebuild
the identical artifacts every round — can skip the rebuild entirely.

Keying: sha256 over the input arrays' raw bytes (dtype/shape included),
every knob, and a **code fingerprint** (the bytes of the modules that
compute the artifacts — ``data/graphs.py``, ``kernels/cluster.py``,
``kernels/segment.py``, and this file), so editing any producer
invalidates every entry instead of silently serving stale layouts.

Storage: one pickle per entry under ``<repo>/.cache/graphprep`` (already
gitignored), written atomically (tmp + rename) so an interrupted run
never leaves a half-written entry that a later run would load.  A
corrupt/unreadable entry is treated as a miss and rebuilt in place.

Knobs:

- ``HYPERSPACE_CACHE_DIR``      — cache root override.
- ``HYPERSPACE_GRAPH_CACHE=0``  — disables the "auto" default (explicit
  ``cache=True``/``PrepCache`` arguments still work).

Call sites (``data/graphs.py``) default to ``cache="auto"``: caching
engages only at scales where the prep is measurably expensive (the same
~200 k-edge gate as the cluster split), so unit-test-sized graphs never
touch the disk.  Each hit/miss bumps the telemetry registry
(``prep_cache/hit`` / ``prep_cache/miss`` — docs/observability.md), so
the "second run skips rebuild" contract is visible in every JSONL log
record and bench artifact instead of as a scattered stdout line; each
lookup/build/store transaction runs under one ``prep`` trace span, so
cache effectiveness (and a slow cache) shows up as host-timeline time
in ``trace_out=`` dumps.
"""

from __future__ import annotations

import hashlib
import os
import pickle
from typing import Any, Callable, Optional

import numpy as np

# bump to invalidate every entry on format changes
CACHE_FORMAT = 1

# producers whose source participates in the key (paths relative to the
# package root) — edit any of them and every cached artifact misses.
# The native C++ pipeline is the PREFERRED path inside
# _build_edge_layout / sample_negative_edges, so its sources (and the
# ctypes wrapper that dispatches to it) must invalidate too.
_CODE_FILES = (
    os.path.join("data", "graphs.py"),
    os.path.join("data", "prep_cache.py"),
    os.path.join("data", "native.py"),
    os.path.join("data", "_native", "graphprep.cc"),
    os.path.join("data", "_native", "closure.cc"),
    os.path.join("data", "_native", "localorder.cc"),
    os.path.join("data", "_native", "sampler.cc"),
    os.path.join("kernels", "cluster.py"),
    os.path.join("kernels", "segment.py"),
)

_ENV_DIR = "HYPERSPACE_CACHE_DIR"
_ENV_SWITCH = "HYPERSPACE_GRAPH_CACHE"

_code_fp: Optional[str] = None


def default_root() -> str:
    root = os.environ.get(_ENV_DIR)
    if root:
        return os.path.abspath(root)
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".cache", "graphprep")


def auto_enabled() -> bool:
    """Whether ``cache="auto"`` call sites may cache at all."""
    return os.environ.get(_ENV_SWITCH, "1").lower() not in (
        "0", "false", "no", "off")


def code_fingerprint() -> str:
    """sha256 of the producer modules' bytes (memoized per process)."""
    global _code_fp
    if _code_fp is None:
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for rel in _CODE_FILES:
            path = os.path.join(pkg, rel)
            h.update(rel.encode())
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(b"<missing>")
        _code_fp = h.hexdigest()
    return _code_fp


def _update(h, part) -> None:
    """Feed one key part into the hash, type-tagged so e.g. the int 1 and
    the string "1" can never collide."""
    if isinstance(part, np.ndarray):
        a = np.ascontiguousarray(part)
        h.update(f"nd:{a.dtype.str}:{a.shape}:".encode())
        h.update(a.tobytes())
    elif isinstance(part, (tuple, list)):
        h.update(f"seq{len(part)}:".encode())
        for p in part:
            _update(h, p)
    elif isinstance(part, bytes):
        h.update(b"b:" + part)
    else:
        h.update(f"{type(part).__name__}:{part!r};".encode())


def key_hash(kind: str, key_parts) -> str:
    h = hashlib.sha256()
    _update(h, (CACHE_FORMAT, code_fingerprint(), kind, tuple(key_parts)))
    return h.hexdigest()


class PrepCache:
    """Content-addressed pickle store with hit/miss counters."""

    def __init__(self, root: Optional[str] = None):
        self.root = os.path.abspath(root or default_root())
        self.hits = 0
        self.misses = 0

    def _path(self, kind: str, digest: str) -> str:
        return os.path.join(self.root, f"{kind}-{digest}.pkl")

    def get_or_build(self, kind: str, key_parts, builder: Callable[[], Any]):
        """Load the entry for (kind, key_parts) or build + store it.

        The builder's return value must be picklable (numpy arrays and
        plain containers of them).  Any storage failure degrades to
        building without caching — the cache can slow nothing down and
        break nothing."""
        from hyperspace_tpu.telemetry import registry as telem
        from hyperspace_tpu.telemetry.trace import span

        # ONE span over the whole lookup/build/store: a slow cache (a
        # multi-hundred-MB pickle.load off slow disk) must be visible
        # in the host timeline just like the build it replaces
        info = {"kind": kind, "hit": False}
        with span("prep", info):
            digest = key_hash(kind, key_parts)
            path = self._path(kind, digest)
            if os.path.exists(path):
                try:
                    with open(path, "rb") as f:
                        payload = pickle.load(f)
                    self.hits += 1
                    telem.inc("prep_cache/hit")
                    info["hit"] = True
                    return payload
                except Exception:  # noqa: BLE001 — corrupt entry = miss
                    try:
                        os.remove(path)
                    except OSError:
                        pass
            payload = builder()
            self.misses += 1
            telem.inc("prep_cache/miss")
            try:
                os.makedirs(self.root, exist_ok=True)
                tmp = path + f".tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
                os.replace(tmp, path)
            except OSError:
                pass  # read-only checkout etc.: serve the built value
            return payload


_default: Optional[PrepCache] = None


def default_cache() -> PrepCache:
    global _default
    if _default is None:
        _default = PrepCache()
    return _default


def stats() -> dict:
    """Process-wide default-cache counters (bench observability)."""
    if _default is None:
        return {"hits": 0, "misses": 0}
    return {"hits": _default.hits, "misses": _default.misses}


def resolve(cache, *, auto_ok: bool) -> Optional[PrepCache]:
    """Normalize a call-site ``cache`` argument.

    ``None``/``False`` → off; ``True`` → the default cache; a
    :class:`PrepCache` → itself; ``"auto"`` → the default cache iff the
    call site says the workload is big enough (``auto_ok``) AND the env
    switch has not disabled auto caching."""
    if cache is None or cache is False:
        return None
    if isinstance(cache, PrepCache):
        return cache
    if cache is True:
        return default_cache()
    if cache == "auto":
        return default_cache() if (auto_ok and auto_enabled()) else None
    raise ValueError(f"unknown cache argument {cache!r}")
