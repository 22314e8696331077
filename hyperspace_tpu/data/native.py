"""ctypes bindings for the native C++ data helpers (SURVEY.md §2 "Data").

Compiles the ``_native/*.cc`` sources with g++ on first use into the
package's ``_native`` directory and exposes:

- :func:`transitive_closure` — WordNet-scale DAG closure (the hook
  :mod:`hyperspace_tpu.data.wordnet` dispatches to),
- :func:`sample_negative_edges` — rejection-sampled LP negatives at
  arxiv scale (used by :mod:`hyperspace_tpu.data.graphs`).

The built library is named by a hash of its sources
(``libhsdata-<sha256[:16]>.so``): a library built from other sources —
a stale one copied along with a checkout, whatever its mtime — has
another name and is never loaded.

No pybind11 in this environment: plain C ABI + ctypes (the sanctioned
binding route).  Raises ImportError if no C++ compiler is installed, and
callers then keep their pure-Python/numpy paths.  With a compiler
present, a failed build or load is an error (RuntimeError / OSError),
not a quiet switch to the slow path.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess

import numpy as np

_DIR = os.path.join(os.path.dirname(__file__), "_native")
_SRCS = [os.path.join(_DIR, "closure.cc"), os.path.join(_DIR, "graphprep.cc"),
         os.path.join(_DIR, "localorder.cc"), os.path.join(_DIR, "sampler.cc")]

_lib = None


def _lib_path() -> str:
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_DIR, f"libhsdata-{h.hexdigest()[:16]}.so")


def _build() -> str:
    lib = _lib_path()
    if os.path.exists(lib):
        return lib
    cxx = shutil.which("g++") or shutil.which("c++")
    if cxx is None:
        raise ImportError("no C++ compiler for hyperspace_tpu native helpers")
    # a name of its own per builder, renamed whole: concurrent first
    # uses (test workers) never load a half-written library
    tmp = f"{lib[:-3]}.{os.getpid()}.so.tmp"
    cmd = [cxx, "-O2", "-std=c++17", "-shared", "-fPIC", *_SRCS, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native helper build failed: {e.stderr.decode()[:500]}") from e
    os.replace(tmp, lib)
    return lib


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(_build())
    lib.closure_compute.restype = ctypes.c_void_p
    lib.closure_compute.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32]
    lib.pairbuf_size.restype = ctypes.c_int64
    lib.pairbuf_size.argtypes = [ctypes.c_void_p]
    lib.pairbuf_copy.restype = None
    lib.pairbuf_copy.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
    lib.pairbuf_free.restype = None
    lib.pairbuf_free.argtypes = [ctypes.c_void_p]
    lib.sample_negative_edges.restype = ctypes.c_int64
    lib.sample_negative_edges.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_uint64, ctypes.POINTER(ctypes.c_int32)]
    lib.graph_prepare.restype = ctypes.c_void_p
    lib.graph_prepare.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.graph_prepare_copy.restype = None
    lib.graph_prepare_copy.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int32]
    lib.graph_prepare_free.restype = None
    lib.graph_prepare_free.argtypes = [ctypes.c_void_p]
    lib.locality_order.restype = None
    lib.locality_order.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64)]
    lib.sample_neighbors.restype = None
    lib.sample_neighbors.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_int32)]
    _lib = lib
    return lib


def _as_i32_pairs(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(np.asarray(a, np.int32))
    if a.ndim != 2 or a.shape[1] != 2:
        raise ValueError(f"expected [N, 2] pairs, got {a.shape}")
    return a


def transitive_closure(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """All (node, ancestor) pairs of the parent DAG; [P, 2] int32."""
    lib = _load()
    e = _as_i32_pairs(edges)
    ptr = e.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    handle = lib.closure_compute(ptr, e.shape[0], int(num_nodes))
    try:
        n = lib.pairbuf_size(handle)
        out = np.empty((n, 2), np.int32)
        if n:
            lib.pairbuf_copy(handle, out.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)))
    finally:
        lib.pairbuf_free(handle)
    return out


def prepare_edges(
    edges: np.ndarray,
    num_nodes: int,
    *,
    symmetrize: bool = True,
    self_loops: bool = True,
    pad_multiple: int = 1024,
):
    """Native edge-layout pipeline (symmetrize → self-loops → dedupe →
    receiver-major sort → pad → reverse involution → in-degree).

    Returns (senders, receivers, mask, rev_perm, deg) matching the numpy
    path in :func:`hyperspace_tpu.data.graphs.prepare` exactly
    (tests/data/test_native.py asserts bit-equality); ``rev_perm`` is
    only meaningful when ``symmetrize`` — callers drop it otherwise.
    At arxiv scale the two are comparable in wall time (~1 s each); the
    native path keeps the full data-prep pipeline in the C++ layer
    alongside closure/negative-sampling and avoids materializing the
    intermediate int64 edge copies the numpy path allocates.
    """
    lib = _load()
    e = _as_i32_pairs(edges) if len(edges) else np.zeros((0, 2), np.int32)
    e_pad = ctypes.c_int64()
    handle = lib.graph_prepare(
        e.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), e.shape[0],
        int(num_nodes), int(symmetrize), int(self_loops), int(pad_multiple),
        ctypes.byref(e_pad))
    try:
        n = e_pad.value
        senders = np.empty(n, np.int32)
        receivers = np.empty(n, np.int32)
        mask = np.empty(n, np.uint8)
        rev_perm = np.empty(n, np.int32)
        deg = np.empty(num_nodes, np.float32)
        lib.graph_prepare_copy(
            handle,
            senders.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            receivers.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            rev_perm.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            deg.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            int(num_nodes))
    finally:
        lib.graph_prepare_free(handle)
    return senders, receivers, mask.astype(bool), rev_perm, deg


def locality_order(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """BFS locality relabeling; [N] int64 with ``order[rank] = old id``.

    Exact twin of :func:`hyperspace_tpu.data.graphs.locality_order`
    (same adjacency order and seed tie-breaking — parity-tested).
    """
    lib = _load()
    e = _as_i32_pairs(edges) if len(edges) else np.zeros((0, 2), np.int32)
    # the C++ side does no bounds checks (silent OOB write); fail here the
    # way the numpy twin would (IndexError) instead
    if len(e) and (e.min() < 0 or e.max() >= num_nodes):
        raise IndexError(
            f"edge ids out of range [0, {num_nodes}): min {e.min()}, "
            f"max {e.max()}")
    out = np.empty(num_nodes, np.int64)
    lib.locality_order(
        e.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), e.shape[0],
        int(num_nodes), out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return out


def sample_neighbors(indptr: np.ndarray, indices: np.ndarray,
                     seeds: np.ndarray, fanout: int,
                     seed: int = 0) -> np.ndarray:
    """[len(seeds), fanout] uniform with-replacement neighbor draws.

    CSR adjacency (``indptr`` int64 [N+1], ``indices`` int32); isolated
    nodes yield themselves.  Per-cell stateless splitmix64 RNG —
    :func:`sample_neighbors_numpy` is the bit-exact oracle.
    """
    lib = _load()
    indptr = np.ascontiguousarray(indptr, np.int64)
    indices = np.ascontiguousarray(indices, np.int32)
    seeds = np.ascontiguousarray(seeds, np.int32)
    # the C++ side does no bounds checks (silent OOB read); fail here the
    # way the numpy twin would (IndexError) instead
    if len(seeds) and (seeds.min() < 0 or seeds.max() >= len(indptr) - 1):
        raise IndexError(
            f"seed ids out of range [0, {len(indptr) - 1}): "
            f"min {seeds.min()}, max {seeds.max()}")
    out = np.empty((len(seeds), fanout), np.int32)
    lib.sample_neighbors(
        indptr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        seeds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(seeds), int(fanout), int(seed) & (2**64 - 1),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out


def sample_neighbors_numpy(indptr: np.ndarray, indices: np.ndarray,
                           seeds: np.ndarray, fanout: int,
                           seed: int = 0) -> np.ndarray:
    """Vectorized numpy twin of :func:`sample_neighbors` — same splitmix64
    stream per output cell, so the two agree bit-exactly (parity oracle
    and the no-toolchain fallback)."""
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int32)
    seeds = np.asarray(seeds, np.int64)
    # same guard as the native path — without it numpy would wrap
    # negative ids instead of raising, and the twins would diverge
    if len(seeds) and (seeds.min() < 0 or seeds.max() >= len(indptr) - 1):
        raise IndexError(
            f"seed ids out of range [0, {len(indptr) - 1}): "
            f"min {seeds.min()}, max {seeds.max()}")
    off = indptr[seeds]                                     # [K]
    deg = indptr[seeds + 1] - off                           # [K]
    cells = (np.arange(len(seeds), dtype=np.uint64)[:, None]
             * np.uint64(fanout)
             + np.arange(fanout, dtype=np.uint64)[None, :])  # [K, f]
    with np.errstate(over="ignore"):
        x = np.uint64(seed) ^ cells
        x += np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    if len(indices) == 0:  # every node isolated: all-self
        return np.broadcast_to(seeds[:, None], (len(seeds), fanout)
                               ).astype(np.int32).copy()
    safe_deg = np.maximum(deg, 1).astype(np.uint64)[:, None]
    # isolated rows (deg 0) produce an in-range dummy pick, then np.where
    # replaces them with the seed itself (the C++ branch does the same)
    pick = np.minimum((x % safe_deg).astype(np.int64) + off[:, None],
                      len(indices) - 1)
    return np.where(deg[:, None] > 0, indices[pick],
                    seeds[:, None]).astype(np.int32)


def sample_negative_edges(
    edges: np.ndarray, num_nodes: int, k: int, seed: int = 0
) -> np.ndarray:
    """k uniform undirected non-edges (canonical u<v form); [k, 2] int32."""
    lib = _load()
    e = _as_i32_pairs(edges)
    out = np.empty((k, 2), np.int32)
    got = lib.sample_negative_edges(
        e.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), e.shape[0],
        int(num_nodes), int(k), int(seed) & (2**64 - 1),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out[:got]
