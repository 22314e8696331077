"""WordNet-style hierarchy data: transitive closure + negative sampling.

Reference workload 1 (BASELINE.json configs[0]): Poincaré embeddings on the
WordNet noun hypernymy closure (Nickel & Kiela 2017).  This environment has
no network access and no bundled WordNet dump, so the loader accepts any
edge list in TSV form (``child<TAB>parent`` per line, the format the
published closure files use) and can also synthesize benchmark trees of a
chosen size.  The transitive closure is computed by the native C++ helper
(``hyperspace_tpu.data.native``, built from source on first use), or by
a pure-Python DFS fallback where no C++ compiler is installed.

Negative sampling is done *on device* inside the jitted train step with
``jax.random`` — the host never touches the per-step batch (SURVEY.md §3.1:
host→device once per batch, or none when the closure fits on device).
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np


@dataclasses.dataclass
class ClosureDataset:
    """A hierarchy as (child, ancestor) pairs over ``num_nodes`` vocab ids."""

    pairs: np.ndarray  # [P, 2] int32 (u, v): v is an ancestor of u
    num_nodes: int
    names: list[str] | None = None

    @property
    def num_pairs(self) -> int:
        return int(self.pairs.shape[0])

    def adjacency_set(self) -> set[tuple[int, int]]:
        return {(int(u), int(v)) for u, v in self.pairs}


def load_edges_tsv(path: str) -> tuple[np.ndarray, list[str]]:
    """Read ``child<TAB>parent`` lines; returns (edges [E,2] int32, names)."""
    ids: dict[str, int] = {}
    edges = []
    with open(path) as f:
        for line in f:
            parts = line.rstrip("\n").split("\t")
            if len(parts) < 2 or parts[0].startswith("#"):
                continue
            u, v = parts[0], parts[1]
            for t in (u, v):
                if t not in ids:
                    ids[t] = len(ids)
            edges.append((ids[u], ids[v]))
    names = [None] * len(ids)
    for t, i in ids.items():
        names[i] = t
    return np.asarray(edges, np.int32), names


def transitive_closure(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """All (node, ancestor) pairs reachable through the parent relation.

    Uses the native C++ closure (hyperspace_tpu.data.native); the
    pure-Python DFS runs only where no C++ compiler is installed.
    """
    try:
        from hyperspace_tpu.data import native

        return native.transitive_closure(edges, num_nodes)
    except ImportError:  # no C++ compiler installed (data/native.py)
        return _closure_numpy(edges, num_nodes)


def _closure_numpy(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    parents: list[list[int]] = [[] for _ in range(num_nodes)]
    for u, v in edges:
        parents[int(u)].append(int(v))
    out = []
    for start in range(num_nodes):
        seen: set[int] = set()
        stack = list(parents[start])
        while stack:
            p = stack.pop()
            if p in seen:
                continue
            seen.add(p)
            stack.extend(parents[p])
        out.extend((start, a) for a in seen)
    if not out:
        return np.zeros((0, 2), np.int32)
    return np.asarray(out, np.int32)


def load_closure_tsv(path: str, already_closed: bool = True) -> ClosureDataset:
    edges, names = load_edges_tsv(path)
    n = len(names)
    pairs = edges if already_closed else transitive_closure(edges, n)
    return ClosureDataset(pairs=pairs, num_nodes=n, names=names)


def write_closure_tsv(path: str, ds: ClosureDataset) -> None:
    """Write ``ds.pairs`` as the ``child<TAB>ancestor`` lines
    :func:`load_closure_tsv` reads (node names are the integer ids) —
    the disk end of the disk → load → train pipeline for generated
    hierarchies."""
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        f.writelines(f"{u}\t{v}\n" for u, v in ds.pairs.tolist())
    os.replace(tmp, path)


def synthetic_tree(depth: int, branching: int, seed: int = 0) -> ClosureDataset:
    """A complete ``branching``-ary tree of the given depth, closed.

    Node 0 is the root.  Used by tests (SURVEY.md §4.5: recover a tiny tree
    to MAP=1.0) and by the Poincaré-embedding benchmark when no WordNet TSV
    is available.
    """
    del seed
    edges = []
    level = [0]
    next_id = 1
    for _ in range(depth):
        nxt = []
        for p in level:
            for _ in range(branching):
                edges.append((next_id, p))
                nxt.append(next_id)
                next_id += 1
        level = nxt
    edges = np.asarray(edges, np.int32)
    pairs = transitive_closure(edges, next_id)
    return ClosureDataset(pairs=pairs, num_nodes=next_id, names=None)
