"""Native C++ data-helper tests: closure parity with the numpy oracle,
negative-sampler invariants (SURVEY.md §4 parity-test strategy)."""

import numpy as np
import pytest

from hyperspace_tpu.data import wordnet

native = pytest.importorskip("hyperspace_tpu.data.native")


def _canon(pairs):
    return {(int(u), int(v)) for u, v in pairs}


def test_closure_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    n = 200
    # random DAG: each node picks ≤2 parents with smaller index
    edges = []
    for u in range(1, n):
        for p in rng.choice(u, size=min(u, rng.integers(0, 3)), replace=False):
            edges.append((u, int(p)))
    edges = np.asarray(edges, np.int32)
    got = native.transitive_closure(edges, n)
    want = wordnet._closure_numpy(edges, n)
    assert _canon(got) == _canon(want)


def test_closure_empty_and_chain():
    assert native.transitive_closure(np.zeros((0, 2), np.int32), 4).shape == (0, 2)
    chain = np.asarray([[1, 0], [2, 1], [3, 2]], np.int32)
    got = _canon(native.transitive_closure(chain, 4))
    assert got == {(1, 0), (2, 1), (2, 0), (3, 2), (3, 1), (3, 0)}


def test_negative_sampler_invariants():
    edges = np.asarray([[0, 1], [1, 2], [2, 3]], np.int32)
    neg = native.sample_negative_edges(edges, 50, 200, seed=7)
    assert neg.shape == (200, 2)
    es = _canon(edges)
    for u, v in neg:
        assert u < v and 0 <= u < 50 and v < 50
        assert (int(u), int(v)) not in es


def test_negative_sampler_deterministic():
    edges = np.asarray([[0, 1]], np.int32)
    a = native.sample_negative_edges(edges, 20, 50, seed=3)
    b = native.sample_negative_edges(edges, 20, 50, seed=3)
    np.testing.assert_array_equal(a, b)
    c = native.sample_negative_edges(edges, 20, 50, seed=4)
    assert not np.array_equal(a, c)


def test_prepare_edges_matches_numpy_oracle():
    """Native pipeline vs the ACTUAL numpy fallback used by graphs.prepare
    (same function object — no drift possible)."""
    from hyperspace_tpu.data.graphs import _prepare_edges_numpy

    rng = np.random.default_rng(0)
    for n, ne, sym, loops in [(40, 100, True, True), (40, 100, True, False),
                              (40, 100, False, True), (7, 0, True, True)]:
        edges = rng.integers(0, n, (ne, 2)).astype(np.int32)
        got = native.prepare_edges(edges, n, symmetrize=sym, self_loops=loops,
                                   pad_multiple=64)
        want = _prepare_edges_numpy(edges, n, symmetrize=sym,
                                    self_loops=loops, pad_multiple=64)
        for a, b, name in zip(got, want,
                              ("senders", "receivers", "mask", "rev", "deg")):
            if name == "rev" and not sym:
                continue
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_locality_order_matches_python_oracle():
    """Native BFS relabeling vs the deque walk — exact order equality
    (adjacency order and seed tie-breaking must match, not just the set
    of visited nodes)."""
    from hyperspace_tpu.data.graphs import _locality_order_python

    rng = np.random.default_rng(1)
    for n, ne in [(1, 0), (30, 0), (60, 150), (200, 800)]:
        edges = (rng.integers(0, n, (ne, 2)).astype(np.int32)
                 if ne else np.zeros((0, 2), np.int32))
        got = native.locality_order(edges, n)
        want = _locality_order_python(edges, n)
        np.testing.assert_array_equal(got, want)
        assert sorted(got.tolist()) == list(range(n))  # a permutation


def test_sample_neighbors_matches_numpy_oracle():
    """C++ sampler vs the vectorized numpy twin: bit-exact draws (same
    per-cell splitmix64 stream), neighbors only, isolated -> self."""
    from hyperspace_tpu.models.hgcn_sampled import build_adjacency

    rng = np.random.default_rng(3)
    edges = rng.integers(0, 40, (120, 2)).astype(np.int32)
    indptr, indices = build_adjacency(edges, 41)  # node 40 isolated
    seeds = np.concatenate([rng.integers(0, 40, 30), [40]]).astype(np.int32)
    for seed in (0, 7):
        a = native.sample_neighbors(indptr, indices, seeds, 5, seed=seed)
        b = native.sample_neighbors_numpy(indptr, indices, seeds, 5,
                                          seed=seed)
        np.testing.assert_array_equal(a, b)
    assert np.all(a[-1] == 40)  # isolated node samples itself
    for i, u in enumerate(seeds[:-1]):
        nbrs = set(indices[indptr[u]:indptr[u + 1]].tolist())
        assert set(a[i].tolist()) <= nbrs


def _copy_sources(monkeypatch, tmp_path):
    """Point the builder at a private copy of the sources."""
    import shutil

    srcs = []
    for s in native._SRCS:
        srcs.append(shutil.copy(s, tmp_path))
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRCS", srcs)
    return srcs


def test_library_is_named_by_its_sources(monkeypatch, tmp_path):
    """A stale library — the old fixed name, or one built from other
    sources, however fresh its mtime — is never the one loaded: the
    name carries a hash of the sources and moves when they change."""
    srcs = _copy_sources(monkeypatch, tmp_path)
    (tmp_path / "libhsdata.so").write_bytes(b"stale, not even ELF")
    before = native._lib_path()
    assert before != str(tmp_path / "libhsdata.so")
    built = native._build()
    assert built == before and built.endswith(".so")
    assert native._build() == built  # second call: found, not rebuilt
    with open(srcs[0], "a") as f:
        f.write("\n// edited\n")
    assert native._lib_path() != before


def test_build_failure_is_an_error_not_a_fallback(monkeypatch, tmp_path):
    """With a compiler installed, sources that do not compile raise —
    ImportError (which callers turn into the numpy path) is kept for a
    machine with no compiler at all."""
    srcs = _copy_sources(monkeypatch, tmp_path)
    with open(srcs[0], "a") as f:
        f.write("\nthis is not C++;\n")
    with pytest.raises(RuntimeError, match="build failed"):
        native._build()
    assert not [p for p in tmp_path.iterdir() if p.suffix == ".so"]
