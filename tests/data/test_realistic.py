"""Realistic-locality dataset machinery (VERDICT r3 #3): the community
power-law generator, the OGB-csv disk roundtrip, and the community
(LPA+BFS) reordering."""

import numpy as np
import pytest

from hyperspace_tpu.data import graphs as G


def _small_graph(seed=0):
    return G.community_power_law_graph(
        num_nodes=3000, num_edges=24000, num_classes=8, feat_dim=16,
        sub_size=120, seed=seed)


def test_generator_shape_statistics():
    edges, x, labels, k = _small_graph()
    n = x.shape[0]
    assert edges.shape == (24000, 2)  # exactly the edge count asked for
    assert np.all(edges >= 0) and np.all(edges < n)
    assert np.all(edges[:, 0] != edges[:, 1])  # no self loops
    assert labels.shape == (n,) and labels.max() < k
    # power-law degrees: hub far above mean
    deg = np.bincount(edges.ravel(), minlength=n)
    assert deg.max() > 10 * deg.mean()
    # community structure: most edges stay within the label group
    same = (labels[edges[:, 0]] == labels[edges[:, 1]]).mean()
    assert same > 0.55, same
    # determinism
    e2, x2, l2, _ = _small_graph()
    np.testing.assert_array_equal(edges, e2)
    np.testing.assert_array_equal(x, x2)


def test_ogb_csv_roundtrip(tmp_path):
    edges, x, labels, k = G.community_power_law_graph(
        num_nodes=200, num_edges=800, num_classes=5, feat_dim=8,
        sub_size=40, seed=1)
    root = str(tmp_path / "ds")
    G.write_ogb_csv_layout(root, edges, x, labels)
    e2, x2, l2, k2 = G.load_ogbn_arxiv(root)
    np.testing.assert_array_equal(e2, edges)
    np.testing.assert_array_equal(l2, labels)
    assert k2 == labels.max() + 1
    np.testing.assert_allclose(x2, x, rtol=1e-4, atol=1e-5)
    # the dispatching loader reports the disk source
    e3, x3, l3, k3, source = G.load_graph("ogbn-arxiv", root)
    assert source == "disk"
    np.testing.assert_array_equal(e3, edges)


def test_ensure_dataset_writes_once_and_loads_from_disk(tmp_path):
    """The stand-in for the download: generated into the OGB layout,
    loaded back as source "disk" at the size asked for, and not
    regenerated when the files are already there."""
    import os

    shape = dict(num_nodes=300, num_edges=1500, num_classes=5, feat_dim=8,
                 sub_size=40)
    root = G.ensure_arxiv_scale_dataset(str(tmp_path / "ds"), seed=3,
                                        **shape)
    edges, x, labels, k, source = G.load_graph("ogbn-arxiv", root)
    assert source == "disk"
    assert x.shape == (300, 8) and edges.shape == (1500, 2) and k <= 5
    stamp = os.path.getmtime(os.path.join(root, "raw", "edge.csv"))
    assert G.ensure_arxiv_scale_dataset(root, seed=4, **shape) == root
    assert os.path.getmtime(os.path.join(root, "raw", "edge.csv")) == stamp


def test_community_order_is_permutation_and_deterministic():
    edges, x, labels, k = _small_graph()
    n = x.shape[0]
    order = G.community_order(edges, n)
    assert sorted(order.tolist()) == list(range(n))
    np.testing.assert_array_equal(order, G.community_order(edges, n))
    with pytest.raises(IndexError):
        G.community_order(np.asarray([[0, n]]), n)


def test_community_order_beats_bfs_on_community_graph():
    """The point of the LPA order: more block-clusterable edges than the
    plain BFS on a community-structured graph (measured at full scale
    ~31% vs ~21%; this pins the small-scale direction with slack)."""
    from hyperspace_tpu.kernels.cluster import build_cluster_split

    edges, x, labels, k = _small_graph()
    n = x.shape[0]

    def frac(method):
        e2, x2, _, _ = G.apply_locality_order(edges, x, labels,
                                              method=method)
        g = G.prepare(e2, n, x2, pad_multiple=1024, cluster=False)
        sp = build_cluster_split(g.senders, g.receivers, g.edge_mask,
                                 g.deg, n, bn=64, bs=64, min_pair_edges=32)
        return sp.frac_clustered

    assert frac("community") >= frac("bfs") - 0.02, (
        frac("community"), frac("bfs"))


def test_apply_locality_order_rejects_unknown_method():
    edges, x, labels, k = _small_graph()
    with pytest.raises(ValueError):
        G.apply_locality_order(edges, x, labels, method="sorted")
