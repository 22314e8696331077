"""Real-data loader fixture tests (VERDICT r2 next #4).

The on-disk parsers (`load_cora`, `load_ogb_csv`, the WordNet closure
TSV) had never executed before this file: every quality claim ultimately
refers to these datasets, so a parse bug would invalidate the story the
day real data appears.  Each fixture is a hand-written miniature of the
real format; each test goes loader → prepare/split → a few real train
steps, not just a parse check.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.data import graphs as G


# --- cora (Planetoid raw format) ---------------------------------------------

CORA_CONTENT = """\
p100\t1\t0\t0\t1\tGenetic_Algorithms
p200\t0\t1\t0\t0\tNeural_Networks
p300\t0\t0\t1\t1\tNeural_Networks
p400\t1\t1\t0\t0\tTheory
p500\t0\t0\t0\t1\tGenetic_Algorithms
p600\t1\t0\t1\t0\tTheory
"""

# includes one citation of an unknown paper id (real cora.cites has these
# when content rows are filtered) — the loader must drop it
CORA_CITES = """\
p100\tp200
p200\tp300
p300\tp400
p400\tp500
p500\tp600
p600\tp100
p100\tp300
p999\tp100
"""


@pytest.fixture
def cora_root(tmp_path):
    (tmp_path / "cora.content").write_text(CORA_CONTENT)
    (tmp_path / "cora.cites").write_text(CORA_CITES)
    return str(tmp_path)


def test_load_cora_parses(cora_root):
    edges, x, labels, ncls = G.load_cora(cora_root)
    assert x.shape == (6, 4) and x.dtype == np.float32
    assert labels.shape == (6,) and ncls == 3
    # first row: features 1,0,0,1; label ids assigned in encounter order
    np.testing.assert_array_equal(x[0], [1, 0, 0, 1])
    assert labels[0] == labels[4]  # both Genetic_Algorithms
    assert labels[1] == labels[2]  # both Neural_Networks
    # the p999 line referenced an unknown id and must be dropped
    assert len(edges) == 7
    assert edges.max() < 6


def test_load_graph_dispatches_to_disk(cora_root):
    edges, x, labels, ncls, source = G.load_graph("cora", cora_root)
    assert source == "disk"
    assert x.shape[0] == 6


def test_cora_trains_nc(cora_root):
    from hyperspace_tpu.models import hgcn

    edges, x, labels, ncls, _ = G.load_graph("cora", cora_root)
    n = x.shape[0]
    tr, va, te = G.node_split_masks(n, seed=0)
    g = G.prepare(edges, n, x, labels=labels, num_classes=ncls,
                  train_mask=tr, val_mask=va, test_mask=te, pad_multiple=16)
    cfg = hgcn.HGCNConfig(feat_dim=x.shape[1], hidden_dims=(8, 4),
                          num_classes=ncls)
    model, opt, state = hgcn.init_nc(cfg, g, seed=0)
    ga = G.to_device(g)
    lab, msk = jnp.asarray(g.labels), jnp.asarray(g.train_mask)
    for _ in range(5):
        state, loss = hgcn.train_step_nc(model, opt, state, ga, lab, msk)
    assert np.isfinite(float(loss))


# --- ogbn-arxiv (OGB extracted-csv layout) ------------------------------------


@pytest.fixture
def arxiv_root(tmp_path):
    raw = tmp_path / "raw"
    raw.mkdir()
    rng = np.random.default_rng(0)
    n, f = 12, 5
    feats = rng.standard_normal((n, f)).round(3)
    labels = rng.integers(0, 4, n)
    edges = np.array([[i, (i + 1) % n] for i in range(n)]
                     + [[0, 5], [3, 9], [7, 2]])
    np.savetxt(raw / "edge.csv", edges, fmt="%d", delimiter=",")
    np.savetxt(raw / "node-feat.csv", feats, fmt="%.3f", delimiter=",")
    np.savetxt(raw / "node-label.csv", labels[:, None], fmt="%d",
               delimiter=",")
    return str(tmp_path), edges, feats, labels


def test_load_ogb_csv_parses(arxiv_root):
    root, edges_w, feats_w, labels_w = arxiv_root
    edges, x, labels, ncls = G.load_ogb_csv(root)
    np.testing.assert_array_equal(edges, edges_w)
    np.testing.assert_allclose(x, feats_w.astype(np.float32), atol=1e-6)
    np.testing.assert_array_equal(labels, labels_w)
    assert ncls == labels_w.max() + 1


def test_arxiv_trains_lp(arxiv_root):
    from hyperspace_tpu.models import hgcn

    root, *_ = arxiv_root
    edges, x, labels, ncls, source = G.load_graph("ogbn-arxiv", root)
    assert source == "disk"
    n = x.shape[0]
    split = G.split_edges(edges, n, x, val_frac=0.1, test_frac=0.1, seed=0,
                          pad_multiple=16)
    cfg = hgcn.HGCNConfig(feat_dim=x.shape[1], hidden_dims=(8, 4))
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
    ga = G.to_device(split.graph)
    pos = jnp.asarray(split.train_pos)
    for _ in range(5):
        state, loss = hgcn.train_step_lp(model, opt, n, state, ga, pos)
    assert np.isfinite(float(loss))
    ev = hgcn.evaluate_lp(model, state.params, split, "test", ga=ga)
    assert 0.0 <= ev["roc_auc"] <= 1.0


# --- WordNet closure TSV ------------------------------------------------------

WORDNET_TSV = """\
# child\tparent lines; comments and blanks ignored
dog.n.01\tcanine.n.02
cat.n.01\tfeline.n.01
canine.n.02\tcarnivore.n.01
feline.n.01\tcarnivore.n.01
carnivore.n.01\tmammal.n.01

dog.n.01\tcarnivore.n.01
"""


@pytest.fixture
def wordnet_tsv(tmp_path):
    p = tmp_path / "closure.tsv"
    p.write_text(WORDNET_TSV)
    return str(p)


def test_load_closure_tsv_parses(wordnet_tsv):
    from hyperspace_tpu.data import wordnet

    ds = wordnet.load_closure_tsv(wordnet_tsv)
    assert ds.num_nodes == 6
    assert ds.num_pairs == 6
    by_name = {n: i for i, n in enumerate(ds.names)}
    pairs = ds.adjacency_set()
    assert (by_name["dog.n.01"], by_name["canine.n.02"]) in pairs
    assert (by_name["dog.n.01"], by_name["carnivore.n.01"]) in pairs


def test_write_closure_tsv_round_trips(tmp_path):
    """write → load gives the same closure back (ids are assigned in
    order of first appearance, so compare through the names)."""
    from hyperspace_tpu.data import wordnet

    ds = wordnet.synthetic_tree(depth=3, branching=3)
    path = str(tmp_path / "closure.tsv")
    wordnet.write_closure_tsv(path, ds)
    back = wordnet.load_closure_tsv(path)
    assert back.num_nodes == ds.num_nodes
    assert back.num_pairs == ds.num_pairs
    named = {(int(back.names[u]), int(back.names[v])) for u, v in back.pairs}
    assert named == ds.adjacency_set()


def test_load_closure_tsv_closes_edges(wordnet_tsv):
    """already_closed=False must expand parent edges to full ancestry."""
    from hyperspace_tpu.data import wordnet

    ds = wordnet.load_closure_tsv(wordnet_tsv, already_closed=False)
    by_name = {n: i for i, n in enumerate(ds.names)}
    pairs = ds.adjacency_set()
    # dog -> mammal is only reachable transitively
    assert (by_name["dog.n.01"], by_name["mammal.n.01"]) in pairs
    assert (by_name["cat.n.01"], by_name["mammal.n.01"]) in pairs


def test_wordnet_tsv_trains(wordnet_tsv):
    from hyperspace_tpu.data import wordnet
    from hyperspace_tpu.models import poincare_embed as pe

    ds = wordnet.load_closure_tsv(wordnet_tsv, already_closed=False)
    cfg = pe.PoincareEmbedConfig(num_nodes=ds.num_nodes, dim=3,
                                 batch_size=8, neg_samples=3,
                                 burnin_steps=0)
    state, opt = pe.init_state(cfg, seed=0)
    pairs = jnp.asarray(ds.pairs)
    for _ in range(5):
        state, loss = pe.train_step(cfg, opt, state, pairs)
    assert np.isfinite(float(loss))
    assert np.linalg.norm(np.asarray(state.table), axis=-1).max() < 1.0


# --- locality reordering ------------------------------------------------------


def test_locality_order_is_permutation_and_clusters_communities():
    """BFS relabeling must be a valid permutation and must turn an
    id-interleaved community graph into contiguous blocks (what the
    cluster-pair kernel needs from real citation graphs)."""
    rng = np.random.default_rng(0)
    n, k = 512, 4
    comm = np.arange(n) % k  # communities interleaved in id space
    edges = []
    for c in range(k):
        members = np.flatnonzero(comm == c)
        for _ in range(n):
            u, v = rng.choice(members, 2, replace=False)
            edges.append((u, v))
    edges = np.asarray(edges, np.int64)

    order = G.locality_order(edges, n)
    assert sorted(order.tolist()) == list(range(n))

    new_edges, new_x, new_labels, order2 = G.apply_locality_order(
        edges, np.eye(n, 8, dtype=np.float32), comm.astype(np.int32))
    np.testing.assert_array_equal(order, order2)
    # labels/features follow their nodes
    np.testing.assert_array_equal(new_labels, comm[order])
    # community locality: most edges now span a small id distance
    spread_before = np.abs(edges[:, 0] - edges[:, 1])
    spread_after = np.abs(new_edges[:, 0] - new_edges[:, 1])
    assert np.median(spread_after) < np.median(spread_before) / 2


def test_locality_order_preserves_training(cora_root):
    """Relabeled graphs are isomorphic: the NC task still trains."""
    from hyperspace_tpu.models import hgcn

    edges, x, labels, ncls, _ = G.load_graph("cora", cora_root)
    edges, x, labels, _ = G.apply_locality_order(edges, x, labels)
    n = x.shape[0]
    tr, va, te = G.node_split_masks(n, seed=0)
    g = G.prepare(edges, n, x, labels=labels, num_classes=ncls,
                  train_mask=tr, val_mask=va, test_mask=te, pad_multiple=16)
    cfg = hgcn.HGCNConfig(feat_dim=x.shape[1], hidden_dims=(8, 4),
                          num_classes=ncls)
    model, opt, state = hgcn.init_nc(cfg, g, seed=0)
    ga = G.to_device(g)
    lab, msk = jnp.asarray(g.labels), jnp.asarray(g.train_mask)
    for _ in range(5):
        state, loss = hgcn.train_step_nc(model, opt, state, ga, lab, msk)
    assert np.isfinite(float(loss))
