"""The main path's kernels, compiled for the chip without the chip.

The TPU's compiler is installed beside jax and compiles for a device
that is described, not attached (``jax.experimental.topologies``).  It
refuses what the Pallas interpreter lets through — a slice off the
tiling, too much fast memory, a kernel the partitioner cannot place, a
program that does not fit 16 GB — so these compiles guard every later PR
at no chip time.  Nothing runs: a compile that passes says nothing
about results or speed (``chip_smoke.py`` is the run).

All in ONE file, everything in the test's own process, the topology
described inside a module-scoped fixture: only one process at a time
may load the TPU's library, and under several test workers only the one
that is given this file does (on-chip-measurement guide, section 2).
The persistent compilation cache is off around the compiles — an
executable compiled for a described device cannot be read back here.
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32

# the published ogbn-arxiv shape (configs/hgcn_arxiv_lp.yaml's workload)
# and the serving shapes chip_smoke.py and the old bench legs use
ARXIV_FEATS, HIDDEN = 128, (128, 32)
SLAB_ROWS = 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _pallas_kernels_and_no_cache():
    """Kernel dispatch follows the backend, which here is the CPU: name
    the Pallas path outright for this module; keep the compiles out of
    the persistent cache; and trace as the program does on the chip —
    in 32-bit mode, not the suite's 64-bit one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev_mode = os.environ.get("HYPERSPACE_KERNELS")
    prev_cache = jax.config.jax_enable_compilation_cache
    os.environ["HYPERSPACE_KERNELS"] = "pallas"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_mode is None:
        os.environ.pop("HYPERSPACE_KERNELS", None)
    else:
        os.environ["HYPERSPACE_KERNELS"] = prev_mode


@pytest.fixture(scope="module")
def arxiv_split():
    """The LP split of the generated arxiv-shape graph, prepared the way
    ``cli.train hgcn --yaml configs/hgcn_arxiv_lp.yaml`` prepares it:
    the kernels below take their plan shapes from the real layout."""
    from hyperspace_tpu.data import graphs as G

    edges, x, labels, _ = G.community_power_law_graph(seed=0)
    assert x.shape == (169_343, ARXIV_FEATS) and len(edges) == 1_166_243
    edges, x, labels, _ = G.apply_locality_order(edges, x, labels,
                                                 method="bfs", cache=False)
    return G.split_edges(edges, x.shape[0], x, seed=0,
                         cluster_min_pair=G.cluster_min_pair_for(False),
                         cache=False)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args, n_kernels=None):
    """Lower and compile ``fn`` for the shardings the argument shapes
    carry; returns (compiled, its text).  ``n_kernels``: how many Mosaic
    kernels the program must contain at least — a kernel that quietly
    became its twin compiles too."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    if n_kernels:
        assert text.count("tpu_custom_call") >= n_kernels, (
            f"{text.count('tpu_custom_call')} Mosaic kernel(s) in the "
            f"compiled program, wanted at least {n_kernels}")
    return compiled, text


def _arg(one_chip):
    return lambda shape, dtype=F32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


# --- the graph kernels, on the real plan shapes -------------------------------


@pytest.mark.parametrize("shape", ["arxiv_128", "att_stragglers_129",
                                   "mag_shard_128"])
def test_csr_segment_sum_bf16(one_chip, arxiv_split, shape):
    """The one-pass bf16 kernel at the three cells' shapes: the mean
    cell's whole edge list at 128 lanes; the attention cell's 1,571,840
    stragglers at conv0's 129 ``[num | den]`` lanes (padded to 256); the
    four-chip cell's shard, 6,393,344 padded edges into 368,256 rows.
    The last two plans have their greatest length, chunks + node blocks
    (a compile reads a plan's shape only)."""
    from hyperspace_tpu.kernels.segment import csr_segment_sum

    g = arxiv_split.graph
    A = _arg(one_chip)
    if shape == "arxiv_128":
        n, e, f = g.num_nodes, g.senders.shape[0], ARXIV_FEATS
        plan = _shapes(tuple(jnp.asarray(a) for a in g.csr_plan), one_chip)
    else:
        n, e, f = {"att_stragglers_129": (g.num_nodes, 1_571_840, 129),
                   "mag_shard_128": (368_256, 6_393_344, 128)}[shape]
        plan = (A((e // 512 + -(-n // 128),), I32),) * 3
    _compile(lambda v, r, p: csr_segment_sum(v, r, p, n),
             A((e, f), BF16), A((e,), I32), plan, n_kernels=1)


def test_csr_segment_expand_1d_at_the_cell_shape(one_chip, arxiv_split):
    """The attention arm's receiver-side pick over the mean split's
    straggler layout: the in-kernel 128 x 128 transpose and the (1, 1,
    128) value blocks are what interpret mode lets through unasked."""
    from hyperspace_tpu.kernels.segment import csr_segment_expand_1d

    cl = arxiv_split.graph.cluster_split
    n, e = arxiv_split.graph.num_nodes, cl.s_recv.shape[0]
    A = _arg(one_chip)
    plan = _shapes(tuple(jnp.asarray(a) for a in cl.s_plan), one_chip)
    _, text = _compile(lambda v, r, p: csr_segment_expand_1d(v, r, p, n),
                       A((n,)), A((e,), I32), plan, n_kernels=1)
    assert "csr_segment_expand_1d" in text


def test_pair_scatter_sum_at_the_cell_shape(one_chip):
    """The LP decoder's backward sum as both benchmark cells run it: the
    cotangent rows of 2 x 1,880,610 pair ends, 33 bf16 lanes, handed over
    transposed, into 169,343 nodes under a plan built on the device."""
    from hyperspace_tpu.kernels.segment import (
        pair_scatter_sum,
        rows_for_device_plan,
    )

    n, e = 169_343, rows_for_device_plan(2 * 1_880_610)
    A = _arg(one_chip)
    _, text = _compile(lambda v, r: pair_scatter_sum(v, r, n),
                       A((33, e), BF16), A((e,), I32), n_kernels=1)
    assert "pair_scatter_sum" in text


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_rows_to_columns_at_the_cell_shape(one_chip, dtype):
    """The 2 x 3,762,176 rows the decoder's backward re-gathers, at the
    stated lanes and at the twin's."""
    from hyperspace_tpu.kernels.segment import rows_to_columns

    _compile(rows_to_columns, _arg(one_chip)((7_524_352, 33), dtype),
             n_kernels=1)


@pytest.mark.parametrize("kind", ["lorentz", "poincare"])
def test_pair_sqdist_grad_at_the_cell_shape(one_chip, kind):
    """`nn.edge_dist.pair_sqdist` forward and backward alone, bf16 lanes:
    the sort, the one gather, sqdist's VJP and the kernel compile and fit."""
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    n, p, d = 169_343, 1_880_610, 33 if kind == "lorentz" else 32
    A = _arg(one_chip)
    compiled, text = _compile(
        jax.grad(lambda z, u, v, w: jnp.sum(
            pair_sqdist(z, 1.0, u, v, kind).astype(F32) * w)),
        A((n, d), BF16), A((p,), I32), A((p,), I32), A((p,)), n_kernels=2)
    assert "pair_scatter_sum" in text and "rows_to_columns" in text
    assert "scatter-add" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 6 * 2**30


@pytest.fixture(scope="module")
def cluster_args(one_chip, arxiv_split):
    cs = arxiv_split.graph.cluster_split
    assert cs is not None and len(cs.c_recv) > 100_000
    A = _arg(one_chip)
    ec = len(cs.c_recv)
    plan = _shapes(tuple(jnp.asarray(a) for a in cs.c_plan), one_chip)
    return A, arxiv_split.graph.num_nodes, (A((ec,), I32), A((ec,), I32),
                                            plan), ec


def test_cluster_aggregate(cluster_args):
    from hyperspace_tpu.kernels.cluster import cluster_aggregate

    A, n, (recv, send, plan), ec = cluster_args
    _compile(lambda h, w, r, s, p: cluster_aggregate(h, w, r, s, p, n),
             A((n, ARXIV_FEATS), BF16), A((ec,)), recv, send, plan,
             n_kernels=1)


def test_cluster_att_fwd(cluster_args):
    from hyperspace_tpu.kernels.cluster import cluster_att_fwd

    A, n, (recv, send, plan), _ = cluster_args
    _compile(lambda h, a_s, a_r, r, s, p: cluster_att_fwd(
        h, a_s, a_r, r, s, p, n),
        A((n, ARXIV_FEATS), BF16), A((n,)), A((n,)), recv, send, plan,
        n_kernels=1)


def test_cluster_att_bwd(cluster_args):
    from hyperspace_tpu.kernels.cluster import cluster_att_bwd

    A, n, (recv, send, plan), _ = cluster_args
    _compile(lambda g, h, a_s, a_r, r, s, p: cluster_att_bwd(
        g, h, a_s, a_r, r, s, p, n),
        A((n, ARXIV_FEATS + 1)), A((n, ARXIV_FEATS), BF16), A((n,)),
        A((n,)), recv, send, plan, n_kernels=1)


# --- dense kernels at the widths their callers use ----------------------------


@pytest.mark.parametrize("manifold, dim", [("poincare", 10),
                                           ("lorentz", 33)])
def test_pdist(one_chip, manifold, dim):
    from hyperspace_tpu.kernels.distmat import pdist

    A = _arg(one_chip)
    _compile(lambda x, y: pdist(x, y, 1.0, manifold=manifold),
             A((1024, dim)), A((SLAB_ROWS, dim)), n_kernels=1)


@pytest.mark.parametrize("lane", ["f32", "int4"])
def test_fused_scan_topk(one_chip, lane):
    from hyperspace_tpu.kernels import scan_topk as ST

    A = _arg(one_chip)
    spec, dim, b = ("poincare", 1.0), 16, 64
    if lane == "f32":
        _compile(lambda s, q, qi: ST.scan_topk(
            s, q, qi, 0, spec=spec, k=10, n=SLAB_ROWS, exclude_self=True),
            A((SLAB_ROWS, dim)), A((b, dim)), A((b,), I32), n_kernels=1)
    else:
        _compile(lambda s, q, qi, sc: ST.scan_topk(
            s, q, qi, 0, spec=spec, k=42, n=SLAB_ROWS, exclude_self=True,
            scale=sc, packed=True),
            A((SLAB_ROWS, dim // 2), jnp.uint8), A((b, dim)), A((b,), I32),
            A((SLAB_ROWS,), jnp.float16), n_kernels=1)


def test_flash_attention_fwd_bwd(one_chip):
    from hyperspace_tpu.kernels import flash_attention

    A = _arg(one_chip)
    q = A((256, 4, 128, 33))
    loss = lambda q, k, v: flash_attention(q, k, v, 1.0).sum()
    # forward + the two recomputing backward kernels (dq, dk/dv)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q, n_kernels=3)


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_flash_dot_attention_fwd_bwd_at_the_looped_models_shape(one_chip,
                                                                dtype):
    """The dot form, causal, 16 heads of 128 over 4,096 positions
    (ouro_2p6b.pretrain4k's call): the three kernels under their own
    names, bf16 operands with a transposed-operand matmul in dk/dv."""
    from hyperspace_tpu.kernels.attention import flash_dot_attention

    q = _arg(one_chip)((16, 4096, 128), dtype)
    loss = lambda q, k, v: flash_dot_attention(
        q, k, v, causal=True).astype(F32).sum()
    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q,
                       n_kernels=3)
    for name in ("flash_dot_fwd", "flash_dot_dq", "flash_dot_dkv"):
        assert name in text, name


def test_hyp_linear(one_chip):
    from hyperspace_tpu.kernels import hyp_linear

    A = _arg(one_chip)
    _compile(lambda x, m, b: hyp_linear(x, m, b, 1.0),
             A((169_343, ARXIV_FEATS)), A((ARXIV_FEATS, HIDDEN[0])),
             A((HIDDEN[0],)), n_kernels=1)


def test_hyp_mlr(one_chip):
    from hyperspace_tpu.kernels import hyp_mlr

    A = _arg(one_chip)
    _compile(lambda x, p, a: hyp_mlr(x, p, a, 1.0),
             A((169_343, HIDDEN[1])), A((40, HIDDEN[1])), A((40, HIDDEN[1])),
             n_kernels=1)


# --- the whole step ------------------------------------------------------------


def test_train_step_lp_at_arxiv_width(one_chip, arxiv_split):
    """``hgcn.train_step_lp`` as ``cli.train hgcn --yaml
    configs/hgcn_arxiv_lp.yaml`` builds it, at the published graph size:
    it must compile for one chip, keep its Mosaic kernels, and fit the
    chip's 16 GB with room for what else the process holds."""
    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn
    from hyperspace_tpu.precision import parse_dtype

    g = arxiv_split.graph
    cfg = hgcn.HGCNConfig(
        feat_dim=ARXIV_FEATS, hidden_dims=HIDDEN, kind="lorentz",
        agg_dtype=parse_dtype("bfloat16"),
        decoder_dtype=parse_dtype("bfloat16"))
    model, opt = hgcn.HGCNLinkPred(cfg), hgcn.make_optimizer(cfg)
    ga = G.to_device(g)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: model.init(
        {"params": key}, ga, jnp.zeros((2, 2), I32))["params"])
    state = hgcn.TrainState(params, jax.eval_shape(opt.init, params), key,
                            jnp.zeros((), I32))
    args = _shapes((state, ga, jnp.asarray(arxiv_split.train_pos)), one_chip)
    t0 = time.perf_counter()
    compiled = hgcn.train_step_lp.lower(model, opt, g.num_nodes,
                                        *args).compile()
    took = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert text.count("tpu_custom_call") >= 6, text.count("tpu_custom_call")
    # the decoder's backward runs on its kernels, not on XLA's scatter-add
    assert "pair_scatter_sum" in text and "rows_to_columns" in text
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held < 12 * 2**30, f"{held / 2**30:.2f} GiB of a 16 GB chip"
    # the graph arrays are the arguments' bulk: ~127 MB at this size
    assert 100e6 < mem.argument_size_in_bytes < 160e6
    assert took < 300, f"compile took {took:.0f}s"


# (the configuration's lane, temporaries as read + 5 %, arguments + outputs
# − aliased + temporaries under).  The float32 twin is what the benchmark
# drives after the window (``check_twin``): it keeps f32[32,16,4096,128],
# 1.07 GB, where the stated lane keeps 0.54
LOOPLM_LANES = {
    "the cell: bf16": ({}, 8.1e9, 16.3e9),
    "its check twin: f32 at highest": (
        {"precision": "f32", "matmul_precision": "highest"}, 8.85e9, 16.6e9),
}


@pytest.mark.parametrize("lane", LOOPLM_LANES)
def test_looplm_train_step_at_the_published_widths(one_chip, lane):
    """``looplm.train_step`` as ``cli.train looplm --yaml
    configs/looplm_ouro_2p6b.yaml num_hidden_layers=8`` builds it (the
    benchmark's ouro_2p6b.pretrain4k): Ouro-2.6B's widths, 8 layers, 4
    passes, one 4,096-token sequence.  It must compile for one chip with
    its three flash kernels and hold under the chip's 16.9 GB
    ``bytes_limit``: 7.35 GB of parameters and moments, and 7.71 GB of
    temporaries by the compiler's upper count (the gradient, 32 saved
    layer inputs, the 32 kept flash outputs and row statistics, the four
    passes' normed streams, a block of logits; 6.84 before the flash
    results were kept) — 8.34 with the head under the end-of-pass cond,
    10.9 with a scan over the passes round a scan over the layers.  The
    forward flash call is in the program ONCE, in the forward loop: the
    backward reads the kept output and never runs it again.  The loop
    over the layer applications holds no array of the head's shape,
    forward or backward: what a cond in the loop's body closes over is
    added whole to a carry at each of the 32 applications
    (``models/looplm.py`` ``forward``)."""
    import dataclasses

    import yaml

    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.models import looplm

    stated, temp_under, held_under = LOOPLM_LANES[lane]
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "configs",
            "looplm_ouro_2p6b.yaml")) as f:
        doc = yaml.safe_load(f)
    import json

    pairs = [f"{k}={json.dumps(v) if isinstance(v, list) else v}"
             for k, v in doc.items()] + ["num_hidden_layers=8"]
    run, overrides = T.split_overrides(pairs, T.RunConfig())
    cfg, _ = T._looplm_config(run, overrides)
    assert (cfg.hidden_size, cfg.head_dim, cfg.sequence_length,
            cfg.precision) == (2048, 128, 4096, "bf16")
    cfg = dataclasses.replace(cfg, **stated)
    kept_dtype = "bf16" if cfg.precision == "bf16" else "f32"
    opt = looplm.make_optimizer(cfg)
    state = jax.eval_shape(lambda: looplm.init_state(cfg, 0)[1])
    args = _shapes((state, jax.ShapeDtypeStruct((1 << 24,), I32)), one_chip)
    t0 = time.perf_counter()
    compiled = looplm.train_step.lower(cfg, opt, *args).compile()
    took = time.perf_counter() - t0
    text = compiled.as_text()
    calls = {name: len(re.findall(rf"%[\w.\-]*{name}[\w.\-]* = ", text))
             for name in ("flash_dot_fwd", "flash_dot_dq", "flash_dot_dkv")}
    assert calls == {"flash_dot_fwd": 1, "flash_dot_dq": 1,
                     "flash_dot_dkv": 1}
    # the forward loop's stacks: the inputs, the flash outputs, their rows'
    # log-sum-exp; never the kernel's [.., 128] statistics tile
    for kept in ("f32[32,4096,2048]", f"{kept_dtype}[32,16,4096,128]",
                 "f32[32,16,4096]"):
        assert kept + "{" in text, kept
    assert "f32[32,16,4096,128]" not in text or kept_dtype == "f32"
    mem = compiled.memory_analysis()
    assert 7.3e9 < mem.alias_size_in_bytes < 7.4e9   # the state, donated
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # what the program reads (7,710,826,496; the twin 8,429,889,024) + 5 %
    assert mem.temp_size_in_bytes < temp_under, mem.temp_size_in_bytes
    in_the_loop = [ln.strip()[:200] for ln in text.splitlines() if re.match(
        r"\s*(?:ROOT )?%\S+ = f32\[2048,49152\]", ln) and re.search(
        r'op_name="[^"]*ut_step', ln)]
    assert not in_the_loop, in_the_loop
    assert held < held_under, f"{held / 1e9:.2f} GB of a 16.9 GB bytes_limit"
    assert took < 300, f"compile took {took:.0f}s"


def test_flash_window_and_grouped_calls_at_the_moe_models_shapes(one_chip):
    """The dot form at Laguna-S-2.1's shapes (laguna_s21.pretrain4k's
    calls): 72 query heads over 8 K/V heads with a 512-key window under
    their own names, and 48 over 8, causal, under the dot form's."""
    from hyperspace_tpu.kernels.attention import flash_dot_attention

    A = _arg(one_chip)
    kv = A((8, 4096, 128), BF16)
    for heads, window, prefix in ((72, 512, "flash_window"),
                                  (48, None, "flash_dot")):
        loss = lambda q, k, v: flash_dot_attention(
            q, k, v, causal=True, window=window).astype(F32).sum()
        _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                           A((heads, 4096, 128), BF16), kv, kv, n_kernels=3)
        for which in ("fwd", "dq", "dkv"):
            assert f"{prefix}_{which}" in text, (prefix, which)


def test_flash_dot_calls_at_256_lanes_the_glm_models_shape(one_chip):
    """The dot form, causal, 20 heads of 256 lanes over 4,096 positions
    (glm47_flash.pretrain4k's latent-attention call: 192 + 64 query/key
    lanes, 256 value lanes): the three kernels, under their own names,
    in the smaller blocks the 256-lane VMEM budget takes."""
    from hyperspace_tpu.kernels.attention import flash_dot_attention

    q = _arg(one_chip)((20, 4096, 256), BF16)
    loss = lambda q, k, v: flash_dot_attention(
        q, k, v, causal=True).astype(F32).sum()
    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q,
                       n_kernels=3)
    for name in ("flash_dot_fwd", "flash_dot_dq", "flash_dot_dkv"):
        assert name in text, name


def test_gmm_at_the_moe_models_shapes(one_chip):
    """The grouped matmul's three calls at each MoE cell's static bound
    (4,096 tokens x its slots + 8 tiles of 256 rows) and widths (Laguna
    3,072 -> 1,024 and back, 8 slots; GLM 2,048 -> 1,536 and back, 4
    slots; 8 held experts), in the stated bf16 lane and the float32
    twin's (``check_twin``): each grid step must stay in the v5e's 16 MiB
    of scoped VMEM (a [2,048, 256] float32 weight block asked 16.58)."""
    from hyperspace_tpu.kernels.gmm import Groups, gmm

    A = _arg(one_chip)
    for d, f, slots in ((3072, 1024, 8), (2048, 1536, 4)):
        rows = 4096 * slots + 8 * 256
        groups = Groups(A((rows // 256,), I32), A((1,), I32), A((8,), I32))
        for lane in (BF16, F32):
            for k, n in ((d, f), (f, d)):
                loss = lambda x, w, g: jnp.square(
                    gmm(x, w, g, 256, lane)).sum()
                _, text = _compile(jax.grad(loss, argnums=(0, 1)),
                                   A((rows, k), lane), A((8, k, n)),
                                   groups, n_kernels=3)
                for name in ("gmm_fwd", "gmm_dx", "gmm_dw"):
                    assert name in text, (d, lane, name)


def test_moe_lm_train_step_at_the_published_widths(one_chip):
    """``moe_lm.train_step`` as ``cli.train moe_lm --yaml
    configs/moe_lm_laguna_s21.yaml`` cut to the benchmark's
    laguna_s21.pretrain4k builds it: Laguna-S-2.1's widths, 5 layers, 8
    of 256 experts, an eighth of the vocabulary, one 4,096-token
    sequence.  It must compile for one chip with its windowed and causal
    flash calls and its grouped matmuls; the state (parameters and two
    moments, 9.73 GB) is donated, and the temporaries are held to what
    the compiler reads (8.60 GB by its upper count; its buffer
    assignment packs them into 6.0 GB beside the state) + 5 %."""
    import json

    import yaml

    from hyperspace_tpu.cli import train as T
    from hyperspace_tpu.models import moe_lm

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "configs",
            "moe_lm_laguna_s21.yaml")) as f:
        doc = yaml.safe_load(f)
    pairs = [f"{k}={json.dumps(v) if isinstance(v, (list, dict)) else v}"
             for k, v in doc.items()] + [
        "num_hidden_layers=5", "num_experts=8", "expert_shards=32",
        "vocab_size=12544"]
    run, overrides = T.split_overrides(pairs, T.RunConfig())
    cfg, _ = T._moe_lm_config(run, overrides)
    assert (cfg.hidden_size, cfg.router_width, cfg.sequence_length,
            cfg.precision) == (3072, 256, 4096, "bf16")
    opt = moe_lm.make_optimizer(cfg)
    state = jax.eval_shape(lambda: moe_lm.init_state(cfg, 0)[1])
    args = _shapes((state, jax.ShapeDtypeStruct((1 << 22,), I32)), one_chip)
    t0 = time.perf_counter()
    compiled = moe_lm.train_step.lower(cfg, opt, *args).compile()
    took = time.perf_counter() - t0
    text = compiled.as_text()
    for name in ("flash_window_fwd", "flash_window_dq", "flash_window_dkv",
                 "flash_dot_fwd", "flash_dot_dq", "flash_dot_dkv",
                 "gmm_fwd", "gmm_dx", "gmm_dw"):
        assert name in text, name
    mem = compiled.memory_analysis()
    assert 9.7e9 < mem.alias_size_in_bytes < 9.8e9   # the state, donated
    assert mem.temp_size_in_bytes < 9.03e9, mem.temp_size_in_bytes
    assert took < 300, f"compile took {took:.0f}s"
