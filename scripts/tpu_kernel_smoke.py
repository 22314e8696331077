"""On-chip Pallas kernel smoke: every N1-N7 kernel lowered through Mosaic on
the real TPU, compared against its pure-JAX twin on identical inputs.

Prints one JSON line per kernel: {"kernel", "max_err", "ok"}.  Run it on
the machine with the chip, from the repo root (it refuses the CPU):

    python scripts/tpu_kernel_smoke.py

The kernel/twin switch is the HYPERSPACE_KERNELS env var read at trace time
(kernels/_support.mode), so each op is evaluated eagerly twice — once forced
'pallas', once forced 'xla' — inside one process.
"""

from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

# runnable as a plain script from anywhere (the package is not installed)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(name, fn, tol=5e-4):
    """Mixed abs/rel check: |pallas - xla| / max(|xla|, 1) < tol.

    Relative for amplified quantities (MLR logits reach O(100) for points
    near the boundary; TPU transcendental precision gives ~1e-4 relative),
    absolute for O(1) outputs — one formula covers both.
    """
    os.environ["HYPERSPACE_KERNELS"] = "pallas"
    out_p = np.asarray(jax.device_get(fn()), np.float64)
    os.environ["HYPERSPACE_KERNELS"] = "xla"
    out_x = np.asarray(jax.device_get(fn()), np.float64)
    err = float(np.max(np.abs(out_p - out_x) / np.maximum(np.abs(out_x), 1.0)))
    ok = bool(err < tol and np.isfinite(out_p).all())
    print(json.dumps({"kernel": name, "max_err": err, "ok": ok}), flush=True)
    return ok


def one_pass_against_six(f, recv, plan, n, rng):
    """``csr_segment_sum``'s bfloat16 path (one MXU pass) against the same
    kernel forced down the float32 values' six passes, and against
    ``segment_sum`` accumulated in float32: the first difference is 0 by
    construction (a 0/1 one-hot times a bfloat16 value), the second is a
    summation order's."""
    from hyperspace_tpu.kernels.segment import _pallas_csr

    vals = jnp.asarray(rng.standard_normal((recv.shape[0], f)), jnp.bfloat16)
    one, six = (_pallas_csr(vals, recv, plan, n, False, p)
                for p in (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGHEST))
    want = jax.ops.segment_sum(vals.astype(jnp.float32), recv, n,
                               indices_are_sorted=True)
    d_six = float(jnp.max(jnp.abs(one - six)))
    d_sum = float(jnp.max(jnp.abs(one - want) / jnp.maximum(jnp.abs(want), 1.0)))
    ok = bool(d_six == 0.0 and d_sum < 5e-5 and jnp.isfinite(one).all())
    lanes = -(-f // 128) * 128  # the kernel pads its rows to whole lanes
    print(json.dumps({"kernel": f"csr_segment_sum_one_pass_{lanes}",
                      "edges": int(recv.shape[0]), "features": f,
                      "max_abs_diff_vs_six_pass": d_six,
                      "max_err_vs_f32_segment_sum": d_sum, "ok": ok}),
          flush=True)
    return ok


def flash_dot_checks(oks: list) -> None:
    """The flash kernel's dot form at the looped model's own shape (16
    heads, 4,096 positions, head width 128, bf16, causal): output and the
    three gradients against the dense XLA twin; the output against jax's
    own Pallas TPU flash attention (an independent program, not this
    repo's path); and how long a forward and a forward + backward take."""
    import time

    from hyperspace_tpu.kernels.attention import flash_dot_attention

    ks = jax.random.split(jax.random.PRNGKey(33), 4)
    shape = (16, 4096, 128)
    q, k, v = (jax.random.normal(ks[i], shape, jnp.float32)
               .astype(jnp.bfloat16) for i in range(3))
    w = jax.random.normal(ks[3], shape, jnp.float32)

    def loss(q, k, v):
        out = flash_dot_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * w)

    # bf16 operands: p and d-sigma are rounded to bf16 for their matmuls
    # on both sides, in another summation order
    oks.append(run("flash_dot_causal_bf16", lambda: flash_dot_attention(
        q, k, v, causal=True).astype(jnp.float32), tol=2e-2))
    for i, name in enumerate(("dq", "dk", "dv")):
        oks.append(run(f"flash_dot_causal_bf16_{name}", lambda: jax.grad(
            loss, argnums=i)(q, k, v).astype(jnp.float32), tol=5e-2))
    qf, kf, vf = (a[:2, :1024].astype(jnp.float32) for a in (q, k, v))
    oks.append(run("flash_dot_full_f32", lambda: flash_dot_attention(
        qf, kf, vf, causal=False), tol=1e-4))

    os.environ["HYPERSPACE_KERNELS"] = "pallas"
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash)

        theirs = jax_flash(q[None], k[None], v[None], causal=True,
                           sm_scale=128 ** -0.5)[0]
        ours = flash_dot_attention(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(ours.astype(jnp.float32)
                                    - theirs.astype(jnp.float32))))
        print(json.dumps({"kernel": "flash_dot_vs_jax_pallas_flash",
                          "max_err": err, "ok": err < 2e-2}), flush=True)
        oks.append(err < 2e-2)
    except Exception as e:  # noqa: BLE001 — an optional, independent check
        print(json.dumps({"kernel": "flash_dot_vs_jax_pallas_flash",
                          "skipped": repr(e)[:200]}), flush=True)
    fwd = jax.jit(functools.partial(flash_dot_attention, causal=True))
    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    for name, fn, matmuls in (("fwd", fwd, 2), ("fwd_bwd", both, 6)):
        jax.block_until_ready(fn(q, k, v))
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 10
        flops = matmuls * 2.0 * 16 * 128 * 4096 * 4097 / 2
        print(json.dumps({"kernel": f"flash_dot_{name}_time", "ms": dt * 1e3,
                          "required_tflops_per_s": flops / dt / 1e12}),
              flush=True)


def main():
    from hyperspace_tpu import kernels as K
    from hyperspace_tpu.kernels.segment import build_csr_plan, csr_segment_sum
    from hyperspace_tpu.manifolds import Lorentz, PoincareBall

    assert jax.default_backend() != "cpu", "smoke needs the TPU backend"
    if sys.argv[1:] == ["flash_dot"]:  # that family alone
        oks = []
        flash_dot_checks(oks)
        sys.exit(0 if all(oks) else 1)
    key = jax.random.PRNGKey(0)
    ks = list(jax.random.split(key, 16))
    ball, lor = PoincareBall(1.0), Lorentz(1.0)
    c = 1.0
    B, D = 256, 48

    x = ball.random_normal(ks[0], (B, D), jnp.float32, std=0.3)
    y = ball.random_normal(ks[1], (B, D), jnp.float32, std=0.3)
    v = 0.3 * jax.random.normal(ks[2], (B, D), jnp.float32)
    r = 0.7  # kernel N2 takes a scalar multiplier

    oks = [
        run("mobius_add", lambda: K.mobius_add(x, y, c)),
        run("mobius_scalar_mul", lambda: K.mobius_scalar_mul(r, x, c)),
        run("expmap", lambda: K.expmap(x, v, c)),
        run("logmap", lambda: K.logmap(x, y, c)),
        run("expmap0", lambda: K.expmap0(v, c)),
        run("logmap0", lambda: K.logmap0(y, c)),
        run("ptransp", lambda: K.ptransp(x, y, v, c)),
        run("poincare_pdist", lambda: K.poincare_pdist(x, y, c)),
    ]

    lx = lor.random_normal(ks[4], (B, D + 1), jnp.float32, std=0.3)
    ly = lor.random_normal(ks[5], (B, D + 1), jnp.float32, std=0.3)
    oks.append(run("lorentz_pdist", lambda: K.lorentz_pdist(lx, ly, c)))

    m = 0.2 * jax.random.normal(ks[6], (D, 32), jnp.float32)
    b = ball.random_normal(ks[7], (32,), jnp.float32, std=0.1)
    oks.append(run("hyp_linear", lambda: K.hyp_linear(x, m, b, c)))

    p = ball.random_normal(ks[8], (16, D), jnp.float32, std=0.2)
    a = 0.3 * jax.random.normal(ks[9], (16, D), jnp.float32)
    oks.append(run("hyp_mlr", lambda: K.hyp_mlr(x, p, a, c)))

    q = lor.random_normal(ks[10], (2, 128, 17), jnp.float32, std=0.3)
    kk = lor.random_normal(ks[11], (2, 128, 17), jnp.float32, std=0.3)
    oks.append(run("flash_attention",
                   lambda: K.flash_attention(q, kk, kk, c)))

    # large batch×heads: regression for the β/τ SMEM windowing (a whole
    # [B, 1] SMEM block overflowed the 1 MB budget at B ≈ 1k)
    qb = lor.random_normal(ks[12], (1024, 32, 17), jnp.float32, std=0.3)
    kb = lor.random_normal(ks[13], (1024, 32, 17), jnp.float32, std=0.3)
    oks.append(run("flash_attention_B1024",
                   lambda: K.flash_attention(qb, kb, kb, c)))

    rng = np.random.default_rng(0)
    recv = np.sort(rng.integers(0, 200, 1024)).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(1024, 64)).astype(np.float32))
    plan = tuple(jnp.asarray(a_) for a_ in build_csr_plan(recv, 200))
    recv_d = jnp.asarray(recv)
    oks.append(run("csr_segment_sum",
                   lambda: csr_segment_sum(vals, recv_d, plan, 200)))

    # the bf16 lanes' one-pass selection on the mean cell's own stragglers
    # (1,815,552 edges, the layout `cli.train hgcn --yaml
    # configs/hgcn_arxiv_lp.yaml` prepares), at conv's 128 lanes and at
    # the attention arm's 129 -> 256
    from hyperspace_tpu.data import graphs as G

    edges, feats, labels, _ = G.community_power_law_graph(seed=0)
    edges, feats, labels, _ = G.apply_locality_order(
        edges, feats, labels, method="bfs", cache=False)
    cell = G.split_edges(edges, feats.shape[0], feats, seed=0,
                         cluster_min_pair=G.cluster_min_pair_for(False),
                         cache=False).graph
    s_recv = jnp.asarray(cell.cluster_split.s_recv)
    s_plan = tuple(jnp.asarray(a_) for a_ in cell.cluster_split.s_plan)
    for f in (128, 129):
        oks.append(one_pass_against_six(f, s_recv, s_plan, cell.num_nodes,
                                        rng))

    # the LP decoder's call: rows handed over transposed, the plan built
    # on the device from ids no host has seen, bf16 rows in one MXU pass
    from hyperspace_tpu.kernels.segment import (
        pair_scatter_sum,
        rows_for_device_plan,
        rows_to_columns,
    )
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    e = 5000
    ids = np.full(rows_for_device_plan(e), 200, np.int32)
    ids[:e] = np.sort(rng.integers(0, 200, e))
    ids[: e // 3] = 7  # one hub
    ids_d = jnp.asarray(np.sort(ids))
    vt = np.zeros((33, len(ids)), np.float32)
    vt[:, :e] = rng.normal(size=(33, e))
    for dt in (jnp.bfloat16, jnp.float32):
        vt_d = jnp.asarray(vt, dt)
        oks.append(run(
            f"pair_scatter_sum_device_plan_{jnp.dtype(dt).name}",
            lambda: pair_scatter_sum(vt_d, ids_d, 200)))
    for dt in (jnp.bfloat16, jnp.float32):
        xr = jnp.asarray(rng.normal(size=(3072, 33)), dt)
        oks.append(run(f"rows_to_columns_{jnp.dtype(dt).name}",
                       lambda: rows_to_columns(xr).astype(jnp.float32),
                       tol=1e-12))
    zl = lor.random_normal(ks[15], (200, 33), jnp.float32, std=0.3)
    pu = jnp.asarray(rng.integers(0, 200, e), jnp.int32)
    pv = jnp.asarray(rng.integers(0, 200, e), jnp.int32)
    tw = jnp.asarray(rng.normal(size=e), jnp.float32)
    oks.append(run("pair_sqdist_grad", lambda: jax.grad(
        lambda zz: jnp.sum(pair_sqdist(zz, c, pu, pv, "lorentz") * tw))(zl)))

    # scalar CSR reductions: the lane-partial accumulator layout is exactly
    # what interpret mode can't exercise — real-chip parity matters here
    from hyperspace_tpu.kernels.segment import csr_segment_reduce_1d

    svals = jnp.asarray(rng.normal(size=(1024,)).astype(np.float32))
    oks.append(run("csr_segment_reduce_1d_sum",
                   lambda: csr_segment_reduce_1d(svals, recv_d, plan, 200,
                                                 op="sum")))
    # empty segments: the kernel's contract is the finite NEG_FILL
    # sentinel where XLA's segment_max gives -inf — clamp both so the
    # comparison tests the real values, not the sentinel encodings
    from hyperspace_tpu.kernels.segment import NEG_FILL

    oks.append(run("csr_segment_reduce_1d_max",
                   lambda: jnp.maximum(
                       csr_segment_reduce_1d(svals, recv_d, plan, 200,
                                             op="max"), NEG_FILL)))

    # the receiver-side pick inside the CSR walk: a selection, so the
    # kernel and its twin (the gather) agree to the last bit
    from hyperspace_tpu.kernels.segment import csr_segment_expand_1d

    nvals = jnp.asarray(rng.normal(size=(200,)).astype(np.float32))
    oks.append(run("csr_segment_expand_1d",
                   lambda: csr_segment_expand_1d(nvals, recv_d, plan, 200),
                   tol=1e-12))

    # cluster-pair SpMM kernel (r03): one-hot matmuls over VMEM tiles,
    # f32 and the fast single-pass bf16 mode
    from hyperspace_tpu.kernels.cluster import (
        build_cluster_plan,
        cluster_aggregate,
    )

    n_cl = 700
    r_cl = rng.integers(0, n_cl, 4096).astype(np.int32)
    s_cl = rng.integers(0, n_cl, 4096).astype(np.int32)
    from hyperspace_tpu.kernels import cluster as CL

    key_cl = ((r_cl // CL._BN).astype(np.int64) * (n_cl // CL._BS + 1)
              + s_cl // CL._BS)
    o_cl = np.argsort(key_cl, kind="stable")
    r_cl, s_cl = r_cl[o_cl], s_cl[o_cl]
    w_cl = jnp.asarray(rng.random(4096).astype(np.float32))
    h_cl = jnp.asarray(rng.normal(size=(n_cl, 64)).astype(np.float32))
    cplan = tuple(jnp.asarray(a_)
                  for a_ in build_cluster_plan(r_cl, s_cl, n_cl))
    r_cld, s_cld = jnp.asarray(r_cl), jnp.asarray(s_cl)
    oks.append(run("cluster_aggregate_f32",
                   lambda: cluster_aggregate(h_cl, w_cl, r_cld, s_cld,
                                             cplan, n_cl)))
    h_bf = h_cl.astype(jnp.bfloat16)
    oks.append(run("cluster_aggregate_bf16",
                   lambda: cluster_aggregate(h_bf, w_cl, r_cld, s_cld,
                                             cplan, n_cl), tol=2e-2))

    # fused scan-top-k (r12): the twin is bitwise by construction on
    # CPU-interpret — the chip run is the Mosaic-lowering check the
    # interpreter can't give (docs/kernels.md "Twin contract").  Compare
    # distances (f32 contract, tol covers transcendental drift); the
    # int ids ride along in the distance comparison (a rank flip would
    # change a distance by a visible gap on this point scale).
    from hyperspace_tpu.kernels import scan_topk as ST

    st_tab = ball.random_normal(ks[14], (1024, 16), jnp.float32, std=0.3)
    st_qi = jnp.arange(64, dtype=jnp.int32)
    st_q = st_tab[st_qi]
    oks.append(run("scan_topk",
                   lambda: ST.scan_topk(st_tab, st_q, st_qi, 0,
                                        spec=("poincare", 1.0), k=10,
                                        n=1024, exclude_self=True,
                                        tile_rows=512)[0]))
    st_cand = jnp.asarray(rng.integers(0, 1024, (64, 256)).astype(np.int32))
    oks.append(run("scan_topk_cand",
                   lambda: ST.scan_topk_cand(st_tab, st_cand, st_q, st_qi,
                                             spec=("poincare", 1.0),
                                             k=5)[0]))

    flash_dot_checks(oks)
    print(json.dumps({"all_ok": all(oks), "backend": jax.default_backend()}),
          flush=True)
    sys.exit(0 if all(oks) else 1)


if __name__ == "__main__":
    main()
