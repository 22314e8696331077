"""Shared loopback multi-process worker: one process of an N-process
``jax.distributed`` group over 127.0.0.1, 2 virtual CPU devices each.

One worker, three consumers (so the pod story is drilled by ONE code
path, not three diverging copies):

- ``tests/parallel/test_multihost_smoke.py`` — the tier-1 FAST smoke
  (``--task pipeline`` at tiny sizes): group forms, the per-host data
  plane assembles a global batch from host-local shards, the
  per-host-owned table checkpoint commits behind the coordination
  barrier, and the process-0-gated export yields ONE artifact.
- ``scripts/check_multihost.py`` — the same pipeline plus the
  single-process half: restore-at-1-process, fingerprint cross-check,
  serve-query smoke.
- ``bench.py bench_multihost`` — ``--task bench``: timed chunked HGCN
  steps at 1 vs 2 processes for the scaling row.

What the CPU loopback drills: the process group, the
coordination-service barriers, ``host_local_array_to_global_array``
assembly, and all filesystem commit protocols are REAL across
processes; the training step itself runs on each process's LOCAL device
mesh (the harness was written when the CPU backend refused
cross-process device computations; the installed jax 0.9.0 runs them,
and the harness has not been moved onto a global mesh) — the degenerate
data-parallel case where every replica sees the same batch and the
gradient all-reduce is the identity.  Determinism then pins the rest:
every process must produce bit-identical params/tables (checked by
digest exchange through the shared workdir behind a barrier), which is
exactly the invariant the cross-host all-reduce preserves on a real
pod.  On TPU the same code paths run with the collectives live.

Process 0 prints one ``RESULT {json}`` line; non-0 processes exit 0
silently (or non-0 on a cross-process consistency failure).  Runnable
by hand:

    python -m hyperspace_tpu.benchmarks.mh_worker --pid 0 --nprocs 2 \
        --port 9731 --workdir /tmp/mh --task pipeline &
    python -m hyperspace_tpu.benchmarks.mh_worker --pid 1 --nprocs 2 \
        --port 9731 --workdir /tmp/mh --task pipeline
"""

import argparse
import hashlib
import json
import os
import sys
import time


def _local_mesh():
    """Mesh over THIS process's devices only (the CPU loopback cannot
    run cross-process device programs; on a pod the trainers use
    ``multihost_mesh`` instead)."""
    import jax

    from hyperspace_tpu.parallel.mesh import make_mesh

    return make_mesh({"data": -1}, devices=jax.local_devices())


def _build_hgcn(nodes: int, feat: int, mesh, chunk: int):
    """(step_callable, state, num_pairs): the production trainer path —
    node-sharded HGCN LP (what ``cli/train.py`` runs on a mesh) with the
    supervision batch entering batch-sharded, as the data plane feeds
    it."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn
    from hyperspace_tpu.parallel.mesh import batch_sharding
    from hyperspace_tpu.train import loop as train_loop

    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=nodes, feat_dim=feat, seed=0)
    split = G.split_edges(edges, nodes, x, seed=0, pad_multiple=128)
    cfg = hgcn.HGCNConfig(feat_dim=feat, hidden_dims=(16, 8))
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=0)
    pairs_host = hgcn.round_up_pairs(split.train_pos, mesh)
    train_pos = jax.device_put(jnp.asarray(pairs_host),
                               batch_sharding(mesh, ndim=2))
    step, state, nsg = hgcn.make_node_sharded_step_lp(
        model, opt, split.graph.num_nodes, mesh, state, split)
    fn = lambda st: step(st, nsg, train_pos)
    if chunk > 1:
        fn = train_loop.make_chunked_stepper(fn, chunk)
    return fn, state, pairs_host.shape[0]


def _check_data_plane(args, mh) -> dict:
    """The per-host data plane, REAL across processes: assemble a global
    batch over the host×data mesh from only this host's rows and verify
    this process's addressable shards hold exactly its owned slice."""
    import numpy as np

    from hyperspace_tpu.parallel.mesh import data_extent, multihost_mesh

    mesh = multihost_mesh({"data": 2})
    rows = 4 * data_extent(mesh)
    batch = np.arange(rows * 3, dtype=np.float32).reshape(rows, 3)
    g = mh.distribute_batch(batch, mesh)
    if tuple(g.shape) != (rows, 3):
        raise AssertionError(f"global batch shape {g.shape} != {(rows, 3)}")
    for s in g.addressable_shards:
        start = s.index[0].start or 0
        want = batch[start:start + s.data.shape[0]]
        if not np.array_equal(np.asarray(s.data), want):
            raise AssertionError(
                f"pid {args.pid}: shard at row {start} does not hold the "
                "host-local slice it owns")
    lo, hi = mh.local_batch_rows(np.arange(rows))[[0, -1]]
    return {"batch_rows": rows,
            "local_rows": [int(lo), int(hi) + 1],
            "local_shards": len(g.addressable_shards)}


def run_pipeline(args, mh) -> int:
    """Train (deterministic replicas) → per-host-owned checkpoint →
    process-0-gated export.  The single-process halves (elastic restore,
    serve query) live in scripts/check_multihost.py."""
    import jax
    import numpy as np

    from hyperspace_tpu.parallel import host_table as HT
    from hyperspace_tpu.serve.artifact import export_artifact, fingerprint_of

    plane = _check_data_plane(args, mh)

    fn, state, npairs = _build_hgcn(args.nodes, args.feat,
                                    _local_mesh(), chunk=1)
    losses = []
    for _ in range(args.steps):
        state, loss = fn(state)
        losses.append(float(jax.device_get(loss)))
    leaf = mh.fetch_replicated(jax.tree_util.tree_leaves(state.params)[0])
    params_sha = hashlib.sha256(
        np.ascontiguousarray(leaf).tobytes()).hexdigest()

    # a deterministic Poincaré table, trained a few steps for real —
    # host-identical by construction (the replicated-table DP contract)
    from hyperspace_tpu.data.wordnet import synthetic_tree
    from hyperspace_tpu.models import poincare_embed as pe

    ds = synthetic_tree(depth=4, branching=3)
    cfg = pe.PoincareEmbedConfig(num_nodes=ds.num_nodes, dim=8,
                                 batch_size=64, neg_samples=4,
                                 burnin_steps=0)
    pstate, popt = pe.init_state(cfg, seed=0)
    pstep = pe.make_train_step(cfg)
    import jax.numpy as jnp

    ppairs = jnp.asarray(ds.pairs)
    for _ in range(args.steps):
        pstate, _ = pstep(cfg, popt, pstate, ppairs)
    table = np.asarray(jax.device_get(pstate.table), np.float32)
    table_sha = hashlib.sha256(table.tobytes()).hexdigest()

    # the DP invariant, checked host-side: every replica bit-identical.
    # (assert_equal_across_hosts rides a device collective the CPU
    # loopback lacks; digests cross the shared filesystem instead.)
    digest = {"params_sha": params_sha, "table_sha": table_sha,
              "losses": losses}
    with open(os.path.join(args.workdir, f"digest.{args.pid}.json"),
              "w") as f:
        json.dump(digest, f)
    mh.sync("digests")
    if args.pid == 0:
        for p in range(1, args.nprocs):
            with open(os.path.join(args.workdir,
                                   f"digest.{p}.json")) as f:
                other = json.load(f)
            if other != digest:
                print(f"CONSISTENCY MISMATCH pid0 vs pid{p}: "
                      f"{digest} != {other}", flush=True)
                return 1

    # per-host-owned checkpoint: THIS process writes only its row range;
    # process 0 commits the manifest behind the barrier
    ckpt_dir = os.path.join(args.workdir, "host_table")
    master = HT.HostEmbedTable.from_array(table)
    HT.save_owned_rows(master, ckpt_dir,
                       barrier=lambda: mh.sync("host_table"))

    # process-0-gated export: every process calls, ONE artifact lands;
    # non-0 processes get the committed artifact back and must agree
    export_dir = os.path.join(args.workdir, "artifact")
    spec = ("poincare", float(cfg.c))
    art = export_artifact(export_dir, table, spec,
                          model_config={"dim": cfg.dim}, overwrite=True)
    want = fingerprint_of(table, spec)
    if art.fingerprint != want:
        print(f"FINGERPRINT MISMATCH pid={args.pid}: "
              f"{art.fingerprint} != {want}", flush=True)
        return 1

    if args.pid == 0:
        lo, hi = mh.process_row_range(master.num_rows)
        print("RESULT " + json.dumps({
            "losses": losses, "devices": jax.local_device_count(),
            "processes": jax.process_count(),
            "pairs": int(npairs), "num_rows": int(master.num_rows),
            "owned_rows_p0": [int(lo), int(hi)], "data_plane": plane,
            "fingerprint": art.fingerprint,
            "params_sha": params_sha, "table_sha": table_sha,
            "ckpt_dir": ckpt_dir, "export_dir": export_dir,
        }), flush=True)
    return 0


def run_bench(args, mh) -> int:
    """Timed chunked HGCN LP steps for the scaling row: warmup one
    chunk (compile), then time ``--steps`` steps in ``--chunk``-step
    dispatches.  Every process times its own replica and drops a
    timing file; process 0 aggregates behind the barrier, so the
    reported throughput is the fleet's, not one host's."""
    import jax

    fn, state, npairs = _build_hgcn(args.nodes, args.feat,
                                    _local_mesh(), chunk=args.chunk)
    state, loss = fn(state)  # warmup: compile + first chunk
    jax.block_until_ready(loss)
    nchunks = max(1, args.steps // max(args.chunk, 1))
    losses = []
    t0 = time.perf_counter()
    for _ in range(nchunks):
        state, loss = fn(state)
        lv = loss[-1] if getattr(loss, "ndim", 0) else loss
        losses.append(float(jax.device_get(lv)))  # per-chunk sync point
    elapsed = time.perf_counter() - t0
    steps = nchunks * max(args.chunk, 1)
    timing = {"elapsed_s": elapsed, "losses": losses}
    with open(os.path.join(args.workdir, f"timing.{args.pid}.json"),
              "w") as f:
        json.dump(timing, f)
    mh.sync("timings")
    if args.pid == 0:
        per_proc = [timing] + [
            json.load(open(os.path.join(args.workdir,
                                        f"timing.{p}.json")))
            for p in range(1, args.nprocs)]
        slowest = max(t["elapsed_s"] for t in per_proc)
        print("RESULT " + json.dumps({
            "losses": losses, "devices": jax.local_device_count(),
            "processes": jax.process_count(),
            "steps": steps, "chunk": args.chunk, "pairs": int(npairs),
            "elapsed_s": slowest, "step_time_s": slowest / steps,
            # fleet rate: nprocs replicas each advancing steps/slowest
            "steps_per_s": args.nprocs * steps / slowest,
            "per_process_elapsed_s": [t["elapsed_s"] for t in per_proc],
        }), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pid", type=int, required=True)
    ap.add_argument("--nprocs", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--task", choices=["pipeline", "bench"],
                    default="pipeline")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--nodes", type=int, default=128)
    ap.add_argument("--feat", type=int, default=8)
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"

    # persistent XLA compile cache, same resolution as the CLIs: every
    # group in a test/bench run compiles the SAME tiny programs, so
    # only the first-ever worker pays the cold compile — the rest
    # deserialize (the smoke/check/bench trio spawns 6+ processes)
    from hyperspace_tpu import compile_cache

    compile_cache.activate()

    from hyperspace_tpu.parallel import multihost as mh

    mh.initialize(f"127.0.0.1:{args.port}", args.nprocs, args.pid,
                  local_device_count=2)
    os.makedirs(args.workdir, exist_ok=True)
    if args.task == "bench":
        return run_bench(args, mh)
    return run_pipeline(args, mh)


if __name__ == "__main__":
    sys.exit(main())
