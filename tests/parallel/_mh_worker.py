"""Loopback multi-host worker (SURVEY.md §4.6): one process of an
N-process DP training job over a ``host × data`` mesh.

Trains a tiny least-squares model with SGD, checkpointing every
``--ckpt-every`` steps; ``--crash-at S`` makes this process die abruptly
(os._exit) right after the step-S checkpoint commits — the fault half of
the restart-from-checkpoint drill.  Process 0 prints the final params as
one JSON line prefixed ``RESULT``.

Run by tests/parallel/test_multihost.py; also runnable by hand:

    python tests/parallel/_mh_worker.py --pid 0 --nprocs 2 --port 9731 \
        --workdir /tmp/mh &
    python tests/parallel/_mh_worker.py --pid 1 --nprocs 2 --port 9731 \
        --workdir /tmp/mh
"""

import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pid", type=int, required=True)
    ap.add_argument("--nprocs", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--crash-at", type=int, default=0)  # 0 = never
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--hgcn", action="store_true",
                    help="train the node-sharded HGCN LP step instead of "
                         "the least-squares toy (north-star workload over "
                         "DCN)")
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"

    from hyperspace_tpu.parallel import multihost as mh

    mh.initialize(f"127.0.0.1:{args.port}", args.nprocs, args.pid,
                  local_device_count=2)

    if args.hgcn:
        return run_hgcn(args, mh)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hyperspace_tpu.parallel.mesh import multihost_mesh
    from hyperspace_tpu.train.checkpoint import CheckpointManager

    mesh = multihost_mesh({"data": 2})
    repl = NamedSharding(mesh, P())
    batch_spec = P(("host", "data"))

    # fixed global problem; each process feeds only its own row slice
    rng = np.random.default_rng(0)
    xh = rng.standard_normal((16, 4)).astype(np.float32)
    yh = (xh @ np.asarray([1.0, -2.0, 3.0, 0.5], np.float32)).astype(np.float32)
    rows = 16 // args.nprocs
    sl = slice(args.pid * rows, (args.pid + 1) * rows)
    xg = mh.host_local_to_global(xh[sl], mesh, batch_spec)
    yg = mh.host_local_to_global(yh[sl], mesh, batch_spec)

    opt = optax.sgd(0.2)
    params = jnp.zeros(4, jnp.float32)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    state = jax.device_put(state, repl)

    @jax.jit
    def train_step(state, x, y):
        def loss_fn(p):
            return jnp.mean((x @ p - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(state["params"])
        updates, opt_state = opt.update(g, state["opt"], state["params"])
        return {
            "params": optax.apply_updates(state["params"], updates),
            "opt": opt_state,
            "step": state["step"] + 1,
        }, loss

    mgr = CheckpointManager(os.path.join(args.workdir, "ckpt"),
                            async_save=False)
    start = 0
    if args.resume:
        latest = mgr.latest_step()
        if latest is not None:
            state, start = mgr.restore(state)

    loss = None
    for i in range(start, args.steps):
        state, loss = train_step(state, xg, yg)
        done = i + 1
        if done % args.ckpt_every == 0:
            mgr.save(done, state)
            mgr.wait()
            mh.sync(f"ckpt-{done}")
            if args.crash_at == done and args.pid == args.nprocs - 1:
                os._exit(7)  # simulated host failure, post-commit
    mgr.wait()
    mgr.close()

    final = mh.fetch_replicated(state["params"])
    if args.pid == 0:
        print("RESULT " + json.dumps({
            "params": [float(v) for v in final],
            "loss": float(jax.device_get(loss)) if loss is not None else None,
            "devices": jax.device_count(),
        }), flush=True)
    return 0


def run_hgcn(args, mh) -> int:
    """The north-star workload's mesh step over a real host×data mesh,
    fed as ``cli.train``'s multihost path feeds it: every process builds
    the same graph deterministically and device_puts its addressable
    shards of the partitioned graph, the supervision batch is sharded
    over (host, data), and the encoder's exchange and the gradient
    all-reduce cross the process boundary inside XLA (SURVEY.md §3.4:
    Python never communicates across hosts, only collectives do)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.data import graphs as G
    from hyperspace_tpu.models import hgcn
    from hyperspace_tpu.parallel.mesh import multihost_mesh

    mesh = multihost_mesh({"data": 2})
    edges, x, labels, ncls = G.synthetic_hierarchy(
        num_nodes=128, feat_dim=8, seed=0)
    split = G.split_edges(edges, 128, x, seed=0, pad_multiple=128)
    cfg = hgcn.HGCNConfig(feat_dim=8, hidden_dims=(16, 8))
    model, opt, state = hgcn.init_lp(cfg, split.graph, seed=1)
    train_pos = jnp.asarray(hgcn.round_up_pairs(split.train_pos, mesh))
    step, state, nsg = hgcn.make_node_sharded_step_lp(
        model, opt, 128, mesh, state, split)
    # per-host data plane: the step takes its supervision batch SHARDED,
    # so each host contributes only its own row slice and the global
    # [P, 2] batch is assembled across processes
    train_pos_g = mh.distribute_batch(train_pos, mesh)
    losses = []
    for _ in range(args.steps):
        state, loss = step(state, nsg, train_pos_g)
        losses.append(float(jax.device_get(loss)))
    if args.pid == 0:
        print("RESULT " + json.dumps({
            "losses": losses, "devices": jax.device_count(),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
