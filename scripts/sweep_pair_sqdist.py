"""The LP decoder's distances, forward + backward, over table sizes:
`nn.edge_dist.pair_sqdist` (sorted VJP; Lorentz, so the backward that
lists one scalar a pair and gathers the other end only) against
``m.sqdist(z[u], z[v])`` (XLA's scatter-adds).  Run on the machine with
the chip, from the repo root (it refuses the CPU); PERF.md §6, PR 34 has
a v5e's readings (PR 27: the backward that gathered both ends):

    python scripts/sweep_pair_sqdist.py [N:P ...]

One JSON line a shape: host clock round a jitted ``value_and_grad`` with
respect to a float32 ``z [N, 33]`` cast to bf16 (the configuration's
decoder lane), mean of 10 calls after 3 warm ones.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 368,256 rows: a shard of the four-chip cell's table, with the arxiv
# step's pairs and with its own (8.66 M pairs a step over `data=2`)
SHAPES = [(2708, 8976), (19717, 75000), (169343, 1880610), (338686, 1880610),
          (368256, 1880610), (368256, 4330000), (677372, 1880610),
          (1354744, 1880610), (2449029, 1880610), (2449029, 7522440)]


def main(argv):
    from hyperspace_tpu.manifolds import Lorentz
    from hyperspace_tpu.nn.edge_dist import pair_sqdist

    assert jax.default_backend() != "cpu", "a timing needs the chip"
    shapes = [tuple(int(x) for x in a.split(":")) for a in argv] or SHAPES
    print(json.dumps({"device_kind": jax.devices()[0].device_kind}),
          flush=True)
    m = Lorentz(1.0)

    def loss(zz, u, v, w, wrap):
        zb = zz.astype(jnp.bfloat16)
        sq = (pair_sqdist(zb, m.c, u, v, "lorentz") if wrap
              else m.sqdist(zb[u], zb[v]))
        return jnp.sum(sq.astype(jnp.float32) * w)

    # one wrapper a path; each shape compiles it anew
    paths = {name: jax.jit(jax.value_and_grad(partial(loss, wrap=wrap)))
             for name, wrap in (("scatter_add", False), ("sorted_vjp", True))}
    for n, p in shapes:
        k = jax.random.split(jax.random.PRNGKey(n % 1000 + p % 1000), 4)
        z = m.random_normal(k[0], (n, 33), jnp.float32, std=0.3)
        u = jax.random.randint(k[1], (p,), 0, n)
        v = jax.random.randint(k[2], (p,), 0, n)
        w = jax.random.normal(k[3], (p,), jnp.float32)
        line, grads = {"n": n, "p": p}, {}
        for name, f in paths.items():
            for _ in range(3):
                _, grads[name] = jax.block_until_ready(f(z, u, v, w))
            t0 = time.perf_counter()
            for _ in range(10):
                r = f(z, u, v, w)
            jax.block_until_ready(r)
            line[name + "_ms"] = (time.perf_counter() - t0) / 10 * 1e3
        line["rel_grad_diff"] = float(
            jnp.linalg.norm(grads["sorted_vjp"] - grads["scatter_add"])
            / jnp.linalg.norm(grads["scatter_add"]))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
