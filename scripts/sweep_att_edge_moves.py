"""What the chip charges to move one scalar per edge: the forms that
`nn/scatter.py`'s attention path can take for its two kinds of pick.
Run on the machine with the chip, from the repo root (it refuses the
CPU); PERF.md §6, PR 29 has a v5e's readings:

    python scripts/sweep_att_edge_moves.py [E ...]

- the involution ``x[rev_perm]`` (bfloat16 and float32 payloads): XLA's
  gather, the gather told that the indices are in bounds and unique, the
  key-sort ``nn.scatter.involute`` uses, and that sort carrying both
  payloads at once (``sort_both``, the attention backward's call);
- the receiver-side pick ``alpha[receivers]`` (receivers ascending): XLA's
  gather, and `kernels.segment.csr_segment_expand_1d`.

The graph is a random symmetric one over N = 169,343 nodes in the
receiver-sorted layout, with its true involution.  One JSON line per E:
ms a call and ns an element, host clock round a jitted loop of REPS
dependent applications (so a dispatch's cost is spread thin at small E),
mean of 5 calls after 2 warm ones.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = 169_343
SIZES = [13_312, 100_352, 1_571_840]
REPS = 20


def symmetric_graph(e: int, rng):
    """Receivers (ascending) and the involution of a random symmetric
    edge list of ``e`` directed edges."""
    u, v = rng.integers(0, N, e // 2), rng.integers(0, N, e // 2)
    s, r = np.concatenate([u, v]), np.concatenate([v, u])
    mate = np.concatenate([np.arange(e // 2) + e // 2, np.arange(e // 2)])
    order = np.lexsort((s, r))
    slot = np.empty(e, np.int64)
    slot[order] = np.arange(e)
    return r[order].astype(np.int32), slot[mate[order]].astype(np.int32)


def looped(step):
    """REPS dependent applications of ``step`` as one jitted program."""
    return jax.jit(
        lambda c: jax.lax.fori_loop(0, REPS, lambda _, x: step(x), c))


def timed(step, carry):
    """ms for one application of ``step`` (carry -> carry)."""
    f = looped(step)
    for _ in range(2):
        jax.block_until_ready(f(carry))
    t0 = time.perf_counter()
    for _ in range(5):
        out = f(carry)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (5 * REPS) * 1e3


def main(argv):
    from hyperspace_tpu.kernels.segment import (
        build_csr_plan,
        csr_segment_expand_1d,
    )
    from hyperspace_tpu.nn.scatter import involute

    assert jax.default_backend() != "cpu", "a timing needs the chip"
    print(json.dumps({"device_kind": jax.devices()[0].device_kind, "n": N,
                      "reps": REPS}), flush=True)
    rng = np.random.default_rng(0)
    for e in [int(a) for a in argv] or SIZES:
        recv_h, rev_h = symmetric_graph(e, rng)
        recv, rev = jnp.asarray(recv_h), jnp.asarray(rev_h)
        plan = tuple(jnp.asarray(a) for a in build_csr_plan(recv_h, N))
        alpha = jnp.asarray(rng.standard_normal(N).astype(np.float32))
        line = {"e": e}

        def put(name, ms):
            line[name + "_ms"] = round(ms, 4)
            line[name + "_ns_per_el"] = round(ms * 1e6 / e, 3)

        pair = []
        for dt in (jnp.bfloat16, jnp.float32):
            x = jnp.asarray(rng.standard_normal(e), dt)
            pair.append(x)
            tag = jnp.dtype(dt).name
            put(f"gather_{tag}", timed(lambda y: y[rev], x))
            put(f"gather_promised_{tag}", timed(
                lambda y: y.at[rev].get(mode="promise_in_bounds",
                                        unique_indices=True), x))
            put(f"sort_{tag}", timed(lambda y: involute(rev, y), x))
            assert bool(jnp.all(involute(rev, x) == x[rev]))
        # the attention backward's form: both payloads in one sort
        put("sort_both", timed(lambda xs: involute(rev, *xs), tuple(pair)))
        # the picks go alpha [N] -> [E]; the carry stays alpha, perturbed by
        # a product with 0 that the compiler cannot fold for floats, behind
        # a barrier so that all of the [E] result has to exist
        barrier = jax.lax.optimization_barrier
        put("pick_gather", timed(
            lambda a: a + 0.0 * barrier(a[recv])[:1], alpha))
        put("pick_expand", timed(
            lambda a: a + 0.0 * barrier(
                csr_segment_expand_1d(a, recv, plan, N))[:1], alpha))
        assert bool(jnp.all(csr_segment_expand_1d(alpha, recv, plan, N)
                            == alpha[recv]))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
