"""The two faults a mesh cell's ``correct`` has to catch, planted in the
program under the driver for as long as the context lasts (the program
has to be wired and traced inside it).  ``calibrate_mesh.py`` reads them
on the chips, the CPU tests at a tiny size."""

from __future__ import annotations

import contextlib
from unittest import mock

import numpy as np


@contextlib.contextmanager
def exchange_left_out():
    """Every shard aggregates its local senders only: the weights of its
    cross-shard edges are zeroed at partition time, so the rows the
    exchange brings never enter a sum (forward or backward)."""
    from hyperspace_tpu.parallel import node_shard as NS

    real = NS.partition_graph

    def local_only(g, ndev, *a, **k):
        hp = real(g, ndev, *a, **k)
        shard = np.arange(ndev)[:, None]
        remote = (hp.senders >= hp.n_shard if hp.halo
                  else hp.senders // hp.n_shard != shard)
        return hp._replace(w_fwd=np.where(remote, 0, hp.w_fwd),
                           w_bwd=np.where(remote, 0, hp.w_bwd))

    with mock.patch.object(NS, "partition_graph", local_only):
        yield


@contextlib.contextmanager
def a_data_shards_pairs_left_out():
    """The second data shard's pairs (the second half of the positives
    and of the negatives, as the batch is sharded) stay in the loss and
    fall out of its gradient."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.models import hgcn

    real = hgcn._bce_pos_neg

    def first_half_only(x):
        keep = (jnp.arange(x.shape[0]) < x.shape[0] // 2).astype(x.dtype)
        return x * keep + jax.lax.stop_gradient(x) * (1 - keep)

    with mock.patch.object(
            hgcn, "_bce_pos_neg",
            lambda pos, neg, w_pos=None: real(first_half_only(pos),
                                              first_half_only(neg), w_pos)):
        yield
