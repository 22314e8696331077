"""Block-CSR segment-sum kernel parity (SURVEY.md §4.4): the Pallas kernel
in interpret mode must match ``jax.ops.segment_sum`` exactly-ish, over
random sorted segment layouts including empty segments, hub nodes, and
padding tails."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.kernels.segment import build_csr_plan, csr_segment_sum


def _run(receivers, vals, n):
    plan = tuple(jnp.asarray(a) for a in build_csr_plan(receivers, n))
    return csr_segment_sum(jnp.asarray(vals), jnp.asarray(receivers), plan, n)


@pytest.mark.parametrize(
    "n,e,f", [(300, 2000, 17), (50, 64, 128), (1000, 5000, 64), (7, 3, 5)]
)
def test_matches_segment_sum(n, e, f, rng, interp):
    r = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = rng.standard_normal((e, f)).astype(np.float32)
    got = _run(r, vals, n)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(r), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_hub_node_and_empty_segments(rng, interp):
    # one node receives 90% of edges; most segments empty
    n, e, f = 500, 4000, 32
    r = np.where(rng.random(e) < 0.9, 137, rng.integers(0, n, e))
    r = np.sort(r).astype(np.int32)
    vals = rng.standard_normal((e, f)).astype(np.float32)
    got = _run(r, vals, n)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(r), n)
    # a ~3600-edge hub sums in a different order than segment_sum's chain:
    # tolerance scales with sqrt(deg)·eps
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=5e-4)


def test_zero_padding_tail_is_inert(rng, interp):
    # padding convention: receivers = n-1 with zero values
    n, e, f = 100, 700, 16
    r = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = rng.standard_normal((e, f)).astype(np.float32)
    r_pad = np.concatenate([r, np.full(300, n - 1, np.int32)])
    vals_pad = np.concatenate([vals, np.zeros((300, f), np.float32)])
    got = _run(r_pad, vals_pad, n)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(r), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_plan_requires_sorted():
    with pytest.raises(ValueError):
        build_csr_plan(np.asarray([3, 1, 2], np.int32), 5)


def test_plan_chunks_in_range_for_empty_trailing_blocks(rng, interp):
    # E an exact multiple of bk with all receivers far below num_nodes:
    # trailing node blocks are empty and their mandatory zeroing item must
    # not index one chunk past the end of the padded edge array
    n, e, f = 300, 512, 8
    r = np.sort(rng.integers(0, 128, e)).astype(np.int32)
    plan = build_csr_plan(r, n)
    assert int(plan.chunk.max()) < max(e // 512, 1)
    vals = rng.standard_normal((e, f)).astype(np.float32)
    got = _run(r, vals, n)
    want = jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(r), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("op", ["sum", "max"])
def test_csr_segment_reduce_1d_parity(op, monkeypatch):
    """Scalar per-segment sum/max kernel == jax.ops reference (interpret)."""
    from hyperspace_tpu.kernels.segment import (
        build_csr_plan,
        csr_segment_reduce_1d,
    )

    monkeypatch.setenv("HYPERSPACE_KERNELS", "interpret")
    rng = np.random.default_rng(3)
    n, e = 300, 2048
    recv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=e).astype(np.float32))
    plan = tuple(jnp.asarray(a) for a in build_csr_plan(recv, n))
    got = csr_segment_reduce_1d(vals, jnp.asarray(recv), plan, n, op=op)
    ref_f = jax.ops.segment_sum if op == "sum" else jax.ops.segment_max
    ref = ref_f(vals, jnp.asarray(recv), n, indices_are_sorted=True)
    if op == "max":
        # empty segments: kernel yields the -inf stand-in, ref yields -inf
        got = np.where(np.asarray(got) < -1e37, -np.inf, np.asarray(got))
        ref = np.where(np.isinf(np.asarray(ref)) | (np.asarray(ref) < -1e37),
                       -np.inf, np.asarray(ref))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# --- the plan built on the device, and the transposed call that runs on it ----


def _sorted_ids(case, n, e, rng):
    """Sorted ids as the LP decoder's backward hands them over: the
    data, then `rows_for_device_plan`'s tail with id ``n``."""
    from hyperspace_tpu.kernels.segment import rows_for_device_plan

    if case == "hub":  # one node takes a third of the rows
        ids = rng.integers(0, n, e)
        ids[: e // 3] = n // 2
    elif case == "empty_ends":  # empty leading and trailing node blocks
        ids = rng.integers(n // 3, 2 * n // 3, e)
    elif case == "last_node":  # the data's largest id beside the tail's
        ids = np.full(e, n - 1)
    else:
        ids = rng.integers(0, n, e)
    out = np.full(rows_for_device_plan(e), n, np.int32)
    out[:e] = np.sort(ids)
    return out


PLAN_CASES = [("uniform", 1000, 5000), ("uniform", 300, 1024),
              ("uniform", 128, 513), ("uniform", 130, 100),
              ("hub", 1000, 5000), ("hub", 300, 1536),
              ("empty_ends", 1000, 5000), ("empty_ends", 300, 700),
              ("last_node", 200, 600)]


@pytest.mark.parametrize("case,n,e", PLAN_CASES)
def test_device_plan_is_the_host_plan_with_an_inert_tail(case, n, e, rng):
    from hyperspace_tpu.kernels.segment import device_csr_plan

    ids = _sorted_ids(case, n, e, rng)
    host = build_csr_plan(ids, n)
    dev = [np.asarray(a) for a in
           jax.jit(lambda r: device_csr_plan(r, n))(jnp.asarray(ids))]
    t, nb, nchunks = len(host.block), -(-n // 128), len(ids) // 512
    assert all(a.shape == (nchunks + nb,) and a.dtype == np.int32
               for a in dev)
    assert t < nchunks + nb
    for want, got in zip(host, dev):
        np.testing.assert_array_equal(got[:t], want)
    # the unused items: the last block again, nothing zeroed, the chunk
    # that holds only the tail's zero rows
    assert (dev[0][t:] == nb - 1).all() and (dev[2][t:] == 0).all()
    assert (dev[1][t:] == nchunks - 1).all() and (ids[-512:] == n).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case,n,e", PLAN_CASES)
def test_pair_scatter_sum_matches_segment_sum(case, n, e, dtype, rng, interp):
    from hyperspace_tpu.kernels.segment import pair_scatter_sum

    ids = _sorted_ids(case, n, e, rng)
    vt = np.zeros((33, len(ids)), np.float32)
    vt[:, :e] = rng.standard_normal((33, e))
    vt_d, ids_d = jnp.asarray(vt, dtype), jnp.asarray(ids)
    got = pair_scatter_sum(vt_d, ids_d, n)
    assert got.shape == (33, n) and got.dtype == jnp.float32
    # the oracle accumulates what the kernel was given, in float32
    want = jax.ops.segment_sum(vt_d.astype(jnp.float32).T[:e],
                               ids_d[:e], n).T
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=5e-4)


def test_pair_scatter_sum_twin_is_the_same_sum(rng, monkeypatch):
    from hyperspace_tpu.kernels.segment import pair_scatter_sum

    monkeypatch.setenv("HYPERSPACE_KERNELS", "xla")
    ids = _sorted_ids("hub", 300, 1536, rng)
    vt = jnp.asarray(rng.standard_normal((33, len(ids))), jnp.bfloat16)
    vt = vt.at[:, 1536:].set(0)
    got = pair_scatter_sum(vt, jnp.asarray(ids), 300)
    want = jax.ops.segment_sum(vt.astype(jnp.float32).T, jnp.asarray(ids),
                               300).T
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("what", ["plan", "kernel"])
def test_device_plan_wants_whole_chunks(what, interp):
    from hyperspace_tpu.kernels.segment import (
        device_csr_plan,
        pair_scatter_sum,
    )

    ids = jnp.zeros(700, jnp.int32)
    with pytest.raises(ValueError, match="whole"):
        if what == "plan":
            device_csr_plan(ids, 100)
        else:
            pair_scatter_sum(jnp.zeros((33, 700)), ids, 100)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [512, 3072, 4096])
def test_rows_to_columns_is_the_transpose(rows, dtype, rng, interp):
    from hyperspace_tpu.kernels.segment import rows_to_columns

    x = jnp.asarray(rng.standard_normal((rows, 33)), dtype)
    got = rows_to_columns(x)
    assert got.shape == (33, rows) and got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(x.T, np.float32))
    with pytest.raises(ValueError, match="whole"):
        rows_to_columns(x[:500])


# --- the receiver-side pick inside the CSR walk --------------------------------


def _expand_receivers(case, rng):
    """(receivers, num_nodes) of the cases the reductions are held to."""
    if case == "uniform":
        n, r = 1000, rng.integers(0, 1000, 5000)
    elif case == "tiny":
        n, r = 7, rng.integers(0, 7, 3)
    elif case == "hub_and_empty_segments":  # one node takes 90% of the edges
        n = 500
        r = np.where(rng.random(4000) < 0.9, 137, rng.integers(0, n, 4000))
    elif case == "empty_trailing_blocks":  # E a whole number of chunks
        n, r = 300, rng.integers(0, 128, 512)
    elif case == "chunk_straddles_blocks":  # 130 nodes in one 513-edge list
        n, r = 130, np.concatenate([rng.integers(0, 128, 500),
                                    np.full(13, 129)])
    else:  # zero_padding_tail: the layout's padding points at n - 1
        n = 100
        r = np.concatenate([rng.integers(0, n, 700), np.full(300, n - 1)])
    return np.sort(r).astype(np.int32), n


EXPAND_CASES = ["uniform", "tiny", "hub_and_empty_segments",
                "empty_trailing_blocks", "chunk_straddles_blocks",
                "zero_padding_tail"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", EXPAND_CASES)
def test_csr_segment_expand_1d_is_the_gather_bit_for_bit(case, dtype, rng,
                                                         interp):
    from hyperspace_tpu.kernels.segment import csr_segment_expand_1d

    r, n = _expand_receivers(case, rng)
    plan = tuple(jnp.asarray(a) for a in build_csr_plan(r, n))
    vals = jnp.asarray(rng.standard_normal(n), dtype)
    got = jax.jit(csr_segment_expand_1d, static_argnums=3)(
        vals, jnp.asarray(r), plan, n)
    assert got.shape == r.shape and got.dtype == vals.dtype
    view = np.uint32 if dtype == "float32" else np.uint16
    np.testing.assert_array_equal(np.asarray(got).view(view),
                                  np.asarray(vals[r]).view(view))


@pytest.mark.parametrize("case", EXPAND_CASES)
def test_csr_segment_expand_1d_twin_agrees(case, rng, monkeypatch):
    from hyperspace_tpu.kernels.segment import csr_segment_expand_1d

    r, n = _expand_receivers(case, rng)
    plan = tuple(jnp.asarray(a) for a in build_csr_plan(r, n))
    vals = jnp.asarray(rng.standard_normal(n), jnp.float32)
    out = {}
    for mode in ("xla", "interpret"):
        monkeypatch.setenv("HYPERSPACE_KERNELS", mode)
        out[mode] = np.asarray(csr_segment_expand_1d(vals, jnp.asarray(r),
                                                     plan, n))
    np.testing.assert_array_equal(out["xla"], out["interpret"])
    np.testing.assert_array_equal(out["xla"], np.asarray(vals)[r])


def test_csr_segment_expand_1d_is_the_transpose_of_the_sum(rng, interp):
    """<expand(v), t> = <v, reduce_sum(t)>: the same plan walked the
    other way."""
    from hyperspace_tpu.kernels.segment import (
        csr_segment_expand_1d,
        csr_segment_reduce_1d,
    )

    r, n = _expand_receivers("hub_and_empty_segments", rng)
    plan = tuple(jnp.asarray(a) for a in build_csr_plan(r, n))
    v = jnp.asarray(rng.standard_normal(n), jnp.float32)
    t = jnp.asarray(rng.standard_normal(len(r)), jnp.float32)
    lhs = jnp.vdot(csr_segment_expand_1d(v, jnp.asarray(r), plan, n), t)
    rhs = jnp.vdot(v, csr_segment_reduce_1d(t, jnp.asarray(r), plan, n))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-4)
