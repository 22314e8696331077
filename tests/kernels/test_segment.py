"""Block-CSR segment-sum kernel parity (SURVEY.md §4.4): the Pallas kernel
in interpret mode must match ``jax.ops.segment_sum`` exactly-ish, over
random sorted segment layouts including empty segments, hub nodes, and
padding tails."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.kernels.segment import build_csr_plan, csr_segment_sum

HIGHEST, DEFAULT = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT


def _run(receivers, vals, n):
    plan = tuple(jnp.asarray(a) for a in build_csr_plan(receivers, n))
    return csr_segment_sum(jnp.asarray(vals), jnp.asarray(receivers), plan, n)


def _accumulator(receivers, vals, n, precision=None):
    """The kernel's float32 sums before ``csr_segment_sum`` casts them to
    the values' dtype, with the body built at ``precision`` (default:
    what the values' dtype gets)."""
    from hyperspace_tpu.kernels.segment import (
        _pallas_csr,
        selection_precision,
    )

    if precision is None:
        precision = selection_precision(vals.dtype)
    plan = tuple(jnp.asarray(a) for a in build_csr_plan(receivers, n))
    out = _pallas_csr(jnp.asarray(vals), jnp.asarray(receivers), plan, n,
                      True, precision)
    assert out.dtype == jnp.float32 and out.shape == (n, vals.shape[1])
    return out


def _check_against_segment_sum(r, vals, n, rtol, atol):
    """Float32 values: the call against ``segment_sum``.  bfloat16 values:
    the kernel's float32 accumulator against ``segment_sum`` of the same
    values accumulated in float32, under the SAME tolerance (a summation
    order's, not bfloat16's), and the call is that accumulator rounded
    once."""
    want = jax.ops.segment_sum(vals.astype(jnp.float32), jnp.asarray(r), n)
    got = _run(r, vals, n)
    assert got.dtype == vals.dtype
    if vals.dtype == jnp.bfloat16:
        acc = _accumulator(r, vals, n)
        assert jnp.array_equal(got, acc.astype(jnp.bfloat16))
        got = acc
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "n,e,f", [(300, 2000, 17), (50, 64, 128), (1000, 5000, 64), (7, 3, 5)]
)
def test_matches_segment_sum(n, e, f, dtype, rng, interp):
    r = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = jnp.asarray(rng.standard_normal((e, f)), dtype)
    _check_against_segment_sum(r, vals, n, rtol=1e-5, atol=1e-5)


def _hub_layout(rng):
    # one node receives 90% of edges; most segments empty
    n, e = 500, 4000
    r = np.where(rng.random(e) < 0.9, 137, rng.integers(0, n, e))
    return np.sort(r).astype(np.int32), n


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_hub_node_and_empty_segments(dtype, rng, interp):
    r, n = _hub_layout(rng)
    vals = jnp.asarray(rng.standard_normal((len(r), 32)), dtype)
    # a ~3600-edge hub sums in a different order than segment_sum's chain:
    # tolerance scales with sqrt(deg)·eps
    _check_against_segment_sum(r, vals, n, rtol=1e-4, atol=5e-4)


@pytest.mark.parametrize("f", [32, 128, 129])
@pytest.mark.parametrize("layout", ["hub", "padding_tail"])
def test_one_pass_is_six_passes_bit_for_bit_on_bf16(layout, f, rng, interp):
    """A 0/1 one-hot times a bfloat16 value, summed in float32: the body
    built at DEFAULT gives what the body built at HIGHEST gives.  Here,
    on the CPU, the interpreter multiplies in float32 under either, so
    this holds the wiring; the chip's answer is
    ``scripts/tpu_kernel_smoke.py``'s ``csr_segment_sum_one_pass_*``
    lines."""
    if layout == "hub":
        r, n = _hub_layout(rng)
        vals = rng.standard_normal((len(r), f))
    else:  # the layout's padding: receivers n - 1 with zero rows
        n = 100
        r = np.concatenate([np.sort(rng.integers(0, n, 700)),
                            np.full(300, n - 1)]).astype(np.int32)
        vals = np.concatenate([rng.standard_normal((700, f)),
                               np.zeros((300, f))])
    vals = jnp.asarray(vals, jnp.bfloat16)
    one = _accumulator(r, vals, n, DEFAULT)
    six = _accumulator(r, vals, n, HIGHEST)
    assert jnp.array_equal(one, six)
    assert jnp.array_equal(one, _accumulator(r, vals, n))
    assert bool(jnp.any(one != 0))


def _kernel_dots(fn, *args):
    """The ``dot_general`` equations inside the Pallas kernel that
    ``fn(*args)`` traces, whatever ``pl.when`` nests them in."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)

    calls = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0].params["name"], list(walk(calls[0].params["jaxpr"]))


@pytest.mark.parametrize("kernel", ["csr_segment_sum", "pair_scatter_sum"])
@pytest.mark.parametrize("dtype,precision", [("bfloat16", DEFAULT),
                                             ("float32", HIGHEST),
                                             ("float16", HIGHEST)])
def test_selection_matmul_takes_the_passes_its_values_need(
        kernel, dtype, precision, rng, monkeypatch):
    """The counter that says the one-pass path engaged is static: the
    traced kernel's matmuls carry DEFAULT for bfloat16 values and HIGHEST
    for float32 (and anything else), in both kernels that take it from
    ``selection_precision``; float32 accumulation either way."""
    from hyperspace_tpu.kernels.segment import (
        pair_scatter_sum,
        selection_precision,
    )

    assert selection_precision(jnp.dtype(dtype)) == precision
    monkeypatch.setenv("HYPERSPACE_KERNELS", "pallas")
    n = 300
    if kernel == "csr_segment_sum":
        r = np.sort(rng.integers(0, n, 2000)).astype(np.int32)
        plan = tuple(jnp.asarray(a) for a in build_csr_plan(r, n))
        name, dots = _kernel_dots(
            lambda v: csr_segment_sum(v, jnp.asarray(r), plan, n),
            jnp.zeros((2000, 17), dtype))
    else:
        ids = jnp.asarray(_sorted_ids("uniform", n, 1024, rng))
        name, dots = _kernel_dots(lambda v: pair_scatter_sum(v, ids, n),
                                  jnp.zeros((33, ids.shape[0]), dtype))
    assert name == kernel
    assert len(dots) == 4  # one per 128-edge sub-chunk of a 512-edge chunk
    for eqn in dots:
        assert eqn.params["precision"] == (precision, precision)
        assert eqn.params["preferred_element_type"] == jnp.float32


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
