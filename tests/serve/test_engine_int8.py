"""int8 quantized table-scan lane (serve/engine.py + serve/quant.py,
docs/serving.md "Quantized scan lane").

Acceptance contracts (ISSUE 14):

- **rank identity**: on all three manifold specs the int8-coarse-scan +
  f32-rescore engine returns EXACTLY the exact f32 engine's neighbors
  and f32-tight distances, checked against an f64 oracle — including
  the IVF, fused-kernel, and mesh-sharded compositions;
- **quarter bytes**: the resident scan copy is int8 + a per-row f32
  scale — the 4×-capacity lever the beyond-HBM ROADMAP item names;
- **lane isolation**: the scan signature and the batcher cache key
  carry the lane, so f32/bf16/int8 rows can never cross;
- **quant module**: per-row symmetric scaling round-trips within half a
  quantization step, zero rows stay exactly zero.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from hyperspace_tpu.manifolds import Lorentz, PoincareBall
from hyperspace_tpu.serve.batcher import RequestBatcher
from hyperspace_tpu.serve.engine import QueryEngine
from hyperspace_tpu.serve.quant import (QLEVELS, dequantize_rows,
                                        quantize_rows)

N, DIM, K, B = 600, 8, 7, 16


def _poincare_table(rng, n=N, dim=DIM, scale=0.5):
    return np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * scale, jnp.float32)))


def _lorentz_table(rng, n=N, dim=DIM, c=0.8):
    v = jnp.concatenate(
        [jnp.zeros((n, 1), jnp.float32),
         jnp.asarray(rng.standard_normal((n, dim)) * 0.5, jnp.float32)],
        axis=1)
    return np.asarray(Lorentz(c).expmap0(v))


def _specs(rng):
    return [
        ("poincare", _poincare_table(rng), ("poincare", 1.0)),
        ("lorentz", _lorentz_table(rng), ("lorentz", 0.8)),
        ("product", _poincare_table(rng),
         ("product", (("poincare", 4, 1.0), ("euclidean", 4, 0.0)))),
    ]


def _f64_oracle(table, spec, q_idx, k):
    """Exact top-k in f64 via the live manifolds — the independent
    ranking the int8 lane must reproduce."""
    from hyperspace_tpu.serve.artifact import manifold_from_spec

    t64 = jnp.asarray(np.asarray(table, np.float64))
    m = manifold_from_spec(spec)
    d = np.array(m.dist(t64[q_idx][:, None, :], t64[None, :, :]))
    d[np.arange(len(q_idx)), q_idx] = np.inf  # exclude_self
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return idx, np.take_along_axis(d, idx, axis=1)


# --- quant module -------------------------------------------------------------


def test_quantize_rows_roundtrip_and_zero_rows(rng):
    t = rng.standard_normal((50, 6)).astype(np.float32)
    t[7] = 0.0
    q, s = quantize_rows(t)
    assert q.dtype == np.int8 and s.shape == (50, 1)
    assert np.abs(q).max() <= QLEVELS
    err = np.abs(dequantize_rows(q, s) - t)
    assert np.all(err <= s / 2 + 1e-9)
    assert s[7] == 0 and np.all(q[7] == 0)
    assert np.all(dequantize_rows(q, s)[7] == 0.0)
    with pytest.raises(ValueError, match=r"\[N, D\]"):
        quantize_rows(np.zeros(5))


# --- rank identity vs the f64 oracle -----------------------------------------


@pytest.mark.parametrize("scan_mode", ["two_stage", "carry", "fused"])
def test_int8_rank_identical_all_manifolds(rng, scan_mode):
    """All three specs × every scan mode: neighbors identical to the
    exact f32 engine AND the f64 oracle; distances f32-tight (they
    come from the f32 rescore, never the quantized pass)."""
    q = rng.integers(0, N, size=B)
    for name, table, spec in _specs(rng):
        e32 = QueryEngine(table, spec, chunk_rows=128)
        e8 = QueryEngine(table, spec, chunk_rows=128, precision="int8",
                         scan_mode=scan_mode)
        i32, d32 = (np.asarray(a) for a in e32.topk_neighbors(q, K))
        i8, d8 = (np.asarray(a) for a in e8.topk_neighbors(q, K))
        assert np.array_equal(i32, i8), (name, scan_mode)
        assert np.allclose(d32, d8, rtol=1e-6, atol=1e-7), name
        oi, od = _f64_oracle(table, spec, q, K)
        assert np.array_equal(i8, oi), (name, scan_mode)
        assert np.allclose(d8, od, rtol=2e-4, atol=1e-5), name


def test_int8_quarter_table_bytes(rng):
    table = _poincare_table(rng)
    e32 = QueryEngine(table, ("poincare", 1.0))
    e8 = QueryEngine(table, ("poincare", 1.0), precision="int8")
    assert e8.scan_table.dtype == jnp.int8
    assert e8.scan_scale is not None
    assert e8.scan_table.nbytes * 4 == e32.scan_table.nbytes
    # total lane bytes (code + scale) still well under half of f32
    lane = e8.scan_table.nbytes + e8.scan_scale.nbytes
    assert lane < e32.scan_table.nbytes / 2


def test_int8_ivf_rank_identical(rng):
    """IVF composition: probing through the int8 candidate scorer
    (per-candidate scale gather + f32 rescore) returns exactly the f32
    probing engine's rows, fused and two-stage."""
    from hyperspace_tpu.serve.index import build_index

    n = 4096
    table = _poincare_table(rng, n=n)
    idx = build_index(table, ("poincare", 1.0), 32, seed=0)
    q = rng.integers(0, n, size=B)
    for mode in ("two_stage", "fused"):
        e32 = QueryEngine(table, ("poincare", 1.0), index=idx, nprobe=8,
                          scan_mode=mode)
        e8 = QueryEngine(table, ("poincare", 1.0), index=idx, nprobe=8,
                         precision="int8", scan_mode=mode)
        assert e8.scan_strategy == "ivf"
        i32, d32 = (np.asarray(a) for a in e32.topk_neighbors(q, K))
        i8, d8 = (np.asarray(a) for a in e8.topk_neighbors(q, K))
        assert np.array_equal(i32, i8), mode
        assert np.allclose(d32, d8, rtol=1e-6, atol=1e-7), mode


def test_int8_sharded_rank_identical(rng):
    """4-way mesh sharding: int8 code + per-row scale shard
    P("model", None) beside the master; the per-shard scan + all-gather
    + f32 rescore matches the single-device f32 engine."""
    import jax

    from hyperspace_tpu.parallel.mesh import model_mesh

    if len(jax.local_devices()) < 4:
        pytest.skip("needs 4 local devices (tests/conftest.py forces them)")
    n = 4096
    table = _poincare_table(rng, n=n)
    q = rng.integers(0, n, size=B)
    e32 = QueryEngine(table, ("poincare", 1.0), chunk_rows=128)
    i32, d32 = (np.asarray(a) for a in e32.topk_neighbors(q, K))
    for mode in ("two_stage", "fused"):
        e8 = QueryEngine(table, ("poincare", 1.0), chunk_rows=128,
                         precision="int8", mesh=model_mesh(4),
                         scan_mode=mode)
        i8, d8 = (np.asarray(a) for a in e8.topk_neighbors(q, K))
        assert np.array_equal(i32, i8), mode
        # two compiled float32 programs of the same rescoring: the
        # sharded scan + all-gather + rescore reduces in another order
        # than the one-device program (largest relative difference read
        # here 1.2e-6, ten float32 epsilons), so the repo's bar for such
        # a pair (test_engine_precision.py), not 1e-6
        assert np.allclose(d32, d8, rtol=1e-5, atol=1e-6), mode


# --- lane isolation -----------------------------------------------------------


def test_scan_signature_carries_the_lane(rng):
    table = _poincare_table(rng)
    assert QueryEngine(table, ("poincare", 1.0)).scan_signature == \
        ("exact",)
    e8 = QueryEngine(table, ("poincare", 1.0), precision="int8")
    assert e8.scan_signature == ("exact", "int8")
    ef = QueryEngine(table, ("poincare", 1.0), precision="int8",
                     scan_mode="fused")
    assert ef.scan_signature == ("exact", "fused", "int8")


def test_batcher_cache_never_crosses_lanes(rng):
    """The same ids through f32 / bf16 / int8 batchers over the SAME
    fingerprint: each lane computes its own rows (distinct cache keys —
    the serve counters are process-wide, so assert per-pass deltas),
    and stats reports the lane."""
    from hyperspace_tpu.telemetry import registry as telem

    table = _poincare_table(rng)
    ids = rng.integers(0, N, size=8).tolist()
    reg = telem.default_registry()
    batchers = {p: RequestBatcher(QueryEngine(table, ("poincare", 1.0),
                                              precision=p))
                for p in ("f32", "bf16", "int8")}
    for p, bat in batchers.items():
        base = reg.mark()
        bat.topk(ids, K)
        assert bat.stats()["precision"] == p
        d = reg.snapshot(baseline=base)
        assert d.get("serve/cache_hit", 0) == 0  # no cross-lane reuse
        base = reg.mark()
        bat.topk(ids, K)
        d = reg.snapshot(baseline=base)
        assert d.get("serve/cache_hit", 0) > 0  # same-lane reuse works


def test_int8_prewarm(rng):
    """Prewarm composes: the lane's executables warm without touching
    request/cache counters (process-wide — assert the pass's delta)."""
    from hyperspace_tpu.telemetry import registry as telem

    table = _poincare_table(rng)
    bat = RequestBatcher(QueryEngine(table, ("poincare", 1.0),
                                     precision="int8"),
                         min_bucket=8, max_bucket=16)
    reg = telem.default_registry()
    base = reg.mark()
    bat.prewarm([K])
    d = reg.snapshot(baseline=base)
    assert d.get("serve/prewarmed", 0) > 0
    assert d.get("serve/requests", 0) == 0


def test_bad_precision_rejected(rng):
    with pytest.raises(ValueError, match="precision"):
        QueryEngine(_poincare_table(rng), ("poincare", 1.0),
                    precision="int2")


def test_serve_cli_accepts_int8(tmp_path, rng):
    """ServeConfig precision=int8 reaches the engine (flag row:
    docs/serving.md)."""
    from hyperspace_tpu.cli.serve import ServeConfig, _build
    from hyperspace_tpu.serve.artifact import export_artifact

    table = _poincare_table(rng)
    art = str(tmp_path / "art")
    export_artifact(art, table, ("poincare", 1.0))
    cfg = ServeConfig(artifact=art, precision="int8")
    engine, batcher = _build(cfg)
    assert engine.precision == "int8"
    ids = rng.integers(0, N, size=4).tolist()
    e32, _ = _build(ServeConfig(artifact=art))
    i8, _ = batcher.topk(ids, 5)
    i32, _ = RequestBatcher(e32).topk(ids, 5)
    assert np.array_equal(np.asarray(i8), np.asarray(i32))
