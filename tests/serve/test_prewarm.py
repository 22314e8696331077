"""Serve-startup bucket prewarm (`RequestBatcher.prewarm` — ISSUE 13
pillar 2, docs/serving.md "Warm starts").

The contracts: every ladder executable is compiled exactly once and
BEFORE traffic (zero recompiles on subsequent traffic, idempotent on a
second prewarm), prewarm respects the engine's scan-signature /
precision isolation (a prewarmed engine is warm for exactly what it
serves — a different mode still compiles fresh), the IVF degradation
ladder's narrowed widths are warmed too, and prewarm traffic never
masquerades as served requests."""

import numpy as np
import pytest

from hyperspace_tpu.manifolds import PoincareBall
from hyperspace_tpu.serve.batcher import RequestBatcher
from hyperspace_tpu.serve.engine import QueryEngine
from hyperspace_tpu.telemetry import registry as telem


def _table(n=300, dim=6, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, dim)) * 0.3, jnp.float32)))


def _recompiles():
    return telem.default_registry().get("jax/recompiles")


def test_prewarm_covers_every_bucket_and_traffic_stays_flat():
    eng = QueryEngine(_table(), ("poincare", 1.0))
    bat = RequestBatcher(eng, min_bucket=8, max_bucket=32, cache_size=0)
    info = bat.prewarm([5])
    assert info["buckets"] == [8, 16, 32] and info["ks"] == [5]
    # one executable per (ladder bucket × exclude_self flavor)
    assert info["programs"] == 6
    c0 = _recompiles()
    # traffic landing on EVERY rung, BOTH request flavors: all warm
    for n_ids in (3, 12, 30):
        bat.topk(list(range(n_ids)), 5)
        bat.topk(list(range(n_ids)), 5, exclude_self=False)
    assert _recompiles() == c0, "prewarmed traffic recompiled"


def test_prewarm_idempotent_second_pass_compiles_nothing():
    eng = QueryEngine(_table(seed=1), ("poincare", 1.0))
    bat = RequestBatcher(eng, min_bucket=8, max_bucket=16)
    bat.prewarm([4])
    c0 = _recompiles()
    info = bat.prewarm([4])  # every ladder bucket compiled exactly once
    assert _recompiles() == c0
    assert info["programs"] == 4  # 2 buckets × 2 exclude_self flavors


def test_prewarm_counts_no_requests_or_cache_traffic():
    eng = QueryEngine(_table(seed=2), ("poincare", 1.0))
    bat = RequestBatcher(eng, min_bucket=8, max_bucket=16)
    reg = telem.default_registry()
    base = reg.mark()
    bat.prewarm([3])
    delta = reg.snapshot(baseline=base)
    assert delta.get("serve/prewarmed", 0) == 4
    assert delta.get("serve/prewarm_s", 0) > 0
    for name in ("serve/requests", "serve/cache_hit", "serve/cache_miss",
                 "serve/slots", "serve/padded_waste"):
        assert delta.get(name, 0) == 0, name
    assert "hist/serve/e2e_ms" not in delta
    assert len(bat.cache) == 0  # no LRU writes
    # and stats surfaces the prewarm + compile counters
    s = bat.stats()
    assert s["prewarmed"] >= 4 and "recompiles" in s


def test_prewarm_precision_isolation():
    """A bf16 engine's prewarm warms the bf16 executables — its own
    traffic is flat, while a fresh f32 engine over the SAME table still
    compiles (prewarm never falsely covers another signature)."""
    # a shape no other test in this process compiles: the jit cache is
    # process-wide, so a shared (dim, k) would warm the control for free
    table = _table(n=280, dim=10, seed=3)
    bf = QueryEngine(table, ("poincare", 1.0), precision="bf16")
    bat_bf = RequestBatcher(bf, min_bucket=8, max_bucket=8, cache_size=0)
    bat_bf.prewarm([9])
    c0 = _recompiles()
    bat_bf.topk([0, 1, 2], 9)
    assert _recompiles() == c0, "bf16 prewarm did not cover bf16 traffic"
    f32 = QueryEngine(table, ("poincare", 1.0))
    bat_f32 = RequestBatcher(f32, min_bucket=8, max_bucket=8,
                             cache_size=0)
    bat_f32.topk([0, 1, 2], 9)
    assert _recompiles() > c0, (
        "an unprewarmed f32 engine answered with no compile — the "
        "isolation assertion proves nothing")


def test_prewarm_scan_mode_isolation():
    """Same for scan signatures: a two_stage prewarm leaves a carry
    engine cold (distinct executables; the batcher cache key already
    keeps their ROWS apart, prewarm keeps their warmth apart)."""
    table = _table(seed=4)
    two = QueryEngine(table, ("poincare", 1.0), scan_mode="two_stage")
    RequestBatcher(two, min_bucket=8, max_bucket=8).prewarm([4])
    c0 = _recompiles()
    carry = QueryEngine(table, ("poincare", 1.0), scan_mode="carry")
    RequestBatcher(carry, min_bucket=8, max_bucket=8,
                   cache_size=0).topk([0, 1], 4)
    assert _recompiles() > c0


def test_prewarm_ivf_ladder_widths_all_warm():
    """A probing engine with overload machinery warms the degradation
    ladder's narrowed nprobe widths too — stepping down under pressure
    must not hand the compiler a fresh program."""
    import jax.numpy as jnp

    from hyperspace_tpu.serve.index import IVF_MIN_TABLE_ROWS, build_index

    rng = np.random.default_rng(5)
    n = max(IVF_MIN_TABLE_ROWS, 2048)
    table = np.asarray(PoincareBall(1.0).expmap0(
        jnp.asarray(rng.standard_normal((n, 6)) * 0.3, jnp.float32)))
    idx = build_index(table, ("poincare", 1.0), 16, iters=2, seed=0,
                      balance=3.0)
    eng = QueryEngine(table, ("poincare", 1.0), index=idx, nprobe=8)
    bat = RequestBatcher(eng, min_bucket=8, max_bucket=8, cache_size=0,
                         queue_max=4)
    widths = [m for m in bat._modes if isinstance(m, int)]
    assert widths, "ladder has no narrowed widths to prove anything"
    bat.prewarm([4])
    c0 = _recompiles()
    ids = list(range(8))
    eng.topk_neighbors(np.asarray(ids, np.int32), 4)  # full width
    for p in widths:  # every ladder override the batcher can serve
        eng.topk_neighbors(np.asarray(ids, np.int32), 4, nprobe=p)
    assert _recompiles() == c0, "a ladder width was left cold"


def test_prewarm_validates_k():
    eng = QueryEngine(_table(n=50, seed=6), ("poincare", 1.0))
    bat = RequestBatcher(eng, min_bucket=8, max_bucket=8)
    with pytest.raises(ValueError, match="out of range"):
        bat.prewarm([50])  # k == N with exclude_self: one too many
    with pytest.raises(ValueError, match="out of range"):
        bat.prewarm([0])


def test_prewarm_cli_flag_parsing():
    from hyperspace_tpu.cli.serve import ServeConfig, _prewarm_ks

    assert _prewarm_ks(ServeConfig()) == []
    assert _prewarm_ks(ServeConfig(prewarm="1", k=7)) == [7]
    assert _prewarm_ks(ServeConfig(prewarm="true", k=3)) == [3]
    assert _prewarm_ks(ServeConfig(prewarm="5,10")) == [5, 10]
    with pytest.raises(SystemExit):
        _prewarm_ks(ServeConfig(prewarm="abc"))
    with pytest.raises(SystemExit):
        _prewarm_ks(ServeConfig(prewarm="0,-3"))
