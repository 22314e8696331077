"""Request batcher: bucket ladder, padding waste accounting, LRU result
cache semantics, and the one-compile-per-bucket contract."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hyperspace_tpu.manifolds import PoincareBall
from hyperspace_tpu.serve.artifact import spec_from_manifold
from hyperspace_tpu.serve.batcher import (RequestBatcher, bucket_for,
                                          bucket_sizes)
from hyperspace_tpu.serve.engine import QueryEngine
from hyperspace_tpu.telemetry import registry as telem


def _engine(rng, n=64, d=4, c=1.0):
    v = jnp.asarray(rng.standard_normal((n, d)) * 0.5, jnp.float32)
    table = np.asarray(PoincareBall(c).expmap0(v))
    return QueryEngine(table, spec_from_manifold(PoincareBall(c)))


def test_bucket_ladder():
    assert bucket_sizes(8, 64) == (8, 16, 32, 64)
    assert bucket_sizes(1, 4) == (1, 2, 4)
    assert bucket_sizes(5, 48) == (8, 16, 32, 48)  # top bucket = max exactly
    assert bucket_for(3, (8, 16)) == 8
    assert bucket_for(9, (8, 16)) == 16
    assert bucket_for(99, (8, 16)) == 16  # callers slab-split first
    with pytest.raises(ValueError):
        bucket_sizes(16, 8)


def test_topk_results_and_padding_counters(rng):
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32)
    reg = telem.default_registry()
    req0, waste0 = reg.get("serve/requests"), reg.get("serve/padded_waste")
    idx, dist = b.topk([3, 1, 4], 5)
    assert idx.shape == (3, 5) and dist.shape == (3, 5)
    # the batcher's padded call returns exactly the engine's rows
    ref_i, ref_d = (np.asarray(a)
                    for a in eng.topk_neighbors(np.asarray([3, 1, 4]), 5))
    assert np.array_equal(idx, ref_i)
    assert np.array_equal(dist, ref_d)
    assert reg.get("serve/requests") == req0 + 1
    assert reg.get("serve/padded_waste") == waste0 + 5  # 3 -> bucket 8


def test_cache_hits_skip_the_engine(rng, monkeypatch):
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32)
    reg = telem.default_registry()
    first_i, first_d = b.topk([0, 1, 2], 4)
    calls = {"n": 0}
    real = eng.topk_neighbors

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(eng, "topk_neighbors", counting)
    hit0, miss0 = reg.get("serve/cache_hit"), reg.get("serve/cache_miss")
    again_i, again_d = b.topk([2, 0, 1], 4)  # same ids, new order
    assert calls["n"] == 0  # all rows served from cache
    assert reg.get("serve/cache_hit") == hit0 + 3
    assert reg.get("serve/cache_miss") == miss0
    assert np.array_equal(again_i[1], first_i[0])  # row for id 0
    # mixed hit/miss: only the cold id computes, rows stay request-ordered
    mix_i, mix_d = b.topk([5, 0], 4)
    assert calls["n"] == 1
    assert np.array_equal(mix_i[1], first_i[0])
    ref_i, _ = (np.asarray(a)
                for a in real(np.asarray([5], np.int32), 4))
    assert np.array_equal(mix_i[0], ref_i[0])


def test_duplicate_cold_ids_compute_once(rng, monkeypatch):
    """A request repeating a COLD id must compute it once and count one
    cache miss — not burn a padded slot (and a counter) per duplicate."""
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32)
    reg = telem.default_registry()
    seen_batches = []
    real = eng.topk_neighbors

    def recording(q_idx, k, **kw):
        seen_batches.append(np.asarray(q_idx))
        return real(q_idx, k, **kw)

    monkeypatch.setattr(eng, "topk_neighbors", recording)
    hit0, miss0 = reg.get("serve/cache_hit"), reg.get("serve/cache_miss")
    idx, _dist = b.topk([7, 7, 9, 7], 3)
    assert idx.shape == (4, 3)
    assert np.array_equal(idx[0], idx[1]) and np.array_equal(idx[0], idx[3])
    # one dispatch, id 7 in exactly one slot of the padded batch's real
    # prefix (the pad repeats the last real id)
    assert len(seen_batches) == 1
    assert (seen_batches[0][:2] == 7).sum() == 1
    assert reg.get("serve/cache_miss") == miss0 + 2  # unique ids: 7, 9
    assert reg.get("serve/cache_hit") == hit0


def test_cache_keys_include_k_and_fingerprint(rng):
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32)
    i4, _ = b.topk([7], 4)
    i2, _ = b.topk([7], 2)  # different k: different cache entry
    assert i2.shape == (1, 2)
    assert np.array_equal(i2[0], i4[0, :2])
    # a different table (fingerprint) must not see this cache's rows
    eng2 = _engine(rng)  # rng advanced -> different table
    assert eng2.fingerprint != eng.fingerprint
    b2 = RequestBatcher(eng2, min_bucket=8, max_bucket=32)
    b2.cache = b.cache  # share the LRU on purpose
    reg = telem.default_registry()
    miss0 = reg.get("serve/cache_miss")
    b2.topk([7], 4)
    assert reg.get("serve/cache_miss") == miss0 + 1


def test_lru_eviction(rng):
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32, cache_size=4)
    b.topk([0, 1, 2, 3], 3)
    b.topk([10], 3)  # evicts the oldest entry (id 0)
    assert len(b.cache) == 4
    reg = telem.default_registry()
    miss0 = reg.get("serve/cache_miss")
    b.topk([0], 3)
    assert reg.get("serve/cache_miss") == miss0 + 1


def test_large_request_slab_split(rng):
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=4, max_bucket=8, cache_size=0)
    ids = list(range(20))  # 8 + 8 + 4-bucket slabs
    idx, dist = b.topk(ids, 3)
    assert idx.shape == (20, 3)
    ref_i, _ = (np.asarray(a)
                for a in eng.topk_neighbors(np.asarray(ids, np.int32), 3))
    assert np.array_equal(idx, ref_i)


def test_id_validation_happens_before_any_cast(rng):
    """Bad ids must fail the request — never silently truncate (floats)
    or wrap (ints past int32) into another node's answer."""
    eng = _engine(rng)  # 64 rows
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32)
    with pytest.raises(ValueError, match="integer"):
        b.topk([1.9], 3)
    with pytest.raises(ValueError, match="out of range"):
        b.topk([2**32], 3)  # would wrap to id 0 through int32
    with pytest.raises(ValueError, match="out of range"):
        b.score([2**32], [1])
    with pytest.raises(ValueError, match="integer"):
        b.score([0.5], [1])
    with pytest.raises(ValueError, match="out of range"):
        b.topk([-1], 3)
    with pytest.raises(ValueError, match="non-empty"):
        b.topk([], 3)
    with pytest.raises(ValueError, match="bool"):
        b.topk([True], 3)  # would index-coerce to node 1
    with pytest.raises(ValueError, match="k must be"):
        b.topk([0], 2.9)  # float k: reject, don't truncate to 2
    with pytest.raises(ValueError, match="k must be"):
        b.topk([0], True)  # bool k: reject, don't coerce to 1


def test_score_bucketed(rng):
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32)
    u, v = [0, 1, 2], [3, 4, 5]
    out = b.score(u, v)
    ref = np.asarray(eng.score_edges(np.asarray(u, np.int32),
                                     np.asarray(v, np.int32)))
    np.testing.assert_array_equal(out, ref.astype(np.float64))


def test_within_bucket_sizes_share_one_compile(rng):
    """THE serving contract: after one warmup per (bucket, k), requests
    of any size inside that bucket trigger zero XLA recompiles (asserted
    via the PR-2 ``jax/recompiles`` monitoring counter)."""
    eng = _engine(rng, n=80)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32, cache_size=0)
    reg = telem.default_registry()
    b.topk([0, 1, 2], 5)  # warmup: compiles the (8, 5) program
    before = reg.get("jax/recompiles")
    b.topk([10, 11], 5)
    b.topk([20, 21, 22, 23, 24], 5)
    b.topk(list(range(30, 38)), 5)  # exactly the bucket size
    assert reg.get("jax/recompiles") == before
    # crossing the bucket boundary MAY compile once; coming back doesn't
    b.topk(list(range(40, 49)), 5)  # bucket 16 warmup
    before = reg.get("jax/recompiles")
    b.topk(list(range(50, 60)), 5)
    assert reg.get("jax/recompiles") == before


def test_request_lifecycle_histograms(rng):
    """Each request observes serve/queue_wait_ms, serve/dispatch_ms and
    serve/e2e_ms with queue_wait ≤ e2e (the enqueue→batch-form stamp is
    inside the enqueue→complete window) and nonzero counts after a warm
    pass; all-cache-hit requests skip the dispatch histogram."""
    eng = _engine(rng)
    b = RequestBatcher(eng, min_bucket=8, max_bucket=32)
    reg = telem.default_registry()
    base = reg.mark()
    b.topk([0, 1, 2], 4)          # cold: one engine dispatch
    b.score([0, 1], [2, 3])       # score path observes too
    snap = reg.snapshot(baseline=base)
    qw, disp, e2e = (snap[f"hist/serve/{n}"]
                     for n in ("queue_wait_ms", "dispatch_ms", "e2e_ms"))
    assert qw["count"] == 2 and e2e["count"] == 2 and disp["count"] == 2
    assert qw["max"] <= e2e["max"]      # batch-form precedes complete
    assert disp["max"] <= e2e["max"]    # dispatch is inside the window
    assert e2e["p50"] is not None and e2e["p99"] is not None
    assert e2e["max"] > 0
    # a fully-cached request observes queue_wait/e2e but NO dispatch
    base = reg.mark()
    b.topk([2, 0, 1], 4)  # same ids → all hits
    snap = reg.snapshot(baseline=base)
    assert snap["hist/serve/e2e_ms"]["count"] == 1
    assert snap["hist/serve/queue_wait_ms"]["count"] == 1
    assert "hist/serve/dispatch_ms" not in snap
    # the stats() surface carries the cumulative e2e summary
    lat = b.stats()["latency_e2e_ms"]
    assert lat["count"] >= 3 and lat["p95"] >= lat["p50"]
