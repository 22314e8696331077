"""Fused streaming scan-top-k: hyperbolic k-NN without a distance matrix.

The serve hot path is HBM-bandwidth-bound, not FLOPs-bound: the
two-stage engine scan (serve/engine.py) materializes a [B, chunk]
distance tile per step, runs ``lax.top_k`` on it, and merges the stacked
candidates after the scan — every distance is written to and re-read
from memory at least once.  This kernel applies flash-attention's trick
(kernels/attention.py: the online-softmax recurrence keeps running state
in VMEM) to distance-scan-top-k:

- **Grid** ``(query blocks, table tiles)``, table tiles innermost and
  sequential.  Each step streams one ``[bm, dp]`` table tile through
  VMEM, computes the ``[bq, bm]`` distance tile **in-register** via the
  einsum-Gram closed forms (the same math as ``kernels/distmat.py``:
  one MXU matmul + cheap elementwise work; poincare / lorentz /
  euclidean), and folds it into the carry.
- **Carry** = the running per-row top-k: ``cd [bq, K]`` f32 distances
  (ascending, +inf beyond the live entries) and ``ci [bq, K]`` int32
  *global* column ids (−1 on empty slots), ``K = round_up(k, 128)``
  lanes, held in VMEM scratch for the whole tile walk.  The ``[B, N]``
  distance matrix is never written to HBM and the per-chunk
  ``lax.top_k`` + post-scan merge of the two-stage path disappear; HBM
  traffic is one table read plus ``2·B·K`` result bytes.
- **Merge** = ``k`` min-extract passes over the concatenated
  ``[bq, K + bm]`` candidate row (select row-min, pick its lowest
  column on ties, retire it to +inf) — pure VPU work, exact (extracted
  values are copies, never re-derived arithmetic), and deterministic:
  ties resolve to the lowest combined column, which is global-column
  order (carry entries come from earlier tiles).  A slot whose
  extracted distance is +inf gets id −1 (narrow shards / k > reachable
  candidates surface ``(+inf, −1)``, never a wrong row).
- **Threshold prune** (the two-stage fast path, kept): a tile whose
  per-row minimum meets the carried k-th distance on EVERY row cannot
  change the result — the merge is skipped outright.
- **Masking by index**: global column ids start at ``col0`` (shard-local
  offsets — ``_topk_sharded`` composes); rows at global index >= ``n``
  (engine zero-padding) or local index >= the slab's true rows (kernel
  tile padding) are +inf, as is each query's own row under
  ``exclude_self``.
- **bf16 tables** stream at half the HBM bytes; tiles are cast to f32
  in-register, so the scan's *arithmetic* is f32 either way (the
  low-precision cost is the table quantization only — the engine's
  f32 rescore repairs k-th-boundary near-ties, docs/precision.md).
- **int8 tables** (``scale=``; serve/quant.py) stream at a QUARTER of
  the f32 bytes: the slab is the per-row symmetric int8 code and the
  companion per-row f32 scale rides beside it as one extra streamed
  block per tile ([bm, 1] lanes against the [bm, dp] rows).  Tiles
  dequantize in-register (``rows.astype(f32) * scale``) before the
  identical distance math — same f32 arithmetic, same carry, same twin
  contract; only the table bytes shrink.  ``scale=None`` (default) is
  byte-for-byte the pre-int8 program.
- **int4 tables** (``packed=True`` + ``scale=``) stream at an EIGHTH:
  the slab is the planar two-nibble packing of ``serve/quant.py``
  (byte column j = element j low nibble, element hw+j high nibble,
  hw = ceil(D/2)), and the in-register unpack is two shifts, a
  sign-extend and a lane concatenate — element 0 stays in lane 0, so
  the Lorentz time flip and every Gram closed form run unchanged on
  the ``[bm, 2*hwp]`` unpacked tile.  Queries are re-laid to the same
  split-lane layout by :func:`int4_query_layout` (zero lanes between
  the halves are exact no-ops — sums of products).
- **PQ tables** (:func:`scan_topk_pq`) replace the Gram matmul with
  ADC: the slab is one uint8 centroid code per subspace ([M, m]), the
  per-query input is a lookup table of subspace partial sums
  (:func:`pq_lut`), and the tile math is a one-hot matmul
  ``dotT(lut, onehot(codes))`` whose row sums ARE the Lorentz inner
  product (hyperbolic lanes) or the squared distance (euclidean) of
  the RECONSTRUCTED rows — one arcosh/sqrt at the end, same carry,
  same twin contract.
- **Explicit double-buffered DMA pipeline** (ISSUE 16): the slab-side
  variants keep the grid over query blocks only and walk the table
  tiles in-kernel — two VMEM tile slots, the async HBM→VMEM copy of
  tile i+1 issued BEFORE tile i's Gram/fold math, one DMA semaphore
  per slot (the slab and its scale/code companions live in
  ``pl.ANY`` memory space).  The tile ORDER and math are exactly
  the implicit-grid schedule's, so the twin (and results) are
  unchanged; only the copy/compute overlap is now explicit.  The
  candidate variant keeps the implicit grid pipeline (its stream is a
  pre-gathered per-query block, already double-buffered by Pallas).

**Twin contract** (the ``kernels/distmat.py`` convention, tightened):
the XLA twin is not merely value-close — it executes the *same padded
block schedule and op sequence* (`_slab_tile` / `_cand_tile` / `_fold`
are shared functions over identically shaped blocks), so on CPU the
twin matches the Pallas kernel under the interpreter **bitwise**
(tested).  Gradients are not defined: top-k ids are integer outputs;
callers (negative mining) wrap inputs in ``stop_gradient``.

**Capability fallback**: product manifolds, ``k > FUSED_MAX_K`` or
``dim > FUSED_MAX_DIM`` are not supported — callers gate on
:func:`supports` / :func:`supports_cand` and keep the two-stage path,
bit-identical to today's default (serve/engine.py ``scan_mode="fused"``
does exactly that).

Two entry points (docs/kernels.md):

- :func:`scan_topk` — shared-slab scan: the engine's exact k-NN walk,
  the IVF builder's nearest-centroid assignment at ``k=1``
  (serve/index.py), sampled hard-negative mining
  (models/poincare_embed.py ``neg_mode="mined"``);
- :func:`scan_topk_cand` — per-query candidate rows (the IVF probing
  scorer: each query scores its OWN gathered cells' rows; grid
  ``(query blocks of 8, candidate tiles)`` with ``[8, bm, dp]`` row
  blocks and the identical carry/merge machinery).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperspace_tpu.kernels import _support as S

# carry lanes cap: k beyond this falls back to the two-stage scan (the
# merge cost is k passes over K+bm lanes — linear in k)
FUSED_MAX_K = 256
# feature-lane cap: a [bq, dp] query block past this blows the VMEM
# schedule below
FUSED_MAX_DIM = 1024
# per-query candidate variant: cap on the pre-gathered [B, C, dp] f32
# bytes (the gather IS the input stream; a runaway probe capacity must
# fall back rather than allocate).  Judged at a NOMINAL batch — the
# fused-vs-fallback decision must be a function of the ENGINE
# configuration only, never of a request's bucket: the batcher cache
# key carries the engine's scan signature, so the same query must
# always answer through the same path whatever batch it rode in on
CAND_GATHER_BUDGET = 256 * 1024 * 1024
NOMINAL_CAND_BATCH = 1024  # the batcher's default max bucket
# PQ subspace cap: the per-query LUT block is [bq, m*256] f32 — past
# this m it stops fitting the VMEM schedule
FUSED_MAX_PQ_M = 8

_KINDS = ("poincare", "lorentz", "euclidean")
_SLAB_BQ = 256   # query rows per block (slab variant)
_CAND_BQ = 8     # query rows per block (candidate variant: [8, bm, dp])


def kind_supported(spec: tuple) -> bool:
    """Manifold families with an in-kernel closed distance form."""
    return spec[0] in _KINDS


def supports(spec: tuple, *, k: int, dim: int) -> bool:
    """Can :func:`scan_topk` serve this (spec, k, dim)?  Callers gate on
    this and fall back to the two-stage scan (bit-identical) when False."""
    return (kind_supported(spec) and 1 <= int(k) <= FUSED_MAX_K
            and int(dim) <= FUSED_MAX_DIM)


def supports_pq(spec: tuple, *, k: int, m: int) -> bool:
    """Can :func:`scan_topk_pq` serve this (spec, k, m)?  Callers gate
    on this and fall back to the two-stage decode-and-scan (the engine's
    PQ path) when False — product specs always fall back (their distance
    is not additive across a uniform subspace grid)."""
    return (kind_supported(spec) and 1 <= int(k) <= FUSED_MAX_K
            and 1 <= int(m) <= FUSED_MAX_PQ_M)


def supports_cand(spec: tuple, *, k: int, dim: int, cand: int) -> bool:
    """Can :func:`scan_topk_cand` serve this shape?  Adds the gathered
    candidate-row footprint cap to the :func:`supports` rules — judged
    at ``NOMINAL_CAND_BATCH`` rows, NOT the actual batch, so the
    decision is a function of (spec, k, dim, capacity) alone and a
    given engine serves every bucket through the same path (the cache
    signature's ``"fused"`` marker depends on it)."""
    if not supports(spec, k=k, dim=dim):
        return False
    dp = S.round_up(int(dim), 128)
    return (NOMINAL_CAND_BATCH * S.round_up(int(cand), 128) * dp * 4
            <= CAND_GATHER_BUDGET)


def fused_tile_rows(dim: int, dtype, k: int, *,
                    tile_budget: int = S.VMEM_BUDGET,
                    bq: int = _SLAB_BQ, allow_tuned: bool = True,
                    lane: str = "dense", pq_m: int = 0) -> int:
    """Table-tile rows for the slab kernel.

    A **tuned entry** for this (dim, dtype, k) on the current device
    kind wins when one exists (``kernels/autotune.py`` — the empirical
    table ``scripts/autotune_scan_topk.py`` persists; consulted only at
    the default budget/bq, since a caller passing its own budget is
    asking the model a question the table never measured).  Otherwise
    the static dim × dtype × k VMEM-footprint model below (NOT a
    fixed-byte distance-tile budget: the fused working set is the
    double-buffered table tile + the query block + the carry + the
    merge temporaries) — deterministic and pinned by tests.  Tile
    choice is result-invisible either way (the merge extracts exact
    copies with global-column tie-breaks — tested bitwise across
    tiles), so a missing/stale table costs only speed.  A tuned entry
    is CLAMPED to the static model's answer: the model is the VMEM-fit
    bound a real chip's Mosaic enforces, so a stale table (tuned under
    a looser footprint) can never hand the kernel a tile that only the
    CPU twin would accept.  The engine's ``auto_chunk_rows`` delegates
    here for ``scan_mode="fused"``.

    ``lane`` extends the model to the packed lanes (ISSUE 16) without
    touching the dense answers: ``"int4"`` counts the half-width packed
    byte tile PLUS its full-width f32 unpack temporary and the scale
    block; ``"pq"`` (with ``pq_m`` subspaces) counts the [bm, 128] code
    tile, the per-query [bq, m*256] LUT block and the one-hot matmul
    temporaries.  Packed lanes never consult the tuned table (its keys
    are element dtypes; the static model is the only authority)."""
    tuned = None
    if (lane == "dense" and allow_tuned and tile_budget == S.VMEM_BUDGET
            and bq == _SLAB_BQ):
        from hyperspace_tpu.kernels import autotune

        tuned = autotune.lookup("slab", dim, dtype, k)
    dp = S.round_up(int(dim), 128)
    kp = S.round_up(int(k), 128)
    dt = jnp.dtype(dtype)
    it = dt.itemsize
    # int8 slabs stream a companion [bm, 128] f32 per-row-scale block
    # per tile (double-buffered like the slab) — at dim <= 128 that is
    # 4× the int8 tile bytes, so the fit model MUST count it: this
    # model is the VMEM bound the engine's fused demotion check and
    # the autotune clamp both trust
    scale_bytes = (2 * 128 * 4) if dt.kind == "i" else 0

    def footprint(bm: int) -> int:
        if lane == "int4":
            wp = S.round_up((int(dim) + 1) // 2, 128)  # packed byte lanes
            return (2 * bm * wp               # double-buffered packed tile
                    + 2 * bm * 128 * 4        # streamed f32 scale block
                    + bm * 2 * wp * 4         # unpacked f32 tile temporary
                    + bq * 2 * wp * 4         # query block (split-lane)
                    + bq * 128 * 4
                    + 2 * bq * kp * 4
                    + 3 * bq * (kp + bm) * 4)
        if lane == "pq":
            mlut = max(int(pq_m), 1) * 256
            return (2 * bm * 128              # double-buffered code tile
                    + bq * mlut * 4           # per-query LUT block
                    + 2 * bm * mlut * 4       # one-hot + compare temporaries
                    + bq * 128 * 4
                    + 2 * bq * kp * 4
                    + 3 * bq * (kp + bm) * 4)
        return (2 * bm * dp * it          # double-buffered table tile
                + bm * scale_bytes        # int8: streamed scale block
                + bq * dp * 4             # query block (f32 compute copy)
                + bq * 128 * 4            # q_idx block
                + 2 * bq * kp * 4         # carry scratch (dists + ids)
                + 3 * bq * (kp + bm) * 4)  # merge concat temporaries

    bm = 1024
    while bm > 128 and footprint(bm) > tile_budget:
        bm //= 2
    return bm if tuned is None else min(tuned, bm)


def fused_cand_tile_rows(dim: int, dtype, k: int, *,
                         tile_budget: int = S.VMEM_BUDGET,
                         bq: int = _CAND_BQ,
                         allow_tuned: bool = True) -> int:
    """Candidate-tile rows for the per-query variant: the row block is
    3-D ``[bq, bm, dp]`` so the footprint scales with bq × bm × dp.
    Tuned-table consultation, static-model clamp and fallback exactly
    as :func:`fused_tile_rows` (variant ``"cand"``)."""
    tuned = None
    if allow_tuned and tile_budget == S.VMEM_BUDGET and bq == _CAND_BQ:
        from hyperspace_tpu.kernels import autotune

        tuned = autotune.lookup("cand", dim, dtype, k)
    dp = S.round_up(int(dim), 128)
    kp = S.round_up(int(k), 128)
    dt = jnp.dtype(dtype)
    it = dt.itemsize
    # int8 candidates gather a [bq, bm] f32 scale block per tile
    # (double-buffered) — counted for the same reason as the slab model
    scale_bytes = (2 * 4) if dt.kind == "i" else 0

    def footprint(bm: int) -> int:
        return (2 * bq * bm * dp * it     # double-buffered row block
                + bq * bm * scale_bytes   # int8: gathered scale block
                + bq * bm * dp * 4        # f32 compute copy
                + bq * dp * 4 + bq * 128 * 4
                + 2 * bq * kp * 4         # carry scratch
                + 3 * bq * (kp + bm) * 4  # merge temporaries
                + 2 * bq * bm * 4)        # distance + id tiles

    bm = 1024
    while bm > 128 and footprint(bm) > tile_budget:
        bm //= 2
    return bm if tuned is None else min(tuned, bm)


# --- shared tile math (kernel body AND twin run exactly this) -----------------


def _pair_dist(kind: str, c, q: jax.Array, rows: jax.Array) -> jax.Array:
    """[r, dp] × [m, dp] → [r, m] distances, f32, closed forms (same
    clamping policy as the kernels/distmat.py bodies; zero-padded
    feature lanes are exact no-ops — sums of products)."""
    if kind == "lorentz":
        lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, dimension=1)
        y_flip = jnp.where(lane == 0, -rows, rows)
        gram = S.dotT(q, y_flip)                         # ⟨q, y⟩_L
        u = jnp.maximum(-c * gram - 1.0, 0.0)
        return S.karcosh1p(u) / jnp.maximum(S.ksafe_sqrt(c),
                                            S.MIN_NORM_F32)
    gram = S.dotT(q, rows)
    xx = S.ksq_norm(q)                                   # [r, 1]
    yy = S.ksq_norm(rows)                                # [m, 1]
    ones = jnp.ones_like(xx)
    yy_t = S.dotT(ones, yy)                              # [r, m] rank-1
    d2 = jnp.maximum(xx - 2.0 * gram + yy_t, 0.0)
    if kind == "euclidean":
        return S.ksafe_sqrt(d2)
    den = S.dotT(1.0 - c * xx, 1.0 - c * yy)
    u = 2.0 * c * d2 / jnp.maximum(den, S.EPS_F32)
    return S.karcosh1p(u) / jnp.maximum(S.ksafe_sqrt(c), S.MIN_NORM_F32)


def _pair_dist_b(kind: str, c, q: jax.Array, rows: jax.Array) -> jax.Array:
    """Batched per-query form: [r, dp] × [r, m, dp] → [r, m] (the IVF
    candidate variant — rows differ per query, so the Gram is an
    elementwise-multiply-and-lane-reduce, not a shared matmul)."""
    if kind == "lorentz":
        lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, dimension=2)
        y_flip = jnp.where(lane == 0, -rows, rows)
        gram = jnp.sum(q[:, None, :] * y_flip, axis=-1)  # [r, m]
        u = jnp.maximum(-c * gram - 1.0, 0.0)
        return S.karcosh1p(u) / jnp.maximum(S.ksafe_sqrt(c),
                                            S.MIN_NORM_F32)
    gram = jnp.sum(q[:, None, :] * rows, axis=-1)        # [r, m]
    xx = jnp.sum(q * q, axis=-1, keepdims=True)          # [r, 1]
    yy = jnp.sum(rows * rows, axis=-1)                   # [r, m]
    d2 = jnp.maximum(xx - 2.0 * gram + yy, 0.0)
    if kind == "euclidean":
        return S.ksafe_sqrt(d2)
    den = jnp.maximum((1.0 - c * xx) * (1.0 - c * yy), S.EPS_F32)
    u = 2.0 * c * d2 / den
    return S.karcosh1p(u) / jnp.maximum(S.ksafe_sqrt(c), S.MIN_NORM_F32)


def _unpack_int4_tile(raw: jax.Array) -> jax.Array:
    """Shared in-register int4 unpack (kernel body AND twin): a packed
    [r, wp] uint8 tile → f32 [r, 2*wp] codes in the planar split-lane
    layout (low nibbles first, sign-extended two's complement).  Zero
    pad bytes unpack to zero codes — exact no-ops downstream."""
    t = raw.astype(jnp.int32)
    lo = t & 15
    hi = t >> 4
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)


def int4_query_layout(q: jax.Array, dim: int) -> jax.Array:
    """Re-lay f32 queries [B, dim] to the unpacked int4 tile's
    split-lane layout [B, 2*wp] (``wp = round_up(ceil(dim/2), 128)``):
    elements 0..hw-1 in lanes 0.., elements hw..dim-1 starting at lane
    wp.  The zero lanes between the halves match the tile's unpacked
    pad bytes, so every Gram closed form is exact; element 0 stays in
    lane 0 (the Lorentz time flip).  Shared by the launcher and the
    twin — ONE layout recipe."""
    b = q.shape[0]
    hw = (int(dim) + 1) // 2
    wp = S.round_up(hw, 128)
    out = jnp.zeros((b, 2 * wp), jnp.float32)
    out = out.at[:, :hw].set(q[:, :hw].astype(jnp.float32))
    out = out.at[:, wp:wp + (dim - hw)].set(
        q[:, hw:dim].astype(jnp.float32))
    return out


def pq_lut(q_lift: jax.Array, codebooks: jax.Array, *,
           kind: str) -> jax.Array:
    """Per-query ADC lookup table [B, m*256] f32 from LIFTED queries
    [B, >=m*ds] and codebooks [m, 256, ds] (serve/quant.py).

    For the lorentz-gram families the scan distance depends on a
    candidate row only through ``⟨q_L, y_L⟩_L``, which is additive over
    subspaces once the GLOBAL time lane's sign is folded into the query
    — so ``LUT[b, s*256+j] = <q_s ⊙ flip_s, cb[s, j]>`` and the tile's
    row sum IS the Lorentz inner product of q with the reconstruction.
    For euclidean, ``LUT[b, s*256+j] = ‖q_s − cb[s, j]‖²`` and the row
    sum is the squared distance.  :func:`_pq_dist_from_sum` applies the
    one closing transform."""
    m, ncent, ds = codebooks.shape
    b = q_lift.shape[0]
    if q_lift.shape[1] < m * ds:
        # the codebooks' pad lanes are exactly zero (trained on
        # zero-padded lifts), so zero query pad lanes are exact no-ops
        q_lift = jnp.concatenate(
            [q_lift, jnp.zeros((b, m * ds - q_lift.shape[1]),
                               q_lift.dtype)], axis=1)
    qs = q_lift[:, :m * ds].reshape(b, m, ds).astype(jnp.float32)
    cb = codebooks.astype(jnp.float32)
    if kind == "euclidean":
        diff = qs[:, :, None, :] - cb[None]              # [B, m, 256, ds]
        lut = jnp.sum(diff * diff, axis=-1)
    else:
        # global lane 0 = the lift's time coordinate = subspace 0 lane 0
        sign = jnp.ones((m, ds), jnp.float32).at[0, 0].set(-1.0)
        lut = jnp.einsum("bmd,mjd->bmj", qs * sign[None], cb,
                         precision=jax.lax.Precision.HIGHEST)
    return lut.reshape(b, m * ncent)


def _pq_dist_from_sum(kind: str, c, ssum: jax.Array) -> jax.Array:
    """Close the ADC partial sums into distances (same clamping policy
    as :func:`_pair_dist`, applied to the RECONSTRUCTED rows)."""
    if kind == "euclidean":
        return S.ksafe_sqrt(ssum)
    u = jnp.maximum(-c * ssum - 1.0, 0.0)
    return S.karcosh1p(u) / jnp.maximum(S.ksafe_sqrt(c), S.MIN_NORM_F32)


def _pq_tile(kind: str, exclude_self: bool, c, n, nloc, col0, loc_base,
             m: int, lut: jax.Array, qi: jax.Array, codes: jax.Array):
    """One PQ slab tile → masked distances + global column ids, the
    ``_slab_tile`` contract via ADC: ``codes`` [r, 128] int32 (the
    uint8 code tile widened; lanes >= m are pad), ``lut`` [bq, m*256].
    The per-subspace one-hot matmul sums LUT entries row-wise — MXU
    work in the kernel, the identical dot in the twin (bitwise: 0/1
    weights select exact copies)."""
    parts = []
    for s in range(m):
        cent = jax.lax.broadcasted_iota(
            jnp.int32, (codes.shape[0], 256), dimension=1)
        parts.append((codes[:, s:s + 1] == cent).astype(jnp.float32))
    oh = jnp.concatenate(parts, axis=-1)                 # [r, m*256]
    ssum = S.dotT(lut, oh)                               # [bq, r]
    d = _pq_dist_from_sum(kind, c, ssum)
    lcol = jax.lax.broadcasted_iota(jnp.int32, d.shape, dimension=1)
    loc = loc_base + lcol
    gcol = (col0 + loc).astype(jnp.int32)
    mask = (loc >= nloc) | (gcol >= n)
    if exclude_self:
        mask = mask | (gcol == qi)
    return jnp.where(mask, jnp.inf, d), gcol


def _slab_tile(kind: str, exclude_self: bool, c, n, nloc, col0, loc_base,
               q: jax.Array, qi: jax.Array, rows: jax.Array):
    """One slab tile → (d [r, m] with masked slots +inf, global column
    ids [r, m] int32).  ``loc_base`` = tile offset within the slab (may
    be traced); ``n`` global valid rows; ``nloc`` the slab's true local
    rows (kernel padding beyond it must not alias the next shard's
    columns); ``qi`` [r, 1] query row ids for ``exclude_self``."""
    d = _pair_dist(kind, c, q, rows)
    lcol = jax.lax.broadcasted_iota(jnp.int32, d.shape, dimension=1)
    loc = loc_base + lcol
    gcol = (col0 + loc).astype(jnp.int32)
    mask = (loc >= nloc) | (gcol >= n)
    if exclude_self:
        mask = mask | (gcol == qi)
    return jnp.where(mask, jnp.inf, d), gcol


def _cand_tile(kind: str, exclude_self: bool, c, q: jax.Array,
               qi: jax.Array, rows: jax.Array, ids: jax.Array):
    """One candidate tile: ``ids`` [r, m] int32 (−1 = padding) carry the
    validity; masked slots are +inf."""
    d = _pair_dist_b(kind, c, q, rows)
    mask = ids < 0
    if exclude_self:
        mask = mask | (ids == qi)
    return jnp.where(mask, jnp.inf, d), ids


def _merge(cd: jax.Array, ci: jax.Array, d: jax.Array, ids: jax.Array,
           k: int):
    """Fold a masked tile into the carry: k min-extract passes over the
    concatenated [r, K+m] row (module docstring "Merge")."""
    cat_d = jnp.concatenate([cd, d], axis=1)             # [r, K+m]
    cat_i = jnp.concatenate([ci, ids], axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, cat_d.shape, dimension=1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, cd.shape, dimension=1)
    big = cat_d.shape[1]

    def body(j, carry):
        rem, ncd, nci = carry
        m = jnp.min(rem, axis=1, keepdims=True)          # [r, 1]
        a = jnp.min(jnp.where(rem == m, cols, big), axis=1, keepdims=True)
        sel = cols == a
        idv = jnp.max(jnp.where(sel, cat_i, -1), axis=1, keepdims=True)
        idv = jnp.where(jnp.isinf(m), -1, idv)
        ncd = jnp.where(kcols == j, m, ncd)
        nci = jnp.where(kcols == j, idv, nci)
        return jnp.where(sel, jnp.inf, rem), ncd, nci

    _, ncd, nci = jax.lax.fori_loop(
        0, k, body, (cat_d, jnp.full_like(cd, jnp.inf),
                     jnp.full_like(ci, -1)))
    return ncd, nci


def _prune(cd: jax.Array, d: jax.Array, k: int):
    """True when NO row of the tile can improve the carried top-k (the
    two-stage threshold-prune condition, applied to the exact carry —
    ``cd[:, k-1]`` IS the running k-th distance, not an upper bound)."""
    kth = cd[:, k - 1:k]
    return jnp.all(jnp.min(d, axis=1, keepdims=True) >= kth)


def _fold(cd, ci, d, ids, k):
    """Prune-or-merge as a pure function (the twin's step; the kernel
    body expresses the same fold with ``pl.when`` over scratch)."""
    return jax.lax.cond(
        _prune(cd, d, k),
        lambda args: (args[0], args[1]),
        lambda args: _merge(*args, k=k),
        (cd, ci, d, ids))


# --- slab variant -------------------------------------------------------------


def _slab_schedule(b: int, dim: int, k: int, tile_rows: int):
    bq = min(S.round_up(max(b, 1), 8), _SLAB_BQ)
    dp = S.round_up(dim, 128)
    kp = S.round_up(k, 128)
    bm = int(tile_rows)
    if bm <= 0 or bm % 128:
        raise ValueError(f"tile_rows must be a positive multiple of 128; "
                         f"got {tile_rows}")
    return bq, dp, kp, bm


def _slab_pad(slab, q, q_idx, bq, bm):
    """The ONE padding recipe both implementations consume: zero lanes/
    rows on the slab and query block, q_idx broadcast to a 128-lane
    int32 block (row ids < 0 on padded query rows so ``exclude_self``
    can never fire on them)."""
    yp = S.pad_rows_lanes(slab, rows_to=bm)
    qp = S.pad_rows_lanes(q, rows_to=bq)
    qip = jnp.broadcast_to(
        jnp.asarray(q_idx, jnp.int32)[:, None], (q.shape[0], 128))
    pad = qp.shape[0] - qip.shape[0]
    if pad:
        qip = jnp.concatenate(
            [qip, jnp.full((pad, 128), -1, jnp.int32)], axis=0)
    return yp, qp, qip


def _scale_pad(scale, bm):
    """Shared per-row-scale padding (int8 slabs): [M] / [M, 1] f32 →
    a [mp, 128] lane-aligned block, rows zero-padded to the tile grid
    (a zero scale dequantizes padding rows to zero — masked anyway,
    identically in kernel and twin)."""
    s = jnp.asarray(scale, jnp.float32)
    if s.ndim == 1:
        s = s[:, None]
    if s.ndim != 2 or s.shape[1] != 1:
        raise ValueError(f"scale must be [M] or [M, 1]; got {s.shape}")
    return S.pad_rows_lanes(s, rows_to=bm)


def _tile_rows_f32(lane: str, raw: jax.Array, sblk) -> jax.Array:
    """The ONE tile-dequantize recipe (kernel body AND twin consume it
    on identically shaped blocks): dense/bf16 tiles cast to f32, scaled
    lanes multiply the per-row scale in-register, int4 tiles unpack
    first (serve/quant.py's planar layout)."""
    if lane == "int4":
        return _unpack_int4_tile(raw) * sblk[:, :1]
    rows = raw.astype(jnp.float32)
    if lane == "int8":
        rows = rows * sblk[:, :1]
    return rows


def _slab_body(kind: str, k: int, bm: int, ntiles: int, exclude_self: bool,
               lane: str = "dense"):
    """The double-buffered slab kernel body (module docstring "Explicit
    double-buffered DMA pipeline"): grid over query blocks only, table
    tiles walked in-kernel — tile i+1's HBM→VMEM copy starts before
    tile i's distance/fold math, alternating two VMEM slots."""
    quant = lane in ("int8", "int4")

    def body(c_ref, col0_ref, n_ref, nloc_ref, q_ref, qi_ref, y_hbm,
             *rest):
        if quant:  # scaled slab: the per-row scale rides beside it
            s_hbm = rest[0]
            rest = rest[1:]
        od_ref, oi_ref = rest[:2]
        if quant:
            cd_scr, ci_scr, ybuf, ysem, sbuf, ssem = rest[2:]
        else:
            cd_scr, ci_scr, ybuf, ysem = rest[2:]
        cd_scr[:] = jnp.full_like(cd_scr, jnp.inf)
        ci_scr[:] = jnp.full_like(ci_scr, -1)
        c = c_ref[0, 0]
        col0 = col0_ref[0, 0]
        n = n_ref[0, 0]
        nloc = nloc_ref[0, 0]
        q = q_ref[:].astype(jnp.float32)
        qi = qi_ref[:, :1]

        def copy_y(slot, i):
            return pltpu.make_async_copy(
                y_hbm.at[pl.ds(i * bm, bm), :], ybuf.at[slot],
                ysem.at[slot])

        def copy_s(slot, i):
            return pltpu.make_async_copy(
                s_hbm.at[pl.ds(i * bm, bm), :], sbuf.at[slot],
                ssem.at[slot])

        copy_y(0, 0).start()
        if quant:
            copy_s(0, 0).start()

        def tile(jt, _):
            slot = jax.lax.rem(jt, 2)

            @pl.when(jt + 1 < ntiles)
            def _prefetch():
                nxt = jax.lax.rem(jt + 1, 2)
                copy_y(nxt, jt + 1).start()
                if quant:
                    copy_s(nxt, jt + 1).start()

            copy_y(slot, jt).wait()
            sblk = None
            if quant:
                copy_s(slot, jt).wait()
                sblk = sbuf[slot]
            rows = _tile_rows_f32(lane, ybuf[slot], sblk)
            d, gids = _slab_tile(kind, exclude_self, c, n, nloc, col0,
                                 jt * bm, q, qi, rows)
            skip = _prune(cd_scr[:], d, k)

            @pl.when(jnp.logical_not(skip))
            def _merge_tile():
                ncd, nci = _merge(cd_scr[:], ci_scr[:], d, gids, k)
                cd_scr[:] = ncd
                ci_scr[:] = nci

            return 0

        jax.lax.fori_loop(0, ntiles, tile, 0)
        od_ref[:] = cd_scr[:]
        oi_ref[:] = ci_scr[:]

    return body


def _launch_slab(slab, q, q_idx, col0, *, kind, c, k, n, bm, exclude_self,
                 mode_, scale=None, lane="dense"):
    b = q.shape[0]
    bq, dp, kp, bm = _slab_schedule(b, q.shape[1], k, bm)
    nloc = slab.shape[0]
    yp, qp, qip = _slab_pad(slab, q, q_idx, bq, bm)
    bp, mp_ = qp.shape[0], yp.shape[0]
    ntiles = mp_ // bm
    wp = yp.shape[1]  # packed byte lanes (int4) or dp
    grid = (bp // bq,)
    smem = lambda: pl.BlockSpec((1, 1), lambda iq: (0, 0),
                                memory_space=pltpu.SMEM)
    i32 = lambda v: jnp.asarray(v, jnp.int32).reshape(1, 1)
    in_specs = [
        smem(), smem(), smem(), smem(),
        pl.BlockSpec((bq, dp), lambda iq: (iq, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bq, 128), lambda iq: (iq, 0),
                     memory_space=pltpu.VMEM),
        # the slab stays in HBM: the body's DMA pipeline streams it
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [S.c_smem(c), i32(col0), i32(n), i32(nloc), qp, qip, yp]
    scratch = [
        pltpu.VMEM((bq, kp), jnp.float32),
        pltpu.VMEM((bq, kp), jnp.int32),
        # two tile slots + one DMA semaphore per slot
        pltpu.VMEM((2, bm, wp), yp.dtype),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if scale is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(_scale_pad(scale, bm))
        scratch += [pltpu.VMEM((2, bm, 128), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]
    od, oi = pl.pallas_call(
        _slab_body(kind, k, bm, ntiles, exclude_self, lane=lane),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, kp), lambda iq: (iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, kp), lambda iq: (iq, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((bp, kp), jnp.int32),
        ],
        scratch_shapes=scratch,
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=S.interpret_flag(mode_),
    )(*operands)
    return od[:b, :k], oi[:b, :k]


def _t_scan_topk(slab, q, q_idx, col0, *, kind, c, k, n, bm, exclude_self,
                 scale=None, lane="dense"):
    """XLA twin: the SAME padded block schedule as the Pallas launcher,
    folded with the same shared tile/merge/dequantize functions —
    bitwise-identical to interpreter mode on CPU (tested).  Runs the
    per-query-block walk as a ``fori_loop`` over tiles with the carry
    as loop state (the kernel's DMA pipeline reorders COPIES only, so
    the twin needs no pipeline model)."""
    b = q.shape[0]
    bq, dp, kp, bm = _slab_schedule(b, q.shape[1], k, bm)
    nloc = jnp.int32(slab.shape[0])
    yp, qp, qip = _slab_pad(slab, q, q_idx, bq, bm)
    sp = None if scale is None else _scale_pad(scale, bm)
    ntiles = yp.shape[0] // bm
    c32 = jnp.asarray(c, jnp.float32)
    col0_ = jnp.asarray(col0, jnp.int32)
    n_ = jnp.int32(n)
    outs_d, outs_i = [], []
    for ib in range(qp.shape[0] // bq):
        qb = qp[ib * bq:(ib + 1) * bq].astype(jnp.float32)
        qib = qip[ib * bq:(ib + 1) * bq, :1]

        def tile_body(jt, carry, qb=qb, qib=qib):
            cd, ci = carry
            raw = jax.lax.dynamic_slice_in_dim(yp, jt * bm, bm)
            sblk = None if sp is None else jax.lax.dynamic_slice_in_dim(
                sp, jt * bm, bm)
            rows = _tile_rows_f32(lane, raw, sblk)
            d, gids = _slab_tile(kind, exclude_self, c32, n_, nloc, col0_,
                                 jt * bm, qb, qib, rows)
            return _fold(cd, ci, d, gids, k)

        cd, ci = jax.lax.fori_loop(
            0, ntiles, tile_body,
            (jnp.full((bq, kp), jnp.inf, jnp.float32),
             jnp.full((bq, kp), -1, jnp.int32)))
        outs_d.append(cd)
        outs_i.append(ci)
    od = jnp.concatenate(outs_d, axis=0)
    oi = jnp.concatenate(outs_i, axis=0)
    return od[:b, :k], oi[:b, :k]


def scan_topk(slab, q, q_idx, col0, *, spec: tuple, k: int, n: int,
              exclude_self: bool = False, tile_rows: int = 0, scale=None,
              packed: bool = False):
    """Streaming top-k of ``q`` [B, D] against the shared row block
    ``slab`` [M, D] → ``(dists ascending f32 [B, k], ids int32 [B, k])``.

    ``ids`` are GLOBAL column ids ``col0 + local`` (``col0`` may be
    traced — shard-local offsets compose); rows at global index >= ``n``
    are masked, as is each query's own row when ``exclude_self`` (by
    ``q_idx`` [B] int32 — pass zeros when unused).  Slots beyond the
    reachable candidates are ``(+inf, −1)``.  ``tile_rows`` (multiple of
    128; 0 = :func:`fused_tile_rows`) is the streamed tile height.

    ``scale`` (the int8 lane, serve/quant.py): per-row dequant scales
    ([M] or [M, 1]) for an int8 ``slab`` — each streamed tile is
    dequantized in-register (``rows.astype(f32) * scale``) before the
    shared distance math, so results are those of the DEQUANTIZED table
    at f32 arithmetic, at a quarter of the table bytes.

    ``packed=True`` (the int4 lane): ``slab`` is the planar two-nibble
    packing [M, ceil(D/2)] uint8 of ``serve/quant.py:pack_int4_rows``
    and ``scale`` is REQUIRED; queries stay [B, D] f32 — the split-lane
    relayout (:func:`int4_query_layout`) and the in-register unpack are
    internal and identical in kernel and twin.

    Dispatch follows ``kernels._support.mode()``: the Pallas kernel on
    TPU, the bitwise-identical XLA twin elsewhere.  Callers gate shapes
    with :func:`supports` — unsupported ones raise here."""
    dim = q.shape[1]
    if packed:
        if scale is None:
            raise ValueError("scan_topk: packed=True (int4) requires scale=")
        hw = (int(dim) + 1) // 2
        if slab.shape[1] != hw:
            raise ValueError(
                f"scan_topk: packed slab width {slab.shape[1]} != "
                f"ceil(dim/2) = {hw} for dim={dim}")
    elif slab.shape[1] != dim:
        raise ValueError(
            f"scan_topk: slab dim {slab.shape[1]} != query dim {dim}")
    if not supports(spec, k=k, dim=dim):
        raise ValueError(
            f"scan_topk: unsupported (spec={spec[0]!r}, k={k}, "
            f"dim={dim}) — gate on scan_topk.supports() and "
            "fall back to the two-stage scan")
    kind = spec[0]
    c = 0.0 if kind == "euclidean" else spec[1]
    lane = "int4" if packed else ("int8" if scale is not None else "dense")
    bm = int(tile_rows) or fused_tile_rows(
        dim, slab.dtype, k, lane=("int4" if packed else "dense"))
    if packed:
        # ONE relayout recipe feeds both implementations
        q = int4_query_layout(q, dim)
    m_ = S.mode()
    if m_ == "xla":
        return _t_scan_topk(slab, q, q_idx, col0, kind=kind, c=c, k=int(k),
                            n=int(n), bm=bm, exclude_self=bool(exclude_self),
                            scale=scale, lane=lane)
    return _launch_slab(slab, q, q_idx, col0, kind=kind, c=c, k=int(k),
                        n=int(n), bm=bm, exclude_self=bool(exclude_self),
                        mode_=m_, scale=scale, lane=lane)


# --- PQ slab variant (ADC over coded tiles) -----------------------------------


def _pq_body(kind: str, k: int, bm: int, ntiles: int, m: int,
             exclude_self: bool):
    """Double-buffered DMA pipeline over the [M, m] code slab — the
    ``_slab_body`` structure with the ADC tile math."""

    def body(c_ref, col0_ref, n_ref, nloc_ref, lut_ref, qi_ref, y_hbm,
             od_ref, oi_ref, cd_scr, ci_scr, ybuf, ysem):
        cd_scr[:] = jnp.full_like(cd_scr, jnp.inf)
        ci_scr[:] = jnp.full_like(ci_scr, -1)
        c = c_ref[0, 0]
        col0 = col0_ref[0, 0]
        n = n_ref[0, 0]
        nloc = nloc_ref[0, 0]
        lut = lut_ref[:].astype(jnp.float32)
        qi = qi_ref[:, :1]

        def copy_y(slot, i):
            return pltpu.make_async_copy(
                y_hbm.at[pl.ds(i * bm, bm), :], ybuf.at[slot],
                ysem.at[slot])

        copy_y(0, 0).start()

        def tile(jt, _):
            slot = jax.lax.rem(jt, 2)

            @pl.when(jt + 1 < ntiles)
            def _prefetch():
                copy_y(jax.lax.rem(jt + 1, 2), jt + 1).start()

            copy_y(slot, jt).wait()
            codes = ybuf[slot].astype(jnp.int32)
            d, gids = _pq_tile(kind, exclude_self, c, n, nloc, col0,
                               jt * bm, m, lut, qi, codes)
            skip = _prune(cd_scr[:], d, k)

            @pl.when(jnp.logical_not(skip))
            def _merge_tile():
                ncd, nci = _merge(cd_scr[:], ci_scr[:], d, gids, k)
                cd_scr[:] = ncd
                ci_scr[:] = nci

            return 0

        jax.lax.fori_loop(0, ntiles, tile, 0)
        od_ref[:] = cd_scr[:]
        oi_ref[:] = ci_scr[:]

    return body


def _launch_pq(codes, lut, q_idx, col0, *, kind, c, k, n, m, bm,
               exclude_self, mode_):
    b = lut.shape[0]
    bq, _, kp, bm = _slab_schedule(b, lut.shape[1], k, bm)
    nloc = codes.shape[0]
    # the shared slab padding recipe, with the LUT as the query block
    yp, lutp, qip = _slab_pad(codes, lut, q_idx, bq, bm)
    bp, mp_ = lutp.shape[0], yp.shape[0]
    ntiles = mp_ // bm
    grid = (bp // bq,)
    smem = lambda: pl.BlockSpec((1, 1), lambda iq: (0, 0),
                                memory_space=pltpu.SMEM)
    i32 = lambda v: jnp.asarray(v, jnp.int32).reshape(1, 1)
    od, oi = pl.pallas_call(
        _pq_body(kind, k, bm, ntiles, m, exclude_self),
        grid=grid,
        in_specs=[
            smem(), smem(), smem(), smem(),
            pl.BlockSpec((bq, lutp.shape[1]), lambda iq: (iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, 128), lambda iq: (iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bq, kp), lambda iq: (iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, kp), lambda iq: (iq, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((bp, kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, kp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.int32),
            pltpu.VMEM((2, bm, yp.shape[1]), yp.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=S.interpret_flag(mode_),
    )(S.c_smem(c), i32(col0), i32(n), i32(nloc), lutp, qip, yp)
    return od[:b, :k], oi[:b, :k]


def _t_scan_topk_pq(codes, lut, q_idx, col0, *, kind, c, k, n, m, bm,
                    exclude_self):
    """XLA twin of the PQ kernel: same padded blocks, same shared
    ``_pq_tile`` (the one-hot dot selects exact LUT copies, so the twin
    matches the interpreter bitwise like every other lane)."""
    b = lut.shape[0]
    bq, _, kp, bm = _slab_schedule(b, lut.shape[1], k, bm)
    nloc = jnp.int32(codes.shape[0])
    yp, lutp, qip = _slab_pad(codes, lut, q_idx, bq, bm)
    ntiles = yp.shape[0] // bm
    c32 = jnp.asarray(c, jnp.float32)
    col0_ = jnp.asarray(col0, jnp.int32)
    n_ = jnp.int32(n)
    outs_d, outs_i = [], []
    for ib in range(lutp.shape[0] // bq):
        lutb = lutp[ib * bq:(ib + 1) * bq].astype(jnp.float32)
        qib = qip[ib * bq:(ib + 1) * bq, :1]

        def tile_body(jt, carry, lutb=lutb, qib=qib):
            cd, ci = carry
            ctile = jax.lax.dynamic_slice_in_dim(
                yp, jt * bm, bm).astype(jnp.int32)
            d, gids = _pq_tile(kind, exclude_self, c32, n_, nloc, col0_,
                               jt * bm, m, lutb, qib, ctile)
            return _fold(cd, ci, d, gids, k)

        cd, ci = jax.lax.fori_loop(
            0, ntiles, tile_body,
            (jnp.full((bq, kp), jnp.inf, jnp.float32),
             jnp.full((bq, kp), -1, jnp.int32)))
        outs_d.append(cd)
        outs_i.append(ci)
    od = jnp.concatenate(outs_d, axis=0)
    oi = jnp.concatenate(outs_i, axis=0)
    return od[:b, :k], oi[:b, :k]


def scan_topk_pq(codes, lut, q_idx, col0, *, spec: tuple, k: int, n: int,
                 exclude_self: bool = False, tile_rows: int = 0):
    """Streaming top-k over a PQ-coded slab via ADC: ``codes`` [M, m]
    uint8 subspace codes (serve/quant.py), ``lut`` [B, m*256] f32 the
    per-query lookup tables (:func:`pq_lut`) → the :func:`scan_topk`
    output contract (global ids via ``col0``, masking by ``n``/local
    rows/``exclude_self``, ``(+inf, −1)`` beyond reachable).

    Distances are those of the RECONSTRUCTED (decoded) rows — a coarse
    lane by construction; callers over-fetch and f32-rescore exactly as
    for int8/int4.  Callers gate with :func:`supports_pq` (product
    specs and m > ``FUSED_MAX_PQ_M`` fall back to the engine's decode
    scan).  Dispatch and twin contract as :func:`scan_topk`."""
    m = int(codes.shape[1])
    if not supports_pq(spec, k=k, m=m):
        raise ValueError(
            f"scan_topk_pq: unsupported (spec={spec[0]!r}, k={k}, m={m}) "
            "— gate on scan_topk.supports_pq() and fall back to the "
            "two-stage decode scan")
    if lut.shape[1] != m * 256:
        raise ValueError(
            f"scan_topk_pq: lut width {lut.shape[1]} != m*256 = {m * 256}")
    kind = spec[0]
    c = 0.0 if kind == "euclidean" else spec[1]
    bm = int(tile_rows) or fused_tile_rows(
        128, jnp.uint8, k, lane="pq", pq_m=m)
    m_ = S.mode()
    if m_ == "xla":
        return _t_scan_topk_pq(codes, lut, q_idx, col0, kind=kind, c=c,
                               k=int(k), n=int(n), m=m, bm=bm,
                               exclude_self=bool(exclude_self))
    return _launch_pq(codes, lut, q_idx, col0, kind=kind, c=c, k=int(k),
                      n=int(n), m=m, bm=bm,
                      exclude_self=bool(exclude_self), mode_=m_)


# --- per-query candidate variant (the IVF probing scorer) ---------------------


def _cand_schedule(dim: int, k: int, cand: int, dtype, tile_rows: int):
    bq = _CAND_BQ
    dp = S.round_up(dim, 128)
    kp = S.round_up(k, 128)
    bm = int(tile_rows) or fused_cand_tile_rows(dim, dtype, k)
    if bm % 128:
        raise ValueError(f"tile_rows must be a multiple of 128; got {bm}")
    bm = min(bm, S.round_up(max(cand, 1), 128))
    return bq, dp, kp, bm


def _cand_pad_idq(ids, q, q_idx, bq, bm):
    """The ONE candidate-side padding recipe (kernel launcher AND twin
    — the bitwise contract depends on both consuming identical blocks):
    ids [B, C] padded with −1 (invalid), q rows zero-padded to a bq
    multiple, q_idx as the 128-lane int32 block (−1 on padded query
    rows so ``exclude_self`` can never fire on them)."""
    b, cc = ids.shape
    cp = S.round_up(cc, bm)
    bp = S.round_up(b, bq)
    ip = jnp.full((bp, cp), -1, jnp.int32)
    ip = ip.at[:b, :cc].set(jnp.asarray(ids, jnp.int32))
    qp = S.pad_rows_lanes(q, rows_to=bq)
    qip = jnp.broadcast_to(
        jnp.asarray(q_idx, jnp.int32)[:, None], (b, 128))
    if bp > b:
        qip = jnp.concatenate(
            [qip, jnp.full((bp - b, 128), -1, jnp.int32)], axis=0)
    return ip, qp, qip


def _cand_pad(rows, ids, q, q_idx, bq, bm):
    """Kernel-launcher padding: the shared id/query recipe plus the
    pre-gathered rows block (zero lanes / rows — padded id slots are
    masked by their −1 id, so their row content never matters)."""
    rp = S.pad_axis(S.pad_axis(S.pad_axis(rows, -1, 128), 1, bm), 0, bq)
    ip, qp, qip = _cand_pad_idq(ids, q, q_idx, bq, bm)
    return rp, ip, qp, qip


def _cand_body(kind: str, k: int, exclude_self: bool,
               quant: bool = False):
    def body(c_ref, q_ref, qi_ref, r_ref, id_ref, *rest):
        if quant:  # int8 rows: the gathered per-row scale block follows
            s_ref, od_ref, oi_ref, cd_scr, ci_scr = rest
        else:
            od_ref, oi_ref, cd_scr, ci_scr = rest
        jt = pl.program_id(1)

        @pl.when(jt == 0)
        def _init():
            cd_scr[:] = jnp.full_like(cd_scr, jnp.inf)
            ci_scr[:] = jnp.full_like(ci_scr, -1)

        c = c_ref[0, 0]
        q = q_ref[:].astype(jnp.float32)
        qi = qi_ref[:, :1]
        rows = r_ref[:].astype(jnp.float32)
        if quant:
            rows = rows * s_ref[:][..., None]
        ids = id_ref[:]
        d, ids = _cand_tile(kind, exclude_self, c, q, qi, rows, ids)
        skip = _prune(cd_scr[:], d, k)

        @pl.when(jnp.logical_not(skip))
        def _merge_tile():
            ncd, nci = _merge(cd_scr[:], ci_scr[:], d, ids, k)
            cd_scr[:] = ncd
            ci_scr[:] = nci

        @pl.when(jt == pl.num_programs(1) - 1)
        def _write():
            od_ref[:] = cd_scr[:]
            oi_ref[:] = ci_scr[:]

    return body


def _launch_cand(rows, ids, q, q_idx, *, kind, c, k, exclude_self, bm,
                 mode_, sc=None):
    b, cc = ids.shape
    bq, dp, kp, bm = _cand_schedule(q.shape[1], k, cc, rows.dtype, bm)
    rp, ip, qp, qip = _cand_pad(rows, ids, q, q_idx, bq, bm)
    bp, cp = ip.shape
    grid = (bp // bq, cp // bm)
    in_specs = [
        pl.BlockSpec((1, 1), lambda iq, jt: (0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((bq, dp), lambda iq, jt: (iq, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bq, 128), lambda iq, jt: (iq, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bq, bm, dp), lambda iq, jt: (iq, jt, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bq, bm), lambda iq, jt: (iq, jt),
                     memory_space=pltpu.VMEM),
    ]
    operands = [S.c_smem(c), qp, qip, rp, ip]
    if sc is not None:
        # gathered per-candidate dequant scales, blocked like the ids
        scp = jnp.zeros((bp, cp), jnp.float32)
        scp = scp.at[:b, :cc].set(jnp.asarray(sc, jnp.float32))
        in_specs.append(pl.BlockSpec((bq, bm), lambda iq, jt: (iq, jt),
                                     memory_space=pltpu.VMEM))
        operands.append(scp)
    od, oi = pl.pallas_call(
        _cand_body(kind, k, exclude_self, quant=sc is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, kp), lambda iq, jt: (iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, kp), lambda iq, jt: (iq, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((bp, kp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, kp), jnp.float32),
            pltpu.VMEM((bq, kp), jnp.int32),
        ],
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=S.interpret_flag(mode_),
    )(*operands)
    return od[:b, :k], oi[:b, :k]


def _t_scan_topk_cand(scan_table, cand, q, q_idx, *, kind, c, k,
                      exclude_self, bm, scale=None):
    """XLA twin of the candidate kernel: gathers each tile's rows from
    ``scan_table`` on the fly (a gather is value-exact, so this matches
    the kernel's pre-gathered stream bitwise) and folds with the shared
    functions over the identical [bq, bm, dp] block shapes."""
    b, cc = cand.shape
    bq, dp, kp, bm = _cand_schedule(q.shape[1], k, cc, scan_table.dtype, bm)
    # pad the table's feature lanes exactly like the kernel's row stream
    tp = S.pad_axis(scan_table, -1, 128)
    sf = None if scale is None else jnp.asarray(scale,
                                                jnp.float32).reshape(-1)
    ip, qp, qip = _cand_pad_idq(cand, q, q_idx, bq, bm)
    bp, cp = ip.shape
    c32 = jnp.asarray(c, jnp.float32)
    ntiles = cp // bm
    outs_d, outs_i = [], []
    for ib in range(bp // bq):
        qb = qp[ib * bq:(ib + 1) * bq].astype(jnp.float32)
        qib = qip[ib * bq:(ib + 1) * bq, :1]
        idsb = ip[ib * bq:(ib + 1) * bq]

        def tile_body(jt, carry, qb=qb, qib=qib, idsb=idsb):
            cd, ci = carry
            ids = jax.lax.dynamic_slice_in_dim(idsb, jt * bm, bm, axis=1)
            rows = tp[jnp.maximum(ids, 0)].astype(jnp.float32)
            if sf is not None:
                # same gather + in-register dequantize as the launcher's
                # pre-gathered scale stream (masked slots never read)
                rows = rows * sf[jnp.maximum(ids, 0)][..., None]
            d, ids = _cand_tile(kind, exclude_self, c32, qb, qib, rows, ids)
            return _fold(cd, ci, d, ids, k)

        cd, ci = jax.lax.fori_loop(
            0, ntiles, tile_body,
            (jnp.full((bq, kp), jnp.inf, jnp.float32),
             jnp.full((bq, kp), -1, jnp.int32)))
        outs_d.append(cd)
        outs_i.append(ci)
    od = jnp.concatenate(outs_d, axis=0)
    oi = jnp.concatenate(outs_i, axis=0)
    return od[:b, :k], oi[:b, :k]


def scan_topk_cand(scan_table, cand, q, q_idx, *, spec: tuple, k: int,
                   exclude_self: bool = False, tile_rows: int = 0,
                   scale=None):
    """Per-query-candidate streaming top-k (the IVF probing scorer):
    ``cand`` [B, C] int32 row ids into ``scan_table`` [N, D] (−1 =
    padding), ``q`` [B, D] → ``(dists f32 [B, k], ids int32 [B, k])``
    where ids are TABLE row ids.  Same carry/merge/prune machinery and
    twin contract as :func:`scan_topk`; the kernel path pre-gathers the
    [B, C, D] candidate rows (``supports_cand`` caps that footprint),
    the twin gathers per tile.  ``scale`` ([N] / [N, 1] f32): per-row
    dequant scales for an int8 ``scan_table`` — gathered with the rows
    and applied in-register (the int8 lane, serve/quant.py)."""
    if not supports_cand(spec, k=k, dim=scan_table.shape[1],
                         cand=cand.shape[1]):
        raise ValueError(
            f"scan_topk_cand: unsupported (spec={spec[0]!r}, k={k}, "
            f"C={cand.shape[1]}) — gate on scan_topk.supports_cand() "
            "and fall back to the two-stage candidate scan")
    kind = spec[0]
    c = 0.0 if kind == "euclidean" else spec[1]
    m_ = S.mode()
    if m_ == "xla":
        return _t_scan_topk_cand(scan_table, cand, q, q_idx, kind=kind,
                                 c=c, k=int(k),
                                 exclude_self=bool(exclude_self),
                                 bm=int(tile_rows), scale=scale)
    safe = jnp.maximum(jnp.asarray(cand, jnp.int32), 0)
    rows = S.pad_axis(scan_table, -1, 128)[safe]
    sc = (None if scale is None
          else jnp.asarray(scale, jnp.float32).reshape(-1)[safe])
    return _launch_cand(rows, jnp.asarray(cand, jnp.int32), q, q_idx,
                        kind=kind, c=c, k=int(k),
                        exclude_self=bool(exclude_self),
                        bm=int(tile_rows), mode_=m_, sc=sc)
