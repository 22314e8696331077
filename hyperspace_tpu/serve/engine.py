"""Jitted batched query engine over a frozen embedding table.

The inference workloads of the paper's retrieval models are two device
programs over an [N, D] table of manifold points:

- ``topk_neighbors(q_idx, k)`` — the k nearest table rows to each query
  row under the hyperbolic metric (Poincaré-embedding retrieval à la
  Nickel & Kiela 2017);
- ``score_edges(u_idx, v_idx)`` — per-pair distances (optionally pushed
  through the Fermi–Dirac link decoder) for edge scoring à la the HGCN
  LP head (Chami et al. 2019).

Mechanics:

- **Distance tiles come from the fused kernels.**  Poincaré/Lorentz
  tiles go through :func:`hyperspace_tpu.kernels.distmat.pdist` — the
  Pallas TPU kernel on a TPU backend, the XLA twin on CPU — so a [B, M]
  tile never materializes a [B, M, D] difference tensor.  Product
  manifolds use ``Product.dist`` broadcast per tile (exactly the trained
  geometry, learned curvatures frozen into the spec).
- **The table is chunked.**  The k-NN scan walks the table
  ``chunk_rows`` rows at a time, so the live distance working set is one
  [B, chunk] tile (plus [B, chunk, D] on the product path) regardless of
  N — ``tile_budget`` picks the chunk.  The table is zero-padded ONCE at
  engine build to a chunk multiple; padded rows are masked to +inf
  distance by index, so they can never appear in a result.
- **Three scan strategies** (``scan_mode``).  ``fused`` dispatches the
  chunk walk to the Pallas scan-top-k kernel
  (``kernels/scan_topk.py``; XLA twin on CPU): distance tiles are
  computed in-register and the running per-row top-k lives in the
  kernel carry, so the distance matrix never touches HBM and the
  per-chunk ``lax.top_k`` + post-scan merge disappear — the
  flash-attention trade applied to retrieval.  Results are
  rank-identical to the default (tested on every supported spec);
  product manifolds and oversized k/dim fall back to the two-stage
  path bit-identically.  The default ``two_stage``
  takes a per-chunk ``lax.top_k`` over the [B, chunk] tile only (k
  candidates per chunk, stacked by the scan) and merges the
  [B, nchunks·k] candidate buffer ONCE after the scan — the per-step
  sort never sees the carried candidates, so each step sorts chunk rows
  instead of chunk+k.  A running per-row k-th-distance bound lets a tile
  whose row-minimum already exceeds it skip its sort entirely (the
  threshold-prune fast path — a big win on locality-ordered tables
  where late chunks are all far).  ``carry`` is the original variant —
  the scan carries a running [B, k] top-k and re-sorts [B, chunk+k]
  every step — kept selectable for A/B timing and as the low-memory
  fallback when nchunks·k is large.
- **The table shards across the device mesh** (``mesh=``).  With a mesh
  whose ``model`` axis has S > 1 devices, the padded table is laid out
  ``P("model", None)`` (``parallel/sharded_embed.table_sharding``) —
  each device holds N/S rows, so tables larger than one chip's HBM
  serve fine and the scan walks only the local shard (per-device work
  cut by S).  Inside one ``shard_map`` program: query rows are
  assembled by the same gather-owned-rows + psum trick the training
  lookup uses, each device runs the chunked scan over its shard with
  shard-local column offsets, then one all-gather of the per-shard
  [B, k] candidates and a final merge top-k.  A mesh whose model axis
  has ONE device falls back to the single-device program — bit-compatible
  by construction (same executable).
- **Optional bf16 table scan** (``precision="bf16"``; docs/precision.md).
  A bf16 copy of the padded table lives beside the f32 one and the scan
  runs over THAT (half the HBM traffic of the dominant pass), keeping
  ``k + max(k, 8)`` candidates; the merged candidates are re-scored
  with f32 manifold distances against the f32 table before the final
  top-k, so returned distances are always f32-accurate and rank
  agreement holds at ordinary point distributions.  ``"f32"`` (default)
  is the unchanged pre-policy executable.
- **Optional int8 table scan** (``precision="int8"``; docs/serving.md
  "Quantized scan lane") — the same scan-then-rescore shape at a
  QUARTER of the table bytes: a per-row symmetric int8 code + per-row
  f32 scale (``serve/quant.py``) live beside the f32 table, the coarse
  scan dequantizes tiles in-register (``q8.astype(f32) * scale`` —
  arithmetic stays f32) keeping ``k + max(4k, 32)`` candidates (a wider
  over-fetch than bf16: the quantization step is coarser), and the
  merged candidates are rescored with f32 manifold distances against
  the f32 master before the final top-k.  Queries are NOT quantized —
  they are f32 rows of the master table.  Composes with IVF probing,
  the fused kernel (int8 slabs stream at quarter bytes through the
  same carry), and mesh sharding; the scan signature and the batcher
  cache key carry the lane, so f32/bf16/int8 rows never cross.
- **Sub-int8 lanes** (``precision="int4"|"pq"``; ISSUE 16,
  docs/serving.md "Sub-int8 lanes") — the same scan-then-rescore shape
  below a quarter of the bytes.  int4 packs two signed nibbles per
  byte with a per-row f16 scale (tiles unpack in-register; the fused
  kernel streams the packed bytes through a double-buffered DMA
  pipeline).  PQ stores one uint8 code per subspace against codebooks
  trained by subspace k-means in the tangent/Lorentz lift
  (``serve/quant.py``); the fused kernel scores coded tiles by ADC
  (per-query lookup tables), the two-stage path decodes tiles to the
  lift and scores with the lift's closed forms.  Both keep the int8
  lane's over-fetch + f32-rescore shape at a wider ``k + max(16k,
  128)`` window (a 4-bit step / a 256-way codebook is far coarser than
  int8's per-element step), so final
  ranks come from full-precision manifold distances; product specs
  serve PQ through the two-stage decode path (their distance is not
  subspace-additive).
- **Optional IVF probing** (``index=`` + ``nprobe=``; docs/serving.md
  "Approximate retrieval", built by ``serve/index.py``).  Queries score
  against the index's hyperbolic-k-means centroids, gather the nearest
  ``nprobe`` cells' row ids from the dense ``[ncells, max_cell]`` cell
  layout, and run the SAME two-stage scan (threshold prune, per-chunk
  top-k, one merge) over the gathered candidates — sub-linear work per
  query instead of the O(N) slab walk, at a recall cost ``bench_serve``
  tracks (recall@10 vs the exact engine, qps at recall ≥ 0.99).  The
  bf16 scan-then-f32-rescore path composes unchanged.  Exact fallback:
  ``nprobe=0`` / ``nprobe >= ncells`` (degenerate probe = exact answer,
  served bit-identically by the exact program) / tables under
  ``IVF_MIN_TABLE_ROWS`` / sharded meshes (probing is single-device).
- **Compiles are keyed on (bucket, k, nprobe), never on request.**  The jitted
  programs hang everything shape-like on static arguments (batch size,
  k, chunk, N, the manifold spec tuple, the mesh); the request batcher
  (``serve/batcher.py``) pads incoming batches to a small set of
  power-of-two buckets, so the engine compiles once per (bucket, k) and
  then serves any request size out of the same executable —
  ``jax/recompiles`` stays flat (the e2e test asserts it).

Determinism: for a fixed (bucket, k, chunk, scan_mode, mesh) the
program is one fixed XLA executable — the same table bytes give
bitwise-identical results, which is what lets
``scripts/check_serve_artifact.py`` demand export → load → query equals
the live model bit-for-bit.  Across DIFFERENT shardings the distances
agree but tied distances may order differently (the merge concatenates
per-shard candidates, not global column order).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from hyperspace_tpu import precision as precision_mod
from hyperspace_tpu.parallel.sharded_embed import local_gather, table_sharding
from hyperspace_tpu.serve.artifact import (ServingArtifact, fingerprint_of,
                                           manifold_from_spec)
from hyperspace_tpu.telemetry import registry as telem
from hyperspace_tpu.telemetry import spans

# f32 bytes a distance tile may occupy ([B, chunk] on the kernel path,
# [B, chunk, D] on the product path), per the nominal batch below.
DEFAULT_TILE_BUDGET = 8 * 1024 * 1024
# chunk sizing assumes batches up to this (the batcher's default
# max_bucket); bigger batches just run a proportionally bigger tile.
NOMINAL_BATCH = 1024
_ROW_ALIGN = 128

SCAN_MODES = ("two_stage", "carry", "fused")
# the serve table-scan lanes: the precision-policy presets plus the
# serve-only quantized lanes (serve/quant.py — not training policies,
# so they live here rather than in precision.PRESET_NAMES): int8
# (per-row symmetric code), int4 (two nibbles per byte, ISSUE 16) and
# pq (product-quantized codes + hyperbolic-aware codebooks)
QUANT_PRECISIONS = ("int8", "int4", "pq")
PRECISIONS = precision_mod.PRESET_NAMES + QUANT_PRECISIONS

# extra candidates the bf16 scan keeps beyond the requested k, so a
# near-tie the low-precision pass mis-ranks at the k-th boundary is still
# IN the candidate set when the f32 rescore re-ranks it (docs/precision.md
# "serving": the scan picks candidates, f32 picks the answer)
_RESCORE_PAD = 8
# the int8 lane's wider over-fetch: a quantization step is ~2⁻⁸ of the
# row's dynamic range (vs bf16's ~2⁻⁸ RELATIVE per element — similar
# magnitude but correlated per row), so the coarse ranking is noisier
# and the rescore margin scales with k (k + max(4k, 32) candidates)
_QUANT_RESCORE_MIN = 32
_QUANT_RESCORE_MULT = 4
# the int4 lane's wider-still over-fetch: a 4-bit step is 2^4 = 16×
# int8's, so the coarse ranking noise swamps neighbor gaps much sooner
# as table density grows — measured at 200k clustered rows (dim 8,
# bench_big_table's generator) the int8-width window plateaus at
# recall@10 ≈ 0.95 while k + max(16k, 128) holds 1.0; same budget as
# the pq window, so the fused-kernel liveness bound is unchanged
_INT4_RESCORE_MIN = 128
_INT4_RESCORE_MULT = 16
# the PQ lane's even-wider over-fetch: subspace codebooks quantize whole
# ds-wide blocks to one of 256 centers, so the coarse ADC ranking is far
# noisier than any per-element lane — the window must absorb coarse
# ranks a few hundred deep, while k + max(16k, 128) still keeps
# k_scan <= FUSED_MAX_K for k <= 8 so the fused ADC kernel stays live
_PQ_RESCORE_MIN = 128
_PQ_RESCORE_MULT = 16


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def auto_chunk_rows(dim: int, spec_kind: str, n: int,
                    tile_budget: int = DEFAULT_TILE_BUDGET, *,
                    scan_mode: str = "two_stage",
                    dtype=jnp.float32, lane: str = "dense",
                    pq_m: int = 0) -> int:
    """Table-chunk rows that keep one distance tile under the budget.

    For ``scan_mode="fused"`` on a fused-capable family the chunk IS the
    kernel's streamed tile height, so sizing delegates to
    :func:`hyperspace_tpu.kernels.scan_topk.fused_tile_rows` — a
    VMEM-footprint model over dim × dtype × k (worst-case ``k =
    FUSED_MAX_K``, so every supported per-call k fits), not the fixed
    HBM distance-tile byte budget the two-stage scan uses.  Unsupported
    families keep the default sizing (the engine then IS the default
    two-stage executable — the bit-identical fallback contract).

    ``lane``/``pq_m`` extend the fused sizing to the packed scan lanes
    (``"int4"``/``"pq"`` — kernels/scan_topk.py's footprint branches);
    the default ``"dense"`` covers f32/bf16/int8 unchanged."""
    if scan_mode == "fused":
        from hyperspace_tpu.kernels import scan_topk as fused_kernel

        if (fused_kernel.kind_supported((spec_kind,))
                and dim <= fused_kernel.FUSED_MAX_DIM):
            chunk = fused_kernel.fused_tile_rows(
                dim, dtype, fused_kernel.FUSED_MAX_K, lane=lane, pq_m=pq_m)
            return min(chunk, _round_up(max(n, 1), _ROW_ALIGN))
    per_row = 4 * NOMINAL_BATCH * (dim if spec_kind == "product" else 1)
    chunk = max(_ROW_ALIGN, (tile_budget // per_row) // _ROW_ALIGN * _ROW_ALIGN)
    return min(chunk, _round_up(max(n, 1), _ROW_ALIGN))


def _tile_dist(spec: tuple, q: jax.Array, rows: jax.Array) -> jax.Array:
    """[B, D] × [M, D] → [B, M] distances under the spec's manifold."""
    kind = spec[0]
    if kind in ("poincare", "lorentz"):
        from hyperspace_tpu.kernels.distmat import pdist

        return pdist(q, rows, spec[1], manifold=kind)
    m = manifold_from_spec(spec)
    return m.dist(q[:, None, :], rows[None, :, :])


def _int4_rows_f32(packed: jax.Array, scale: jax.Array,
                   dim: int) -> jax.Array:
    """Packed planar int4 rows [..., ceil(dim/2)] uint8 + per-row scale
    [..., 1] → dequantized f32 rows [..., dim] (serve/quant.py's layout:
    byte j = element j in the LOW nibble, element ceil(dim/2)+j in the
    HIGH one, two's complement) — the two-stage scan's in-register
    unpack; the fused kernel carries its own identical recipe
    (kernels/scan_topk.py ``_tile_rows_f32``)."""
    from hyperspace_tpu.serve.quant import unpack_int4_jnp

    rows = unpack_int4_jnp(packed, dim)
    return rows.astype(jnp.float32) * scale.astype(jnp.float32)


def _pq_decode_rows(cb: jax.Array, codes: jax.Array,
                    lift_dim: int) -> jax.Array:
    """PQ codes [..., m] uint8 + codebooks [m, 256, ds] f32 → the
    reconstructed LIFTED rows [..., lift_dim] (serve/quant.py trains the
    codebooks in the tangent/Lorentz lift; pad lanes beyond the lift
    width are exactly zero and are sliced off)."""
    m = int(cb.shape[0])
    sel = cb[jnp.arange(m), codes.astype(jnp.int32)]      # [..., m, ds]
    out = sel.reshape(codes.shape[:-1] + (m * int(cb.shape[2]),))
    return out[..., :lift_dim]


def _pq_lift_dist(spec: tuple, q_lift: jax.Array,
                  rows_lift: jax.Array) -> jax.Array:
    """Coarse scan distances in the LIFT space: lifted f32 queries
    [B, DL] × reconstructed lifted rows ([M, DL] shared, or [B, C, DL]
    per-query) → [B, M] / [B, C].

    The lift of a poincare/lorentz family is Lorentz coordinates at the
    same curvature, so the distance closed form is the Lorentz one —
    exactly what the fused PQ kernel's ADC sum closes over
    (kernels/scan_topk.py ``_pq_dist_from_sum``); euclidean lifts are
    the identity.  Product specs recurse per factor and combine like
    ``Product.dist`` (root of summed squares).  Reconstructions sit
    slightly off the manifold — the same clamps the kernel tiles use
    keep the math finite, and the f32 rescore against the master table
    picks the final ranks anyway."""
    from hyperspace_tpu.manifolds import smath

    kind = spec[0]
    prec = jax.lax.Precision.HIGHEST
    shared = rows_lift.ndim == 2
    if kind == "product":
        from hyperspace_tpu.serve.index import _lift_dim

        o, acc = 0, 0.0
        for fk, d, c in spec[1]:
            dl = _lift_dim((fk, c), d)
            df = _pq_lift_dist((fk, c), q_lift[:, o:o + dl],
                               rows_lift[..., o:o + dl])
            acc = acc + jnp.square(df)
            o += dl
        return smath.safe_sqrt(acc)
    if kind in ("poincare", "lorentz"):
        c = jnp.asarray(spec[1], q_lift.dtype)
        if shared:
            gram = (jnp.einsum("bd,md->bm", q_lift[:, 1:], rows_lift[:, 1:],
                               precision=prec)
                    - q_lift[:, :1] * rows_lift[None, :, 0])
        else:
            gram = (jnp.einsum("bd,bcd->bc", q_lift[:, 1:],
                               rows_lift[..., 1:], precision=prec)
                    - q_lift[:, :1] * rows_lift[..., 0])
        u = smath.clamp_min(-c * gram - 1.0, 0.0)
        return smath.arcosh1p(u) / smath.clamp_min(
            smath.sqrt_c(c), smath.min_norm(q_lift.dtype))
    if kind == "euclidean":
        if shared:
            gram = jnp.einsum("bd,md->bm", q_lift, rows_lift,
                              precision=prec)
            yy = jnp.sum(rows_lift * rows_lift, axis=-1)[None, :]
        else:
            gram = jnp.einsum("bd,bcd->bc", q_lift, rows_lift,
                              precision=prec)
            yy = jnp.sum(rows_lift * rows_lift, axis=-1)
        xx = jnp.sum(q_lift * q_lift, axis=-1, keepdims=True)
        return smath.safe_sqrt(smath.clamp_min(xx - 2.0 * gram + yy, 0.0))
    # sphere (lift = identity): project the reconstruction back onto
    # the sphere and use the factor manifold's own distance
    m = manifold_from_spec(spec)
    rows = m.proj(rows_lift)
    if shared:
        return m.dist(q_lift[:, None, :], rows[None, :, :])
    return m.dist(q_lift[:, None, :], rows)


def _scan_topk(slab, q, q_idx, col0, *, spec: tuple, k: int, chunk: int,
               n: int, exclude_self: bool, mode: str, scale=None,
               lane: str = "dense", drop=None):
    """Chunked top-k over ``slab`` rows → ``(dists ascending, ids int32)``,
    each ``[B, min(k, slab_rows)]`` (a shard narrower than k contributes
    everything it has; the cross-shard merge restores the full k).

    ``slab`` is a chunk-multiple row block of the padded table whose
    global column ids start at ``col0`` (0 on the single-device path,
    ``axis_index * local_rows`` per shard on the sharded path — may be
    traced).  Rows at global index >= ``n`` are zero padding and are
    masked to +inf by index, as is each query's own row under
    ``exclude_self``.

    ``scale``/``lane`` (the quantized lanes, serve/quant.py): ``"int8"``
    — per-row [rows, 1] f32 dequant scales for an int8 ``slab``, tiles
    dequantize in-register before the distance math; ``"int4"`` — the
    slab is the planar packed [rows, ceil(D/2)] uint8 and ``scale`` its
    per-row (f16) scales, tiles unpack + dequantize in-register;
    ``"pq"`` — the slab is the [rows, m] uint8 code table and ``scale``
    carries the [m, 256, ds] codebooks, tiles decode to the LIFT space
    and score against the lifted query.  Every lane's scan arithmetic
    stays f32; only the table bytes shrink.

    ``drop`` (the live-index tombstone mask, serve/delta.py) is an
    optional ``[n_pad]`` f32 penalty row — 0 for live rows, ``+inf``
    for deleted or delta-superseded ones — ADDED to every tile's
    distances before the top-k, so a masked master row can never win a
    slot whatever its geometry.  The mask is a traced operand: its
    VALUES change per mutation generation without recompiling (the
    compile contract's shapes stay static).  The fused kernel has no
    mask lane, so a masked scan dispatches the two-stage path.
    """
    if drop is not None and mode == "fused":
        mode = "two_stage"  # the fused carry has no tombstone lane
    b = q.shape[0]
    dim = q.shape[1]
    nchunks = slab.shape[0] // chunk
    # per-chunk candidate count: a chunk narrower than k keeps ALL its
    # rows (lax.top_k needs k <= the sorted width)
    kc = min(k, chunk)
    # a slab narrower than k (a small shard under a large k) contributes
    # every row it has; the cross-shard merge restores the full k
    ko = min(k, nchunks * chunk)
    # distances of a quantized scan are f32 (dequantize-then-f32-math);
    # float slabs keep their own dtype (the bf16 scan's tiles are bf16)
    ddt = jnp.float32 if lane != "dense" or scale is not None \
        else slab.dtype
    q_lift = None
    if lane == "pq":
        from hyperspace_tpu.serve.index import _lift, _lift_dim

        lift_dim = _lift_dim(spec, dim)
        q_lift = _lift(spec, q).astype(jnp.float32)

    if mode == "fused":
        from hyperspace_tpu.kernels import scan_topk as fused_kernel

        if (lane == "pq"
                and fused_kernel.supports_pq(spec, k=k, m=slab.shape[1])
                and chunk % 128 == 0):
            # ADC in the kernel: per-query LUTs over the codebooks, the
            # coded tiles never decode to full rows (kernels/scan_topk)
            lut = fused_kernel.pq_lut(q_lift, scale, kind=spec[0])
            d, i = fused_kernel.scan_topk_pq(
                slab, lut, q_idx, col0, spec=spec, k=k, n=n,
                exclude_self=exclude_self, tile_rows=chunk)
            return d[:, :ko], i[:, :ko]
        if (lane != "pq"
                and fused_kernel.supports(spec, k=k, dim=dim)
                and chunk % 128 == 0):
            # the fused Pallas kernel (XLA twin on CPU): distance tiles
            # stay in-register, the running top-k lives in the kernel
            # carry — no [B, chunk] HBM tile, no per-chunk lax.top_k,
            # no post-scan merge (kernels/scan_topk.py)
            d, i = fused_kernel.scan_topk(
                slab, q, q_idx, col0, spec=spec, k=k, n=n,
                exclude_self=exclude_self, tile_rows=chunk, scale=scale,
                packed=(lane == "int4"))
            return d[:, :ko], i[:, :ko]
        # capability fallback (product spec, oversized k/dim/m): the
        # two-stage path below, bit-identical to scan_mode="two_stage"
        mode = "two_stage"

    def masked_tile(i):
        rows = jax.lax.dynamic_slice_in_dim(slab, i * chunk, chunk)
        if lane == "int4":
            s = jax.lax.dynamic_slice_in_dim(scale, i * chunk, chunk)
            rows = _int4_rows_f32(rows, s, dim)
        elif scale is not None and lane != "pq":
            rows = rows.astype(jnp.float32) * jax.lax.dynamic_slice_in_dim(
                scale, i * chunk, chunk)
        if lane == "pq":
            recon = _pq_decode_rows(scale, rows, lift_dim)
            d = _pq_lift_dist(spec, q_lift, recon)        # [B, chunk]
        else:
            d = _tile_dist(spec, q, rows)                 # [B, chunk]
        # pin int32: under x64 the traced chunk offset would promote the
        # index dtype and break the scan carry/stack contract
        cols = (col0 + i * chunk + jnp.arange(chunk)).astype(jnp.int32)
        mask = cols[None, :] >= n                         # zero-padded rows
        if exclude_self:
            mask = mask | (cols[None, :] == q_idx[:, None])
        if drop is not None:
            # tombstone/supersede penalty for this tile's global rows
            d = d + jax.lax.dynamic_slice_in_dim(
                drop, col0 + i * chunk, chunk).astype(d.dtype)[None, :]
        return jnp.where(mask, jnp.inf, d), cols

    if mode == "carry":
        def body(carry, i):
            best_d, best_i = carry
            d, cols = masked_tile(i)
            cat_d = jnp.concatenate([best_d, d], axis=1)
            cat_i = jnp.concatenate(
                [best_i, jnp.broadcast_to(cols, d.shape)], axis=1)
            top_negd, sel = jax.lax.top_k(-cat_d, ko)
            return (-top_negd, jnp.take_along_axis(cat_i, sel, axis=1)), None

        init = (jnp.full((b, ko), jnp.inf, ddt),
                jnp.full((b, ko), -1, jnp.int32))
        (dist, idx), _ = jax.lax.scan(body, init, jnp.arange(nchunks))
        return dist, idx

    # two_stage: per-chunk top-kc over [B, chunk] only (never chunk+k),
    # candidates stacked by the scan, ONE [B, nchunks*kc] merge after it.
    def tile2d(i):
        d, cols = masked_tile(i)
        return d, jnp.broadcast_to(cols, d.shape)

    return _two_stage_core(tile2d, b=b, nchunks=nchunks, k=k, kc=kc, ko=ko,
                           dtype=ddt)


def _two_stage_core(masked_tile, *, b: int, nchunks: int, k: int, kc: int,
                    ko: int, dtype):
    """The ONE two-stage scan body — shared by the slab walk
    (:func:`_scan_topk` ``two_stage``) and the IVF candidate scan
    (:func:`_scan_topk_cand`), which differ only in where a tile's rows
    come from.  ``masked_tile(i)`` → ``(d [B, chunk], ids [B, chunk]
    int32)`` with masked slots at ``+inf``.  Returns
    ``(dists ascending, ids)``, each ``[B, ko]``.
    """
    def body(kth, i):
        d, ids = masked_tile(i)

        def sort_tile(_):
            top_negd, sel = jax.lax.top_k(-d, kc)
            return -top_negd, jnp.take_along_axis(ids, sel, axis=1)

        def skip_tile(_):
            return (jnp.full((b, kc), jnp.inf, d.dtype),
                    jnp.full((b, kc), -1, jnp.int32))

        # threshold prune: ``kth`` is an upper bound on the true running
        # k-th distance (the k-th smallest of a union is <= the k-th of
        # any member chunk), so a tile whose per-row minimum meets it on
        # EVERY row cannot change the result — skip its sort outright
        cd, ci = jax.lax.cond(
            jnp.all(jnp.min(d, axis=1) >= kth), skip_tile, sort_tile, None)
        if kc == k:  # narrower chunks (kc < k) have no k-th to tighten with
            kth = jnp.minimum(kth, cd[:, k - 1])  # inf when skipped: no-op
        return kth, (cd, ci)

    kth0 = jnp.full((b,), jnp.inf, dtype)
    _, (cd, ci) = jax.lax.scan(body, kth0, jnp.arange(nchunks))
    cat_d = jnp.moveaxis(cd, 0, 1).reshape(b, nchunks * kc)
    cat_i = jnp.moveaxis(ci, 0, 1).reshape(b, nchunks * kc)
    top_negd, sel = jax.lax.top_k(-cat_d, ko)
    return -top_negd, jnp.take_along_axis(cat_i, sel, axis=1)


@partial(jax.jit, static_argnames=("spec", "k", "chunk", "n", "exclude_self",
                                   "mode"))
def _topk_chunked(table: jax.Array, q_idx: jax.Array, drop=None,
                  q_rows=None, *, spec: tuple,
                  k: int, chunk: int, n: int, exclude_self: bool,
                  mode: str = "two_stage"):
    """Single-device chunked top-k; one fixed program per
    (batch, k, chunk, n, spec, mode).  ``drop``/``q_rows`` are the live
    subsystem's traced hooks (serve/delta.py): the tombstone penalty
    row, and explicit f32 query rows gathered from the MUTABLE master
    (a superseded id's frozen device row must never be the query)."""
    q = table[q_idx] if q_rows is None else q_rows        # [B, D]
    dist, idx = _scan_topk(table, q, q_idx, 0, spec=spec, k=k, chunk=chunk,
                           n=n, exclude_self=exclude_self, mode=mode,
                           drop=drop)
    return idx, dist


@partial(jax.jit, static_argnames=("spec", "k", "chunk", "n", "exclude_self",
                                   "mode", "mesh", "axis"))
def _topk_sharded(table: jax.Array, q_idx: jax.Array, drop=None,
                  q_rows=None, *, spec: tuple,
                  k: int, chunk: int, n: int, exclude_self: bool,
                  mode: str, mesh, axis: str):
    """Mesh-sharded top-k: per-shard chunked scan + one merge.

    ``table`` is the padded table laid out ``P(axis, None)`` (each of
    the S devices owns ``padded/S`` rows — a chunk multiple).  Per
    device: assemble the [B, D] query rows with the gather-owned-rows +
    psum trick (``parallel/sharded_embed.local_gather`` — one B×D
    all-reduce), scan the LOCAL shard with shard-local column offsets,
    then all-gather the per-shard [B, k] winners (S·k·B elements — tiny
    next to the table) and take the final merge top-k everywhere, so
    the output is replicated.
    """
    npad = table.shape[0]
    has_drop, has_q = drop is not None, q_rows is not None

    def local(tloc, qi, *extra):
        dr = extra[0] if has_drop else None
        q = (extra[-1] if has_q
             else local_gather(tloc, qi, npad, axis))     # [B, D]
        lo = (jax.lax.axis_index(axis) * tloc.shape[0]).astype(jnp.int32)
        d, i = _scan_topk(tloc, q, qi, lo, spec=spec, k=k, chunk=chunk,
                          n=n, exclude_self=exclude_self, mode=mode,
                          drop=dr)
        gd = jax.lax.all_gather(d, axis)                  # [S, B, k]
        gi = jax.lax.all_gather(i, axis)
        b = qi.shape[0]
        cat_d = jnp.moveaxis(gd, 0, 1).reshape(b, -1)     # [B, S*k]
        cat_i = jnp.moveaxis(gi, 0, 1).reshape(b, -1)
        top_negd, sel = jax.lax.top_k(-cat_d, k)
        return jnp.take_along_axis(cat_i, sel, axis=1), -top_negd

    # the live hooks ride replicated (the drop row and query rows are
    # B/N-scale vectors, tiny next to the sharded table)
    extras = ([drop] if has_drop else []) + ([q_rows] if has_q else [])
    run = shard_map(local, mesh=mesh,
                    in_specs=(P(axis, None), P()) + (P(),) * len(extras),
                    out_specs=(P(), P()), check_vma=False)
    return run(table, q_idx, *extras)


def _rescore_f32(spec: tuple, rows: jax.Array, q: jax.Array,
                 idx: jax.Array, scan_d: jax.Array) -> jax.Array:
    """f32 distances for gathered candidate rows ``rows`` [B, K, D]
    against f32 queries ``q`` [B, D].  Slots the low-precision scan
    filled with ``-1``/``inf`` (skipped tiles, narrow shards) stay
    ``+inf`` so they can never outrank a real candidate."""
    m = manifold_from_spec(spec)
    d = m.dist(q[:, None, :], rows)                       # [B, K] f32
    return jnp.where((idx < 0) | ~jnp.isfinite(scan_d), jnp.inf, d)


def _merge_rescored(d32: jax.Array, idx: jax.Array, k: int):
    """Final ranking: top-k of the f32-rescored candidate buffer."""
    top_negd, sel = jax.lax.top_k(-d32, k)
    return jnp.take_along_axis(idx, sel, axis=1), -top_negd


@partial(jax.jit, static_argnames=("spec", "k", "k_scan", "chunk", "n",
                                   "exclude_self", "mode", "lane"))
def _topk_chunked_mixed(table: jax.Array, scan_table: jax.Array,
                        scan_aux, q_idx: jax.Array, drop=None,
                        q_rows=None, *, spec: tuple,
                        k: int, k_scan: int, chunk: int, n: int,
                        exclude_self: bool, mode: str,
                        lane: str = "dense"):
    """Low-precision table-scan variant of :func:`_topk_chunked`: the
    chunked scan runs over ``scan_table`` (the bf16 copy, the int8/int4
    code, or the PQ code table — half / a quarter / an eighth-and-below
    of the HBM traffic of the dominant pass; ``scan_aux`` is the lane's
    companion: per-row dequant scales for int8/int4, the codebooks for
    pq, ``None`` for bf16) keeping ``k_scan >= k`` candidates, then the
    candidates are gathered from the f32 ``table`` and rescored with
    full-precision manifold distances before the final top-k — so
    returned distances carry f32 accuracy and the boundary-sensitive
    math never runs in low precision on anything that reaches the
    caller.  A ``drop``-masked candidate's scan distance is ``+inf``,
    which :func:`_rescore_f32` preserves — a tombstoned row can never
    re-enter through the rescore."""
    q = table[q_idx] if q_rows is None else q_rows        # [B, D] f32
    # quantized scans keep f32 queries (the table is quantized, not the
    # query rows); the bf16 scan casts them to the scan dtype
    q_scan = q.astype(scan_table.dtype) if lane == "dense" else q
    sd, sidx = _scan_topk(scan_table, q_scan, q_idx, 0, spec=spec,
                          k=k_scan, chunk=chunk, n=n,
                          exclude_self=exclude_self, mode=mode,
                          scale=scan_aux, lane=lane, drop=drop)
    rows = table[jnp.maximum(sidx, 0)]                    # [B, K, D] f32
    d32 = _rescore_f32(spec, rows, q, sidx, sd)
    return _merge_rescored(d32, sidx, k)


@partial(jax.jit, static_argnames=("spec", "k", "k_scan", "chunk", "n",
                                   "exclude_self", "mode", "mesh", "axis",
                                   "lane"))
def _topk_sharded_mixed(table: jax.Array, scan_table: jax.Array,
                        scan_aux, q_idx: jax.Array, drop=None,
                        q_rows=None, *, spec: tuple,
                        k: int, k_scan: int, chunk: int, n: int,
                        exclude_self: bool, mode: str, mesh, axis: str,
                        lane: str = "dense"):
    """Mesh-sharded twin of :func:`_topk_chunked_mixed`: per-shard
    low-precision scan over the local slab (bf16 copy, int8/int4 code +
    per-row scale, or PQ code table — all laid out ``P(axis, None)``
    like the master; PQ codebooks are replicated, they are KB-scale),
    all-gather + merge of the per-shard candidates, then an f32 rescore
    of the merged ``k_scan`` winners (candidate rows assembled from the
    f32 shards by the same psum gather the query rows use) before the
    final top-k."""
    npad = table.shape[0]
    has_drop, has_q = drop is not None, q_rows is not None

    def local_body(tloc, sloc, scl, qi, *extra):
        dr = extra[0] if has_drop else None
        q = (extra[-1] if has_q
             else local_gather(tloc, qi, npad, axis))     # [B, D] f32
        lo = (jax.lax.axis_index(axis) * tloc.shape[0]).astype(jnp.int32)
        qs = q.astype(sloc.dtype) if lane == "dense" else q
        d, i = _scan_topk(sloc, qs, qi, lo, spec=spec,
                          k=k_scan, chunk=chunk, n=n,
                          exclude_self=exclude_self, mode=mode, scale=scl,
                          lane=lane, drop=dr)
        gd = jax.lax.all_gather(d, axis)                  # [S, B, <=k_scan]
        gi = jax.lax.all_gather(i, axis)
        b = qi.shape[0]
        cat_d = jnp.moveaxis(gd, 0, 1).reshape(b, -1)
        cat_i = jnp.moveaxis(gi, 0, 1).reshape(b, -1)
        km = min(k_scan, cat_d.shape[1])
        top_negd, sel = jax.lax.top_k(-cat_d, km)
        sd = -top_negd
        sidx = jnp.take_along_axis(cat_i, sel, axis=1)    # [B, km]
        rows = local_gather(tloc, jnp.maximum(sidx, 0), npad, axis)
        d32 = _rescore_f32(spec, rows, q, sidx, sd)
        idx, dist = _merge_rescored(d32, sidx, k)
        return idx, dist

    # the live hooks ride replicated, like the query ids
    extras = ([drop] if has_drop else []) + ([q_rows] if has_q else [])
    especs = (P(),) * len(extras)
    if scan_aux is None:
        run = shard_map(
            lambda t, s, qi, *ex: local_body(t, s, None, qi, *ex),
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P()) + especs,
            out_specs=(P(), P()), check_vma=False)
        return run(table, scan_table, q_idx, *extras)
    # the aux rides row-sharded beside the code table (per-row scales)
    # — except PQ codebooks, which every shard needs whole
    aux_spec = P() if lane == "pq" else P(axis, None)
    run = shard_map(local_body, mesh=mesh,
                    in_specs=(P(axis, None), P(axis, None),
                              aux_spec, P()) + especs,
                    out_specs=(P(), P()), check_vma=False)
    return run(table, scan_table, scan_aux, q_idx, *extras)


def _cand_dist(spec: tuple, q: jax.Array, rows: jax.Array) -> jax.Array:
    """[B, D] queries × per-query candidate rows [B, C, D] → [B, C].

    The batched form of the distmat closed expressions
    (``kernels/distmat.py`` twins — same math as the slab scan's
    tiles), so the IVF candidate scorer is one einsum Gram plus cheap
    elementwise work instead of an elementwise Möbius chain over
    [B, C, D] (measured ~3× on the CPU twin).  Product manifolds use
    ``Product.dist`` broadcast — the exact trained geometry, like the
    slab scan's product path."""
    from hyperspace_tpu.manifolds import smath

    kind = spec[0]
    prec = jax.lax.Precision.HIGHEST
    if kind == "poincare":
        c = jnp.asarray(spec[1], q.dtype)
        gram = jnp.einsum("bd,bcd->bc", q, rows, precision=prec)
        xx = smath.sq_norm(q)                             # [B, 1]
        yy = smath.sq_norm(rows)[..., 0]                  # [B, C]
        d2 = smath.clamp_min(xx - 2.0 * gram + yy, 0.0)
        den = smath.clamp_min((1.0 - c * xx) * (1.0 - c * yy),
                              smath.eps_for(q.dtype))
        u = 2.0 * c * d2 / den
        return smath.arcosh1p(u) / smath.clamp_min(
            smath.sqrt_c(c), smath.min_norm(q.dtype))
    if kind == "lorentz":
        c = jnp.asarray(spec[1], q.dtype)
        gram = (jnp.einsum("bd,bcd->bc", q[:, 1:], rows[..., 1:],
                           precision=prec)
                - q[:, :1] * rows[..., 0])                # ⟨x, y⟩_L
        u = smath.clamp_min(-c * gram - 1.0, 0.0)
        return smath.arcosh1p(u) / smath.clamp_min(
            smath.sqrt_c(c), smath.min_norm(q.dtype))
    return manifold_from_spec(spec).dist(q[:, None, :], rows)


def _scan_topk_cand(scan_table: jax.Array, q: jax.Array, cand: jax.Array,
                    q_idx: jax.Array, *, spec: tuple, k: int, chunk: int,
                    exclude_self: bool, mode: str = "two_stage",
                    scale=None, lane: str = "dense", drop=None):
    """Chunked top-k over per-query candidate ids — the IVF in-cell
    scorer.  The two-stage machinery of :func:`_scan_topk` (per-chunk
    ``lax.top_k`` over the tile only, one post-scan merge, the running
    k-th-distance threshold prune), re-aimed: instead of walking a
    shared table slab, each chunk gathers every query's OWN candidate
    rows (``cand`` [B, C] int32, a chunk multiple wide, ``-1`` =
    padding) and scores them with :func:`_cand_dist` (per-query rows
    can't use the shared-row kernel tiles).  Returns
    ``(dists ascending, ids int32)``, each ``[B, min(k, C)]``; padded /
    self slots are ``+inf``/``-1`` and can never outrank a real row.
    """
    b, ctot = cand.shape
    nchunks = ctot // chunk
    q_lift = None
    if lane == "pq":
        from hyperspace_tpu.serve.index import _lift, _lift_dim

        lift_dim = _lift_dim(spec, q.shape[1])
        q_lift = _lift(spec, q).astype(jnp.float32)

    # the packed lanes have no fused candidate variant (the per-query
    # gather dominates; unpack/decode rides the two-stage scorer); a
    # tombstone-masked scan likewise rides the two-stage scorer
    if mode == "fused" and lane in ("dense", "int8") and drop is None:
        from hyperspace_tpu.kernels import scan_topk as fused_kernel

        if fused_kernel.supports_cand(spec, k=k, dim=scan_table.shape[1],
                                      cand=ctot):
            d, i = fused_kernel.scan_topk_cand(
                scan_table, cand, q, q_idx, spec=spec, k=k,
                exclude_self=exclude_self, scale=scale)
            ko = min(k, ctot)
            return d[:, :ko], i[:, :ko]
    if mode == "fused":
        mode = "two_stage"  # capability fallback — bit-identical path

    def masked_tile(i):
        ids = jax.lax.dynamic_slice_in_dim(cand, i * chunk, chunk, axis=1)
        safe = jnp.maximum(ids, 0)
        rows = scan_table[safe]                 # [B, chunk, D|hw|m]
        if lane == "pq":
            recon = _pq_decode_rows(scale, rows, lift_dim)
            d = _pq_lift_dist(spec, q_lift, recon)        # [B, chunk]
        else:
            if lane == "int4":
                rows = _int4_rows_f32(rows, scale[safe], q.shape[1])
            elif scale is not None:
                # int8 lane: gather each candidate's dequant scale too
                rows = rows.astype(jnp.float32) * scale[safe]
            d = _cand_dist(spec, q, rows)                 # [B, chunk]
        mask = ids < 0
        if exclude_self:
            mask = mask | (ids == q_idx[:, None])
        if drop is not None:
            # tombstone/supersede penalty, gathered per candidate id
            d = d + drop[safe].astype(d.dtype)
        return jnp.where(mask, jnp.inf, d), ids

    return _two_stage_core(masked_tile, b=b, nchunks=nchunks, k=k,
                           kc=min(k, chunk), ko=min(k, ctot),
                           dtype=(jnp.float32
                                  if lane != "dense" or scale is not None
                                  else scan_table.dtype))


@partial(jax.jit, static_argnames=("spec", "k", "k_scan", "nprobe", "chunk",
                                   "exclude_self", "mixed", "mode", "lane"))
def _topk_ivf(table: jax.Array, scan_table: jax.Array,
              centroids: jax.Array,
              cells: jax.Array, q_idx: jax.Array, drop=None, q_rows=None,
              *, spec: tuple, k: int,
              k_scan: int, nprobe: int, chunk: int, exclude_self: bool,
              mixed: bool, mode: str = "two_stage", scan_scale=None,
              lane: str = "dense"):
    """IVF probing top-k: centroid scoring → nearest-``nprobe`` cell
    gather → two-stage candidate scan (docs/serving.md "Approximate
    retrieval").  One executable per (batch, k, nprobe, spec) — same
    compile contract as the exact programs.

    The candidate scan runs over ``scan_table`` (the bf16 copy when
    ``mixed``), and the merged ``k_scan`` winners are then rescored
    with f32 manifold distances against the f32 ``table`` before the
    final ranking — PR 5's scan-then-rescore, unchanged.  Since the
    cells partition the table, a probed candidate appears at most once:
    no dedup pass is needed.  Cells holding fewer than ``k`` reachable
    rows surface ``-1``/``+inf`` slots rather than wrong neighbors —
    the engine wrapper (:meth:`QueryEngine._probe_topk`) turns those
    into a loud ValueError, never a served answer.
    """
    q = table[q_idx] if q_rows is None else q_rows        # [B, D] f32
    dc = _tile_dist(spec, q, centroids)                   # [B, ncells]
    _, cell_sel = jax.lax.top_k(-dc, nprobe)              # [B, nprobe]
    cand = cells[cell_sel].reshape(q_idx.shape[0], -1)    # [B, nprobe*mc]
    pad = -cand.shape[1] % chunk
    if pad:
        cand = jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)
    # quantized scans keep f32 queries (rows dequantize in the scorer)
    qs = q.astype(scan_table.dtype) if lane == "dense" else q
    sd, sidx = _scan_topk_cand(scan_table, qs, cand, q_idx, spec=spec,
                               k=(k_scan if mixed else k), chunk=chunk,
                               exclude_self=exclude_self, mode=mode,
                               scale=scan_scale, lane=lane, drop=drop)
    if not mixed:
        return sidx, sd
    rows = table[jnp.maximum(sidx, 0)]                    # [B, K, D] f32
    d32 = _rescore_f32(spec, rows, q, sidx, sd)
    return _merge_rescored(d32, sidx, k)


def _fermi_dirac(d: jax.Array, r, t) -> jax.Array:
    """The HGCN LP head's link decoder — the ONE definition both the
    single-device and sharded scoring programs trace, so the 1-device
    bitwise guarantee can never mask a divergence between copies."""
    return 1.0 / (jnp.exp((jnp.square(d) - r) / t) + 1.0)


@partial(jax.jit, static_argnames=("spec", "prob"))
def _edge_dist(table: jax.Array, u_idx: jax.Array, v_idx: jax.Array,
               fd_r, fd_t, *, spec: tuple, prob: bool) -> jax.Array:
    m = manifold_from_spec(spec)
    d = m.dist(table[u_idx], table[v_idx])
    if prob:
        # Fermi–Dirac decoder INSIDE the jitted program: one dispatch
        # per scoring request, not one per arithmetic op (fd_r/fd_t are
        # traced scalars — changing them never recompiles)
        d = _fermi_dirac(d, fd_r, fd_t)
    return d


@partial(jax.jit, static_argnames=("spec", "prob"))
def _edge_dist_rows(xu: jax.Array, xv: jax.Array, fd_r, fd_t, *,
                    spec: tuple, prob: bool) -> jax.Array:
    """Edge scoring over explicit endpoint rows (the live-index path:
    serve/delta.py gathers FRESH rows from the mutable master instead of
    the frozen device table, so post-upsert scores are current)."""
    m = manifold_from_spec(spec)
    d = m.dist(xu, xv)
    if prob:
        d = _fermi_dirac(d, fd_r, fd_t)
    return d


@partial(jax.jit, static_argnames=("spec", "prob", "mesh", "axis"))
def _edge_dist_sharded(table: jax.Array, u_idx: jax.Array, v_idx: jax.Array,
                       fd_r, fd_t, *, spec: tuple, prob: bool, mesh,
                       axis: str) -> jax.Array:
    """Edge scoring over a row-sharded table: two psum gathers assemble
    the endpoint rows, then the distance math runs replicated."""
    npad = table.shape[0]

    def local(tloc, u, v, r, t):
        xu = local_gather(tloc, u, npad, axis)
        xv = local_gather(tloc, v, npad, axis)
        m = manifold_from_spec(spec)
        d = m.dist(xu, xv)
        if prob:
            d = _fermi_dirac(d, r, t)
        return d

    run = shard_map(local, mesh=mesh,
                    in_specs=(P(axis, None), P(), P(), P(), P()),
                    out_specs=P(), check_vma=False)
    return run(table, u_idx, v_idx, jnp.asarray(fd_r), jnp.asarray(fd_t))


class QueryEngine:
    """Batched k-NN / edge-score queries over one frozen table.

    ``table`` is moved to device once (zero-padded to a chunk multiple;
    with a ``mesh`` it is row-sharded over ``mesh_axis`` and padded to a
    chunk-per-shard multiple); every query after that is a single jitted
    dispatch.  Construct via :meth:`from_artifact` for the serving path,
    or directly on a live table (tests, the round-trip lint).

    ``scan_mode`` picks the chunk-scan strategy (``"two_stage"``
    default, ``"carry"`` for the original running-top-k variant,
    ``"fused"`` for the Pallas scan-top-k kernel — rank-identical
    answers, no HBM distance tiles; unsupported specs/shapes fall back
    to two-stage bit-identically — see the module docstring).
    ``mesh=None`` (or a mesh whose model axis has one device) runs the
    single-device program.

    ``precision`` picks the table-scan dtype policy (docs/precision.md):
    ``"f32"`` (default) is the exact pre-policy program, bit-identical;
    ``"bf16"`` keeps a bf16 copy of the padded table beside the f32 one
    and scans THAT (half the HBM traffic of the dominant pass), keeping
    ``k + max(k, 8)`` candidates which are then rescored with f32
    manifold distances against the f32 table before the final ranking —
    returned distances are always f32-accurate, and a near-tie the bf16
    pass mis-ranks at the k-th boundary is recovered by the over-fetch.
    ``"int8"`` is the same shape at a quarter of the table bytes: a
    per-row symmetric int8 code + per-row f32 scale (``serve/quant.py``)
    replace the scan copy, tiles dequantize in-register, and the coarse
    pass keeps ``k + max(4k, 32)`` candidates for the f32 rescore
    (docs/serving.md "Quantized scan lane").  ``"int4"`` packs two
    signed nibbles per byte beside a per-row f16 scale (~an eighth of
    f32), and ``"pq"`` stores one byte per subspace against
    hyperbolic-aware codebooks trained in the tangent/Lorentz lift
    (serve/quant.py; ``quant=`` accepts a precomputed payload, e.g.
    from an artifact) — both serve through the same over-fetch +
    f32-rescore machinery at the wider ``k + max(16k, 128)``
    window, so returned ranks and distances
    always come from full-precision manifold math (docs/serving.md
    "Sub-int8 lanes").  Edge scoring
    (``score_edges``) is always f32: it is two cheap
    gathers plus one distance per pair, with no table scan to save.

    ``index=`` + ``nprobe=`` turn on **IVF probing** (docs/serving.md
    "Approximate retrieval"): queries score against the index's
    hyperbolic-k-means centroids, gather the nearest ``nprobe`` cells'
    rows, and run the two-stage candidate scan (+ f32 rescore under
    ``precision=bf16``) over those instead of the whole table —
    sub-linear work per query at a recall cost ``bench_serve`` tracks.
    Exact-fallback rules (the engine then IS the exact executable):
    ``nprobe=0``; ``nprobe >= ncells`` (degenerate probe — covering
    every cell is the exact answer, so the exact program serves it
    bit-identically); tables under ``IVF_MIN_TABLE_ROWS``; any mesh
    with >1 shard (probing is single-device — raise ``nprobe=`` there
    is an error, not a silent slowdown).  ``scan_strategy`` /
    ``scan_signature`` expose which program answers — the batcher's
    cache key and ``stats()`` carry them.
    """

    def __init__(self, table, manifold_spec: tuple, *,
                 fingerprint: Optional[str] = None,
                 chunk_rows: int = 0,
                 tile_budget: int = DEFAULT_TILE_BUDGET,
                 mesh=None, mesh_axis: str = "model",
                 scan_mode: str = "two_stage",
                 precision: str = "f32",
                 index=None, nprobe: int = 0,
                 quant=None, pq_m: int = 0):
        table = np.ascontiguousarray(np.asarray(table))
        if table.ndim != 2:
            raise ValueError(f"table must be [N, D]; got {table.shape}")
        if scan_mode not in SCAN_MODES:
            raise ValueError(
                f"scan_mode must be one of {SCAN_MODES}; got {scan_mode!r}")
        if precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}; got {precision!r}")
        self.num_nodes, self.dim = (int(s) for s in table.shape)
        self.spec = tuple(manifold_spec)
        self.scan_mode = scan_mode
        self.precision = precision
        # int8/int4/pq are serve-only scan lanes (serve/quant.py), not
        # precision-policy presets: the policy object stays f32 (master
        # table, rescore math) and the quantized copy rides beside it
        self._quant = precision in QUANT_PRECISIONS
        self._policy = precision_mod.get_policy(
            "f32" if self._quant else precision)
        # the static lane tag the jitted programs key on ("dense" covers
        # f32 AND bf16 — the slab dtype distinguishes those)
        self._lane = precision if self._quant else "dense"
        # quant= accepts a serve/artifact.py QuantPayload (precomputed
        # codes, e.g. shipped inside an artifact); it is consulted only
        # when its lane matches the requested precision — an artifact
        # may carry an int4 payload while this engine serves f32
        self._payload = None
        if quant is not None and getattr(quant, "lane", None) == precision:
            if int(quant.num_nodes) != self.num_nodes:
                raise ValueError(
                    f"quant payload covers {quant.num_nodes} rows; table "
                    f"has {self.num_nodes} — re-export for THIS table")
            self._payload = quant
        self.fingerprint = fingerprint or fingerprint_of(table, self.spec)
        self.mesh, self.mesh_axis = mesh, mesh_axis
        shards = 1
        if mesh is not None:
            if mesh_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh has no {mesh_axis!r} axis (axes: "
                    f"{mesh.axis_names}); pass mesh_axis=")
            shards = int(mesh.shape[mesh_axis])
        self.shards = shards
        chunk_rows = int(chunk_rows)
        if chunk_rows < 0:
            # a negative chunk would make the scan run ZERO chunks and
            # silently answer every query with -1/inf
            raise ValueError(f"chunk_rows must be >= 0 (0 = auto); "
                             f"got {chunk_rows}")
        from hyperspace_tpu.kernels import scan_topk as fused_kernel

        # PQ geometry is fixed before chunk sizing: the fused gate and
        # the VMEM footprint depend on the subspace count m
        self._pq_m = 0
        if precision == "pq":
            from hyperspace_tpu.serve.index import _lift_dim
            from hyperspace_tpu.serve.quant import default_pq_m

            # a payload's trained geometry wins; pq_m= retunes the
            # bytes/fidelity trade only when the engine trains fresh
            self._pq_m = (int(self._payload.params["m"])
                          if self._payload is not None
                          else int(pq_m)
                          or default_pq_m(_lift_dim(self.spec, self.dim)))
        # fused-capable = the family/dim the fused kernel can serve; k-
        # level fallback (oversized k per call) is decided per dispatch.
        # An engine whose spec is NOT fused-capable keeps the default
        # two-stage chunk sizing and executable — bit-identical fallback
        self._fused_kind = (scan_mode == "fused"
                            and fused_kernel.kind_supported(self.spec)
                            and self.dim <= fused_kernel.FUSED_MAX_DIM)
        if precision == "pq" and self._fused_kind:
            # the PQ kernel is gated on the subspace count, not the dim
            # (its tiles are [bm, m] codes, never [bm, D] rows)
            self._fused_kind = self._pq_m <= fused_kernel.FUSED_MAX_PQ_M
        scan_dtype = (jnp.uint8 if precision in ("int4", "pq")
                      else jnp.int8 if self._quant
                      else self._policy.compute if self._policy.mixed
                      else jnp.float32)
        # the packed lanes size their fused tiles off their own VMEM
        # footprint branches (packed width / code+LUT blocks)
        sizing_dim = 128 if precision == "pq" else self.dim
        self.chunk_rows = chunk_rows or auto_chunk_rows(
            sizing_dim, self.spec[0], self.num_nodes, tile_budget,
            scan_mode=("fused" if self._fused_kind else "two_stage"),
            dtype=scan_dtype, lane=self._lane, pq_m=self._pq_m)
        if self._fused_kind and (
                self.chunk_rows % 128
                or self.chunk_rows > fused_kernel.fused_tile_rows(
                    sizing_dim, scan_dtype, fused_kernel.FUSED_MAX_K,
                    allow_tuned=False, lane=self._lane, pq_m=self._pq_m)):
            # allow_tuned=False: this check is the VMEM-FIT bound (what
            # a real chip's Mosaic would accept), not the autotuner's
            # speed preference — a tuned table picking a SMALLER tile
            # must not demote an explicit chunk_rows the model fits
            # a user chunk_rows off the 128 grid can never stream, and
            # one past the kernel's VMEM footprint model would compile
            # only on the CPU twin (Mosaic would reject the tile on a
            # real chip) — demote the ENGINE: it must advertise itself
            # as what it actually serves (scan_signature without the
            # "fused" marker) and dispatch two-stage everywhere, IVF
            # probes included, not just where a per-call gate happens
            # to catch it
            self._fused_kind = False
        # the mode every dispatch actually uses: a demoted fused engine
        # IS the two-stage executable (bit-identical fallback contract)
        self._scan_mode_eff = (scan_mode
                               if scan_mode != "fused" or self._fused_kind
                               else "two_stage")
        # each shard's slab must itself be a chunk multiple, so the
        # padded table is a (chunk × shards) multiple (shards=1: the
        # original chunk-multiple padding, bit-identical layout)
        padded = _round_up(self.num_nodes, self.chunk_rows * shards)
        if padded > self.num_nodes:
            table = np.concatenate(
                [table, np.zeros((padded - self.num_nodes, self.dim),
                                 table.dtype)], axis=0)
        if shards > 1:
            # [padded, D] row-sharded: each device holds padded/S rows
            self.table = jax.device_put(
                table, table_sharding(mesh, mesh_axis))
        else:
            self.table = jnp.asarray(table)  # [padded, D] device-resident
        # the low-precision scan copy lives beside the f32 table (same
        # layout/sharding) — built ONCE here, not per query; the f32
        # policy aliases the table so the default path holds one array
        self.scan_scale = None
        self.pq_codebooks = None
        self._pq_fp = None
        if self._quant:
            put = ((lambda a: jax.device_put(
                a, table_sharding(mesh, mesh_axis)))
                if shards > 1 else jnp.asarray)
            pad_rows = padded - self.num_nodes

            def _pad0(a):
                # payload arrays cover the UNPADDED table; grow them
                # with zero rows (zero codes/scales dequantize to exact
                # zeros — and padded rows are masked by index anyway)
                if not pad_rows:
                    return np.ascontiguousarray(a)
                return np.concatenate(
                    [a, np.zeros((pad_rows,) + a.shape[1:], a.dtype)],
                    axis=0)

            if precision == "int8":
                from hyperspace_tpu.serve.quant import quantize_rows

                # quantize the PADDED table: zero padding rows get scale
                # 0 and dequantize to exact zeros, like the f32 padding
                q8, sc = quantize_rows(table)
                self.scan_table, self.scan_scale = put(q8), put(sc)
            elif precision == "int4":
                from hyperspace_tpu.serve.quant import pack_int4_rows

                if self._payload is not None:
                    pk = _pad0(self._payload.arrays["packed"])
                    sc = _pad0(self._payload.arrays["scale"])
                else:
                    pk, sc = pack_int4_rows(table)
                # the scale stays f16 resident (the lane's byte budget);
                # both scan paths cast to f32 at the point of use
                self.scan_table, self.scan_scale = put(pk), put(sc)
            else:  # pq
                from hyperspace_tpu.serve.quant import (build_pq,
                                                        pq_fingerprint_of)

                if self._payload is not None:
                    codes = _pad0(self._payload.arrays["codes"])
                    cb = np.asarray(self._payload.arrays["codebooks"],
                                    np.float32)
                    pp = self._payload.params
                    self._pq_fp = pq_fingerprint_of(
                        cb, lift_dim=int(pp["lift_dim"]),
                        iters=int(pp["iters"]), seed=int(pp["seed"]))
                else:
                    # train on the UNPADDED rows (pad rows would skew
                    # the subspace k-means), pad the codes after
                    codes, cbk = build_pq(table[:self.num_nodes],
                                          self.spec, m=self._pq_m)
                    codes, cb = _pad0(codes), cbk.codebooks
                    self._pq_fp = cbk.fingerprint
                self.scan_table = put(codes)
                # codebooks are KB-scale: replicated, never sharded
                self.pq_codebooks = jnp.asarray(cb, jnp.float32)
        elif self._policy.mixed:
            scan_np = table.astype(self._policy.compute)
            self.scan_table = (
                jax.device_put(scan_np, table_sharding(mesh, mesh_axis))
                if shards > 1 else jnp.asarray(scan_np))
        else:
            self.scan_table = self.table

        # --- IVF probing (docs/serving.md "Approximate retrieval") ---
        from hyperspace_tpu.serve.index import IVF_MIN_TABLE_ROWS
        self.index, self.nprobe = index, int(nprobe)
        if self.nprobe < 0:
            raise ValueError(f"nprobe must be >= 0; got {nprobe}")
        if self.nprobe > 0 and index is None:
            raise ValueError(
                "nprobe > 0 needs an IVF index (build one with "
                "serve.index.build_index, or export with index=1)")
        if index is not None:
            if int(index.num_nodes) != self.num_nodes:
                raise ValueError(
                    f"index was built over {index.num_nodes} rows; "
                    f"table has {self.num_nodes}")
            if int(index.centroids.shape[1]) != self.dim:
                raise ValueError(
                    f"index centroid width {index.centroids.shape[1]} "
                    f"!= table width {self.dim}")
            if self.nprobe > 0 and shards > 1:
                raise ValueError(
                    "IVF probing is single-device; drop mesh= or nprobe= "
                    "(a sharded table answers by exact scan)")
        self._ivf = (index is not None and 0 < self.nprobe < index.ncells
                     and self.num_nodes >= IVF_MIN_TABLE_ROWS)
        if self._ivf:
            self._centroids = jnp.asarray(index.centroids, jnp.float32)
            self._cells = jnp.asarray(index.cells, jnp.int32)
            # candidate chunks gather [B, chunk, D] rows per tile — the
            # product-path footprint whatever the family — but unlike
            # the slab scan there is no resident table sharing the
            # budget, so the tile gets 4× of it; measured sweet spot on
            # the CPU twin (chunk 512 at D=16: 1.5× over 128)
            self._cand_chunk = auto_chunk_rows(
                self.dim, "product", self.nprobe * index.max_cell,
                4 * tile_budget)

    @property
    def scan_strategy(self) -> str:
        """``"ivf"`` when queries probe the index, else ``"exact"``
        (covers every fallback rule — what `batcher.stats()` reports)."""
        return "ivf" if self._ivf else "exact"

    @property
    def scan_signature(self) -> tuple:
        """Result-identity of the scan path: ``("exact",)`` or
        ``("ivf", nprobe, index fingerprint)`` — a batcher cache-key
        element, so exact and probed rows (or rows probed through two
        different indexes) never cross-contaminate.  A fused-capable
        engine appends ``"fused"``: fused answers are rank-identical to
        the two-stage scan but only ulp-close in distance, so its cached
        rows must never be served back as two-stage rows (or vice
        versa) over the same table."""
        sig = (("ivf", self.nprobe, self.index.fingerprint) if self._ivf
               else ("exact",))
        return sig + self._lane_markers()

    def scan_signature_for(self, nprobe: int) -> tuple:
        """The signature :attr:`scan_signature` would have at an
        overridden probe width — the degradation ladder's cache-key hook
        (``serve/batcher.py``): narrowed-width rows carry the narrowed
        signature, fused and lane markers included."""
        sig = ("ivf", int(nprobe), self.index.fingerprint)
        return sig + self._lane_markers()

    def _lane_markers(self) -> tuple:
        """Result-identity suffixes shared by every signature variant:
        ``"fused"`` (rank-identical but only ulp-close distances) and
        the quantized scan lane (``"int8"``/``"int4"``, or ``("pq",
        codebook fingerprint)`` — different candidate sets than the f32
        or bf16 scans, and two PQ engines with different codebooks
        produce different candidate sets, so the fingerprint rides in
        the key; quantized rows must never be served back as
        full-precision rows, whatever else the cache key carries)."""
        lane = ()
        if self._quant:
            lane = (("pq", self._pq_fp) if self.precision == "pq"
                    else (self.precision,))
        return (("fused",) if self._fused_kind else ()) + lane

    def _k_scan(self, k: int, cap: int) -> int:
        """Over-fetch width of the low-precision coarse scan: the f32
        rescore can only repair a k-th-boundary mis-rank that is IN the
        candidate set.  int8 gets a wider margin than bf16 (coarser
        quantization step), int4/pq wider still — a 4-bit step / a
        per-subspace codebook error dominates neighbor gaps at serve
        densities (docs/serving.md)."""
        if self.precision == "pq":
            return min(k + max(_PQ_RESCORE_MULT * k,
                               _PQ_RESCORE_MIN), cap)
        if self.precision == "int4":
            return min(k + max(_INT4_RESCORE_MULT * k,
                               _INT4_RESCORE_MIN), cap)
        if self._quant:
            return min(k + max(_QUANT_RESCORE_MULT * k,
                               _QUANT_RESCORE_MIN), cap)
        return min(k + max(k, _RESCORE_PAD), cap)

    @classmethod
    def from_artifact(cls, art: ServingArtifact, **kw) -> "QueryEngine":
        kw.setdefault("index", art.index)
        kw.setdefault("quant", getattr(art, "quant", None))
        return cls(art.table, art.manifold_spec,
                   fingerprint=art.fingerprint, **kw)

    @property
    def _scan_aux(self):
        """The scan lane's traced companion operand: per-row dequant
        scales (int8/int4), the PQ codebooks, or None (f32/bf16)."""
        return (self.pq_codebooks if self.precision == "pq"
                else self.scan_scale)

    # --- queries --------------------------------------------------------------

    def topk_neighbors(self, q_idx, k: int, *, exclude_self: bool = True,
                       nprobe: int | None = None, q_rows=None, drop=None,
                       allow_underfill: bool = False):
        """``(neighbors [B, k] int32, dists [B, k])`` for query row ids.

        Results are sorted ascending by distance.  ``k`` must leave room
        in the table (``k <= N - exclude_self``); ids are validated on
        host — a bad id must fail the request, not gather a clipped row.

        ``nprobe`` (probing engines only) overrides the configured probe
        width for THIS call, within ``[1, self.nprobe]`` — the
        degradation ladder's lever (docs/resilience.md): under pressure
        the batcher steps the width down toward its floor without
        rebuilding the engine.  Each distinct width is one extra
        compiled program (bounded by the ladder's few levels); answers
        at a narrower width are coarser, and the batcher's cache key
        carries the effective width so they never mix with full-width
        rows.  Exact engines reject an override — a silent ignore would
        misreport the quality served.

        ``q_rows`` / ``drop`` / ``allow_underfill`` are the live-index
        hooks (serve/delta.py).  ``q_rows`` ([B, D] f32) supplies the
        query vectors explicitly — fresh post-upsert rows from the
        mutable master — instead of gathering the (possibly stale)
        frozen device rows by id; ids are then used only for the
        exclude-self mask and may exceed this engine's row range.
        ``drop`` ([npad] f32, 0 = live / +inf = tombstoned) is a TRACED
        penalty row added to every scan tile before top-k so a deleted
        or superseded master row can never win — values change per
        mutation generation without recompiling.  ``allow_underfill``
        lets a probing engine return +inf filler rows instead of
        raising, so the caller's merge with a delta segment can repair
        them (and raise only if the MERGED top-k is still under-filled).
        """
        if q_rows is None:
            q_idx = self._check_ids(q_idx, "q_idx")
        else:
            arr = np.asarray(q_idx)
            if arr.ndim != 1 or arr.size == 0:
                raise ValueError("q_idx must be a non-empty 1-D id array")
            if not np.issubdtype(arr.dtype, np.integer):
                raise ValueError(
                    f"q_idx must be integer ids; got {arr.dtype}")
            q_rows = jnp.asarray(q_rows, self.table.dtype)
            if q_rows.ndim != 2 or q_rows.shape[0] != arr.size:
                raise ValueError(
                    f"q_rows {q_rows.shape} must be [B, D] aligned with "
                    f"q_idx (B={arr.size})")
            q_idx = jnp.asarray(arr, jnp.int32)
        if drop is not None:
            drop = jnp.asarray(drop, self.table.dtype)
            if drop.shape != (self.table.shape[0],):
                raise ValueError(
                    f"drop mask shape {drop.shape} must match the padded "
                    f"table rows ({self.table.shape[0]},)")
        k = int(k)
        limit = self.num_nodes - (1 if exclude_self else 0)
        if not 1 <= k <= limit:
            raise ValueError(
                f"k={k} out of range [1, {limit}] for a {self.num_nodes}-row "
                f"table (exclude_self={exclude_self})")
        if nprobe is not None and not self._ivf:
            raise ValueError(
                "nprobe override needs a probing engine (this one "
                "answers by exact scan)")
        # the "device_compute" span stage: the whole fused program —
        # scan + f32 rescore + merge run inside ONE jit executable, so
        # this window is the engine's full device dispatch; inside a
        # span scope the results are forced before the stage closes, so
        # the window times execution, not async enqueue (spans off:
        # a shared no-op context manager, nothing blocks)
        with spans.stage("device_compute",
                         metric="serve/stage/device_compute_ms"):
            if self._ivf:
                out = self._probe_topk(q_idx, k, exclude_self=exclude_self,
                                       nprobe=nprobe, drop=drop,
                                       q_rows=q_rows,
                                       allow_underfill=allow_underfill)
            elif self._policy.mixed or self._quant:
                # over-fetch margin: the low-precision scan keeps k_scan
                # candidates so the f32 rescore can repair k-th-boundary
                # near-ties (wider for int8 — coarser quantization)
                k_scan = self._k_scan(k, self.num_nodes)
                if self.shards > 1:
                    out = _topk_sharded_mixed(
                        self.table, self.scan_table, self._scan_aux, q_idx,
                        drop, q_rows,
                        spec=self.spec, k=k, k_scan=k_scan,
                        chunk=self.chunk_rows,
                        n=self.num_nodes, exclude_self=exclude_self,
                        mode=self._scan_mode_eff, mesh=self.mesh,
                        axis=self.mesh_axis, lane=self._lane)
                else:
                    out = _topk_chunked_mixed(
                        self.table, self.scan_table, self._scan_aux, q_idx,
                        drop, q_rows,
                        spec=self.spec, k=k,
                        k_scan=k_scan, chunk=self.chunk_rows,
                        n=self.num_nodes,
                        exclude_self=exclude_self, mode=self._scan_mode_eff,
                        lane=self._lane)
            elif self.shards > 1:
                out = _topk_sharded(
                    self.table, q_idx, drop, q_rows, spec=self.spec, k=k,
                    chunk=self.chunk_rows, n=self.num_nodes,
                    exclude_self=exclude_self, mode=self._scan_mode_eff,
                    mesh=self.mesh, axis=self.mesh_axis)
            else:
                out = _topk_chunked(
                    self.table, q_idx, drop, q_rows, spec=self.spec, k=k,
                    chunk=self.chunk_rows,
                    n=self.num_nodes, exclude_self=exclude_self,
                    mode=self._scan_mode_eff)
            if spans.active():
                jax.block_until_ready(out)
        return out

    def _probe_topk(self, q_idx: jax.Array, k: int, *, exclude_self: bool,
                    nprobe: int | None = None, drop=None, q_rows=None,
                    allow_underfill: bool = False):
        """The probing path: validate capacity, dispatch
        :func:`_topk_ivf`, record the probe telemetry
        (``serve/index_probe_ms``: host wall-clock around the dispatch —
        on CPU, execution; ``serve/recall_candidates``: candidate slots
        gathered, the work the probe actually did vs the exact scan's
        ``B × N``).  ``nprobe`` narrows the probe for this call (the
        ladder's lever; validated against the configured width)."""
        p = self.nprobe if nprobe is None else int(nprobe)
        if not 1 <= p <= self.nprobe:
            raise ValueError(
                f"nprobe override {p} out of range [1, {self.nprobe}] "
                "(wider than configured would gather rows the resident "
                "chunking was not sized for)")
        capacity = p * self.index.max_cell
        if capacity < k:
            raise ValueError(
                f"k={k} exceeds the probe capacity nprobe×max_cell = "
                f"{p}×{self.index.max_cell} = {capacity}; "
                "raise nprobe=")
        k_scan = k
        if self._policy.mixed or self._quant:
            k_scan = self._k_scan(k, capacity)
        t0 = time.perf_counter()
        idx, dist = _topk_ivf(
            self.table, self.scan_table,
            self._centroids, self._cells,
            q_idx, drop, q_rows, spec=self.spec, k=k, k_scan=k_scan,
            nprobe=p,
            chunk=self._cand_chunk, exclude_self=exclude_self,
            mixed=self._policy.mixed or self._quant,
            mode=self._scan_mode_eff, scan_scale=self._scan_aux,
            lane=self._lane)
        telem.observe("serve/index_probe_ms",
                      (time.perf_counter() - t0) * 1e3)
        telem.inc("serve/recall_candidates", int(q_idx.shape[0]) * capacity)
        # under-filled probe: some query's nprobe nearest cells held
        # fewer than k reachable rows, so filler reached the top-k —
        # not an answer (docs/serving.md), and +inf would break the
        # serve protocol's JSON.  The distance is the reliable tell
        # (a padded slot carries -1 OR a masked self id, but always
        # +inf).  Fail loudly like the capacity check (a scalar fetch;
        # callers fetch these results next anyway, and the serve loop
        # isolates it per request)
        if not allow_underfill and \
                bool(jax.device_get(jnp.any(jnp.isinf(dist)))):
            raise ValueError(
                f"IVF probe under-filled: some query's {p} "
                f"nearest cell(s) hold fewer than k={k} reachable rows "
                "(sparse/empty cells, or exclude_self masking one) — "
                "raise nprobe= or rebuild the index with more balance")
        return idx, dist

    def score_edges(self, u_idx, v_idx, *, prob: bool = False,
                    fd_r: float = 2.0, fd_t: float = 1.0):
        """Per-pair manifold distances ``d(table[u], table[v])`` ([B]).

        ``prob=True`` maps distances through the Fermi–Dirac link
        decoder ``1 / (exp((d² − r)/t) + 1)`` (the HGCN LP head's form)
        — monotone decreasing in distance, so rankings agree.
        """
        u_idx = self._check_ids(u_idx, "u_idx")
        v_idx = self._check_ids(v_idx, "v_idx")
        if u_idx.shape != v_idx.shape:
            raise ValueError(
                f"u_idx {u_idx.shape} and v_idx {v_idx.shape} must match")
        with spans.stage("device_compute",
                         metric="serve/stage/device_compute_ms"):
            if self.shards > 1:
                out = _edge_dist_sharded(
                    self.table, u_idx, v_idx, fd_r, fd_t,
                    spec=self.spec, prob=bool(prob),
                    mesh=self.mesh, axis=self.mesh_axis)
            else:
                out = _edge_dist(self.table, u_idx, v_idx, fd_r, fd_t,
                                 spec=self.spec, prob=bool(prob))
            if spans.active():
                jax.block_until_ready(out)
        return out

    def _check_ids(self, ids, name: str) -> jax.Array:
        arr = np.asarray(ids)
        if arr.ndim != 1 or arr.size == 0:
            raise ValueError(f"{name} must be a non-empty 1-D id array")
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(f"{name} must be integer ids; got {arr.dtype}")
        if arr.min() < 0 or arr.max() >= self.num_nodes:
            raise ValueError(
                f"{name} out of range [0, {self.num_nodes}): "
                f"min={arr.min()}, max={arr.max()}")
        return jnp.asarray(arr, jnp.int32)
