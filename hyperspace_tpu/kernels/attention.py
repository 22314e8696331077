"""Flash-style hyperbolic attention kernel (reference CUDA kernel N7).

Scores are affine in squared Lorentz distance (Gulcehre et al. 2019 /
HyboNet),   s(q,k) = (−d²_L(q,k) + β)/τ = (2/c + 2⟨q,k⟩_L + β)/τ ,
and values aggregate to the **Lorentz centroid** (Law et al. 2019) of the
softmax weights.  Because the centroid numerator is a plain weighted sum,
the flash-attention online-softmax recurrence carries over unchanged from
the Euclidean kernel — only the epilogue differs (a Minkowski-norm
row-rescale instead of nothing).  See SURVEY.md §2 N7 and §5
"Long-context": the same recurrence, fed by ``ppermute`` instead of HBM,
is ring attention (hyperspace_tpu/parallel/ring.py).

Kernel shape: grid (batch·heads, Q blocks, KV blocks), KV innermost and
sequential; scratch carries (running max, denominator, centroid
numerator) per Q block.  Scores and accumulation are f32 regardless of
input dtype; the two matmuls per tile (Minkowski Gram, weight × V) hit
the MXU.

β and τ must be constant per (batch, head) — per-position values fall
back to the XLA twin.

**Backward (r04, VERDICT r3 #4):** a recomputing flash backward replaces
the dense-twin VJP on the kernel path.  The forward additionally emits
per-row ``lse`` (softmax log-sum-exp) and the centroid Minkowski norm;
the backward is the Lorentz-epilogue VJP (elementwise, XLA) followed by
two Pallas kernels — dq (KV inner, recomputes the score tile and
weights from lse) and dk/dv (Q inner) — so the [Nq, Nk] score matrix is
never materialized in EITHER direction: backward peak memory is
O(N·D + blocks), not O(N²).  dβ/dτ/dc fold out of per-Q-block partial
sums the dq kernel also emits.  The XLA twin (CPU / per-position β,τ)
keeps plain autodiff.

**Two score forms, one recurrence:** the three kernel bodies take a
static ``_Form``.  ``lorentz`` is everything above, bit for bit what it
was before the second form came.  ``dot`` (:func:`flash_dot_attention`,
the looped language model's attention) is the scaled dot product: score
``q·k × scale``, no epilogue, no c/β/τ, matmuls on the operands' dtype
(bf16: one MXU pass) with float32 scores and accumulators, and a
``causal`` structure built into the block ranges of all three kernels —
a block wholly above the diagonal is neither fetched nor computed, the
diagonal's blocks are masked from positions — where this form takes no
dense mask operand at all.  Its calls are named ``flash_dot_fwd``,
``flash_dot_dq``, ``flash_dot_dkv``, and the two residuals that only the
forward call can produce carry ``jax.ad_checkpoint`` names
(:data:`FLASH_DOT_OUT`, :data:`FLASH_DOT_LSE`), so a ``jax.checkpoint``
round a caller can keep them by name and not run the call a second time.
The dot form also takes K/V with fewer heads than q (grouped-query
attention: a K/V index map, and a dk/dv kernel that sums a K/V head's
gradient over its group) and a sliding ``window`` (a lower bound on the
same block ranges); windowed calls are named ``flash_window_fwd``,
``flash_window_dq``, ``flash_window_dkv`` (:func:`flash_dot_attention`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hyperspace_tpu.kernels import _support as S
from hyperspace_tpu.manifolds import smath

_NEG = -1e30  # finite -inf surrogate (avoids inf-inf NaN in the recurrence)


def _t_flash_attention(q, k, v, c, beta, tau, maskf):
    """XLA twin: dense hyperbolic attention (== nn.attention.lorentz_attention).

    maskf: f32 broadcastable to [..., Nq, Nk]; > 0 means attend (the float
    carrier keeps the custom_vjp signature uniform; it is non-differentiable
    by construction).
    """
    cc = jnp.asarray(c, q.dtype)
    k_flip = k.at[..., 0].multiply(-1.0)
    gram = jnp.matmul(q, jnp.swapaxes(k_flip, -1, -2),
                      precision=jax.lax.Precision.HIGHEST)
    logits = (2.0 / cc + 2.0 * gram + beta) / tau
    if maskf is not None:
        logits = jnp.where(maskf > 0.0, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)  # fully-masked rows
    s = jnp.matmul(w, v, precision=jax.lax.Precision.HIGHEST)
    sp = (jnp.sum(s[..., 1:] * s[..., 1:], axis=-1, keepdims=True)
          - s[..., :1] * s[..., :1])
    nrm = smath.safe_sqrt(smath.clamp_min(-sp, smath.eps_for(q.dtype)))
    return s / (smath.sqrt_c(cc) * nrm)


class _Form(NamedTuple):
    """The static score form of one launch: ``lorentz`` (module doc) or
    ``dot`` (score q·k × ``scale``, no epilogue, no c/β/τ), and whether
    the causal structure (key position ≤ query position) is built into
    the kernel's block ranges.  The dot form may add a sliding
    ``window`` (a query sees the ``window`` positions up to and including
    its own: a lower bound on the same block ranges) and a ``group`` of
    query heads that read one K/V head (grouped-query attention)."""

    kind: str = "lorentz"
    causal: bool = False
    scale: float = 1.0
    window: int = 0
    group: int = 1

    @property
    def n_smem(self) -> int:
        # lorentz: c, nk, beta, tau; dot: nk
        return 4 if self.kind == "lorentz" else 1


_LORENTZ = _Form()
_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def _mm(a, b, contract):
    """One MXU matmul with a float32 product: float32 operands (the
    lorentz form casts every operand up) at HIGHEST, since they feed
    arcosh-amplified quantities; narrower operands (the dot form's bf16
    lane) in one pass."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if a.dtype == jnp.float32 else None))


def _operands(form, refs):
    """The tile's VMEM operands as the form computes on them."""
    vals = [r[0] for r in refs]
    if form.kind == "lorentz":
        vals = [x.astype(jnp.float32) for x in vals]
    return vals


def _score_tile(form, sm, q, k, iq, ik, bq, bk, mask_ref):
    """One [bq, bk] score tile, its validity, and the coefficient and the
    two operands of dσ's pull-back (shared by the forward and both
    backward kernels)."""
    if form.kind == "lorentz":
        c_ref, nk_ref, beta_ref, tau_ref = sm
        c = c_ref[0, 0]
        beta = beta_ref[pl.program_id(0)]
        tau = tau_ref[pl.program_id(0)]
        lane = jax.lax.broadcasted_iota(jnp.int32, k.shape, dimension=1)
        k_side = jnp.where(lane == 0, -k, k)
        gram = S.dotT(q, k_side)       # ⟨q, k⟩_L — MXU matmul 1, [bq, bk]
        sigma = (2.0 / c + 2.0 * gram + beta) / tau
        coef = 2.0 / tau
    else:
        (nk_ref,) = sm
        k_side = k
        sigma = _mm(q, k, _NT) * form.scale
        coef = form.scale
    nk = nk_ref[0, 0]
    col = jax.lax.broadcasted_iota(jnp.int32, sigma.shape, dimension=1) + ik * bk
    valid = col < nk
    if mask_ref is not None:
        valid = jnp.logical_and(valid, mask_ref[0] > 0.0)
    if form.causal:
        row = jax.lax.broadcasted_iota(
            jnp.int32, sigma.shape, dimension=0) + iq * bq
        valid = jnp.logical_and(valid, col <= row)
        if form.window:
            valid = jnp.logical_and(valid, col > row - form.window)
    return sigma, valid, coef, k_side


def _q_side(form, q):
    """dσ/dk's operand: J q for the Minkowski Gram, q for the dot."""
    if form.kind != "lorentz":
        return q
    lane_q = jax.lax.broadcasted_iota(jnp.int32, q.shape, dimension=1)
    return jnp.where(lane_q == 0, -q, q)


def _when_needed(form, iq, ik, bq, bk, tile):
    """Run ``tile`` unless the causal form puts the whole block above the
    diagonal (its first key after the block's last query)."""
    if form.causal:
        pl.when(ik * bk <= iq * bq + (bq - 1))(tile)
    else:
        tile()


# --- the sliding window: block ranges with a lower bound ---------------------
# A windowed launch's inner grid axis does not run over every block of the
# other side: it counts from the first block the window reaches, up to the
# most any block needs (``_window_extent``), and the index maps clamp to
# the last block needed, so the pipeline fetches nothing new past it.


def _window_kv_range(form, iq, bq, bk, nkb, mx=jnp.maximum, mn=jnp.minimum):
    """(first, last) K/V block that queries of block ``iq`` see; ``mx``
    and ``mn`` the builtins for the static count below."""
    return (mx(iq * bq - (form.window - 1), 0) // bk,
            mn((iq * bq + bq - 1) // bk, nkb - 1))


def _window_q_range(form, ik, bq, bk, nqb, mn=jnp.minimum):
    """(first, last) query block that sees keys of block ``ik``."""
    return ((ik * bk) // bq,
            mn((ik * bk + bk - 1 + form.window - 1) // bq, nqb - 1))


def _window_extent(rng, n_outer, n_inner):
    """The most blocks of the inner side any outer block needs, from
    ``rng(i)`` on Python integers: the inner grid axis's static size."""
    return min(n_inner, max(hi - lo + 1 for lo, hi in (
        rng(i) for i in range(n_outer))))


def _run_block(lo, hi, i, tile):
    """Run ``tile(block)`` for inner step ``i`` of a windowed range."""
    block = lo + i
    pl.when(block <= hi)(lambda: tile(block))


def _attn_body(*refs, form: _Form, bq: int, bk: int, masked: bool,
               nkb_all: int = 0):
    sm, refs = refs[:form.n_smem], refs[form.n_smem:]
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if masked else None
    o_ref, res_ref, m_scr, l_scr, acc_scr = refs[3 + masked:]
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk_blocks = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _tile(kb=ik):
        q, k, v = _operands(form, (q_ref, k_ref, v_ref))
        logits, valid, _, _ = _score_tile(form, sm, q, k, iq, kb, bq, bk,
                                          mask_ref)
        logits = jnp.where(valid, logits, _NEG)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        p = jnp.where(valid, p, 0.0)   # exp(_NEG - m) underflows to 0 anyway
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc_scr[:] + _mm(p.astype(v.dtype), v, _NN)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_new

    if form.window:
        lo, hi = _window_kv_range(form, iq, bq, bk, nkb_all)
        _run_block(lo, hi, ik, _tile)
    else:
        _when_needed(form, iq, ik, bq, bk, _tile)

    @pl.when(ik == nk_blocks - 1)
    def _epilogue():
        s = acc_scr[:] / jnp.maximum(l_scr[:, :1], S.MIN_NORM_F32)
        # backward-pass residual: log-sum-exp of the score rows (big
        # positive on fully-masked/padded rows so recomputed weights
        # underflow to 0)
        l_row = l_scr[:, :1]
        lse = jnp.where(l_row > 0.0,
                        m_scr[:, :1] + jnp.log(jnp.maximum(l_row, 1e-38)),
                        1e30)
        if form.kind != "lorentz":
            o_ref[0] = s.astype(o_ref.dtype)
            res_ref[0] = jnp.broadcast_to(lse, res_ref.shape[1:])
            return
        c = sm[0][0, 0]
        lane_o = jax.lax.broadcasted_iota(jnp.int32, s.shape, dimension=1)
        sp = jnp.sum(jnp.where(lane_o == 0, -s * s, s * s), axis=-1, keepdims=True)
        nrm = S.ksafe_sqrt(jnp.maximum(-sp, S.EPS_F32))
        sc = jnp.maximum(S.ksafe_sqrt(c), S.MIN_NORM_F32)
        o_ref[0] = (s / (sc * nrm)).astype(o_ref.dtype)
        # ... and the pre-normalization Minkowski norm — PACKED into one
        # [bq, 128] tile (lane 0 = lse, lanes 1+ = nrm) so the per-row
        # scalars cost one output stream, not two
        lane_r = jax.lax.broadcasted_iota(jnp.int32, res_ref.shape[1:],
                                          dimension=1)
        res_ref[0] = jnp.where(lane_r == 0, lse, nrm)


def _smem_operands(form, b, nk, c=None, beta_b=None, tau_b=None):
    """(block specs, arrays) of the form's scalars.  β/τ ride whole in
    SMEM as flat 1-D [B] arrays (4 B per entry; the body picks its entry
    with program_id).  A 2-D [B, 1] SMEM window pads every row to a 512 B
    sublane and blows the 1 MB SMEM budget once B ≈ 1k (B = batch×heads
    at eval); Mosaic only allows rank-1 blocks that span the whole array,
    which is exactly what we want."""
    one = lambda: pl.BlockSpec((1, 1), lambda ib, i1, i2: (0, 0),
                               memory_space=pltpu.SMEM)
    nk_arr = jnp.asarray(nk, jnp.int32).reshape(1, 1)
    if form.kind != "lorentz":
        return [one()], [nk_arr]
    per_b = lambda: pl.BlockSpec((b,), lambda ib, i1, i2: (0,),
                                 memory_space=pltpu.SMEM)
    return ([one(), one(), per_b(), per_b()],
            [S.c_smem(c), nk_arr, beta_b.reshape(b), tau_b.reshape(b)])


def _block_caps(form):
    """(query rows, key rows) a block may hold at most.  The dot form's
    operands are half as wide and its K/V stream is what a short query
    block re-reads, so its query block may be twice as tall."""
    return (256, 512) if form.kind == "lorentz" else (512, 512)


def _kv_index(form, bq, bk, nkb=0):
    """Block index of K/V for grid point (iq, ik), KV innermost: a block
    above the diagonal is never computed on, so it names the last one
    needed and the pipeline fetches nothing new.  Windowed: ``ik`` counts
    from the first block the window reaches."""
    if form.window:
        def kv(iq, ik):
            lo, hi = _window_kv_range(form, iq, bq, bk, nkb)
            return jnp.minimum(lo + ik, hi)
        return kv
    if not form.causal:
        return lambda iq, ik: ik
    return lambda iq, ik: jnp.minimum(ik, (iq * bq + (bq - 1)) // bk)


def _q_index(form, bq, bk, nqb=0):
    """Block index of the Q-side operands for grid point (ik, iq), Q
    innermost (the dk/dv kernel): blocks before the diagonal are skipped.
    Windowed: ``iq`` counts from the first block that sees block ``ik``."""
    if form.window:
        def qi(ik, iq):
            lo, hi = _window_q_range(form, ik, bq, bk, nqb)
            return jnp.minimum(lo + iq, hi)
        return qi
    if not form.causal:
        return lambda ik, iq: iq
    return lambda ik, iq: jnp.maximum(iq, (ik * bk) // bq)


def _kv_head(form):
    """The K/V head a query head (the leading grid index) reads."""
    if form.group == 1:
        return lambda ib: ib
    return lambda ib: ib // form.group


def _kv_steps(form, bq, bk, nqb, nkb):
    """(inner K/V grid extent, body keywords) of the KV-inner kernels."""
    if not form.window:
        return nkb, {}
    return _window_extent(lambda i: _window_kv_range(
        form, i, bq, bk, nkb, max, min), nqb, nkb), {"nkb_all": nkb}


def _launch(q, k, v, form, scalars, maskf, mode_):
    """q [B, Nq, D], k/v [B, Nk, D], ``scalars`` the form's (c, beta_b [B],
    tau_b [B]) or (), maskf [B, Nq, Nk]|None."""
    b, nq, d = q.shape
    nk = k.shape[1]
    dp = S.round_up(d, 128)
    cap_q, cap_k = _block_caps(form)
    bq = min(S.round_up(nq, 8), cap_q)
    bk = min(S.round_up(nk, 128), cap_k)
    # q + k + v + out + acc blocks (+ mask + logits) under the VMEM budget
    while 4 * (3 * bq * dp + 2 * bk * dp + 2 * bq * bk) > S.VMEM_BUDGET and (bq > 8 or bk > 128):
        if bk > 128 and bk >= bq:
            bk = max(128, (bk // 2) // 128 * 128)
        else:
            bq = max(8, (bq // 2) // 8 * 8)

    pad3 = lambda a, rows: S.pad_axis(S.pad_axis(a, -1, 128), -2, rows)
    qp = pad3(q, bq)
    kp = pad3(k, bk)
    vp = pad3(v, bk)
    nq_p, nk_p = qp.shape[1], kp.shape[1]
    n_kv, extra = _kv_steps(form, bq, bk, nq_p // bq, nk_p // bk)
    grid = (b, nq_p // bq, n_kv)

    kv = _kv_index(form, bq, bk, nk_p // bk)
    kvh = _kv_head(form)
    in_specs, args = _smem_operands(form, b, nk, *scalars)
    in_specs += [
        pl.BlockSpec((1, bq, dp), lambda ib, iq, ik: (ib, iq, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, dp), lambda ib, iq, ik: (kvh(ib), kv(iq, ik), 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, dp), lambda ib, iq, ik: (kvh(ib), kv(iq, ik), 0), memory_space=pltpu.VMEM),
    ]
    args += [qp, kp, vp]
    masked = maskf is not None
    if masked:
        mp = S.pad_axis(S.pad_axis(maskf.astype(jnp.float32), -1, bk), -2, bq)
        in_specs.append(pl.BlockSpec((1, bq, bk), lambda ib, iq, ik: (ib, iq, ik),
                                     memory_space=pltpu.VMEM))
        args.append(mp)

    row_spec = pl.BlockSpec((1, bq, 128), lambda ib, iq, ik: (ib, iq, 0),
                            memory_space=pltpu.VMEM)
    out, res = pl.pallas_call(
        functools.partial(_attn_body, form=form, bq=bq, bk=bk, masked=masked,
                          **extra),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, dp), lambda ib, iq, ik: (ib, iq, 0),
                         memory_space=pltpu.VMEM),
            row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq_p, dp), q.dtype),
            jax.ShapeDtypeStruct((b, nq_p, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, dp), jnp.float32),
        ],
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=S.interpret_flag(mode_),
        name=_call_name(form, "fwd"),
    )(*args)
    return out[:, :nq, :d], res[:, :, 0], res[:, :, 1]


def _call_name(form, which: str):
    """The dot form's calls carry names of their own (XLA makes a call's
    name the instruction's, which a device trace shows): flash_dot_fwd,
    flash_dot_dq, flash_dot_dkv, and flash_window_fwd, flash_window_dq,
    flash_window_dkv where a sliding window bounds them; the lorentz
    form's stay unnamed."""
    if form.kind == "lorentz":
        return None
    return f"flash_{'window' if form.window else 'dot'}_{which}"


def _scalar_per_batch(x, lead, dtype):
    """Broadcast a per-(batch, head) scalar spec (e.g. [h, 1, 1]) to [B]."""
    arr = jnp.asarray(x, dtype)
    return jnp.broadcast_to(arr, lead + (1, 1))[..., 0, 0].reshape(-1)


# --- recomputing flash backward (module doc) ----------------------------------


def _dq_body(*refs, form: _Form, bq: int, bk: int, masked: bool,
             nkb_all: int = 0):
    sm, refs = refs[:form.n_smem], refs[form.n_smem:]
    q_ref, k_ref, v_ref, dsp_ref, ld_ref = refs[:5]
    mask_ref = refs[5] if masked else None
    lorentz = form.kind == "lorentz"
    if lorentz:
        dq_ref, dst_ref, dq_scr, part_scr = refs[5 + masked:]
    else:
        dq_ref, dq_scr = refs[5 + masked:]
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk_blocks = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        if lorentz:
            part_scr[:] = jnp.zeros_like(part_scr)

    def _tile(kb=ik):
        q, k, v, dsp = _operands(form, (q_ref, k_ref, v_ref, dsp_ref))
        lse = ld_ref[0][:, :1]   # packed per-row scalars: lane 0 = lse,
        di = ld_ref[0][:, 1:2]   # lane 1 = di (one stream, not two)

        sigma, valid, coef, k_side = _score_tile(form, sm, q, k, iq, kb,
                                                 bq, bk, mask_ref)
        p = jnp.where(valid, jnp.exp(sigma - lse), 0.0)
        dv_dot = _mm(dsp, v, _NT)                     # ⟨dsp_i, v_j⟩, MXU
        dsig = jnp.where(valid, p * (dv_dot - di), 0.0)
        dq_scr[:] += coef * _mm(dsig.astype(k_side.dtype), k_side, _NN)
        if lorentz:
            # dτ partial Σ dσ·σ accumulates as a (8, 128)-tiled broadcast
            # (a scalar-shaped output block fails the Mosaic (8, 128)
            # tiling rule).  dβ needs no partial: Σ_j dσ_ij = 0 exactly
            # (softmax shift invariance), so dβ ≡ 0 and the score-offset
            # dc term vanishes too.
            part_scr[:] += jnp.sum(jnp.where(valid, dsig * sigma, 0.0))

    if form.window:
        lo, hi = _window_kv_range(form, iq, bq, bk, nkb_all)
        _run_block(lo, hi, ik, _tile)
    else:
        _when_needed(form, iq, ik, bq, bk, _tile)

    @pl.when(ik == nk_blocks - 1)
    def _write():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)
        if lorentz:
            dst_ref[0, 0] = part_scr[:]


def _dkv_body(*refs, form: _Form, bq: int, bk: int, masked: bool,
              n_inner: int = 0, nqb_all: int = 0):
    sm, refs = refs[:form.n_smem], refs[form.n_smem:]
    q_ref, k_ref, v_ref, dsp_ref, ld_ref = refs[:5]
    mask_ref = refs[5] if masked else None
    dk_ref, dv_ref, dk_scr, dv_scr = refs[5 + masked:]
    j = pl.program_id(2)
    nq_blocks = pl.num_programs(2)
    ik = pl.program_id(1)          # KV block index is the OUTER grid dim
    # the inner axis may run over a group's query heads, each over its
    # query blocks: one K/V head then sums the gradient of its whole group
    iq = j % n_inner if n_inner else j

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(iq=iq):
        q, k, v, dsp = _operands(form, (q_ref, k_ref, v_ref, dsp_ref))
        lse = ld_ref[0][:, :1]   # packed: lane 0 = lse, lane 1 = di
        di = ld_ref[0][:, 1:2]

        sigma, valid, coef, _ = _score_tile(form, sm, q, k, iq, ik, bq, bk,
                                            mask_ref)
        p = jnp.where(valid, jnp.exp(sigma - lse), 0.0)
        dv_scr[:] += _mm(p.astype(dsp.dtype), dsp, _TN)      # pᵀ @ dsp
        dv_dot = _mm(dsp, v, _NT)
        dsig = jnp.where(valid, p * (dv_dot - di), 0.0)
        q_side = _q_side(form, q)
        dk_scr[:] += coef * _mm(dsig.astype(q_side.dtype), q_side, _TN)

    if form.window:
        lo, hi = _window_q_range(form, ik, bq, bk, nqb_all)
        _run_block(lo, hi, iq, _tile)
    else:
        _when_needed(form, iq, ik, bq, bk, _tile)

    @pl.when(j == nq_blocks - 1)
    def _write():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_blocks(form, nq, nk, dp):
    cap_q, cap_k = _block_caps(form)
    bq = min(S.round_up(nq, 8), cap_q)
    bk = min(S.round_up(nk, 128), cap_k)
    # q + k + v + dsp + dq/dkv scratch + lse/di + score tiles
    while 4 * (6 * bq * dp + 4 * bk * dp + 3 * bq * bk) > S.VMEM_BUDGET and (
            bq > 8 or bk > 128):
        if bk > 128 and bk >= bq:
            bk = max(128, (bk // 2) // 128 * 128)
        else:
            bq = max(8, (bq // 2) // 8 * 8)
    return bq, bk


def _bwd_launch(q, k, v, form, scalars, maskf, dsp, lse, di, mode_):
    """Run both backward kernels; returns (dq, dk, dv, dst [B] | None)."""
    b, nq, d = q.shape
    nk = k.shape[1]
    dp = S.round_up(d, 128)
    bq, bk = _bwd_blocks(form, nq, nk, dp)
    lorentz = form.kind == "lorentz"
    # the lorentz form's gradients leave in float32; the dot form's in
    # its operands' dtype (the accumulators are float32 either way)
    grad_dtype = jnp.float32 if lorentz else q.dtype
    pad3 = lambda a, rows: S.pad_axis(S.pad_axis(a, -1, 128), -2, rows)
    qp, kp, vp = pad3(q, bq), pad3(k, bk), pad3(v, bk)
    dspp = pad3(dsp, bq)
    nq_p, nk_p = qp.shape[1], kp.shape[1]
    # rows the BACKWARD padding adds beyond the forward-padded length
    # carry the fully-masked 1e30 sentinel (ADVICE r04): lse = 0 there
    # would make p = exp(sigma - 0) overflow and 0·inf = NaN poison
    # dk/dv through the column sums; with the sentinel p underflows to 0
    pad_rows = max(nq_p - lse.shape[1], 0)
    lse_p = jnp.pad(lse, ((0, 0), (0, pad_rows)),
                    constant_values=1e30)[:, :nq_p]
    di_p = S.pad_axis(di, -1, bq)[:, :nq_p]
    # per-row scalars ride PACKED in one [B, nq_p, 128] stream (lane 0 =
    # lse, lane 1 = di) — halves the broadcast residual bytes vs two
    # full-lane arrays (ADVICE r04)
    lane128 = jnp.arange(128)[None, None, :]
    ld_b = jnp.where(lane128 == 0, lse_p[..., None], di_p[..., None])

    smem_specs, base_args = _smem_operands(form, b, nk, *scalars)
    masked = maskf is not None
    mp = None
    if masked:
        mp = S.pad_axis(S.pad_axis(maskf.astype(jnp.float32), -1, bk), -2, bq)

    # dq kernel: grid (B, Qb, KVb), KV inner
    nqb, nkb = nq_p // bq, nk_p // bk
    n_kv, extra = _kv_steps(form, bq, bk, nqb, nkb)
    kv = _kv_index(form, bq, bk, nkb)
    kvh = _kv_head(form)
    in_specs = smem_specs + [
        pl.BlockSpec((1, bq, dp), lambda ib, iq, ik: (ib, iq, 0)),
        pl.BlockSpec((1, bk, dp), lambda ib, iq, ik: (kvh(ib), kv(iq, ik), 0)),
        pl.BlockSpec((1, bk, dp), lambda ib, iq, ik: (kvh(ib), kv(iq, ik), 0)),
        pl.BlockSpec((1, bq, dp), lambda ib, iq, ik: (ib, iq, 0)),
        pl.BlockSpec((1, bq, 128), lambda ib, iq, ik: (ib, iq, 0)),
    ]
    args = base_args + [qp, kp, vp, dspp, ld_b]
    if masked:
        in_specs.append(pl.BlockSpec((1, bq, bk),
                                     lambda ib, iq, ik: (ib, iq, ik)))
        args.append(mp)

    out_specs = [pl.BlockSpec((1, bq, dp), lambda ib, iq, ik: (ib, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, nq_p, dp), grad_dtype)]
    scratch = [pltpu.VMEM((bq, dp), jnp.float32)]
    if lorentz:
        out_specs.append(pl.BlockSpec((1, 1, 8, 128),
                                      lambda ib, iq, ik: (ib, iq, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, nqb, 8, 128), jnp.float32))
        scratch.append(pltpu.VMEM((8, 128), jnp.float32))
    dq, *dst = pl.pallas_call(
        functools.partial(_dq_body, form=form, bq=bq, bk=bk, masked=masked,
                          **extra),
        grid=(b, nqb, n_kv),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=S.interpret_flag(mode_),
        name=_call_name(form, "dq"),
    )(*args)

    # dkv kernel: grid (B, KVb, Qb), Q inner; grouped: grid (B_kv, KVb,
    # group x Qb), each K/V head's inner axis over its group's query heads
    qi = _q_index(form, bq, bk, nqb)
    smem_specs2, _ = _smem_operands(form, b, nk, *scalars)
    b_kv = k.shape[0]
    if form.group == 1 and not form.window:
        n_q, extra = nqb, {}
        qrow = lambda ib, ik, iq: (ib, qi(ik, iq), 0)
    else:
        n_q = nqb if not form.window else _window_extent(
            lambda i: _window_q_range(form, i, bq, bk, nqb, min), nkb, nqb)
        extra = {"n_inner": n_q, "nqb_all": nqb}
        g = form.group
        qrow = lambda ib, ik, j: (ib * g + j // n_q, qi(ik, j % n_q), 0)
    in_specs2 = smem_specs2 + [
        pl.BlockSpec((1, bq, dp), qrow),
        pl.BlockSpec((1, bk, dp), lambda ib, ik, iq: (ib, ik, 0)),
        pl.BlockSpec((1, bk, dp), lambda ib, ik, iq: (ib, ik, 0)),
        pl.BlockSpec((1, bq, dp), qrow),
        pl.BlockSpec((1, bq, 128), qrow),
    ]
    args2 = base_args + [qp, kp, vp, dspp, ld_b]
    if masked:
        in_specs2.append(pl.BlockSpec((1, bq, bk),
                                      lambda ib, ik, iq: (ib, iq, ik)))
        args2.append(mp)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_body, form=form, bq=bq, bk=bk, masked=masked,
                          **extra),
        grid=(b_kv, nkb, form.group * n_q),
        in_specs=in_specs2,
        out_specs=[
            pl.BlockSpec((1, bk, dp), lambda ib, ik, iq: (ib, ik, 0)),
            pl.BlockSpec((1, bk, dp), lambda ib, ik, iq: (ib, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_kv, nk_p, dp), grad_dtype),
            jax.ShapeDtypeStruct((b_kv, nk_p, dp), grad_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dp), jnp.float32),
            pltpu.VMEM((bk, dp), jnp.float32),
        ],
        compiler_params=S.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=S.interpret_flag(mode_),
        name=_call_name(form, "dkv"),
    )(*args2)
    return (dq[:, :nq, :d], dk[:, :nk, :d], dv[:, :nk, :d],
            jnp.sum(dst[0][:, :, 0, 0], axis=1) if lorentz else None)


def _epilogue_jax(s, c):
    """Exact XLA mirror of the kernel epilogue (same clamps) — the
    elementwise piece of the backward runs through its autodiff."""
    lane0 = s[..., :1]
    sp = jnp.sum(s[..., 1:] * s[..., 1:], axis=-1, keepdims=True) - lane0 * lane0
    nrm = S.ksafe_sqrt(jnp.maximum(-sp, S.EPS_F32))
    sc = jnp.maximum(S.ksafe_sqrt(jnp.asarray(c, jnp.float32)), S.MIN_NORM_F32)
    return s / (sc * nrm)


@jax.custom_vjp
def _flash3(q3, k3, v3, c, beta_b, tau_b, maskf, mode_s):
    out, _, _ = _launch(q3, k3, v3, _LORENTZ, (c, beta_b, tau_b), maskf,
                        "interpret" if mode_s.shape[0] else "pallas")
    return out


def _fa3_fwd(q3, k3, v3, c, beta_b, tau_b, maskf, mode_s):
    mode_ = "interpret" if mode_s.shape[0] else "pallas"
    out, lse, nrm = _launch(q3, k3, v3, _LORENTZ, (c, beta_b, tau_b),
                            maskf, mode_)
    return out, (q3, k3, v3, c, beta_b, tau_b, maskf, out, lse, nrm, mode_s)


def _fa3_bwd(res, g):
    q3, k3, v3, c, beta_b, tau_b, maskf, out, lse, nrm, mode_s = res
    mode_ = "interpret" if mode_s.shape[0] else "pallas"
    nq = q3.shape[1]
    c32 = jnp.asarray(c, jnp.float32)
    sc = jnp.maximum(S.ksafe_sqrt(c32), S.MIN_NORM_F32)
    s_pre = out.astype(jnp.float32) * (sc * nrm[:, :nq, None])
    # elementwise Lorentz-normalize epilogue: XLA autodiff
    _, epi_vjp = jax.vjp(_epilogue_jax, s_pre, c32)
    dsp, dc_epi = epi_vjp(g.astype(jnp.float32))
    di = jnp.sum(dsp * s_pre, axis=-1)                      # [B, nq]
    dq, dk, dv, dst = _bwd_launch(q3, k3, v3, _LORENTZ, (c, beta_b, tau_b),
                                  maskf, dsp, lse, di, mode_)
    # β shifts every logit of a softmax row uniformly → dβ ≡ 0 exactly,
    # and the same row-sum identity kills the score-offset dc term; the
    # only c gradient is the epilogue's
    dbeta = jnp.zeros_like(beta_b)
    dtau = -dst / tau_b
    dc = dc_epi.astype(jnp.float32)
    dmask = None if maskf is None else jnp.zeros_like(maskf)
    return (dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype),
            dc, dbeta, dtau, dmask, None)


_flash3.defvjp(_fa3_fwd, _fa3_bwd)


def flash_attention(q, k, v, c, *, beta=0.0, tau=1.0, mask=None):
    """Hyperbolic flash attention (kernel N7); see module docstring.

    q: [..., Nq, D], k/v: [..., Nk, D] hyperboloid points; beta/tau scalars
    or [..., 1, 1]-shaped per-(batch, head) arrays; mask: bool/float
    broadcastable to [..., Nq, Nk], truthy = attend.  Returns hyperboloid
    points [..., Nq, D].  On the kernel path BOTH directions are flash
    (forward online-softmax, recomputing backward); the XLA twin serves
    CPU and per-position β/τ with plain autodiff.
    """
    maskf = None if mask is None else jax.lax.stop_gradient(
        jnp.asarray(mask, jnp.float32))
    mode_ = S.mode()
    bshape = jnp.shape(beta)
    tshape = jnp.shape(tau)
    per_pos = (bshape[-2:] not in ((), (1, 1)) and len(bshape) >= 2) or (
        tshape[-2:] not in ((), (1, 1)) and len(tshape) >= 2)
    if mode_ == "xla" or per_pos:
        return _t_flash_attention(q, k, v, c, beta, tau, maskf)
    # 3-D reshape/broadcast happens OUTSIDE the custom_vjp boundary, so
    # autodiff sums the k/v/β/τ cotangents over broadcast dims for free
    lead = q.shape[:-2]
    bsz = 1
    for s_ in lead:
        bsz *= s_
    q3 = q.reshape((bsz,) + q.shape[-2:])
    k3 = jnp.broadcast_to(k, lead + k.shape[-2:]).reshape((bsz,) + k.shape[-2:])
    v3 = jnp.broadcast_to(v, lead + v.shape[-2:]).reshape((bsz,) + v.shape[-2:])
    beta_b = _scalar_per_batch(beta, lead, jnp.float32)
    tau_b = _scalar_per_batch(tau, lead, jnp.float32)
    if maskf is not None:
        maskf = jnp.broadcast_to(
            maskf, lead + (q.shape[-2], k.shape[-2])
        ).reshape((bsz,) + (q.shape[-2], k.shape[-2]))
    # static mode flag rides as an empty/1-element dummy int array (shape
    # is static under jit — and int dtype means a None cotangent is valid)
    mode_s = jnp.zeros((1 if mode_ == "interpret" else 0,), jnp.int32)
    out = _flash3(q3, k3, v3, c, beta_b, tau_b, maskf, mode_s)
    return out.reshape(lead + out.shape[-2:])


# --- the dot-product form (models/looplm.py) ----------------------------------


def _t_flash_dot(q, k, v, scale, causal, window=0, group=1):
    """XLA twin of the dot form: dense softmax(q kᵀ · scale [+ causal]
    [+ window]) v, float32 scores and accumulation whatever the operands'
    dtype; each K/V head repeated over its ``group`` of query heads."""
    if group > 1:
        k, v = (jnp.repeat(a, group, axis=-3) for a in (k, v))
    hi = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None)
    logits = jnp.einsum("...qd,...kd->...qk", q, k, precision=hi,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        nq, nk = q.shape[-2], k.shape[-2]
        keep = jnp.arange(nk)[None, :] <= jnp.arange(nq)[:, None]
        if window:
            keep &= jnp.arange(nk)[None, :] > (jnp.arange(nq)[:, None]
                                               - window)
        logits = jnp.where(keep, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("...qk,...kd->...qd", w.astype(v.dtype), v,
                     precision=hi, preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_dot3(q3, k3, v3, form, mode_):
    out, _, _ = _launch(q3, k3, v3, form, (), None, mode_)
    return out


# the names of what only the forward call can produce, on the values the
# backward reads: the output [B, Nq, D] in q's dtype and the rows'
# log-sum-exp, float32 [B, Nq padded to the q block] (never the kernel's
# raw [B, Nq, 128] statistics tile).  ``checkpoint_name`` is the identity
# but under a ``jax.checkpoint`` whose policy saves these names
# (``models/looplm.py``); q, k and v are the caller's to recompute.
FLASH_DOT_OUT = "flash_dot_out"
FLASH_DOT_LSE = "flash_dot_lse"


def _fd3_fwd(q3, k3, v3, form, mode_):
    out, lse, _ = _launch(q3, k3, v3, form, (), None, mode_)
    out = checkpoint_name(out, FLASH_DOT_OUT)  # primal result and residual
    lse = checkpoint_name(lse, FLASH_DOT_LSE)
    return out, (q3, k3, v3, out, lse)


def _fd3_bwd(form, mode_, res, g):
    q3, k3, v3, out, lse = res
    # no epilogue: the cotangent of the pre-epilogue sum is g itself
    di = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dq, dk, dv, _ = _bwd_launch(q3, k3, v3, form, (), None,
                                g.astype(q3.dtype), lse, di, mode_)
    return dq, dk, dv


_flash_dot3.defvjp(_fd3_fwd, _fd3_bwd)


def flash_dot_attention(q, k, v, *, causal=False, window=None):
    """Flash attention in the scaled dot-product form: the recurrence and
    the recomputing backward of :func:`flash_attention` with the score
    q·k / √D and no epilogue.

    q: [..., Nq, D], k/v: [..., Nk, D], one dtype; the matmuls run on the
    operands' dtype (bf16: one MXU pass; float32: HIGHEST) with float32
    scores, softmax and accumulators.  ``causal`` (Nq == Nk) keeps key
    position ≤ query position *inside* the kernels: blocks above the
    diagonal are neither fetched nor computed, the diagonal's blocks are
    masked from positions, forward and both backward kernels alike.  No
    dense mask operand exists in this form.  The XLA twin serves the CPU
    (and names no residual: under a checkpoint it is recomputed whole).

    Grouped-query attention: q [..., H, Nq, D] with k/v [..., H_kv, Nk, D]
    and H a multiple of H_kv; query head h reads K/V head h // (H / H_kv)
    through the K/V index map, and the dk/dv kernel sums each K/V head's
    gradient over its group.  ``window`` (causal only): a query also
    needs key position > its own − window, a lower bound on the same
    block ranges, so a query block visits only the K/V blocks the window
    reaches; these calls are named ``flash_window_*``.
    """
    scale = 1.0 / (q.shape[-1] ** 0.5)
    if causal and q.shape[-2] != k.shape[-2]:
        raise ValueError("causal attention needs Nq == Nk, got "
                         f"{q.shape[-2]} and {k.shape[-2]}")
    if window and not causal:
        raise ValueError("a sliding window bounds causal attention only")
    group = 1
    if k.ndim >= 3 and q.ndim == k.ndim and k.shape[-3] != q.shape[-3]:
        if q.shape[-3] % k.shape[-3] or q.shape[:-3] != k.shape[:-3]:
            raise ValueError(f"query heads {q.shape[:-2]} are no whole "
                             f"groups of K/V heads {k.shape[:-2]}")
        group = q.shape[-3] // k.shape[-3]
    window = int(window or 0)
    mode_ = S.mode()
    if mode_ == "xla":
        return _t_flash_dot(q, k, v, scale, causal, window, group)
    lead = q.shape[:-2]
    flat = lambda a: a.reshape((-1,) + a.shape[-2:])
    if group == 1 and not window:
        form = _Form("dot", bool(causal), scale)
        out = _flash_dot3(
            flat(q), flat(jnp.broadcast_to(k, lead + k.shape[-2:])),
            flat(jnp.broadcast_to(v, lead + v.shape[-2:])), form, mode_)
    else:
        form = _Form("dot", bool(causal), scale, window, group)
        out = _flash_dot3(flat(q), flat(k), flat(v), form, mode_)
    return out.reshape(lead + out.shape[-2:])
