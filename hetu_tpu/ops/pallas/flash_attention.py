"""Flash attention — Pallas TPU kernel (fwd + bwd), LSE-returning.

TPU-native replacement for the reference's vendored flash-attn2 CUDA kernels
(``hetu/impl/kernel/FlashAttention.cu``, ``hetu/graph/ops/Attention.cc``).
Design follows the FlashAttention-2 online-softmax algorithm, blocked for
the MXU: the kv loop is the innermost grid dimension with VMEM scratch
accumulators carried across it (TPU grid iterations are sequential).

Returns (out, lse); the log-sum-exp output is what ring attention's online
correction needs (reference ``AttnCommRing::ExecCorr``,
``ops/ParallelAttention.h:361``) and what the backward recompute uses.

Backward is a single fused kernel (dq, dk, dv in one grid pass): grid
(bh, q, kv) with kv innermost; dq accumulates in a per-q-block VMEM
scratch, dk/dv accumulate in full-sequence VMEM scratch written out once
per bh, and delta = rowsum(do*o) is computed in-kernel at kv==0 — so the
score matrix is materialized once per (q, kv) block pair instead of twice
(the split dq / dkv formulation).  Sequences whose dk/dv scratch would
exceed the VMEM budget fall back to the split two-kernel path.

Layout: [batch, seq, heads, head_dim] (reference convention).  Internally
[b*h, s, d].  Causal masking is block-skipped (fully-masked kv blocks are
not computed).  ``segment_ids`` gives packed/varlen semantics (the
cu_seqlens path of the reference, ``ops/Attention.h:286``).  Narrow
(8-lane) layouts are used for the lse / delta / q-segment operands — not
full 128-lane broadcasts.

On CPU the kernel runs in interpret mode so the whole path is testable on
the simulated mesh (SURVEY.md §4 takeaway).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_tpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _empty_rows(m):
    """Rows whose max score is the mask fill value saw no valid kv
    position (ring varlen padding) — real logits can't get near it.
    Shared by the fast path and the accumulate finalize so the
    out=0/lse=-inf empty-row contract can't desynchronize."""
    return m <= DEFAULT_MASK_VALUE * 0.5

# Scores are computed as base-2 logits: the softmax scale AND log2(e) are
# folded into the q operand (one [s, d] multiply outside the kernel
# instead of a [s, s] multiply per block inside), and exp/log become
# exp2/log2 — the VPU-native transcendentals.  LSE stays natural-log at
# every API boundary (ring correction, backward, tests).
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


LANES = 128      # last-dim tile width
SUBLANES = 8     # second-to-last tile width (f32/int32)

# dk/dv full-sequence fp32 scratch budget for the fused backward; above
# this the split two-kernel path is used (e.g. d=64 -> sk <= 8192).
_FUSED_DKV_VMEM_BYTES = 4 * 1024 * 1024


def _padded_segs(segment_ids, b, h, sq, sk):
    """Broadcast segment ids into TPU-tileable layouts: q side
    [bh, sq, SUBLANES] (narrow lanes), kv side [bh, SUBLANES, sk].

    ``segment_ids`` is either a [b, sq] array (shared q/kv — requires
    sq == sk) or a tuple ``(q_ids [b, sq], kv_ids [b, sk])`` — the ring
    attention case where the visiting KV block carries its own ids.
    """
    if segment_ids is None:
        q_segs = jnp.zeros((b * h, sq, SUBLANES), jnp.int32)
        kv_segs = jnp.zeros((b * h, SUBLANES, sk), jnp.int32)
        return q_segs, kv_segs
    if isinstance(segment_ids, (tuple, list)):
        q_ids, kv_ids = segment_ids
    else:
        if sq != sk:
            raise NotImplementedError(
                "segment_ids with sq != sk needs a (q_ids, kv_ids) tuple")
        q_ids = kv_ids = segment_ids
    flat_q = jnp.repeat(q_ids[:, None, :], h, axis=1).reshape(b * h, sq)
    q_segs = jnp.broadcast_to(flat_q[:, :, None], (b * h, sq, SUBLANES))
    flat_kv = jnp.repeat(kv_ids[:, None, :], h, axis=1).reshape(b * h, sk)
    kv_segs = jnp.broadcast_to(flat_kv[:, None, :], (b * h, SUBLANES, sk))
    return q_segs, kv_segs


def _seg_operands(segment_ids, b, h, sq, sk, bq, bk):
    """(in_specs, operands) for the segment-id streams — empty when
    segments are unused, so the common no-packing case pays zero extra
    HBM traffic for them."""
    if segment_ids is None:
        return [], []
    q_segs, kv_segs = _padded_segs(segment_ids, b, h, sq, sk)
    specs = [
        pl.BlockSpec((1, bq, SUBLANES), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, SUBLANES, bk), lambda bh, i, j: (bh, 0, j)),
    ]
    return specs, [q_segs, kv_segs]


def _dim_semantics(*sem):
    """Mosaic dimension semantics (parallel dims may split across
    TensorCores)."""
    return pltpu.CompilerParams(dimension_semantics=sem)


def _nosegs_kernel(kernel, *refs, **kw):
    """Adapter: invoke a seg-aware kernel with no segment operands
    (use_segs=False guarantees the seg refs are never read)."""
    return kernel(None, None, *refs, **kw)


def _causal_mask(s, q_idx, kv_idx, bq, bk, offset):
    """Apply the causal mask to a score block — diag-specialized (fa2
    sweep): blocks fully below the diagonal skip the iota mask entirely,
    so half the causal blocks pay zero masking VPU work.  Shared by all
    four kernels so fwd/bwd masking can never desynchronize."""
    def _masked(sv):
        rows = q_idx * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_idx * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        return jnp.where(cols <= rows + offset, sv, DEFAULT_MASK_VALUE)
    is_diag = kv_idx * bk + bk - 1 > q_idx * bq + offset
    return lax.cond(is_diag, _masked, lambda sv: sv, s)


def _block_sizes(s: int, d: int, dtype, role: str = "fwd"
                 ) -> Tuple[int, int]:
    """Pick q/kv block sizes.  Blocks must divide s AND satisfy TPU tiling
    (last-two-dims rule); a block equal to the full dim is always legal, so
    sequences with no nice divisor fall back to a single block.

    Forward prefers 1024 blocks (fp32 score tile 4MB — the measured sweet
    spot of the round-3 fa3 prototype); the backward passes carry more
    scratch per block, so they cap at 512.  ``HETU_TPU_FLASH_BLOCK_FWD``
    / ``HETU_TPU_FLASH_BLOCK_BWD`` override the preference for sweeps."""
    import os
    cands = (1024, 512, 256, 128) if role == "fwd" and d <= 128 \
        else (512, 256, 128)
    env = os.environ.get(f"HETU_TPU_FLASH_BLOCK_{role.upper()}")
    if env:
        want = int(env)
        # want == s (single block) is always legal, at any size — the
        # fallback path emits exactly that for divisor-less sequences
        if s % want == 0 and (128 <= want <= cands[0] or want == s):
            return want, want
        import warnings
        warnings.warn(
            f"HETU_TPU_FLASH_BLOCK_{role.upper()}={want} ignored: must "
            f"divide s={s} and lie in [128, {cands[0]}] (or equal s) "
            f"for role={role}")
    for cand in cands:
        if s % cand == 0:
            return cand, cand
    return s, s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref,  # inputs
                o_ref, lse_ref,                              # outputs
                acc_ref, m_ref, l_ref,                       # scratch
                *, causal: bool, offset: int, bq: int,
                bk: int, num_kv: int, use_segs: bool):
    # q arrives pre-scaled by softmax_scale * LOG2E: scores are base-2
    # logits and all exps are exp2 (see module constant note).
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    def _scores():
        q = q_ref[0]                       # [bq, d]
        k = k_ref[0]                       # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk] base-2
        if causal:
            s = _causal_mask(s, q_idx, kv_idx, bq, bk, offset)
        if use_segs:
            qs = q_seg_ref[0, :, 0]        # [bq] (narrow-lane layout)
            ks = kv_seg_ref[0, 0, :]       # [bk] (sublane-padded layout)
            seg_ok = qs[:, None] == ks[None, :]
            s = jnp.where(seg_ok, s, DEFAULT_MASK_VALUE)
        return s

    if num_kv == 1 and (not causal or offset == 0):
        # single-kv-block fast path (the whole kv sequence is one block,
        # and the block is never fully skipped): no online-softmax carry,
        # no scratch traffic, outputs written directly
        s = _scores()
        m = jnp.max(s, axis=1)
        p = jnp.exp2(s - m[:, None])
        l = jnp.sum(p, axis=1)             # >= 1: exp2(0) at the max
        o = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) / l[:, None]
        lse = (m + jnp.log2(l)) * LN2
        if use_segs:
            # rows whose every position is seg-masked honor the empty-row
            # contract — out=0, lse=-inf — instead of averaging V through
            # exp2(0)=1 at the mask fill value
            empty = _empty_rows(m)
            o = jnp.where(empty[:, None], 0.0, o)
            lse = jnp.where(empty, -jnp.inf, lse)
        o_ref[0] = o.astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])
        return

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    # block-level causal skip: kv block strictly after q block -> no
    # work (offset shifts the diagonal right: rows are offset global
    # positions ahead of cols — the SYM tail-half case)
    run = True
    if causal:
        run = kv_idx * bk <= q_idx * bq + bq - 1 + offset

    @pl.when(run)
    def _compute():
        s = _scores()
        m_prev = m_ref[:, 0]               # [bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_cur[:, None])
        alpha = jnp.exp2(m_prev - m_cur)
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        m = m_ref[:, 0]
        empty = l == 0.0
        if use_segs:
            # blocks ran but every position was seg-masked: m is the mask
            # fill value, not a real logit — same empty-row contract
            empty = jnp.logical_or(empty, _empty_rows(m))
        safe_l = jnp.where(empty, 1.0, l)
        o = acc_ref[:] / safe_l[:, None]
        o_ref[0] = jnp.where(empty[:, None], 0.0, o).astype(o_ref.dtype)
        lse = jnp.where(empty, -jnp.inf, (m + jnp.log2(safe_l)) * LN2)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _flash_fwd(q, k, v, scale, causal, segment_ids, causal_offset=0):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    # fold softmax scale + log2(e) into q (one [s, d] multiply; scores
    # come out of the kernel's matmul as base-2 logits)
    qr = (q * (scale * LOG2E)).astype(q.dtype) \
        .transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    bq, _ = _block_sizes(sq, d, q.dtype)
    _, bk = _block_sizes(sk, d, q.dtype)
    num_q, num_kv = sq // bq, sk // bk

    use_segs = segment_ids is not None
    seg_specs, seg_args = _seg_operands(segment_ids, b, h, sq, sk, bq, bk)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, offset=causal_offset,
        bq=bq, bk=bk, num_kv=num_kv, use_segs=use_segs)
    if not use_segs:
        kernel = functools.partial(_nosegs_kernel, kernel)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, num_kv),
        in_specs=[
            *seg_specs,
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, SUBLANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, SUBLANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=not on_tpu(),
        name="flash_fwd",
    )(*seg_args, qr, kr, vr)
    out = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse[:, :, 0].reshape(b, h, sq)
    return out, lse


# ---------------------------------------------------------------------------
# backward — fused single kernel (dq + dk + dv)
# ---------------------------------------------------------------------------

def _bwd_fused_kernel(q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref,
                      o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref,
                      dq_acc, dk_acc, dv_acc, delta_scr,
                      *, scale, causal, offset, bq, bk, num_q, num_kv,
                      use_segs):
    # q and lse arrive pre-scaled by LOG2E (q also by softmax_scale), so
    # p = exp2(s2 - lse2) with no per-element scale multiplies; the
    # deferred scales land on the [*, d] accumulators at finalize:
    # dq *= scale, dk /= LOG2E (dk was accumulated against the scaled q).
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(jnp.logical_and(q_idx == 0, kv_idx == 0))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kv_idx == 0)
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        delta = jnp.sum(do * o, axis=1)          # rowsum(do*o), in-kernel
        delta_scr[:] = jnp.broadcast_to(delta[:, None], delta_scr.shape)

    # fully-masked (q, kv) block pairs contribute to none of dq/dk/dv
    run = True
    if causal:
        run = kv_idx * bk <= q_idx * bq + bq - 1 + offset

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_idx, kv_idx, bq, bk, offset)
        if use_segs:
            seg_ok = (q_seg_ref[0, :, 0][:, None]
                      == kv_seg_ref[0, 0, :][None, :])
            s = jnp.where(seg_ok, s, DEFAULT_MASK_VALUE)
        lse = lse_ref[0, :, 0]
        p = jnp.exp2(s - lse[:, None])
        if use_segs or offset != 0:
            # fully-skipped q rows carry lse == -inf (never occurs in the
            # plain causal path — every row sees its diagonal)
            p = jnp.where(jnp.isfinite(lse)[:, None], p, 0.0)
        dv_acc[pl.dslice(kv_idx * bk, bk), :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_scr[:, 0]
        ds = p * (dp - delta[:, None])
        dsl = ds.astype(q.dtype)
        dq_acc[:] += jax.lax.dot_general(
            dsl, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[pl.dslice(kv_idx * bk, bk), :] += jax.lax.dot_general(
            dsl, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kv_idx == num_kv - 1)
    def _fin_q():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(q_idx == num_q - 1, kv_idx == num_kv - 1))
    def _fin_kv():
        dk_ref[0] = (dk_acc[:] * (1.0 / LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_fused(scale, causal, segment_ids, res, do, causal_offset):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qr = (q * (scale * LOG2E)).astype(q.dtype) \
        .transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    dor = do.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    outr = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    lser = jnp.broadcast_to((lse * LOG2E).reshape(b * h, sq)[:, :, None],
                            (b * h, sq, SUBLANES))
    bq, _ = _block_sizes(sq, d, q.dtype, role="bwd")
    _, bk = _block_sizes(sk, d, q.dtype, role="bwd")
    num_q, num_kv = sq // bq, sk // bk

    use_segs = segment_ids is not None
    seg_specs, seg_args = _seg_operands(segment_ids, b, h, sq, sk, bq, bk)

    kernel = functools.partial(
        _bwd_fused_kernel, scale=scale, causal=causal, offset=causal_offset,
        bq=bq, bk=bk, num_q=num_q, num_kv=num_kv, use_segs=use_segs)
    if not use_segs:
        kernel = functools.partial(_nosegs_kernel, kernel)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, num_kv),
        in_specs=[
            *seg_specs,
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, SUBLANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i, j: (bh, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda bh, i, j: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((sk, d), jnp.float32),
            pltpu.VMEM((sk, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "arbitrary", "arbitrary"),
        interpret=not on_tpu(),
        name="flash_bwd_fused",
    )(*seg_args, qr, kr, vr, dor, outr, lser)
    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# backward — split two-kernel fallback (long sequences)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc,
                   *, scale, causal, offset, bq, bk, num_kv, use_segs):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = kv_idx * bk <= q_idx * bq + bq - 1 + offset

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_idx, kv_idx, bq, bk, offset)
        if use_segs:
            seg_ok = q_seg_ref[0, :, 0][:, None] == kv_seg_ref[0, 0, :][None, :]
            s = jnp.where(seg_ok, s, DEFAULT_MASK_VALUE)
        lse = lse_ref[0, :, 0]
        p = jnp.exp2(s - lse[:, None])
        if use_segs or offset != 0:
            p = jnp.where(jnp.isfinite(lse)[:, None], p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0, :, 0]
        ds = p * (dp - delta[:, None])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, offset, bq, bk, num_q, use_segs):
    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(1)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        # q block strictly before kv block -> fully masked
        run = q_idx * bq + bq - 1 + offset >= kv_idx * bk

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_idx, kv_idx, bq, bk, offset)
        if use_segs:
            seg_ok = q_seg_ref[0, :, 0][:, None] == kv_seg_ref[0, 0, :][None, :]
            s = jnp.where(seg_ok, s, DEFAULT_MASK_VALUE)
        lse = lse_ref[0, :, 0]
        p = jnp.exp2(s - lse[:, None])
        if use_segs or offset != 0:
            p = jnp.where(jnp.isfinite(lse)[:, None], p, 0.0)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0, :, 0]
        ds = p * (dp - delta[:, None])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_idx == num_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * (1.0 / LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_split(scale, causal, segment_ids, res, do, causal_offset):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qr = (q * (scale * LOG2E)).astype(q.dtype) \
        .transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    dor = do.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    outr = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    lser = (lse * LOG2E).reshape(b * h, sq)
    # delta = rowsum(do * o)  [bh, sq] -> narrow-lane [bh, sq, SUBLANES]
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[:, :, None], (b * h, sq, SUBLANES))
    lser = jnp.broadcast_to(lser[:, :, None], (b * h, sq, SUBLANES))
    bq, _ = _block_sizes(sq, d, q.dtype, role="bwd")
    _, bk = _block_sizes(sk, d, q.dtype, role="bwd")
    num_q, num_kv = sq // bq, sk // bk

    use_segs = segment_ids is not None
    seg_specs, seg_args = _seg_operands(segment_ids, b, h, sq, sk, bq, bk)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, offset=causal_offset,
        bq=bq, bk=bk, num_kv=num_kv, use_segs=use_segs)
    if not use_segs:
        dq_kernel = functools.partial(_nosegs_kernel, dq_kernel)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, num_q, num_kv),
        in_specs=[
            *seg_specs,
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, SUBLANES), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, SUBLANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=not on_tpu(),
        name="flash_bwd_dq",
    )(*seg_args, qr, kr, vr, dor, lser, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, offset=causal_offset,
        bq=bq, bk=bk, num_q=num_q, use_segs=use_segs)
    if not use_segs:
        dkv_kernel = functools.partial(_nosegs_kernel, dkv_kernel)
    dkv_seg_specs = [] if not use_segs else [
        pl.BlockSpec((1, bq, SUBLANES), lambda bh, j, i: (bh, i, 0)),
        pl.BlockSpec((1, SUBLANES, bk), lambda bh, j, i: (bh, 0, j)),
    ]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, num_kv, num_q),
        in_specs=[
            *dkv_seg_specs,
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bq, SUBLANES), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bq, SUBLANES), lambda bh, j, i: (bh, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=not on_tpu(),
        name="flash_bwd_dkv",
    )(*seg_args, qr, kr, vr, dor, lser, delta)

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


def _flash_bwd(scale, causal, segment_ids, res, g, causal_offset=0):
    do = g[0] if isinstance(g, (tuple, list)) else g
    q, k, v, out, lse = res
    sk, d = k.shape[1], k.shape[3]
    # fused pins two full-sk fp32 scratch planes PLUS the full-sk dk/dv
    # output blocks (constant-index out_specs) in VMEM per bh iteration
    dkv_bytes = 2 * sk * d * (4 + jnp.dtype(k.dtype).itemsize)
    if dkv_bytes <= _FUSED_DKV_VMEM_BYTES:
        return _flash_bwd_fused(scale, causal, segment_ids,
                                (q, k, v, out, lse), do, causal_offset)
    return _flash_bwd_split(scale, causal, segment_ids,
                            (q, k, v, out, lse), do, causal_offset)


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, segment_ids, scale, causal, use_segs):
    out, _ = _flash_fwd(q, k, v, scale, causal,
                        segment_ids if use_segs else None)
    return out


def _flash_fwd_rule(q, k, v, segment_ids, scale, causal, use_segs):
    segs = segment_ids if use_segs else None
    out, lse = _flash_fwd(q, k, v, scale, causal, segs)
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd_rule(scale, causal, use_segs, res, g):
    q, k, v, segment_ids, out, lse = res
    segs = segment_ids if use_segs else None
    dq, dk, dv = _flash_bwd(scale, causal, segs, (q, k, v, out, lse), g)
    dsegs = np.zeros(segment_ids.shape, jax.dtypes.float0)
    return dq, dk, dv, dsegs


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    softmax_scale: Optional[float] = None,
                    segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Flash attention on [b, s, h, d]; differentiable (works under jit —
    segment_ids is a real traced argument with zero cotangent)."""
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / math.sqrt(q.shape[-1])
    use_segs = segment_ids is not None
    if segment_ids is None:
        segment_ids = jnp.zeros((q.shape[0], q.shape[1]), jnp.int32)
    return _flash(q, k, v, segment_ids, scale, causal, use_segs)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             softmax_scale: Optional[float] = None,
                             segment_ids: Optional[jax.Array] = None):
    """Forward-only variant returning (out, lse) — the ring-attention
    building block."""
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / math.sqrt(q.shape[-1])
    return _flash_fwd(q, k, v, scale, causal, segment_ids)
