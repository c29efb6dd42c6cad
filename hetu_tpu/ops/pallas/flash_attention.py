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
(b, h, q, kv) with kv innermost; dq accumulates in a per-q-block VMEM
scratch, dk/dv accumulate in full-sequence VMEM scratch written out once
per head, and delta = rowsum(do*o) is computed in-kernel at kv==0 — so the
score matrix is materialized once per (q, kv) block pair instead of twice
(the split dq / dkv formulation).  Sequences whose dk/dv scratch would
exceed the VMEM budget fall back to the split two-kernel path.

Layout: [batch, seq, heads, head_dim] (reference convention), and the
kernels read it as it is.  With ``head_dim % 128 == 0`` a head's rows in
the [b, s, h*d] view (a reshape that moves nothing) are whole lane tiles,
so the (1, rows, d) block at block index (b, i, h) IS that head's block:
no operand and no result is transposed (``_Layout``, "native").  The
same index maps take q, k and v out of the fused projection's
[b, s, 3*h*d] (``flash_attention_qkv``).  A narrower head (64) cannot be
a lane block of a wider array and is transposed to [b*h, s, d] round the
call ("head_major").  One grid, one set of kernel bodies; the path is
chosen by the shape alone and counted in ``obs.counts("flash_calls")``.
Causal masking is block-skipped (fully-masked kv blocks are not
computed), and which of the other blocks is masked is a BRANCH, not a
value: ``_tiles`` runs a kernel's whole compute body under one of two
``pl.when`` — a block below the diagonal unmasked, a block the diagonal
crosses under the iota mask — where the seed wrapped the mask alone in a
``lax.cond`` over the [bq, bk] float32 scores.  That ``cond`` stood
between the first matmul and the softmax of EVERY block and cost the
forward 45 % and the fused backward 25 % of their time at the train
cells' shape on a v5e ([4, 2048, 12 x 128] bf16: 1.182 -> 0.649 and
1.798 -> 1.340 ms a call; PERF.md, PR 54).  A diagonal block is still
computed WHOLE: walking it in sub-blocks of q rows, each against the kv
columns it can see (3 of 4 or 10 of 16 sub-tiles), read 0.717 - 0.758 ms
forward and 1.297 - 1.355 backward, sub-blocks of kv columns 0.825: the
narrower matmuls and the extra passes over the softmax state cost what
the masked area saves (``_chip/step0_54.py``; the walk is
``_chip/walk_54.patch``).  The same holds for ring attention's shifted
diagonal (``causal_offset``), unequal blocks and the single-kv-block
fast path, whose one block is always masked.
``segment_ids`` gives packed/varlen semantics (the cu_seqlens path of the
reference, ``ops/Attention.h:286``).  Narrow (8-lane) layouts are used
for the lse / delta / q-segment operands — not full 128-lane broadcasts.

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
from ...obs.counters import count

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _empty_rows(m):
    """Rows whose max score is the mask fill value saw no valid kv
    position (ring varlen padding) — real logits can't get near it.
    Shared by the fast path and the accumulate finalize so the
    out=0/lse=-inf empty-row contract can't desynchronize."""
    return m <= DEFAULT_MASK_VALUE * 0.5

# Scores are computed as base-2 logits: the softmax scale AND log2(e) are
# folded into the q block (one [bq, d] multiply per q block, rounded to
# q's dtype, instead of a [bq, bk] multiply per block pair), and exp/log
# become exp2/log2 — the VPU-native transcendentals.  LSE stays
# natural-log at every API boundary (ring correction, backward, tests).
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


LANES = 128      # last-dim tile width
SUBLANES = 8     # second-to-last tile width (f32/int32)

# dk/dv full-sequence fp32 scratch budget for the fused backward; above
# this the split two-kernel path is used (e.g. d=64 -> sk <= 8192).
_FUSED_DKV_VMEM_BYTES = 4 * 1024 * 1024


def _row(axis):
    """``axis`` of a block spec as a function of the grid's block indices."""
    if callable(axis):
        return axis
    return (lambda g: 0) if axis is None else (lambda g: g[axis])


def _kv_row(causal, offset, bq, bk):
    """Row block of the kv-side operands on a grid (.., q, kv).  Under the
    causal block skip the steps past a q block's last visible kv block
    compute nothing: they keep that block's index, so the pipeline
    fetches nothing for them either (a strided native-layout block costs
    more to fetch than a contiguous one, and nothing hides it there)."""
    if not causal:
        return 1
    return lambda g: jnp.minimum(g[1], (g[0] * bq + bq - 1 + offset) // bk)


def _q_row(causal, offset, bq, bk, num_q):
    """The same for the q-side operands on the dkv kernel's (.., kv, q)
    grid: steps before a kv block's first visible q block keep its index."""
    if not causal:
        return 1
    return lambda g: jnp.clip((g[0] * bk - offset) // bq, g[1], num_q - 1)


class _Layout:
    """Where the kernels find a head's [rows, d] block.  Every call has
    the grid (b, h, *g) — ``g`` the q and kv block indices, in the
    kernel's order — and the index maps below are all that tells the two
    layouts apart (module docstring)."""

    def __init__(self, b: int, h: int, d: int):
        self.b, self.h, self.d = b, h, d
        self.native = d % LANES == 0
        count("flash_calls",
              layout="native" if self.native else "head_major")

    def view(self, x):
        """[b, s, h, d] as a kernel operand."""
        b, s, h, d = x.shape
        if self.native:
            return x.reshape(b, s, h * d)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def unview(self, y):
        """A kernel result as [b, s, h, d]."""
        if self.native:
            return y.reshape(self.b, -1, self.h, self.d)
        return y.reshape(self.b, self.h, -1, self.d).transpose(0, 2, 1, 3)

    def shape(self, s: int):
        if self.native:
            return (self.b, s, self.h * self.d)
        return (self.b * self.h, s, self.d)

    def spec(self, rows: int, axis, head0: int = 0):
        """A head's (1, rows, d) block at row block ``g[axis]`` (``None``:
        the whole sequence, block 0; a callable: ``axis(g)``).  ``head0``
        is the head's offset in an array that holds more than these h
        heads (the fused qkv)."""
        h, d = self.h, self.d
        row = _row(axis)
        if self.native:
            return pl.BlockSpec(
                (1, rows, d), lambda b, hh, *g: (b, row(g), head0 + hh))
        return pl.BlockSpec(
            (1, rows, d), lambda b, hh, *g: (b * h + hh, row(g), 0))

    def narrow(self, rows: int, axis):
        """The lse / delta operands, [b*h, s, SUBLANES] in both layouts."""
        h, row = self.h, _row(axis)
        return pl.BlockSpec((1, rows, SUBLANES),
                            lambda b, hh, *g: (b * h + hh, row(g), 0))


def _seg_operands(segment_ids, sq, sk, bq, bk, q_axis=0, kv_axis=1):
    """(in_specs, operands) for the segment-id streams — empty when
    segments are unused, so the common no-packing case pays zero extra
    HBM traffic for them.  TPU-tileable layouts, one per batch row (the
    heads share it): q side [b, sq, SUBLANES] (narrow lanes), kv side
    [b, SUBLANES, sk].

    ``segment_ids`` is either a [b, sq] array (shared q/kv — requires
    sq == sk) or a tuple ``(q_ids [b, sq], kv_ids [b, sk])`` — the ring
    attention case where the visiting KV block carries its own ids.
    """
    if segment_ids is None:
        return [], []
    if isinstance(segment_ids, (tuple, list)):
        q_ids, kv_ids = segment_ids
    else:
        if sq != sk:
            raise NotImplementedError(
                "segment_ids with sq != sk needs a (q_ids, kv_ids) tuple")
        q_ids = kv_ids = segment_ids
    b = q_ids.shape[0]
    q_segs = jnp.broadcast_to(q_ids[:, :, None], (b, sq, SUBLANES))
    kv_segs = jnp.broadcast_to(kv_ids[:, None, :], (b, SUBLANES, sk))
    q_row, kv_row = _row(q_axis), _row(kv_axis)
    specs = [
        pl.BlockSpec((1, bq, SUBLANES), lambda b, hh, *g: (b, q_row(g), 0)),
        pl.BlockSpec((1, SUBLANES, bk), lambda b, hh, *g: (b, 0, kv_row(g))),
    ]
    return specs, [q_segs, kv_segs]


def _scaled(q_ref, qscale):
    """The q block times softmax_scale * LOG2E, rounded to q's dtype."""
    return (q_ref[0].astype(jnp.float32) * qscale).astype(q_ref.dtype)


def _dim_semantics(*sem):
    """Mosaic dimension semantics (parallel dims may split across
    TensorCores)."""
    return pltpu.CompilerParams(dimension_semantics=sem)


def _nosegs_kernel(kernel, *refs, **kw):
    """Adapter: invoke a seg-aware kernel with no segment operands (the
    kernels read ``q_seg_ref is None`` as "not packed")."""
    return kernel(None, None, *refs, **kw)


def _causal_mask(s, q_idx, kv_idx, offset):
    """The causal mask of a [bq, bk] score block that crosses the
    diagonal.  Shared by all four kernels so fwd/bwd masking can never
    desynchronize."""
    bq, bk = s.shape
    row = lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if offset == 0 and bq == bk:
        # the block ON an unshifted diagonal: the same triangle whatever
        # its indices (an operation a score element less: the kernels are
        # bound by the VPU's passes, and the forward read 0.649 ms a call
        # for 0.726 with the scalar below)
        keep = col <= row
    else:
        keep = col - row <= q_idx * bq + offset - kv_idx * bk
    return jnp.where(keep, s, DEFAULT_MASK_VALUE)


def _tiles(causal, offset, q_idx, kv_idx, bq, bk, tile):
    """Run ``tile(masked)`` where this grid step's [bq, bk] score block
    holds a visible position: a block below the diagonal unmasked, a block
    the diagonal crosses under ``_causal_mask``, a block past it not at
    all.  The two are BRANCHES (``pl.when``), each a straight line from
    the scores to the accumulators, and not a ``lax.cond`` round the mask
    (module docstring)."""
    if not causal:
        tile(False)
        return
    # offset shifts the diagonal right: rows are offset global positions
    # ahead of cols — the SYM tail-half case
    first_row, first_col = q_idx * bq + offset, kv_idx * bk
    crossed = first_col + bk - 1 > first_row      # some position is masked
    visible = first_col <= first_row + bq - 1     # and some is not
    pl.when(jnp.logical_not(crossed))(lambda: tile(False))
    pl.when(jnp.logical_and(crossed, visible))(lambda: tile(True))


def _score_tile(q, k, q_seg_ref, kv_seg_ref, masked, q_idx, kv_idx, offset):
    """Base-2 logits of a q block against a kv block, causal (``masked``)
    and segment masks applied."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if masked:
        s = _causal_mask(s, q_idx, kv_idx, offset)
    if q_seg_ref is not None:
        # narrow-lane q ids against sublane-padded kv ids
        seg_ok = (q_seg_ref[0, :, 0][:, None]
                  == kv_seg_ref[0, 0, :][None, :])
        s = jnp.where(seg_ok, s, DEFAULT_MASK_VALUE)
    return s


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
                qs_ref, acc_ref, m_ref, l_ref,               # scratch
                *, qscale: float, causal: bool, offset: int, bq: int,
                bk: int, num_kv: int):
    # the q block is scaled by softmax_scale * LOG2E once, at its first
    # kv block (qs_ref): scores are base-2 logits and all exps are exp2
    # (see module constant note).
    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(3)
    use_segs = q_seg_ref is not None

    def _scores(q, masked):
        return _score_tile(q, k_ref[0], q_seg_ref, kv_seg_ref, masked, q_idx,
                           kv_idx, offset)

    if num_kv == 1 and (not causal or offset == 0):
        # single-kv-block fast path (the whole kv sequence is one block,
        # and the block is never fully skipped): no online-softmax carry,
        # no scratch traffic, outputs written directly
        s = _scores(_scaled(q_ref, qscale), causal)
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
        qs_ref[:] = _scaled(q_ref, qscale)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute(masked):
        s = _scores(qs_ref[:], masked)
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

    _tiles(causal, offset, q_idx, kv_idx, bq, bk, _compute)

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


def _fwd_call(lay, q, k, v, heads0, sq, sk, scale, causal, segment_ids,
              causal_offset):
    """``q``, ``k``, ``v`` as ``lay`` views them: three arrays, or the one
    fused qkv array three times with the head offsets ``heads0``.
    Returns (out in the same layout, lse [b, h, sq])."""
    b, h, d = lay.b, lay.h, lay.d
    bq, _ = _block_sizes(sq, d, q.dtype)
    _, bk = _block_sizes(sk, d, q.dtype)
    num_q, num_kv = sq // bq, sk // bk

    use_segs = segment_ids is not None
    kv = _kv_row(causal, causal_offset, bq, bk)
    seg_specs, seg_args = _seg_operands(segment_ids, sq, sk, bq, bk,
                                        kv_axis=kv)

    kernel = functools.partial(
        _fwd_kernel, qscale=scale * LOG2E, causal=causal,
        offset=causal_offset, bq=bq, bk=bk, num_kv=num_kv)
    if not use_segs:
        kernel = functools.partial(_nosegs_kernel, kernel)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, num_q, num_kv),
        in_specs=[
            *seg_specs,
            lay.spec(bq, 0, heads0[0]),
            lay.spec(bk, kv, heads0[1]),
            lay.spec(bk, kv, heads0[2]),
        ],
        out_specs=[lay.spec(bq, 0), lay.narrow(bq, 0)],
        out_shape=[
            jax.ShapeDtypeStruct(lay.shape(sq), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, SUBLANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), q.dtype),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "parallel", "parallel",
                                       "arbitrary"),
        interpret=not on_tpu(),
        name="flash_fwd",
    )(*seg_args, q, k, v)
    return out, lse[:, :, 0].reshape(b, h, sq)


def _flash_fwd(q, k, v, scale, causal, segment_ids, causal_offset=0):
    """(out [b, sq, h, d], lse [b, h, sq]) of [b, s, h, d] operands."""
    b, sq, h, d = q.shape
    lay = _Layout(b, h, d)
    out, lse = _fwd_call(lay, lay.view(q), lay.view(k), lay.view(v),
                         (0, 0, 0), sq, k.shape[1], scale, causal,
                         segment_ids, causal_offset)
    return lay.unview(out), lse


# ---------------------------------------------------------------------------
# backward — fused single kernel (dq + dk + dv)
# ---------------------------------------------------------------------------

def _p_ds(q, k, v, do, lse, delta, q_seg_ref, kv_seg_ref, masked, q_idx,
          kv_idx, offset):
    """(p float32, ds in q's dtype) of one score tile: q already scaled
    by softmax_scale * LOG2E and ``lse`` by LOG2E, so p = exp2(s2 - lse2)
    with no per-element scale multiplies."""
    s = _score_tile(q, k, q_seg_ref, kv_seg_ref, masked, q_idx, kv_idx,
                    offset)
    p = jnp.exp2(s - lse[:, None])
    if q_seg_ref is not None or offset != 0:
        # fully-skipped q rows carry lse == -inf (never occurs in the
        # plain causal path — every row sees its diagonal)
        p = jnp.where(jnp.isfinite(lse)[:, None], p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, (p * (dp - delta[:, None])).astype(q.dtype)


def _bwd_fused_kernel(q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref,
                      o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref,
                      qs_ref, dq_acc, dk_acc, dv_acc, delta_scr,
                      *, scale, causal, offset, bq, bk, num_q, num_kv):
    # the q block is scaled by softmax_scale * LOG2E at its first kv block
    # (qs_ref) and lse arrives pre-scaled by LOG2E, so p = exp2(s2 - lse2)
    # with no per-element scale multiplies; the deferred scales land on
    # the [*, d] accumulators at finalize: dq *= scale, dk /= LOG2E (dk
    # was accumulated against the scaled q).
    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(3)

    @pl.when(jnp.logical_and(q_idx == 0, kv_idx == 0))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kv_idx == 0)
    def _init_q():
        qs_ref[:] = _scaled(q_ref, scale * LOG2E)
        dq_acc[:] = jnp.zeros_like(dq_acc)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        delta = jnp.sum(do * o, axis=1)          # rowsum(do*o), in-kernel
        delta_scr[:] = jnp.broadcast_to(delta[:, None], delta_scr.shape)

    def _compute(masked):
        q, k, do = qs_ref[:], k_ref[0], do_ref[0]
        p, ds = _p_ds(q, k, v_ref[0], do, lse_ref[0, :, 0], delta_scr[:, 0],
                      q_seg_ref, kv_seg_ref, masked, q_idx, kv_idx, offset)
        kv_rows = pl.dslice(kv_idx * bk, bk)
        dv_acc[kv_rows, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[kv_rows, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # fully-masked (q, kv) block pairs contribute to none of dq/dk/dv
    _tiles(causal, offset, q_idx, kv_idx, bq, bk, _compute)

    @pl.when(kv_idx == num_kv - 1)
    def _fin_q():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(q_idx == num_q - 1, kv_idx == num_kv - 1))
    def _fin_kv():
        dk_ref[0] = (dk_acc[:] * (1.0 / LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _narrow(x, b, h, sq):
    """[b, h, sq] float32 as the kernels' [b*h, sq, SUBLANES] operand."""
    return jnp.broadcast_to(x.reshape(b * h, sq)[:, :, None],
                            (b * h, sq, SUBLANES))


def _bwd_fused_call(lay, q, k, v, heads0, do, out, lse, sq, sk, scale,
                    causal, segment_ids, causal_offset):
    b, h, d = lay.b, lay.h, lay.d
    bq, _ = _block_sizes(sq, d, q.dtype, role="bwd")
    _, bk = _block_sizes(sk, d, q.dtype, role="bwd")
    num_q, num_kv = sq // bq, sk // bk

    use_segs = segment_ids is not None
    kv = _kv_row(causal, causal_offset, bq, bk)
    seg_specs, seg_args = _seg_operands(segment_ids, sq, sk, bq, bk,
                                        kv_axis=kv)

    kernel = functools.partial(
        _bwd_fused_kernel, scale=scale, causal=causal, offset=causal_offset,
        bq=bq, bk=bk, num_q=num_q, num_kv=num_kv)
    if not use_segs:
        kernel = functools.partial(_nosegs_kernel, kernel)
    return pl.pallas_call(
        kernel,
        grid=(b, h, num_q, num_kv),
        in_specs=[
            *seg_specs,
            lay.spec(bq, 0, heads0[0]),
            lay.spec(bk, kv, heads0[1]),
            lay.spec(bk, kv, heads0[2]),
            lay.spec(bq, 0),
            lay.spec(bq, 0),
            lay.narrow(bq, 0),
        ],
        out_specs=[lay.spec(bq, 0), lay.spec(sk, None), lay.spec(sk, None)],
        out_shape=[
            jax.ShapeDtypeStruct(lay.shape(sq), q.dtype),
            jax.ShapeDtypeStruct(lay.shape(sk), k.dtype),
            jax.ShapeDtypeStruct(lay.shape(sk), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), q.dtype),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((sk, d), jnp.float32),
            pltpu.VMEM((sk, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary",
                                       "arbitrary"),
        interpret=not on_tpu(),
        name="flash_bwd_fused",
    )(*seg_args, q, k, v, do, out, _narrow(lse * LOG2E, b, h, sq))


# ---------------------------------------------------------------------------
# backward — split two-kernel fallback (long sequences)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, qs_ref, dq_acc,
                   *, scale, causal, offset, bq, bk, num_kv):
    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(3)

    @pl.when(kv_idx == 0)
    def _init():
        qs_ref[:] = _scaled(q_ref, scale * LOG2E)
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(masked):
        k = k_ref[0]
        _, ds = _p_ds(qs_ref[:], k, v_ref[0], do_ref[0], lse_ref[0, :, 0],
                      delta_ref[0, :, 0], q_seg_ref, kv_seg_ref, masked,
                      q_idx, kv_idx, offset)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tiles(causal, offset, q_idx, kv_idx, bq, bk, _compute)

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_seg_ref, kv_seg_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, offset, bq, bk, num_q):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(3)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        # a new q block every step here: scaled where it is used
        q = _scaled(q_ref, scale * LOG2E)
        do = do_ref[0]
        p, ds = _p_ds(q, k_ref[0], v_ref[0], do, lse_ref[0, :, 0],
                      delta_ref[0, :, 0], q_seg_ref, kv_seg_ref, masked,
                      q_idx, kv_idx, offset)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # (a q block strictly before the kv block is fully masked)
    _tiles(causal, offset, q_idx, kv_idx, bq, bk, _compute)

    @pl.when(q_idx == num_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * (1.0 / LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_split_call(lay, q, k, v, heads0, do, out, lse, sq, sk, scale,
                    causal, segment_ids, causal_offset):
    b, h, d = lay.b, lay.h, lay.d
    # delta = rowsum(do * o): [b, sq, h] out of either layout's [.., d]
    # rows, then head-major like lse (a tensor d times smaller than do)
    delta = jnp.sum((do.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(do.shape[:-1] + (-1, d)), axis=-1)
    if lay.native:
        delta = delta.transpose(0, 2, 1)
    delta = _narrow(delta, b, h, sq)
    lser = _narrow(lse * LOG2E, b, h, sq)
    bq, _ = _block_sizes(sq, d, q.dtype, role="bwd")
    _, bk = _block_sizes(sk, d, q.dtype, role="bwd")
    num_q, num_kv = sq // bq, sk // bk

    use_segs = segment_ids is not None
    kv = _kv_row(causal, causal_offset, bq, bk)
    seg_specs, seg_args = _seg_operands(segment_ids, sq, sk, bq, bk,
                                        kv_axis=kv)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, offset=causal_offset,
        bq=bq, bk=bk, num_kv=num_kv)
    if not use_segs:
        dq_kernel = functools.partial(_nosegs_kernel, dq_kernel)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, num_q, num_kv),
        in_specs=[
            *seg_specs,
            lay.spec(bq, 0, heads0[0]),
            lay.spec(bk, kv, heads0[1]),
            lay.spec(bk, kv, heads0[2]),
            lay.spec(bq, 0),
            lay.narrow(bq, 0),
            lay.narrow(bq, 0),
        ],
        out_specs=lay.spec(bq, 0),
        out_shape=jax.ShapeDtypeStruct(lay.shape(sq), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), q.dtype),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_dim_semantics("parallel", "parallel", "parallel",
                                       "arbitrary"),
        interpret=not on_tpu(),
        name="flash_bwd_dq",
    )(*seg_args, q, k, v, do, lser, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, offset=causal_offset,
        bq=bq, bk=bk, num_q=num_q)
    if not use_segs:
        dkv_kernel = functools.partial(_nosegs_kernel, dkv_kernel)
    # grid (b, h, kv, q): the kv block index is g[0], the q block's g[1]
    qr = _q_row(causal, causal_offset, bq, bk, num_q)
    dkv_seg_specs, _ = _seg_operands(segment_ids, sq, sk, bq, bk,
                                     q_axis=qr, kv_axis=0)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, num_kv, num_q),
        in_specs=[
            *dkv_seg_specs,
            lay.spec(bq, qr, heads0[0]),
            lay.spec(bk, 0, heads0[1]),
            lay.spec(bk, 0, heads0[2]),
            lay.spec(bq, qr),
            lay.narrow(bq, qr),
            lay.narrow(bq, qr),
        ],
        out_specs=[lay.spec(bk, 0), lay.spec(bk, 0)],
        out_shape=[
            jax.ShapeDtypeStruct(lay.shape(sk), k.dtype),
            jax.ShapeDtypeStruct(lay.shape(sk), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "parallel", "parallel",
                                       "arbitrary"),
        interpret=not on_tpu(),
        name="flash_bwd_dkv",
    )(*seg_args, q, k, v, do, lser, delta)
    return dq, dk, dv


def _bwd_call(lay, q, k, v, heads0, do, out, lse, sq, sk, *args):
    """(dq, dk, dv) in ``lay``'s layout; operands as ``_fwd_call`` takes
    them, ``do`` and ``out`` in the layout ``_fwd_call`` returned."""
    # fused pins two full-sk fp32 scratch planes PLUS the full-sk dk/dv
    # output blocks (constant-index out_specs) in VMEM per head
    dkv_bytes = 2 * sk * lay.d * (4 + jnp.dtype(k.dtype).itemsize)
    call = _bwd_fused_call if dkv_bytes <= _FUSED_DKV_VMEM_BYTES \
        else _bwd_split_call
    return call(lay, q, k, v, heads0, do, out, lse, sq, sk, *args)


def _flash_bwd(scale, causal, segment_ids, res, g, causal_offset=0):
    """(dq, dk, dv) of [b, s, h, d] operands; ``res`` = (q, k, v, out,
    lse) as ``_flash_fwd`` took and returned them."""
    do = g[0] if isinstance(g, (tuple, list)) else g
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    lay = _Layout(b, h, d)
    grads = _bwd_call(lay, lay.view(q), lay.view(k), lay.view(v), (0, 0, 0),
                      lay.view(do), lay.view(out), lse, sq, k.shape[1],
                      scale, causal, segment_ids, causal_offset)
    return tuple(lay.unview(x) for x in grads)


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


# ---------------------------------------------------------------------------
# self-attention off the fused projection: q | k | v on one array's lanes
# ---------------------------------------------------------------------------

def _qkv_fwd(qkv, segs, h, scale, causal):
    b, s, w = qkv.shape
    return _fwd_call(_Layout(b, h, w // (3 * h)), qkv, qkv, qkv,
                     (0, h, 2 * h), s, s, scale, causal, segs, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _flash_qkv(qkv, segment_ids, h, scale, causal, use_segs):
    return _qkv_fwd(qkv, segment_ids if use_segs else None, h, scale,
                    causal)[0]


def _flash_qkv_fwd_rule(qkv, segment_ids, h, scale, causal, use_segs):
    out, lse = _qkv_fwd(qkv, segment_ids if use_segs else None, h, scale,
                        causal)
    return out, (qkv, segment_ids, out, lse)


def _flash_qkv_bwd_rule(h, scale, causal, use_segs, res, do):
    qkv, segment_ids, out, lse = res
    b, s, w = qkv.shape
    lay = _Layout(b, h, w // (3 * h))
    grads = _bwd_call(lay, qkv, qkv, qkv, (0, h, 2 * h), do, out, lse, s, s,
                      scale, causal, segment_ids if use_segs else None, 0)
    # dq | dk | dv as a sum of three pads (what a slice's transpose is):
    # XLA fuses that into the operands of the projection's dW and dx
    # matmuls, where a concatenate is materialized by three
    # dynamic-update-slices over the whole [b, s, 3*h*d]
    zero = jnp.zeros((), do.dtype)
    dqkv = sum(lax.pad(g, zero, [(0, 0, 0), (0, 0, 0),
                                 (i * h * lay.d, (2 - i) * h * lay.d, 0)])
               for i, g in enumerate(grads))
    dsegs = np.zeros(segment_ids.shape, jax.dtypes.float0)
    return dqkv, dsegs


_flash_qkv.defvjp(_flash_qkv_fwd_rule, _flash_qkv_bwd_rule)


def flash_attention_qkv(qkv, num_heads: int, causal: bool = True,
                        softmax_scale: Optional[float] = None,
                        segment_ids: Optional[jax.Array] = None):
    """Self-attention on the fused projection's [b, s, 3*h*d] (q | k | v,
    each h heads of d) -> [b, s, h*d]; differentiable.  With
    ``d % 128 == 0`` the three operands are block index maps on the one
    array and nothing is sliced out; a narrower head goes through
    :func:`flash_attention`."""
    b, s, w = qkv.shape
    d = w // (3 * num_heads)
    if d % LANES:
        q, k, v = (x.reshape(b, s, num_heads, d)
                   for x in jnp.split(qkv, 3, axis=-1))
        return flash_attention(q, k, v, causal, softmax_scale,
                               segment_ids).reshape(b, s, num_heads * d)
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / math.sqrt(d)
    use_segs = segment_ids is not None
    if segment_ids is None:
        segment_ids = jnp.zeros((b, s), jnp.int32)
    return _flash_qkv(qkv, segment_ids, num_heads, scale, causal, use_segs)
