"""Ring attention — context parallelism over a mesh axis.

TPU-native re-expression of the reference's ``AttnCommRing``
(``hetu/graph/ops/ParallelAttention.h:342``, ``.cc:611,781``): the sequence
is sharded over the ``cp`` mesh axis; KV blocks circulate the ring
(``lax.ppermute`` — the reference's ``BatchedISendIRecv`` ring exchange)
while each rank runs blockwise flash attention on its local Q against the
visiting KV, merging partial results with online log-sum-exp correction
(the reference's ``ExecCorr``).  XLA overlaps the ppermute with the
per-round kernels the way the reference overlaps its comm/attn CUDA
streams via events.

Split patterns (reference ``SplitPattern`` NORMAL/SYM,
``ParallelAttention.h:19``, env ``HETU_PARALLEL_ATTN_SPLIT_PATTERN``):

- ``normal`` — contiguous split.  Under a causal mask the per-pair
  classes are CAUSAL/FULL/EMPTY and the *last* rank does ~cp× the work
  of rank 0 (the imbalance SYM exists to kill).
- ``sym`` — symmetric (head+tail) split: the global sequence is cut into
  ``2·cp`` chunks and rank i holds chunks ``(i, 2cp-1-i)``.  Per-pair
  masks then fall into the reference's five classes
  (``AttnMask`` CAUSAL/ROW/COL/EMPTY/FULL, ``.cc:140-200``): the pair
  with itself is the composite causal (head-causal / tail-sees-head /
  tail-causal), earlier ranks' KV is visible only in its head half
  (COL), later ranks only to the tail Q half (ROW) — every (rank, round)
  does exactly ``s_local²/2`` score work, i.e. perfectly balanced.

Variable per-rank sequence lengths (reference ``_seq_len_list``) and
packed/varlen sequences ride the same mechanism: local segment ids
(global doc ids, ``-1`` = padding) travel the ring *with* their KV block
and mask score entries whose q/kv ids differ.  This works under BOTH
split patterns: the segment mask is an id-equality test — independent of
position order — so it composes multiplicatively with the SYM structural
masks (CAUSAL_SYM/COL/ROW), each branch slicing the travelling id pair to
its q/kv halves (reference supports ``_seq_len_list`` under SYM,
``ParallelAttention.h:342``, ``.cc:140-200``).

Usage: inside ``shard_map`` with the sequence dim sharded over
``axis_name``; or via :func:`ring_attention_sharded` which wraps the
shard_map for [b, s, h, d] inputs.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .comm import axis_size

from ..ops.pallas.flash_attention import _flash_bwd, _flash_fwd

# pair-mask classes (reference AttnMask, ParallelAttention.h:25);
# at runtime they are compressed into per-pattern 0..2 branch indices
# (see _mask_kind) so only reachable branches compile
CAUSAL, FULL, EMPTY, CAUSAL_SYM, COL, ROW = range(6)


def _seg_slice(segs, qs, ks):
    """Slice a (q_ids, kv_ids) tuple to the given q/kv ranges; None
    ranges keep the full side, segs=None stays None (shared by the SYM
    fwd/bwd branches so their masks cannot diverge)."""
    if segs is None:
        return None
    q_ids, kv_ids = segs
    return (q_ids if qs is None else q_ids[:, qs],
            kv_ids if ks is None else kv_ids[:, ks])


def _merge(acc, o_r, lse_r):
    """Online LSE merge of one round's (normalized out, lse) into the
    accumulator (reference ExecCorr, ParallelAttention.h:361).

    m/denom/lse live in [b, h, s]; the out accumulator in [b, s, h, d].
    """
    m, denom, out = acc
    m_new = jnp.maximum(m, lse_r)
    # where lse_r == -inf (empty round) the contribution vanishes;
    # exp(-inf - -inf) would be nan, so guard the all-empty case
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    c_old = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    c_new = jnp.where(jnp.isfinite(lse_r), jnp.exp(lse_r - m_safe), 0.0)
    denom_new = denom * c_old + c_new
    to_out = lambda c: c.transpose(0, 2, 1)[..., None]  # [b,h,s]->[b,s,h,1]
    out_new = out * to_out(c_old) + o_r * to_out(c_new)
    return m_new, denom_new, out_new


def _pair_fwd(q, k, v, scale, mask_kind, segs, pattern, causal):
    """(out, lse) of one (q-rank, kv-rank) pair.

    ``mask_kind`` is a 0..2 class index whose meaning depends on the
    static ``pattern`` (normal: CAUSAL/FULL/EMPTY; sym:
    CAUSAL_SYM/COL/ROW) so only the three reachable branches compile;
    ``segs`` is None or a ``(q_ids [b,s], kv_ids [b,s])`` tuple — under
    SYM each branch slices the pair to its q/kv halves.
    """
    b, s, h, d = q.shape
    sh = s // 2

    def causal_fn(_):
        o, lse = _flash_fwd(q, k, v, scale, True, segs)
        return o.astype(jnp.float32), lse  # branch dtypes must match empty_fn

    def full_fn(_):
        o, lse = _flash_fwd(q, k, v, scale, False, segs)
        return o.astype(jnp.float32), lse

    def empty_fn(_):
        return (jnp.zeros((b, s, h, d), jnp.float32),
                jnp.full((b, h, s), -jnp.inf, jnp.float32))

    def causal_sym_fn(_):
        # [[causal, empty], [full, causal]] on (head, tail) halves:
        # qh vs kh causal; qt vs full kv causal shifted by sh
        o1, l1 = _flash_fwd(q[:, :sh], k[:, :sh], v[:, :sh], scale, True,
                            _seg_slice(segs, slice(None, sh), slice(None, sh)))
        o2, l2 = _flash_fwd(q[:, sh:], k, v, scale, True,
                            _seg_slice(segs, slice(sh, None), None),
                            causal_offset=sh)
        return (jnp.concatenate([o1, o2], axis=1).astype(jnp.float32),
                jnp.concatenate([l1, l2], axis=2))

    def col_fn(_):
        # all q rows see only the kv head half (earlier chunk)
        o, lse = _flash_fwd(q, k[:, :sh], v[:, :sh], scale, False,
                            _seg_slice(segs, None, slice(None, sh)))
        return o.astype(jnp.float32), lse

    def row_fn(_):
        # only the q tail half sees this (later) rank's kv
        o2, l2 = _flash_fwd(q[:, sh:], k, v, scale, False,
                            _seg_slice(segs, slice(sh, None), None))
        o = jnp.concatenate(
            [jnp.zeros((b, sh, h, d), jnp.float32), o2.astype(jnp.float32)],
            axis=1)
        lse = jnp.concatenate(
            [jnp.full((b, h, sh), -jnp.inf, jnp.float32), l2], axis=2)
        return o, lse

    if not causal:
        return full_fn(None)
    branches = [causal_sym_fn, col_fn, row_fn] if pattern == "sym" \
        else [causal_fn, full_fn, empty_fn]
    return lax.switch(mask_kind, branches, None)


def _pair_bwd(q, k, v, do, out, lse, scale, mask_kind, segs, pattern,
              causal):
    """dq, dk, dv of one pair given global lse; empty pairs short-circuit.
    Branch selection mirrors :func:`_pair_fwd`."""
    b, s, h, d = q.shape
    sh = s // 2

    def causal_fn(_):
        return _flash_bwd(scale, True, segs, (q, k, v, out, lse), do)

    def full_fn(_):
        return _flash_bwd(scale, False, segs, (q, k, v, out, lse), do)

    def empty_fn(_):
        return (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v))

    def causal_sym_fn(_):
        dq1, dk1, dv1 = _flash_bwd(
            scale, True, _seg_slice(segs, slice(None, sh), slice(None, sh)),
            (q[:, :sh], k[:, :sh], v[:, :sh], out[:, :sh], lse[:, :, :sh]),
            do[:, :sh])
        dq2, dk2, dv2 = _flash_bwd(
            scale, True, _seg_slice(segs, slice(sh, None), None),
            (q[:, sh:], k, v, out[:, sh:], lse[:, :, sh:]),
            do[:, sh:], causal_offset=sh)
        dq = jnp.concatenate([dq1, dq2], axis=1)
        pad = jnp.zeros((b, sh, h, d), dk1.dtype)
        dk = jnp.concatenate([dk1, pad], axis=1) + dk2
        dv = jnp.concatenate([dv1, pad], axis=1) + dv2
        return dq, dk, dv

    def col_fn(_):
        dq, dkh, dvh = _flash_bwd(
            scale, False, _seg_slice(segs, None, slice(None, sh)),
            (q, k[:, :sh], v[:, :sh], out, lse), do)
        pad = jnp.zeros((b, s - sh, h, d), dkh.dtype)
        return (dq, jnp.concatenate([dkh, pad], axis=1),
                jnp.concatenate([dvh, pad], axis=1))

    def row_fn(_):
        dq2, dk, dv = _flash_bwd(
            scale, False, _seg_slice(segs, slice(sh, None), None),
            (q[:, sh:], k, v, out[:, sh:], lse[:, :, sh:]), do[:, sh:])
        dq = jnp.concatenate(
            [jnp.zeros((b, sh, h, d), dq2.dtype), dq2], axis=1)
        return dq, dk, dv

    if not causal:
        return full_fn(None)
    branches = [causal_sym_fn, col_fn, row_fn] if pattern == "sym" \
        else [causal_fn, full_fn, empty_fn]
    return lax.switch(mask_kind, branches, None)


def _mask_kind(my_rank, kv_rank, causal: bool, pattern: str):
    """Classify the (q-rank, kv-rank) pair into a 0..2 branch index
    (reference GenerateAttnInfo, ParallelAttention.cc:140-200): under
    "normal" 0/1/2 = CAUSAL/FULL/EMPTY, under "sym" = CAUSAL_SYM/COL/ROW
    — in both patterns self-pair / earlier-rank / later-rank."""
    if not causal:
        return jnp.int32(0)  # unused: _pair_* short-circuit to full
    return jnp.where(kv_rank == my_rank, 0,
                     jnp.where(kv_rank < my_rank, 1, 2)).astype(jnp.int32)


def _ring_segs(q_ids, kv_ids, use_segs):
    return (q_ids, kv_ids) if use_segs else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring_attn(q, k, v, seg_ids, axis_name, scale, causal, pattern,
               use_segs):
    out, _ = _ring_fwd_impl(q, k, v, seg_ids, axis_name, scale, causal,
                            pattern, use_segs)
    return out


def _ring_fwd_impl(q, k, v, seg_ids, axis_name, scale, causal, pattern,
                   use_segs):
    cp = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    # kv-side ids: padding (-1) maps to -2 so q-pad never matches kv-pad
    kv_ids0 = jnp.where(seg_ids < 0, -2, seg_ids)

    def body(r, carry):
        (k_cur, v_cur, kvseg_cur), acc = carry
        kv_rank = (my - r) % cp
        kind = _mask_kind(my, kv_rank, causal, pattern)
        o_r, lse_r = _pair_fwd(q, k_cur, v_cur, scale, kind,
                               _ring_segs(seg_ids, kvseg_cur, use_segs),
                               pattern, causal)
        acc = _merge(acc, o_r, lse_r)
        # rotate KV (and its segment ids) to the next rank (reference
        # BatchedISendIRecv ring); XLA overlaps with the next round
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        s_nxt = lax.ppermute(kvseg_cur, axis_name, perm)
        return (k_nxt, v_nxt, s_nxt), acc

    init_acc = (jnp.full((b, h, s), -jnp.inf, jnp.float32),   # m
                jnp.zeros((b, h, s), jnp.float32),            # denom
                jnp.zeros((b, s, h, d), jnp.float32))         # out (bqhd)
    (_, _, _), (m, denom, out_acc) = lax.fori_loop(
        0, cp, body, ((k, v, kv_ids0), init_acc))
    safe = jnp.where(denom == 0.0, 1.0, denom)
    # denom is [b, h, s]; out_acc is [b, s, h, d]
    out = out_acc / safe.transpose(0, 2, 1)[..., None]
    lse = jnp.where(denom == 0.0, -jnp.inf, m + jnp.log(safe))
    return out.astype(q.dtype), lse


def _ring_fwd_rule(q, k, v, seg_ids, axis_name, scale, causal, pattern,
                   use_segs):
    out, lse = _ring_fwd_impl(q, k, v, seg_ids, axis_name, scale, causal,
                              pattern, use_segs)
    return out, (q, k, v, seg_ids, out, lse)


def _ring_bwd_rule(axis_name, scale, causal, pattern, use_segs, res, do):
    q, k, v, seg_ids, out, lse = res
    cp = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    kv_ids0 = jnp.where(seg_ids < 0, -2, seg_ids)

    def body(r, carry):
        (k_cur, v_cur, kvseg_cur), (dk_cur, dv_cur), dq_acc = carry
        kv_rank = (my - r) % cp
        kind = _mask_kind(my, kv_rank, causal, pattern)
        dq_c, dk_c, dv_c = _pair_bwd(
            q, k_cur, v_cur, do, out, lse, scale, kind,
            _ring_segs(seg_ids, kvseg_cur, use_segs), pattern, causal)
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        dk_cur = dk_cur + dk_c.astype(jnp.float32)
        dv_cur = dv_cur + dv_c.astype(jnp.float32)
        # rotate KV and its grad accumulators together (grad piggyback):
        # after cp shifts they arrive back at the owning rank
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        s_nxt = lax.ppermute(kvseg_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = lax.ppermute(dv_cur, axis_name, perm)
        return (k_nxt, v_nxt, s_nxt), (dk_nxt, dv_nxt), dq_acc

    init = ((k, v, kv_ids0), (jnp.zeros(k.shape, jnp.float32),
                              jnp.zeros(v.shape, jnp.float32)),
            jnp.zeros(q.shape, jnp.float32))
    (_, (dk, dv), dq) = lax.fori_loop(0, cp, body, init)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            np.zeros(seg_ids.shape, jax.dtypes.float0))


_ring_attn.defvjp(_ring_fwd_rule, _ring_bwd_rule)


# ---------------------------------------------------------------------------
# SYM layout helpers


def sym_indices(s_global: int, cp: int) -> np.ndarray:
    """Permutation putting the global sequence into SYM ring layout:
    2·cp chunks, rank i's shard = [chunk i, chunk 2cp-1-i]."""
    assert s_global % (2 * cp) == 0, \
        f"seq {s_global} not divisible by 2*cp={2 * cp}"
    ch = s_global // (2 * cp)
    idx = []
    for i in range(cp):
        idx.extend(range(i * ch, (i + 1) * ch))
        idx.extend(range((2 * cp - 1 - i) * ch, (2 * cp - i) * ch))
    return np.asarray(idx, dtype=np.int64)


def sym_inverse_indices(s_global: int, cp: int) -> np.ndarray:
    fwd = sym_indices(s_global, cp)
    inv = np.empty_like(fwd)
    inv[fwd] = np.arange(s_global)
    return inv


def sym_shard(x, cp: int, axis: int = 1):
    """Reorder a GLOBAL array so contiguous cp-sharding yields the SYM
    layout (apply before feeding a seq-sharded pjit/shard_map)."""
    return jnp.take(x, jnp.asarray(sym_indices(x.shape[axis], cp)),
                    axis=axis)


def sym_unshard(x, cp: int, axis: int = 1):
    return jnp.take(x, jnp.asarray(sym_inverse_indices(x.shape[axis], cp)),
                    axis=axis)


def pair_score_area(cp: int, pattern: str, causal: bool = True
                    ) -> np.ndarray:
    """Relative attention-score work per (rank, round), in units of
    (s_local)² — the balance diagnostic the tests assert on.  Under
    NORMAL+causal the last rank does ~cp× rank 0's work; under SYM every
    entry is 0.5."""
    area = np.zeros((cp, cp))
    for i in range(cp):
        for r in range(cp):
            j = (i - r) % cp
            if not causal:
                area[i, r] = 1.0
            elif pattern == "sym":
                area[i, r] = 0.5  # CAUSAL_SYM, COL and ROW all cover half
            else:
                area[i, r] = 0.5 if j == i else (1.0 if j < i else 0.0)
    return area


# ---------------------------------------------------------------------------
# public API


def ring_attention(q, k, v, axis_name: str = "cp", causal: bool = True,
                   softmax_scale: Optional[float] = None,
                   split_pattern: str = "normal",
                   segment_ids: Optional[jax.Array] = None,
                   seq_len: Optional[jax.Array] = None) -> jax.Array:
    """Ring attention on sequence-sharded [b, s_local, h, d] inputs.

    Must be called inside shard_map/pjit with ``axis_name`` in scope.

    ``split_pattern``: "normal" (contiguous) or "sym" (symmetric causal
    load balancing; shard with :func:`sym_shard`).
    ``segment_ids``: local [b, s_local] global doc ids for packed
    sequences; ``-1`` marks padding.  Under SYM the ids are in the
    rank's local (head+tail chunk) layout and ride the ring with the KV.
    ``seq_len``: this rank's valid length (scalar; positions >= seq_len
    are padding) — the reference's per-rank ``_seq_len_list``.  May be
    combined with ``segment_ids``.
    """
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / math.sqrt(q.shape[-1])
    b, s = q.shape[0], q.shape[1]
    use_segs = segment_ids is not None or seq_len is not None
    if split_pattern == "sym" and s % 2 != 0:
        raise ValueError(f"sym split needs an even local seq, got {s}")
    if segment_ids is None:
        seg_ids = jnp.zeros((b, s), jnp.int32)
    else:
        seg_ids = segment_ids.astype(jnp.int32)
    if seq_len is not None:
        pos = jnp.arange(s, dtype=jnp.int32)[None, :]
        seg_ids = jnp.where(pos < seq_len, seg_ids, -1)
    return _ring_attn(q, k, v, seg_ids, axis_name, scale, causal,
                      split_pattern, use_segs)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "cp",
                           causal: bool = True,
                           softmax_scale: Optional[float] = None,
                           batch_axis: Optional[str] = "dp",
                           head_axis: Optional[str] = "tp",
                           split_pattern: str = "normal",
                           segment_ids: Optional[jax.Array] = None,
                           seq_lens: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Convenience wrapper: shard_map ring attention over a mesh for global
    [b, s, h, d] arrays (seq sharded over ``axis_name``; batch over
    ``batch_axis``; heads over ``head_axis`` — the reference's TP head
    split + CP combination).

    With ``split_pattern="sym"`` the caller's GLOBAL arrays are reordered
    into the SYM layout on the way in and back on the way out.
    ``seq_lens``: [cp] per-rank valid lengths (``_seq_len_list``).
    ``segment_ids``: global [b, s] packed doc ids (-1 pad).
    """
    from jax.sharding import PartitionSpec as P
    from .comm import shard_map

    cp = mesh.shape[axis_name]
    _maybe_profile_ring(q, k, v, mesh, axis_name, causal, split_pattern,
                        softmax_scale)

    def axis_or_none(name):
        return name if (name and name in mesh.axis_names) else None

    spec = P(axis_or_none(batch_axis), axis_name, axis_or_none(head_axis),
             None)
    if split_pattern == "sym":
        q, k, v = (sym_shard(x, cp, axis=1) for x in (q, k, v))

    if segment_ids is not None or seq_lens is not None:
        b, s = q.shape[0], q.shape[1]
        segs = jnp.zeros((b, s), jnp.int32) if segment_ids is None \
            else segment_ids.astype(jnp.int32)
        if split_pattern == "sym":
            # ids follow their tokens into the SYM layout; seq_lens below
            # then mask per-rank LOCAL tail positions (the reference's
            # _seq_len_list semantics), i.e. in the reordered frame.
            segs = sym_shard(segs, cp, axis=1)
        if seq_lens is not None:
            s_local = s // cp
            pos = jnp.arange(s, dtype=jnp.int32)[None, :]
            local_pos = pos % s_local
            rank = pos // s_local
            lens = jnp.asarray(seq_lens, jnp.int32)[rank]
            segs = jnp.where(local_pos < lens, segs, -1)

        fn = shard_map(
            lambda q, k, v, sg: ring_attention(
                q, k, v, axis_name, causal, softmax_scale, split_pattern,
                segment_ids=sg),
            mesh, (spec, spec, spec, P(axis_or_none(batch_axis),
                                       axis_name)), spec)
        out = fn(q, k, v, segs)
        if split_pattern == "sym":
            out = sym_unshard(out, cp, axis=1)
        return out

    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name, causal,
                                       softmax_scale, split_pattern),
        mesh, (spec, spec, spec), spec)
    out = fn(q, k, v)
    if split_pattern == "sym":
        out = sym_unshard(out, cp, axis=1)
    return out


def profile_ring_rounds(q, k, v, mesh, axis_name: str = "cp",
                        causal: bool = True,
                        split_pattern: str = "normal",
                        softmax_scale: Optional[float] = None,
                        reps: int = 3):
    """Measured per-round wall times of the KV ring (the reference's
    optional AttnCommRing per-round profiling, ParallelAttention.h:411-413).

    Each round r is executed as its own jitted program (KV pre-shifted by
    r hops, one _pair_fwd per rank), so the per-(rank, round) cost —
    which pair_score_area predicts analytically — can be measured.
    Returns a list of ``cp`` median times in seconds.

    For the comm/attn/corr/grad decomposition use
    :func:`profile_ring_breakdown`.
    """
    rows = profile_ring_breakdown(q, k, v, mesh, axis_name, causal,
                                  split_pattern, softmax_scale, reps,
                                  include_bwd=False)
    return [r["attn_s"] for r in rows]


def profile_ring_breakdown(q, k, v, mesh, axis_name: str = "cp",
                           causal: bool = True,
                           split_pattern: str = "normal",
                           softmax_scale: Optional[float] = None,
                           reps: int = 3, include_bwd: bool = True,
                           metrics=None):
    """Per-round comm / attn / correction / grad timings of the KV ring —
    the TPU-native analogue of the reference's event-based per-round
    instrumentation (``ParallelAttention.h:411-413`` attn/corr events on
    the comm/attn streams, env-gated).

    XLA fuses the real ring into one program, so intra-program events
    don't exist; instead each phase of each round is jitted standalone:

    - ``comm_s``  — one KV+ids ring hop (``lax.ppermute`` pair)
    - ``attn_s``  — ``_pair_fwd`` for that round's mask class
    - ``corr_s``  — the online-LSE ``_merge`` of the round's partials
    - ``grad_s``  — ``_pair_bwd`` (when ``include_bwd``)

    Returns a list of ``cp`` dicts (one per round).  Pass a
    ``utils.metrics.Metrics`` as ``metrics`` to record each round's times
    as ``ring_{comm,attn,corr,grad}_s`` series (step = round index) — the
    CP bench table.  Also triggered per-shape inside
    :func:`ring_attention_sharded` by ``HETU_TPU_RING_PROFILE=1``.
    """
    import time as _time
    from jax.sharding import PartitionSpec as P
    from .comm import shard_map

    cp = mesh.shape[axis_name]
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / math.sqrt(q.shape[-1])
    if split_pattern == "sym":
        q, k, v = (sym_shard(x, cp, axis=1) for x in (q, k, v))
    b, s = q.shape[0], q.shape[1] // cp
    spec = P(None, axis_name, None, None)
    sspec = P(None, axis_name)
    seg0 = jnp.zeros((b, s * cp), jnp.int32)
    perm1 = [(i, (i + 1) % cp) for i in range(cp)]

    def timed(fn, args):
        jax.block_until_ready(fn(*args))     # compile + warm
        ts = []
        for _ in range(reps):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(_time.perf_counter() - t0)
        return float(np.median(ts))

    def comm_fn(k, v, sg):
        return (lax.ppermute(k, axis_name, perm1),
                lax.ppermute(v, axis_name, perm1),
                lax.ppermute(sg, axis_name, perm1))

    # one ring hop: both the timed comm phase AND the between-round KV
    # rotation, so attn_s times _pair_fwd alone on pre-rotated inputs
    comm_jit = jax.jit(shard_map(comm_fn, mesh, (spec, spec, sspec),
                                 (spec, spec, sspec)))

    def attn_fn(r):
        def f(q, k_r, v_r):
            my = lax.axis_index(axis_name)
            kind = _mask_kind(my, (my - r) % cp, causal, split_pattern)
            o, lse = _pair_fwd(q, k_r, v_r, scale, kind, None,
                               split_pattern, causal)
            return o, lse                    # lse: [b, h, s_local]
        return jax.jit(shard_map(f, mesh, (spec, spec, spec),
                                 (spec, P(None, None, axis_name))))

    def _corr_impl(o_r, lse_r):
        bq, sl, h, d = o_r.shape
        acc = (jnp.full((bq, h, sl), -jnp.inf, jnp.float32),
               jnp.zeros((bq, h, sl), jnp.float32),
               jnp.zeros((bq, sl, h, d), jnp.float32))
        m, denom, out = _merge(acc, o_r.astype(jnp.float32), lse_r)
        return out

    corr_jit = jax.jit(shard_map(
        _corr_impl, mesh, (spec, P(None, None, axis_name)), spec))

    def bwd_fn(r):
        def f(q, k_r, v_r, do, out, lse):
            my = lax.axis_index(axis_name)
            kind = _mask_kind(my, (my - r) % cp, causal, split_pattern)
            return _pair_bwd(q, k_r, v_r, do, out, lse,
                             scale, kind, None, split_pattern, causal)
        lspec = P(None, None, axis_name)
        return jax.jit(shard_map(
            f, mesh, (spec, spec, spec, spec, spec, lspec),
            (spec, spec, spec)))

    rows = []
    k_r, v_r, sg_r = k, v, seg0
    for r in range(cp):
        afn = attn_fn(r)
        o_r, lse_r = afn(q, k_r, v_r)
        jax.block_until_ready(o_r)
        row = {
            "round": r,
            "comm_s": timed(comm_jit, (k_r, v_r, sg_r)),
            "attn_s": timed(lambda *a: afn(*a)[0], (q, k_r, v_r)),
            "corr_s": timed(corr_jit, (o_r, lse_r)),
        }
        if include_bwd:
            bfn = bwd_fn(r)
            row["grad_s"] = timed(
                lambda q, kk, vv: bfn(q, kk, vv, o_r, o_r, lse_r)[0],
                (q, k_r, v_r))
        rows.append(row)
        if metrics is not None:
            metrics.log(r, **{f"ring_{kk[:-2]}_s": vv
                              for kk, vv in row.items() if kk != "round"})
        # rotate KV to the next round's position (same hop the ring takes)
        k_r, v_r, sg_r = comm_jit(k_r, v_r, sg_r)
        jax.block_until_ready(k_r)
    return rows


def _maybe_profile_ring(q, k, v, mesh, axis_name, causal, split_pattern,
                        softmax_scale):
    """HETU_TPU_RING_PROFILE=1: once per (shape, pattern), run the
    per-round breakdown and log the CP table (reference env
    HETU_PARALLEL_ATTN_PROFILE gating its ring events)."""
    import os
    if os.environ.get("HETU_TPU_RING_PROFILE") != "1":
        return
    if any(isinstance(x, jax.core.Tracer) for x in (q, k, v)):
        # called during tracing (ring inside a jitted step): timings
        # would be trace-time garbage; profile only eager concrete calls
        return
    key = (q.shape, k.shape, causal, split_pattern, mesh.shape[axis_name])
    if key in _RING_PROFILED:
        return
    _RING_PROFILED.add(key)
    from ..utils.logging_utils import get_logger
    from ..utils.metrics import Metrics
    log = get_logger("ring_attention")
    path = os.environ.get("HETU_TPU_RING_PROFILE_FILE")
    rec = Metrics(log_file=path) if path else Metrics()
    try:
        rows = profile_ring_breakdown(
            q, k, v, mesh, axis_name, causal, split_pattern, softmax_scale,
            include_bwd=os.environ.get("HETU_TPU_RING_PROFILE_BWD",
                                       "1") == "1",
            metrics=rec)
    finally:
        rec.close()
    hdr = "round   comm_ms   attn_ms   corr_ms" + \
        ("   grad_ms" if "grad_s" in rows[0] else "")
    lines = [hdr]
    for row in rows:
        cells = [f"{row['round']:5d}"] + [
            f"{row[c] * 1e3:9.3f}" for c in
            ("comm_s", "attn_s", "corr_s", "grad_s") if c in row]
        lines.append(" ".join(cells))
    log.info("ring attention per-round profile (%s, cp=%d, s_local=%d):\n%s",
             split_pattern, mesh.shape[axis_name],
             q.shape[1] // mesh.shape[axis_name], "\n".join(lines))
    return rows


_RING_PROFILED: set = set()
