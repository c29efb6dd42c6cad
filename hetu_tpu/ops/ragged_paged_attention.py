"""Ragged paged attention: mixed prefill chunks + decode in ONE kernel.

The serving engine's v1 split (a one-token paged decode kernel, since
removed, + a dense bucketed prefill) paid a compile-grid tax: every
prompt-length bucket and every decode-batch bucket was its own
executable, and each admitted request ran its own prefill call.  This op collapses the two
phases into one program over a **ragged batch** — the Ragged Paged
Attention recipe (PAPERS.md, arxiv 2604.15464):

- the query side is a flat token axis ``q [T, nh, hd]`` holding every
  scheduled token this step: prefill *chunks* (Sarathi-style slices of a
  long prompt) and decode tokens side by side;
- raggedness is described by four per-sequence int32 arrays that ride
  in as **scalar prefetch** on TPU:

  ===============  =======================================================
  ``q_lens   [S]``  query tokens this step (0 = padding row)
  ``cu_q   [S+1]``  cumulative query offsets: row i owns
                    ``q[cu_q[i] : cu_q[i] + q_lens[i]]``
  ``page_tables``   ``[S, maxp]`` physical KV page ids (padding slots
                    point at the reserved trash page)
  ``ctx_lens [S]``  total KV length *including* this step's tokens
  ===============  =======================================================

- a decode row is simply the degenerate ``q_lens[i] == 1`` case — no
  separate code path, no separate executable;
- causal masking is *within* each row's query span: query j of row i
  sits at absolute position ``ctx_lens[i] - q_lens[i] + j`` and attends
  every KV position at or before it.

Two implementations with the same contract:

- ``ragged_paged_attention_reference`` — per-row gather of the page
  table into a contiguous ``[maxp*ps, kvh, hd]`` view + masked dense
  attention over a static ``max_q``-wide query window (CPU oracle).
- ``ragged_paged_attention_pallas`` — Pallas TPU kernel, grid
  ``(kvh, S, maxp)`` with pages innermost.  The k/v BlockSpec index
  maps read the prefetched page table (one ``[ps, hd]`` page-of-one-head
  DMA per grid step — pages are ``[P, kvh, ps, hd]`` so that tile is a
  whole trailing block), ``pl.when`` skips pages past ``ctx_lens`` and
  whole padding rows, and the online-softmax state is carried in VMEM
  scratch.  A kv head's queries lie flat on the sublanes, ``gp`` tile
  rows a token (``gp = g`` where the group size divides the packed
  tile, else ``g`` rounded up to it — an MHA model has ONE row a token,
  not eight).  A grid step computes one query tile: the tile-aligned
  window over row ``i``'s ``max_q * gp`` rows at ``cu_q[i] * gp``,
  loaded with a dynamic ``pl.ds`` slice.  q and k meet on the MXU in
  their own dtype; statistics, ``p``, the PV product and the
  accumulator are float32.  The output window is committed
  read-modify-write so ragged row boundaries never clobber a
  neighbour; tokens that no row owns read as 0.  Runs in interpret
  mode off-TPU.

``max_q`` is the static bound on a row's query tokens IN THIS CALL, and
it sizes the tile every running grid step computes — so a caller whose
rows fall into classes of different width issues one call per class.
The serving step (``serving/decode.py``) does: its decode slots run at
``max_q = 1``, its prefill chunk slots at the scheduler's chunk size,
its verify slots at ``spec_k + 1``; one ``max_q = chunk`` call over
all rows made every decode row pay a ``chunk``-token matmul for one
token.  Inputs are padded by one window internally so the window slide
never reads out of bounds.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (DEFAULT_MASK_VALUE, LANES, SUBLANES,
                              _tiled_bytes, gather_pages, vmem_params)
from .pallas import on_tpu


def _check_ragged_shapes(q, k_pages, v_pages, q_lens, cu_q, page_tables,
                         ctx_lens, max_q):
    t, nh, hd = q.shape
    p_, kvh, ps, hd2 = k_pages.shape
    if v_pages.shape != k_pages.shape:
        raise ValueError(f"k_pages {k_pages.shape} != v_pages "
                         f"{v_pages.shape}")
    if hd != hd2:
        raise ValueError(f"head_dim mismatch: q {hd} vs pages {hd2}")
    if nh % kvh != 0:
        raise ValueError(f"num_heads {nh} not divisible by kv_heads {kvh}")
    s = q_lens.shape[0]
    if cu_q.shape != (s + 1,):
        raise ValueError(f"cu_q must be [S+1]={s + 1}, got {cu_q.shape}")
    if page_tables.ndim != 2 or page_tables.shape[0] != s:
        raise ValueError(f"page_tables must be [S, maxp], got "
                         f"{page_tables.shape}")
    if ctx_lens.shape != (s,):
        raise ValueError(f"ctx_lens must be [S], got {ctx_lens.shape}")
    if not 1 <= int(max_q):
        raise ValueError(f"max_q must be >= 1, got {max_q}")
    return t, nh, hd, ps, kvh, s


# ---------------------------------------------------------------------------
# reference path (CPU / oracle)
# ---------------------------------------------------------------------------

def ragged_paged_attention_reference(q: jax.Array, k_pages: jax.Array,
                                     v_pages: jax.Array, q_lens: jax.Array,
                                     cu_q: jax.Array,
                                     page_tables: jax.Array,
                                     ctx_lens: jax.Array, *, max_q: int,
                                     softmax_scale: Optional[float] = None
                                     ) -> jax.Array:
    """Dense oracle for the ragged contract: per row, gather its pages
    in position order and run masked fp32 attention over a static
    ``max_q`` query window at ``cu_q[i]``.  Returns ``[T, nh, hd]``;
    rows' padding windows never leak into neighbouring rows (masked
    read-modify-write, mirroring the kernel)."""
    t, nh, hd, ps, kvh, s = _check_ragged_shapes(
        q, k_pages, v_pages, q_lens, cu_q, page_tables, ctx_lens, max_q)
    maxp = page_tables.shape[1]
    g = nh // kvh
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    kk = maxp * ps
    kv_pos = jnp.arange(kk)
    qp = jnp.pad(q, ((0, max_q), (0, 0), (0, 0)))
    out = jnp.zeros_like(qp)
    with jax.named_scope("ragged_paged_attention"):
        for i in range(s):
            start, qlen, ctx = cu_q[i], q_lens[i], ctx_lens[i]
            qi = lax.dynamic_slice(qp, (start, 0, 0), (max_q, nh, hd))
            qg = qi.reshape(max_q, kvh, g, hd).astype(jnp.float32)
            k = gather_pages(k_pages, page_tables[i])     # [kk, kvh, hd]
            v = gather_pages(v_pages, page_tables[i])
            sc = jnp.einsum("qhgd,khd->qhgk", qg,
                            k.astype(jnp.float32)) * scale
            qpos = (ctx - qlen) + jnp.arange(max_q)       # absolute pos
            valid = kv_pos[None, :] <= qpos[:, None]      # causal in-row
            sc = jnp.where(valid[:, None, None, :], sc,
                           DEFAULT_MASK_VALUE)
            pr = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("qhgk,khd->qhgd", pr,
                           v.astype(jnp.float32))
            o = o.reshape(max_q, nh, hd).astype(q.dtype)
            rowv = jnp.arange(max_q) < qlen
            cur = lax.dynamic_slice(out, (start, 0, 0), (max_q, nh, hd))
            out = lax.dynamic_update_slice(
                out, jnp.where(rowv[:, None, None], o, cur),
                (start, 0, 0))
    return out[:t]


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def _ragged_kernel(ql_ref, cu_ref, pt_ref, cl_ref,    # scalar prefetch
                   q_ref, k_ref, v_ref,               # inputs
                   o_ref,                             # output
                   m_scr, l_scr, acc_scr,             # scratch
                   *, scale: float, ps: int, maxp: int, gp: int,
                   sub: int):
    """One (kv head, row, page) grid step over a ``win``-row query tile.

    The head's queries lie flat on the sublanes, ``gp`` tile rows per
    token (``q_ref [1, rows, hd]``): row ``i``'s first tile row is
    ``cu_q[i] * gp`` and the tile is the ``sub``-aligned window that
    covers its ``max_q * gp`` rows.  A tile row that is not one of the
    row's ``q_lens[i]`` query tokens (a neighbour's token inside the
    aligned window, the tail of a short chunk) is computed and dropped
    at the commit."""
    i = pl.program_id(1)
    p = pl.program_id(2)
    qlen = ql_ref[i]
    ctx = cl_ref[i]
    win = acc_scr.shape[0]
    first = cu_ref[i] * gp
    base = pl.multiple_of((first // sub) * sub, sub)
    # tile row r holds in-row query (r - (first - base)) // gp; `bias`
    # keeps the dividend non-negative (a gp under sub divides it)
    bias = sub if gp < sub else 0
    lead = bias - (first - base)

    def query_index(shape):
        r = lax.broadcasted_iota(jnp.int32, shape, 0)
        return (r + lead) // gp - bias // gp

    @pl.when(jnp.logical_and(i == 0, p == 0))
    def _clear():                       # tokens no row owns read as 0
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(qlen > 0, p * ps < ctx))
    def _page():
        # q and k meet on the MXU in their own dtype (bf16 products are
        # exact in the float32 accumulator); statistics, p and the PV
        # product stay float32
        dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        q = q_ref[0, pl.ds(base, win), :].astype(dt)   # [win, hd]
        k = k_ref[0, 0].astype(dt)                     # [ps, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        cols = p * ps + lax.broadcasted_iota(jnp.int32, (win, ps), 1)
        qpos = (ctx - qlen) + query_index((win, ps))   # absolute position
        s = jnp.where(cols <= qpos, s, DEFAULT_MASK_VALUE)
        m_prev = m_scr[:, :1]                          # [win, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        pexp = jnp.exp(s - m_cur)                      # [win, ps]
        l_cur = l_scr[:, :1] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(jnp.logical_and(qlen > 0, p == maxp - 1))
    def _finalize():
        l = l_scr[:, :1]
        o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)  # empty rows -> 0
        # ragged row boundaries are not tile-aligned: commit the window
        # read-modify-write so the rows of the tile that are not this
        # row's queries never clobber a neighbour's tokens
        j = query_index((win, 1))
        mine = jnp.logical_and(j >= 0, j < qlen)
        prev = o_ref[0, pl.ds(base, win), :]
        o_ref[0, pl.ds(base, win), :] = jnp.where(
            mine, o.astype(o_ref.dtype), prev)


# jitted: the serving step calls this once per layer and region with the
# same shapes, and an inner jit is traced and lowered to Mosaic ONCE per
# distinct call, not once per layer (the lowering is seconds of every
# process's set-up)
@functools.partial(jax.jit, static_argnames=("max_q", "softmax_scale",
                                             "interpret", "name"))
def ragged_paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, q_lens: jax.Array,
                                  cu_q: jax.Array, page_tables: jax.Array,
                                  ctx_lens: jax.Array, *, max_q: int,
                                  softmax_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  name: str = "ragged_paged_attention"
                                  ) -> jax.Array:
    """Pallas ragged paged attention (same contract as the reference).

    Grid is ``(kvh, S, maxp)`` with pages innermost (sequential on TPU);
    the query/output windows live in a whole-token-axis VMEM block of
    the grid step's kv head while k/v index maps read the prefetched
    page table so each grid step DMAs exactly one head of one physical
    page — pages past ``ctx_lens[i]`` and whole padding rows are skipped
    with ``pl.when``.  ``name`` is the Mosaic call's name on the device
    trace (the serving step names one call per region).
    """
    t, nh, hd, ps, kvh, s = _check_ragged_shapes(
        q, k_pages, v_pages, q_lens, cu_q, page_tables, ctx_lens, max_q)
    maxp = page_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    if interpret is None:
        interpret = not on_tpu()
    g = nh // kvh
    # rows of one packed tile of q's dtype: windows start on its multiples
    sub = SUBLANES * max(1, 4 // q.dtype.itemsize)
    gp = g if sub % g == 0 else -(-g // sub) * sub
    win = -(-(max_q * gp + max(sub - gp, 0)) // sub) * sub
    rows = -(-(t * gp) // sub) * sub + win  # window slide never OOB
    qg = jnp.pad(q.reshape(t, kvh, g, hd),
                 ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    qg = qg.transpose(1, 0, 2, 3).reshape(kvh, t * gp, hd)
    qg = jnp.pad(qg, ((0, 0), (0, rows - t * gp), (0, 0)))
    kernel = functools.partial(_ragged_kernel, scale=float(scale), ps=ps,
                               maxp=maxp, gp=gp, sub=sub)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(kvh, s, maxp),
        in_specs=[
            pl.BlockSpec((1, rows, hd),
                         lambda h, i, p, ql, cu, pt, cl: (h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda h, i, p, ql, cu, pt, cl: (pt[i, p], h, 0,
                                                          0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda h, i, p, ql, cu, pt, cl: (pt[i, p], h, 0,
                                                          0)),
        ],
        out_specs=pl.BlockSpec(
            (1, rows, hd), lambda h, i, p, ql, cu, pt, cl: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((win, LANES), jnp.float32),
            pltpu.VMEM((win, LANES), jnp.float32),
            pltpu.VMEM((win, hd), jnp.float32),
        ],
    )
    with jax.named_scope(name):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((kvh, rows, hd), q.dtype),
            compiler_params=vmem_params(
                blocks=[((rows, hd), q.dtype)] * 2
                + [((ps, hd), k_pages.dtype)] * 2,
                scratch=[((win, LANES), jnp.float32)] * 2
                + [((win, hd), jnp.float32)]),
            interpret=interpret,
            name=name,
        )(q_lens.astype(jnp.int32), cu_q.astype(jnp.int32),
          page_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
          qg, k_pages, v_pages)
    out = out[:, :t * gp].reshape(kvh, t, gp, hd)[:, :, :g]
    return out.transpose(1, 0, 2, 3).reshape(t, nh, hd)


def ragged_paged_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, q_lens: jax.Array,
                           cu_q: jax.Array, page_tables: jax.Array,
                           ctx_lens: jax.Array, *, max_q: int,
                           softmax_scale: Optional[float] = None,
                           use_kernel: Optional[bool] = None) -> jax.Array:
    """Dispatching entry point: Pallas kernel on platform ``tpu``,
    gather-dense reference elsewhere (``ops.sdpa``'s dispatch rule: the
    platform chooses, a kernel error propagates)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return ragged_paged_attention_pallas(
            q, k_pages, v_pages, q_lens, cu_q, page_tables, ctx_lens,
            max_q=max_q, softmax_scale=softmax_scale)
    return ragged_paged_attention_reference(
        q, k_pages, v_pages, q_lens, cu_q, page_tables, ctx_lens,
        max_q=max_q, softmax_scale=softmax_scale)


# ---------------------------------------------------------------------------
# MLA latent path (FlashMLA-ETAP, arxiv 2506.01969; DESIGN.md §21)
# ---------------------------------------------------------------------------
#
# The latent variants run attention directly against ONE compressed KV
# stream per layer: ``c_pages [P, 1, ps, d_c]`` (or int8/packed-nf4
# codes plus a per-token absmax sidecar) and an optional decoupled-rope
# key stream ``r_pages [P, 1, ps, d_r]``.  The query side arrives
# ALREADY weight-absorbed — ``q [*, nh, d_c + d_r]`` is
# ``concat(q_nope @ k_up, rope(q_rope))`` per head — so scores are MQA
# dot products in latent space and the attention output STAYS latent
# (``[*, nh, d_c]``); the caller applies the ``v_up`` fold per query
# token.  No cached token is ever decompressed.
#
# A grid step of the latent kernel covers a GROUP of ``K`` consecutive
# page-table slots of its row (grid ``(S, ceil(maxp / K))``) and runs the
# online softmax once over the group's ``K * ps`` columns: at one page a
# step the call was bound by the number of grid steps that run (~0.5 us
# each whatever they fetch), and a 64-token page fills half the MXU.  ``K``
# comes from :func:`latent_pages_per_grid_step` — the call's own shapes
# (``max_q``, heads, ``maxp``, page bytes) against a VMEM budget, so the
# serving step's decode region (``max_q`` 1) takes 32 pages and its
# 256-token chunk region 8 — and the engine's ``latent_grid_steps``
# counter reads the same function.  A stream whose rows fill the lanes
# stays in HBM and reaches VMEM by the kernel's own double-buffered DMAs,
# one a page; a narrower one through ``K`` page-table-indexed blocks.


def _dequant_latent(codes, scales, quant, latent_dim):
    """fp32 view of a gathered latent window: identity cast when
    ``quant`` is None, else per-token absmax dequant (codes ``[..., w]``
    + scales ``[..., 1]`` -> ``[..., latent_dim]``)."""
    if quant is None:
        return codes.astype(jnp.float32)
    from .quantization import dequantize_rows
    return dequantize_rows(codes, scales, quant, latent_dim)


def _check_latent_shapes(q, c_pages, r_pages, quant, latent_dim):
    nh, dq = q.shape[-2], q.shape[-1]
    p_, one, ps, wc = c_pages.shape
    if one != 1:
        raise ValueError(f"latent c_pages carry ONE shared stream, got "
                         f"{c_pages.shape}")
    d_c = int(latent_dim) if latent_dim is not None else wc
    if quant == "nf4":
        if wc * 2 != d_c:
            raise ValueError(f"nf4 codes width {wc} != latent_dim/2 "
                             f"({d_c})")
    elif wc != d_c:
        raise ValueError(f"c_pages width {wc} != latent_dim {d_c}")
    d_r = 0
    if r_pages is not None and r_pages.shape[-1] > 0:
        if r_pages.shape[:3] != (p_, 1, ps):
            raise ValueError(f"r_pages {r_pages.shape} incompatible with "
                             f"c_pages {c_pages.shape}")
        d_r = r_pages.shape[-1]
    if dq != d_c + d_r:
        raise ValueError(f"absorbed q width {dq} != d_c + d_r "
                         f"({d_c}+{d_r})")
    return nh, ps, d_c, d_r


def latent_paged_attention_reference(q: jax.Array, c_pages: jax.Array,
                                     r_pages: Optional[jax.Array],
                                     page_tables: jax.Array,
                                     seq_lens: jax.Array, *,
                                     softmax_scale: float,
                                     scale_pages: Optional[jax.Array] = None,
                                     quant: Optional[str] = None,
                                     latent_dim: Optional[int] = None
                                     ) -> jax.Array:
    """Decode-slot oracle over latent pages: absorbed ``q [B, nh,
    d_c+d_r]`` (one token per request), ``seq_lens`` counting the token
    just written -> latent output ``[B, nh, d_c]``.  Mirrors
    ``paged_attention_reference``'s gather + ``-inf`` masking so the
    serving step stays bitwise vs the solo MLA oracle."""
    nh, ps, d_c, d_r = _check_latent_shapes(q, c_pages, r_pages, quant,
                                            latent_dim)
    b = q.shape[0]
    maxp = page_tables.shape[1]
    kk = maxp * ps
    with jax.named_scope("latent_paged_attention"):
        c = c_pages[page_tables].reshape(b, kk, c_pages.shape[-1])
        sc = None if scale_pages is None else \
            scale_pages[page_tables].reshape(b, kk, 1)
        cd = _dequant_latent(c, sc, quant, d_c)        # [B, kk, d_c]
        if d_r:
            r = r_pages[page_tables].reshape(b, kk, d_r)
            k = jnp.concatenate([cd, r.astype(jnp.float32)], -1)
        else:
            k = cd
        s = jnp.einsum("bhc,bkc->bhk", q.astype(jnp.float32),
                       k) * softmax_scale
        valid = (jnp.arange(kk)[None] < seq_lens[:, None])[:, None, :]
        s = jnp.where(valid, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhk,bkc->bhc", pr, cd)      # latent, fp32


def latent_ragged_paged_attention_reference(
        q: jax.Array, c_pages: jax.Array, r_pages: Optional[jax.Array],
        q_lens: jax.Array, cu_q: jax.Array, page_tables: jax.Array,
        ctx_lens: jax.Array, *, max_q: int, softmax_scale: float,
        scale_pages: Optional[jax.Array] = None,
        quant: Optional[str] = None,
        latent_dim: Optional[int] = None) -> jax.Array:
    """Latent twin of :func:`ragged_paged_attention_reference` (same
    ragged contract, ``DEFAULT_MASK_VALUE`` masking — the kernel
    oracle): absorbed ``q [T, nh, d_c+d_r]`` -> latent ``[T, nh,
    d_c]``."""
    nh, ps, d_c, d_r = _check_latent_shapes(q, c_pages, r_pages, quant,
                                            latent_dim)
    t = q.shape[0]
    s_rows = q_lens.shape[0]
    maxp = page_tables.shape[1]
    kk = maxp * ps
    kv_pos = jnp.arange(kk)
    qp = jnp.pad(q, ((0, max_q), (0, 0), (0, 0)))
    out = jnp.zeros((t + max_q, nh, d_c), jnp.float32)
    with jax.named_scope("latent_ragged_paged_attention"):
        for i in range(s_rows):
            start, qlen, ctx = cu_q[i], q_lens[i], ctx_lens[i]
            qi = lax.dynamic_slice(
                qp, (start, 0, 0),
                (max_q, nh, d_c + d_r)).astype(jnp.float32)
            c = c_pages[page_tables[i]].reshape(kk, c_pages.shape[-1])
            sc = None if scale_pages is None else \
                scale_pages[page_tables[i]].reshape(kk, 1)
            cd = _dequant_latent(c, sc, quant, d_c)    # [kk, d_c]
            if d_r:
                r = r_pages[page_tables[i]].reshape(kk, d_r)
                k = jnp.concatenate([cd, r.astype(jnp.float32)], -1)
            else:
                k = cd
            s = jnp.einsum("qhc,kc->qhk", qi, k) * softmax_scale
            qpos = (ctx - qlen) + jnp.arange(max_q)
            valid = kv_pos[None, :] <= qpos[:, None]
            s = jnp.where(valid[:, None, :], s, DEFAULT_MASK_VALUE)
            pr = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("qhk,kc->qhc", pr, cd)
            rowv = jnp.arange(max_q) < qlen
            cur = lax.dynamic_slice(out, (start, 0, 0), (max_q, nh, d_c))
            out = lax.dynamic_update_slice(
                out, jnp.where(rowv[:, None, None], o, cur),
                (start, 0, 0))
    return out[:t]


def _nibble_order(d_c: int) -> np.ndarray:
    """Latent column order the 4-bit kernel works in: packed byte ``j``
    holds element ``2j`` in its high nibble and ``2j+1`` in its low one,
    and the kernel dequantizes the two nibble planes side by side
    (``[evens | odds]``) — Mosaic has no lane interleave to restore the
    original order, so q's latent columns go in permuted and the latent
    output comes back permuted."""
    return np.concatenate([np.arange(0, d_c, 2), np.arange(1, d_c, 2)])


def _decode4(idx, code):
    """Codebook lookup as a select chain (Mosaic has no vector gather
    from a table)."""
    c = jnp.zeros(idx.shape, jnp.float32)
    for k, val in enumerate(code):
        c = jnp.where(idx == k, val, c)
    return c


# the widest group of page-table slots one step of the latent call walks
# (Step 0 of PR 36, PERF.md section 6: a 32-row decode call at 263 pages
# a row takes 4.17 / 0.97 / 0.72 / 0.63 ms at 1 / 8 / 16 / 32 on a v5e),
# and the VMEM one group's page buffers and float32 temporaries may take
# (a 256-token chunk of 32 heads fills it at 8 pages: 6.27 -> 1.32 ms)
LATENT_GROUP_MAX = 32
LATENT_GROUP_VMEM = 40 << 20


def _latent_head_rows(heads: int) -> int:
    """Tile rows a query token takes: its heads, to whole sublanes."""
    return max(SUBLANES, -(-heads // SUBLANES) * SUBLANES)


def _latent_group_vmem(pages: int, max_q: int, heads: int, q_width: int,
                       streams):
    """``(shape, dtype)`` entries, as :func:`vmem_params` takes them, of
    what a group of ``pages`` pages holds in VMEM beside the q / out
    blocks and the softmax state: both buffers of its pages in every
    stream (``[P, 1, ps, w]`` arrays or their shapes) and the float32
    temporaries — the upcast pages, the score tile and ``p``."""
    toks = pages * streams[0].shape[2]
    tile = (max_q * _latent_head_rows(heads), toks)
    return [((2 * toks, a.shape[-1]), a.dtype) for a in streams] \
        + [((toks, q_width), jnp.float32), (tile, jnp.float32),
           (tile, jnp.float32)]


def latent_pages_per_grid_step(max_q: int, heads: int, q_width: int,
                               maxp: int, streams) -> int:
    """``K``: how many consecutive page-table slots of its row ONE grid
    step of the latent call covers — the one rule, read by the kernel
    wrapper and by ``Engine`` (the ``latent_grid_steps`` counter), from
    what both can see: the call's ``max_q``, the query heads, the
    absorbed q's width, the page table's width and the pool's page
    streams (``[P, 1, ps, w]`` arrays or their shapes).

    A step costs ~0.5 us whatever it fetches (DMA issue and wait, one
    rescale of the accumulator), and a page of 64 tokens fills half the
    MXU's width (the score tile's columns) and half its depth (the PV
    product's contraction), so every call takes the widest group it can
    hold: the largest power of two, at most ``LATENT_GROUP_MAX`` and
    ``maxp``, whose page buffers and float32 temporaries
    (:func:`_latent_group_vmem`) fit ``LATENT_GROUP_VMEM``.  A decode
    region's tile is ``heads`` rows and takes the cap; a prefill chunk's
    score tile (``max_q * heads`` rows, thousands) is the budget after a
    few pages."""
    def fits(k):
        return sum(_tiled_bytes(s, d) for s, d in _latent_group_vmem(
            k, max_q, heads, q_width, streams)) <= LATENT_GROUP_VMEM

    k = 1
    while 2 * k <= min(LATENT_GROUP_MAX, maxp) and fits(2 * k):
        k *= 2
    return k


def _make_latent_kernel(scale: float, ps: int, pages: int, groups: int,
                        s_rows: int, max_q: int, gp: int, d_c: int,
                        quant: Optional[str], has_rope: bool,
                        has_scales: bool, by_dma, code=None):
    """Latent twin of :func:`_ragged_kernel`: grid ``(S, groups)`` (one
    shared KV stream, so no kv-head grid dim), q/out blocks span the
    padded token axis.  A grid step covers a GROUP of ``pages``
    consecutive page-table slots of its row and runs the online softmax
    ONCE over the group's ``pages * ps`` columns (state in VMEM
    scratch).  The page streams are c, then r and the scale sidecar
    where the pool has them; ``by_dma[x]`` says how stream ``x``'s pages
    reach VMEM:

    - True (rows that fill the 128 lanes): the stream stays in HBM and
      each page of the group is copied into one of two VMEM buffers by
      its own DMA driven from the prefetched page table; a running step
      starts the copies of the NEXT running step (the row's next group,
      or the next row's first) before it waits for its own, so they are
      in flight while this group is computed;
    - False (Mosaic cannot slice an HBM array whose rows are padded to
      the lanes): ``pages`` page-table-indexed blocks of the stream, one
      a slot, fetched by the grid's own pipeline.

    ``code`` is the 4-bit codebook as Python floats (packed pages
    only)."""
    cols_g = pages * ps
    mqg = max_q * gp
    n_dma = sum(by_dma)

    def kernel(ql_ref, cu_ref, pt_ref, cl_ref, q_ref, *rest):
        srcs = []                    # a stream: its HBM ref or its blocks
        for dma in by_dma:
            n = 1 if dma else pages
            srcs.append(rest[0] if dma else rest[:n])
            rest = rest[n:]
        o_ref, m_scr, l_scr, acc_scr = rest[:4]
        sem, count = rest[4 + n_dma:]
        buf_of = dict(zip([x for x, dma in enumerate(by_dma) if dma],
                          rest[4:4 + n_dma]))
        dmas = [(srcs[x], buf) for x, buf in buf_of.items()]
        i = pl.program_id(0)
        g = pl.program_id(1)
        qlen = ql_ref[i]
        start = cu_ref[i]
        ctx = cl_ref[i]

        def live_groups(row):        # 0 for a padding row
            return jnp.where(ql_ref[row] > 0,
                             (cl_ref[row] + cols_g - 1) // cols_g, 0)

        n_g = live_groups(i)

        def copies(row, grp, slot):
            """One DMA a page a stream of group ``grp`` of ``row`` into
            buffer ``slot`` (``row`` None: the same shapes from page 0,
            which is all a wait reads of its descriptor)."""
            return [pltpu.make_async_copy(
                src.at[0 if row is None else pt_ref[row, grp * pages + j],
                       0],
                dst.at[slot, pl.ds(j * ps, ps)], sem.at[slot])
                for j in range(pages) for src, dst in dmas]

        @pl.when(jnp.logical_and(i == 0, g == 0))
        def _first():
            count[0] = 0             # running steps so far: buffer parity

        @pl.when(g == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(g < n_g)
        def _group():
            slot = count[0] % 2
            if dmas:
                nxt = jnp.minimum(i + 1, s_rows - 1)
                next_live = jnp.logical_and(i + 1 < s_rows,
                                            live_groups(nxt) > 0)
                prev_live = jnp.logical_and(
                    i > 0, live_groups(jnp.maximum(i - 1, 0)) > 0)
                more = g + 1 < n_g

                @pl.when(jnp.logical_and(g == 0,
                                         jnp.logical_not(prev_live)))
                def _cold():         # no step before this one fetched it
                    for cp in copies(i, 0, slot):
                        cp.start()

                @pl.when(jnp.logical_or(more, next_live))
                def _prefetch():
                    for cp in copies(jnp.where(more, i, nxt),
                                     jnp.where(more, g + 1, 0), 1 - slot):
                        cp.start()

                for cp in copies(None, 0, slot):
                    cp.wait()
                count[0] = count[0] + 1

            def stream(x):           # [cols_g, w], position order
                if by_dma[x]:
                    return buf_of[x][slot]
                return jnp.concatenate([r[0, 0] for r in srcs[x]], 0)

            q = q_ref[pl.ds(start, max_q)].astype(jnp.float32)
            q2 = q.reshape(mqg, q.shape[-1])           # [mqg, d_c+d_r]
            raw = stream(0)                            # [cols_g, w]
            rope = stream(1) if has_rope else None
            if quant is None:
                c = raw.astype(jnp.float32)
            else:
                sc = stream(len(by_dma) - 1).astype(jnp.float32)
                sc = jnp.where(sc > 0, sc, 1.0)                # [.., 1]
                if quant == "int8":
                    c = raw.astype(jnp.float32) / 127.0 * sc
                else:                  # packed 4-bit, _nibble_order
                    raw = raw.astype(jnp.int32)
                    c = jnp.concatenate([_decode4(raw >> 4, code),
                                         _decode4(raw & 0xF, code)],
                                        -1) * sc
            if has_rope:
                k = jnp.concatenate([c, rope.astype(jnp.float32)], -1)
            else:
                k = c
            s = lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            row_q = lax.broadcasted_iota(jnp.int32, (mqg, cols_g),
                                         0) // gp
            cols = g * cols_g + lax.broadcasted_iota(
                jnp.int32, (mqg, cols_g), 1)
            qpos = (ctx - qlen) + row_q
            # the slots of the row's last group past its context hold
            # the trash page: masked like the tail of its last page
            s = jnp.where(cols <= qpos, s, DEFAULT_MASK_VALUE)
            m_prev = m_scr[:, 0]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_cur)
            pexp = jnp.exp(s - m_cur[:, None])
            l_cur = l_scr[:, 0] * alpha + jnp.sum(pexp, axis=1)
            acc_scr[...] = acc_scr[...] * alpha[:, None] + lax.dot_general(
                pexp, c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

        @pl.when(g == groups - 1)
        def _finalize():
            l = l_scr[:, 0]
            l = jnp.where(l == 0.0, 1.0, l)
            o = (acc_scr[...] / l[:, None]).reshape(max_q, gp, d_c)
            prev = o_ref[pl.ds(start, max_q)]
            rowv = lax.broadcasted_iota(jnp.int32, (max_q, 1, 1), 0) < qlen
            o_ref[pl.ds(start, max_q)] = jnp.where(
                rowv, o.astype(o_ref.dtype), prev)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "max_q", "softmax_scale", "quant", "latent_dim", "interpret", "name",
    "pages_per_step"))
def latent_ragged_paged_attention_pallas(
        q: jax.Array, c_pages: jax.Array, r_pages: Optional[jax.Array],
        q_lens: jax.Array, cu_q: jax.Array, page_tables: jax.Array,
        ctx_lens: jax.Array, *, max_q: int, softmax_scale: float,
        scale_pages: Optional[jax.Array] = None,
        quant: Optional[str] = None, latent_dim: Optional[int] = None,
        interpret: Optional[bool] = None,
        name: str = "latent_ragged_paged_attention",
        pages_per_step: Optional[int] = None) -> jax.Array:
    """Pallas latent ragged paged attention (same contract as
    :func:`latent_ragged_paged_attention_reference`; ``name`` is the
    Mosaic call's name on the device trace).  A grid step covers
    :func:`latent_pages_per_grid_step` page-table slots of its row;
    ``pages_per_step`` overrides the rule for the kernel's own tests and
    timings only (no serving code passes it: the engine's
    ``latent_grid_steps`` counter reads the rule)."""
    nh, ps, d_c, d_r = _check_latent_shapes(q, c_pages, r_pages, quant,
                                            latent_dim)
    t = q.shape[0]
    s_rows = q_lens.shape[0]
    maxp = page_tables.shape[1]
    if interpret is None:
        interpret = not on_tpu()
    gp = _latent_head_rows(nh)
    t_pad = t + max_q
    has_rope, has_scales = d_r > 0, scale_pages is not None
    if quant is not None and not has_scales:
        raise ValueError("quantized latent pages need scale_pages")
    streams = [c_pages] + ([r_pages] if has_rope else []) \
        + ([scale_pages] if has_scales else [])
    kpg = pages_per_step or latent_pages_per_grid_step(
        max_q, nh, d_c + d_r, maxp, streams)
    groups = -(-maxp // kpg)
    # a table the group does not divide ends in the trash page's slots
    page_tables = jnp.pad(page_tables.astype(jnp.int32),
                          ((0, 0), (0, groups * kpg - maxp)))
    code = order = None
    if quant in ("nf4", "fp4"):
        from .quantization import _CODES
        code = tuple(float(c) for c in _CODES[quant])
        order = _nibble_order(d_c)
        q = jnp.concatenate([q[..., :d_c][..., order], q[..., d_c:]], -1)
    qg = jnp.pad(q, ((0, max_q), (0, gp - nh), (0, 0)))
    by_dma = tuple(a.shape[-1] % LANES == 0 for a in streams)
    kernel = _make_latent_kernel(float(softmax_scale), ps, kpg, groups,
                                 s_rows, int(max_q), gp, d_c, quant,
                                 has_rope, has_scales, by_dma, code)

    def slot(j):                     # slot j of the grid step's group
        return lambda i, g, ql, cu, pt, cl: (pt[i, g * kpg + j], 0, 0, 0)

    in_specs = [pl.BlockSpec((t_pad, gp, d_c + d_r),
                             lambda i, g, ql, cu, pt, cl: (0, 0, 0))]
    operands = [qg]
    for a, dma in zip(streams, by_dma):
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] if dma else [
            pl.BlockSpec((1, 1, ps, a.shape[-1]), slot(j))
            for j in range(kpg)]
        operands += [a] * (1 if dma else kpg)
    state = [((max_q * gp, LANES), jnp.float32)] * 2 \
        + [((max_q * gp, d_c), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_rows, groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((t_pad, gp, d_c),
                               lambda i, g, ql, cu, pt, cl: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM(s, d) for s, d in state]
        + [pltpu.VMEM((2, kpg * ps, a.shape[-1]), a.dtype)
           for a, dma in zip(streams, by_dma) if dma]
        + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)],
    )
    with jax.named_scope(name):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((t_pad, gp, d_c), jnp.float32),
            compiler_params=vmem_params(
                blocks=[((t_pad, gp, d_c + d_r), q.dtype),
                        ((t_pad, gp, d_c), jnp.float32)],
                scratch=state + _latent_group_vmem(
                    kpg, max_q, nh, d_c + d_r, streams)),
            interpret=interpret,
            name=name,
        )(q_lens.astype(jnp.int32), cu_q.astype(jnp.int32), page_tables,
          ctx_lens.astype(jnp.int32), *operands)
    out = out[:t, :nh, :]
    return out if order is None else out[..., np.argsort(order)]


def latent_ragged_paged_attention(
        q: jax.Array, c_pages: jax.Array, r_pages: Optional[jax.Array],
        q_lens: jax.Array, cu_q: jax.Array, page_tables: jax.Array,
        ctx_lens: jax.Array, *, max_q: int, softmax_scale: float,
        scale_pages: Optional[jax.Array] = None,
        quant: Optional[str] = None, latent_dim: Optional[int] = None,
        use_kernel: Optional[bool] = None) -> jax.Array:
    """Dispatching entry point for the latent path (kernel on platform
    ``tpu``, gather-dense oracle elsewhere; a kernel error propagates)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return latent_ragged_paged_attention_pallas(
            q, c_pages, r_pages, q_lens, cu_q, page_tables, ctx_lens,
            max_q=max_q, softmax_scale=softmax_scale,
            scale_pages=scale_pages, quant=quant,
            latent_dim=latent_dim)
    return latent_ragged_paged_attention_reference(
        q, c_pages, r_pages, q_lens, cu_q, page_tables, ctx_lens,
        max_q=max_q, softmax_scale=softmax_scale, scale_pages=scale_pages,
        quant=quant, latent_dim=latent_dim)


# ---------------------------------------------------------------------------
# verify-row sampling head (speculative decoding, DESIGN.md §20)
# ---------------------------------------------------------------------------
#
# A speculative **verify row** is structurally a prefill chunk: the row
# feeds ``[last committed token, d_1, ..., d_K]`` (K greedy draft
# proposals) through the unified step, so the kernel above already
# produces per-position attention for it.  What a verify row needs ON
# TOP is a per-position accept/reject decision next to the engine's
# per-row sampler — this head provides it, entirely on device, so the
# host still fetches only ``[rows]``-shaped int32s per step
# (``host_logit_fetches`` stays 0).
#
# Acceptance rule per in-row verify position j (absolute sequence index
# of the token it emits is ``ctx - spec_len + j``):
#
# * temperature 0: accept ``d_{j+1}`` iff it equals ``argmax(logits_j)``
#   — the very argmax a non-speculative decode step would commit, so the
#   longest-prefix accepted tokens plus the first-mismatch bonus token
#   reproduce the non-speculative greedy sequence EXACTLY (bit-for-bit,
#   test-pinned);
# * temperature > 0: leftover-distribution rejection sampling for a
#   deterministic (greedy) draft, in COUPLED form.  The draft's
#   proposal distribution is a point mass ``q = δ_d``, so the generic
#   speculative-sampling accept probability ``min(1, p/q)`` reduces to
#   ``p(d)`` and the leftover distribution ``norm(max(p - q, 0))``
#   reduces to ``p`` with ``d`` removed and renormalized.  Instead of
#   burning two independent draws (an accept coin and a leftover
#   sample), the head draws ONE categorical sample ``X ~ p`` from the
#   truncated distribution with the position's own key and accepts iff
#   ``X == d``: the accept probability is exactly ``p(d)``, and the law
#   of ``X`` conditioned on rejection (``X != d``) is exactly the
#   leftover distribution — the same accept/leftover semantics, one
#   draw.  The payoff of the coupling is replay stability: ``X`` is the
#   IDENTICAL ``(seed, index)``-keyed draw the per-row sampler makes,
#   so the emitted token at a given sequence index is the same whether
#   that index was covered by a verify burst, a plain decode step, or a
#   replay under different batching/chunking/k — sampled-mode spec
#   serving reproduces non-speculative sampled serving bit-for-bit,
#   the same way temperature 0 does.  ``p`` here is the same
#   temperature/top-k/top-p-truncated distribution the per-row sampler
#   draws from.


def _sampled_draw(logits, temp, top_p, top_k, seed, ctx):
    """The sort-based keyed categorical draw for ONE sampled row: fp32
    logits [V], temperature-scaled, top-k/top-p truncated, keyed by
    ``(seed, ctx)``."""
    v = logits.shape[0]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), ctx)
    lg = logits / jnp.where(temp > 0, temp, 1.0)
    order = jnp.argsort(-lg)
    lg_s = lg[order]                                 # descending
    probs = jax.nn.softmax(lg_s)
    csum = jnp.cumsum(probs)
    idxs = jnp.arange(v)
    # nucleus: drop tokens once the mass BEFORE them reaches top_p (the
    # smallest prefix whose mass >= top_p always survives; the argmax
    # token is never cut)
    cut = (csum - probs > top_p) & (top_p > 0.0) & (top_p < 1.0)
    cut = cut | ((idxs >= top_k) & (top_k > 0))
    return order[jax.random.categorical(
        key, jnp.where(cut, -jnp.inf, lg_s))].astype(jnp.int32)


def sample_row(logits, temp, top_p, top_k, seed, ctx):
    """On-device next-token choice for one row, fp32 logits [V].

    Greedy rows take the jit'd argmax (the very ``jnp.argmax`` solo
    ``generate()`` runs — bit-for-bit at temperature 0).  Sampled rows
    draw from temperature-scaled logits with optional top-k truncation
    and top-p (nucleus) truncation, keyed by ``(seed, ctx)`` — ``ctx``
    equals the sampled token's index in the sequence, so replays are
    deterministic regardless of batching/chunking/preemption.  (Moved
    here from ``serving/decode.py`` so the speculative verify head and
    the per-row sampler are one implementation — the coupling above is
    only sound if they draw identically.)"""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    samp = _sampled_draw(logits, temp, top_p, top_k, seed, ctx)
    return jnp.where(temp == 0.0, greedy, samp)


def sample_rows(logits, temps, top_ps, top_ks, seeds, ctxs):
    """Batched :func:`sample_row` over ``[N, V]`` logits with one
    payoff a per-row vmap cannot have: the ENTIRE sort-based sampled
    path hides behind a single ``lax.cond(any(temps > 0))``.  XLA CPU
    sorts are slow enough that N unconditional 50k-vocab argsorts
    dominate a serving step, and the verify head multiplies N by
    ``spec_k`` — on all-greedy traffic (the common serving case and
    the temp-0 bitwise gate) this computes N argmaxes and nothing
    else.  Per-row values are IDENTICAL to :func:`sample_row` either
    way: a batched ``lax.cond`` under vmap would degrade to a
    both-branches select, which is why the predicate is batch-global
    rather than per-row."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled(_):
        return jax.vmap(_sampled_draw)(logits, temps, top_ps, top_ks,
                                       seeds, ctxs)

    samp = lax.cond(jnp.any(temps > 0.0), sampled,
                    lambda _: greedy, None)
    return jnp.where(temps == 0.0, greedy, samp)


def speculative_verify_head(vlogits, draft_next, spec_lens, temps,
                            top_ps, top_ks, seeds, ctx_lens):
    """Batched accept/reject head over verify rows.

    Args (R = verify rows, K = static max draft length):
      vlogits    [R, K, V] fp32 — logits at the row's first K query
                 positions (position j predicts the token the draft
                 proposed at j+1)
      draft_next [R, K] i32 — the draft token fed at in-row position
                 j+1 (i.e. the proposal position j's logits verify)
      spec_lens  [R] i32 — staged draft count per row (0 = not a verify
                 row: accepted comes back 0 and the caller's per-row
                 sampler result stands)
      temps/top_ps/top_ks/seeds [R] — the row's sampling params
      ctx_lens   [R] i32 — total context including this step's tokens

    Returns ``(accepted [R] i32, alt [R, K] i32)``: ``accepted`` is the
    longest-accepted-prefix length (≤ spec_len) and ``alt[r, a]`` is the
    bonus token to emit when ``accepted < spec_len`` (first rejection);
    on full acceptance the caller's last-position sample IS the bonus.
    Each position's choice comes from the ONE row sampler keyed by its
    absolute sequence index — accept iff the draft matches it — so the
    emitted tokens are bitwise what non-speculative serving emits.
    """
    r, k, v = vlogits.shape
    # absolute sequence index of the token emitted at verify position j
    idx = (ctx_lens[:, None] - spec_lens[:, None]
           + jnp.arange(k)[None, :])                       # [R, K]
    rep = lambda a: jnp.repeat(a, k)                       # noqa: E731
    choice = sample_rows(vlogits.reshape(r * k, v), rep(temps),
                         rep(top_ps), rep(top_ks), rep(seeds),
                         idx.reshape(-1)).reshape(r, k)
    accept = choice == draft_next
    live = jnp.arange(k)[None, :] < spec_lens[:, None]     # [R, K]
    accepted = jnp.sum(jnp.cumprod((accept & live).astype(jnp.int32),
                                   axis=1), axis=1)
    return accepted.astype(jnp.int32), choice.astype(jnp.int32)
