"""Paged decode attention: GQA decode against a block-paged KV pool.

Serving keeps KV state in a preallocated page pool
(``hetu_tpu/serving/kv_pool.py``): per layer, ``k_pages``/``v_pages``
of shape ``[num_pages, kv_heads, page_size, head_dim]``, with each
request owning a list of pages through an int32 page table.  Decode
attention then reads *ragged* per-request histories through the page
table instead of a padded dense ``[B, max_len, ...]`` cache — the
Ragged Paged Attention recipe (PAPERS.md, arxiv 2604.15464) that lets
mixed-length requests share one pool with no padding HBM.

Two implementations, numerically interchangeable:

- ``paged_attention_reference`` — gather pages via the page table into a
  contiguous ``[B, maxp*ps, kvh, hd]`` view and run masked dense
  attention.  This is the path off TPU and the oracle the kernel is
  tested against.
- ``paged_attention_pallas`` — Pallas TPU kernel.  The page table and
  sequence lengths ride in as **scalar-prefetch** operands
  (``PrefetchScalarGridSpec``), so the kernel's k/v BlockSpec index maps
  translate grid position -> physical page id and Mosaic DMAs exactly
  the pages a request owns; pages past ``seq_len`` are skipped with
  ``pl.when`` (no gather materialization, no padding FLOPs beyond the
  last partial page).  Runs in interpret mode off-TPU so the whole path
  is testable on the simulated mesh.

Layout notes (DESIGN.md §8): the last two page dims are ``(page_size,
head_dim)``, so the per-(page, kv-head) ``[ps, hd]`` tile a grid step
DMAs is a whole trailing block — the only k/v block Mosaic accepts when
there is more than one KV head (a block of 1 on a ``kv_heads`` axis in
second-to-last position is refused).  ``head_dim`` fills the 128-lane
tile; ``page_size`` is the sublane dim and must be a multiple of 8 (f32
sublanes) — multiples of 128 additionally make one page exactly one
MXU-shaped block.  The GQA group dim is padded to 8 sublanes for the
q/out tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas import on_tpu

LANES = 128
SUBLANES = 8
DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def gather_pages(pages: jax.Array, page_tables: jax.Array) -> jax.Array:
    """Pages ``[P, h, ps, w]`` read through ``page_tables [..., maxp]``
    into position order: ``[..., maxp*ps, h, w]`` (the dense view every
    reference path attends over)."""
    g = jnp.swapaxes(pages[page_tables], -3, -2)   # [..., maxp, ps, h, w]
    return g.reshape(*page_tables.shape[:-1], -1, *g.shape[-2:])


def _tiled_bytes(shape, dtype) -> int:
    """VMEM bytes of one block: the last dim padded to 128 lanes, the
    second-to-last to the dtype's sublane tile (8 rows of 32 bits)."""
    item = jnp.dtype(dtype).itemsize
    sub = SUBLANES * max(1, 4 // item)
    *lead, rows, cols = shape
    n = item * (-(-rows // sub) * sub) * (-(-cols // LANES) * LANES)
    for d in lead:
        n *= d
    return n


def vmem_params(blocks, scratch):
    """Mosaic compiler params for a kernel whose q/out blocks span the
    whole token axis: ``blocks`` (each double-buffered by the pipeline)
    and ``scratch`` are ``(shape, dtype)`` pairs.  Below Mosaic's 16 MiB
    scoped-VMEM default nothing is asked for (None); above it the limit
    is raised to the estimate plus a quarter for the compiler's own
    temporaries — a 256-token chunk at d_c 512 needs 17 MiB."""
    need = 2 * sum(_tiled_bytes(s, d) for s, d in blocks) \
        + sum(_tiled_bytes(s, d) for s, d in scratch)
    need += need // 4
    if need <= 16 << 20:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _check_shapes(q, k_pages, v_pages, page_tables, seq_lens):
    b, nh, hd = q.shape
    p_, kvh, ps, hd2 = k_pages.shape
    if v_pages.shape != k_pages.shape:
        raise ValueError(f"k_pages {k_pages.shape} != v_pages "
                         f"{v_pages.shape}")
    if hd != hd2:
        raise ValueError(f"head_dim mismatch: q {hd} vs pages {hd2}")
    if nh % kvh != 0:
        raise ValueError(f"num_heads {nh} not divisible by kv_heads {kvh}")
    if page_tables.ndim != 2 or page_tables.shape[0] != b:
        raise ValueError(f"page_tables must be [B, max_pages], got "
                         f"{page_tables.shape}")
    if seq_lens.shape != (b,):
        raise ValueError(f"seq_lens must be [B], got {seq_lens.shape}")
    return b, nh, hd, ps, kvh


# ---------------------------------------------------------------------------
# reference path (CPU / oracle): gather-via-page-table + masked dense attn
# ---------------------------------------------------------------------------

def paged_attention_reference(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, page_tables: jax.Array,
                              seq_lens: jax.Array,
                              softmax_scale: Optional[float] = None
                              ) -> jax.Array:
    """q [B, nh, hd] (one decode token per request), pages
    [P, kvh, ps, hd], page_tables [B, maxp] int32, seq_lens [B] int32
    (tokens valid, *including* the one just written) -> out [B, nh, hd].
    """
    b, nh, hd, ps, kvh = _check_shapes(q, k_pages, v_pages, page_tables,
                                       seq_lens)
    maxp = page_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    # named scope: the static analyzer (hetu_tpu/analysis) attributes
    # eqns to this op through the jaxpr name stack
    with jax.named_scope("paged_attention"):
        k = gather_pages(k_pages, page_tables)  # [B, maxp*ps, kvh, hd]
        v = gather_pages(v_pages, page_tables)
        g = nh // kvh
        qg = q.reshape(b, kvh, g, hd).astype(jnp.float32)
        s = jnp.einsum("bhgd,bshd->bhgs", qg,
                       k.astype(jnp.float32)) * scale   # [B, kvh, g, S]
        valid = (jnp.arange(maxp * ps)[None] <
                 seq_lens[:, None])[:, None, None, :]   # [B, 1, 1, S]
        s = jnp.where(valid, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
        return out.reshape(b, nh, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def _paged_kernel(sl_ref, pt_ref,            # scalar prefetch
                  q_ref, k_ref, v_ref,       # inputs
                  o_ref,                     # output
                  m_scr, l_scr, acc_scr,     # scratch
                  *, scale: float, ps: int, maxp: int, gp: int):
    bi = pl.program_id(0)
    p = pl.program_id(2)
    seqlen = sl_ref[bi]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(p * ps < seqlen)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32)            # [gp, hd]
        k = k_ref[0, 0].astype(jnp.float32)            # [ps, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        cols = p * ps + lax.broadcasted_iota(jnp.int32, (gp, ps), 1)
        s = jnp.where(cols < seqlen, s, DEFAULT_MASK_VALUE)
        m_prev = m_scr[:, 0]                           # [gp]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        pexp = jnp.exp(s - m_cur[:, None])             # [gp, ps]
        l_cur = l_scr[:, 0] * alpha + jnp.sum(pexp, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    @pl.when(p == maxp - 1)
    def _finalize():
        l = l_scr[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)                # empty rows -> 0
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           seq_lens: jax.Array,
                           softmax_scale: Optional[float] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Pallas paged decode attention (same contract as the reference).

    Grid is ``(B, kvh, maxp)`` with pages innermost (sequential on TPU);
    the online-softmax state is carried across the page loop in VMEM
    scratch exactly like the flash forward.  k/v index maps read the
    prefetched page table, so each grid step DMAs one physical page.
    """
    b, nh, hd, ps, kvh = _check_shapes(q, k_pages, v_pages, page_tables,
                                       seq_lens)
    maxp = page_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    if interpret is None:
        interpret = not on_tpu()
    g = nh // kvh
    gp = max(SUBLANES, ((g + SUBLANES - 1) // SUBLANES) * SUBLANES)
    qg = q.reshape(b, kvh, g, hd)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    pt = page_tables.astype(jnp.int32)
    sl = seq_lens.astype(jnp.int32)

    kernel = functools.partial(_paged_kernel, scale=float(scale), ps=ps,
                               maxp=maxp, gp=gp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, gp, hd),
                         lambda bi, h, p, sl_r, pt_r: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda bi, h, p, sl_r, pt_r: (pt_r[bi, p], h, 0,
                                                       0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda bi, h, p, sl_r, pt_r: (pt_r[bi, p], h, 0,
                                                       0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, gp, hd), lambda bi, h, p, sl_r, pt_r: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, LANES), jnp.float32),
            pltpu.VMEM((gp, LANES), jnp.float32),
            pltpu.VMEM((gp, hd), jnp.float32),
        ],
    )
    with jax.named_scope("paged_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, kvh, gp, hd), q.dtype),
            interpret=interpret,
            name="paged_attention",
        )(sl, pt, qg, k_pages, v_pages)
    return out[:, :, :g, :].reshape(b, nh, hd)


def paged_attention_decode(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           seq_lens: jax.Array,
                           softmax_scale: Optional[float] = None,
                           use_kernel: Optional[bool] = None) -> jax.Array:
    """Dispatching entry point: Pallas kernel on platform ``tpu``,
    gather-dense reference elsewhere (``ops.sdpa``'s dispatch rule: the
    platform chooses, a kernel error propagates)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return paged_attention_pallas(q, k_pages, v_pages, page_tables,
                                      seq_lens,
                                      softmax_scale=softmax_scale)
    return paged_attention_reference(q, k_pages, v_pages, page_tables,
                                     seq_lens, softmax_scale=softmax_scale)
