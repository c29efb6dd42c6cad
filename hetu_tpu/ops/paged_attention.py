"""Paged decode attention: the plain reference, and the constants and
helpers the ragged kernel shares.

Serving keeps KV state in a preallocated page pool
(``hetu_tpu/serving/kv_pool.py``): per layer, ``k_pages``/``v_pages``
of shape ``[num_pages, kv_heads, page_size, head_dim]``, with each
request owning a list of pages through an int32 page table.  Decode
attention then reads *ragged* per-request histories through the page
table instead of a padded dense ``[B, max_len, ...]`` cache — the
Ragged Paged Attention recipe (PAPERS.md, arxiv 2604.15464) that lets
mixed-length requests share one pool with no padding HBM.

``paged_attention_reference`` gathers pages via the page table into a
contiguous ``[B, maxp*ps, kvh, hd]`` view and runs masked dense
attention: the decode math of the serving step off the TPU
(``serving/decode.py::_split_ragged_attention``) and an oracle the
tests compare against.  On the TPU a decode token is a ``q_len == 1``
row of ``ops/ragged_paged_attention.py``'s kernel, which also takes
``LANES``, ``SUBLANES``, ``DEFAULT_MASK_VALUE``, ``gather_pages`` and
``vmem_params`` from here.

Layout notes (DESIGN.md §8): the last two page dims are ``(page_size,
head_dim)``, so the per-(page, kv-head) ``[ps, hd]`` tile a grid step
DMAs is a whole trailing block — the only k/v block Mosaic accepts when
there is more than one KV head (a block of 1 on a ``kv_heads`` axis in
second-to-last position is refused).  ``head_dim`` fills the 128-lane
tile; ``page_size`` is the sublane dim and must be a multiple of 8 (f32
sublanes) — multiples of 128 additionally make one page exactly one
MXU-shaped block.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

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
