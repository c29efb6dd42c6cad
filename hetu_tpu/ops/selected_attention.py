"""A dsa layer's attention over the rows its selection gathered.

Behind the indexer's exact top-k (``models/hybrid.py::index_select``) a
query of a dsa layer attends ``k`` rows of the latent stream (``c_kv | k_r
| zero lanes``), gathered out of the pages by XLA into ``sel [n, k, w]``.
What follows is, for ONE query, a ``[nh, w] x [w, k]`` product, a row
softmax over ``k`` and a ``[nh, k] x [k, d_c]`` product: at 128 heads over
2,048 rows of 640 bf16 lanes, 2.6 MB of ``sel`` and 1 MB of float32
scores.  It all fits in VMEM at once, so the softmax is a single pass (no
running maximum, no rescaling) and the second product reads the first
``d_c`` lanes of the block the first one read.

:func:`attend_selected` is the entry point.  On platform ``tpu`` it is
ONE Pallas call (:func:`selected_attention_pallas`, ``selected_attention``
on the device trace): a grid step holds one or a few queries' rows of ``sel``,
double-buffered by the pipeline, so ``sel`` is read from HBM once and the
scores and probabilities never reach it.  Elsewhere, and as the oracle,
the caller's XLA arithmetic (``models/hybrid.py::selected_attention``),
whose numerics the kernel keeps: products in the pool's dtype with float32
accumulation, a float32 softmax, the probability cast before the second
product, a finite result for a query with no valid row.

In front of the gather stands the page look-up (:func:`selected_slots`),
on every platform a product with a one-hot and no gather of words: of the
1.78 ms a block of 32 queries cost, the look-up was 0.67, the row gather
0.84 and everything behind it 0.28 (PERF.md section 6, PR 59, step 0).
The row gather stays XLA's: 2,048 descriptors of 1,280 B a query from
inside the kernel would have to beat its ~13 ns a row, and a bf16 row is
half a sublane word of the pool's tiles (DESIGN.md section 32).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..obs.counters import count
from .paged_attention import vmem_bytes, vmem_params
from .pallas import on_tpu

F32 = jnp.float32
_MASKED = -1e30         # models/hybrid.py's: a row outside the selection
# the most queries a grid step holds, and the VMEM their blocks (double-
# buffered) and ONE query's float32 tiles may take: what Mosaic's default
# scoped limit of 16 MiB leaves under ``vmem_params``' quarter of room, so
# the call asks for no VMEM of its own.  The call runs no faster with more
# (113 us a block of 32 queries at one, two and four a grid step), and with
# 35 MB reserved for four the cell's chunk steps were 2-3 ms longer: XLA
# keeps the step's own buffers in VMEM where it finds room (PERF.md section
# 6, PR 59)
SELECTED_QUERY_MAX = 8
SELECTED_VMEM = (16 << 20) * 4 // 5


def _step_vmem(q_blk: int, heads: int, k: int, w: int, d_c: int, dtype):
    """``(blocks, scratch)`` as :func:`vmem_params` takes them: the
    pipeline's blocks of ``q_blk`` queries, and what one query's softmax
    holds beside them (scores and exponentials in float32, the
    probabilities in ``dtype``)."""
    blocks = [((q_blk, heads, w), dtype), ((q_blk, k, w), dtype),
              ((q_blk, 1, k), jnp.int32), ((q_blk, heads, d_c), F32)]
    return blocks, [((heads, k), F32), ((heads, k), F32), ((heads, k), dtype)]


def selected_attention_blocking(n: int, heads: int, k: int, w: int,
                                d_c: int, dtype) -> int:
    """Queries a grid step of the call holds — the one rule, read by the
    kernel wrapper and by its VMEM budget: the largest power of two that
    divides ``n`` (no ragged last block), is at most
    ``SELECTED_QUERY_MAX`` and whose blocks (:func:`_step_vmem`) fit
    ``SELECTED_VMEM``; one query where even that does not fit, as at the
    indexed cell's shapes (2.6 MB of rows a query, twice)."""
    q_blk = 1
    while (2 * q_blk <= SELECTED_QUERY_MAX and n % (2 * q_blk) == 0 and
           vmem_bytes(*_step_vmem(2 * q_blk, heads, k, w, d_c, dtype))
           <= SELECTED_VMEM):
        q_blk *= 2
    return q_blk


def _make_kernel(q_blk: int, d_c: int, scale: float):
    """Grid ``(query blocks,)``; the queries of a block one after the
    other, each against its own ``[k, w]`` rows."""

    def kernel(q_ref, sel_ref, valid_ref, o_ref):
        for j in range(q_blk):
            rows = sel_ref[j]                                   # [k, w]
            s = lax.dot_general(q_ref[j], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)     # [nh, k]
            s = jnp.where(valid_ref[j] != 0, s * scale, _MASKED)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = e / jnp.sum(e, axis=-1, keepdims=True)
            o_ref[j] = jnp.dot(p.astype(rows.dtype), rows[:, :d_c],
                               preferred_element_type=F32)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "d_c", "scale", "interpret", "query_block"))
def selected_attention_pallas(
        q_cat: jax.Array, sel: jax.Array, valid: jax.Array, *, d_c: int,
        scale: float, interpret: Optional[bool] = None,
        query_block: Optional[int] = None) -> jax.Array:
    """The Pallas call (same contract as :func:`attend_selected`);
    ``selected_attention`` on the device trace.  ``query_block`` overrides
    :func:`selected_attention_blocking` for the kernel's own tests and
    timings only."""
    n, heads, w = q_cat.shape
    k = sel.shape[1]
    if interpret is None:
        interpret = not on_tpu()
    q_blk = query_block or selected_attention_blocking(
        n, heads, k, w, d_c, sel.dtype)
    per_query = lambda i: (i, 0, 0)                          # noqa: E731
    with jax.named_scope("selected_attention"):
        return pl.pallas_call(
            _make_kernel(q_blk, d_c, scale),
            grid=(n // q_blk,),
            in_specs=[pl.BlockSpec((q_blk, heads, w), per_query),
                      pl.BlockSpec((q_blk, k, w), per_query),
                      pl.BlockSpec((q_blk, 1, k), per_query)],
            out_specs=pl.BlockSpec((q_blk, heads, d_c), per_query),
            out_shape=jax.ShapeDtypeStruct((n, heads, d_c), F32),
            compiler_params=vmem_params(
                *_step_vmem(q_blk, heads, k, w, d_c, sel.dtype)),
            interpret=interpret,
            name="selected_attention",
        )(q_cat.astype(sel.dtype), sel,
          valid.astype(jnp.int32).reshape(n, 1, k))


def selected_slots(table, pos, page_size: int):
    """The pool rows of positions ``pos [n, k]`` under the page tables
    ``table [1 | n, maxp]`` (one shared, or one a query): ``table[pos //
    page_size] * page_size + pos % page_size`` int32, EXACT.  The look-up
    is a product of a one-hot of the slot with the table's four bytes (0 /
    1 and whole numbers up to 255 in bfloat16, float32 accumulation of one
    term), because XLA gathers 4-byte words out of a table at ~10 ns each:
    0.67 ms for the 65,536 of a block of 32 queries, 0.03 ms as a product
    (PERF.md section 6, PR 59, step 0)."""
    n, maxp = pos.shape[0], table.shape[-1]
    hot = (pos[:, :, None] // page_size == jnp.arange(maxp)).astype(
        jnp.bfloat16)                                       # [n, k, maxp]
    shifts = jnp.arange(0, 32, 8)
    digits = ((table[..., None] >> shifts) & 0xFF).astype(jnp.bfloat16)
    parts = jnp.einsum("nks,nsb->nkb", hot,
                       jnp.broadcast_to(digits, (n, maxp, 4)),
                       preferred_element_type=F32).astype(jnp.int32)
    page = jnp.sum(parts << shifts, -1)
    return page * page_size + pos % page_size


def attend_selected(q_cat, sel, d_c: int, valid, scale: float, *, xla,
                    use_kernel: Optional[bool] = None):
    """Absorbed attention of each query over ITS OWN gathered rows:
    ``q_cat [n, nh, w]`` against ``sel [n, k, w]`` in the pool's dtype
    (the XLA gather's output as it is laid), ``valid [n, k]``; the latent
    output ``[n, nh, d_c]`` float32.  Kernel on platform ``tpu``;
    elsewhere ``xla``, the same contract in plain XLA
    (``models/hybrid.py::selected_attention``, which this package cannot
    import).  The choice is counted where it is traced
    (``obs.counts("selected_attention_calls")`` by ``path``); a kernel
    error propagates."""
    kernel = on_tpu() if use_kernel is None else use_kernel
    count("selected_attention_calls", path="kernel" if kernel else "xla")
    if kernel:
        return selected_attention_pallas(q_cat, sel, valid, d_c=d_c,
                                         scale=scale)
    return xla(q_cat, sel, d_c, valid, scale)
