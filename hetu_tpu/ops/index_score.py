"""The dsa indexer's scores straight out of the index-key pages.

A dsa layer's indexer scores every cached position of a query's context:
``sum_j w[t, j] relu(q[t, j] . k[s])`` over its ``IH`` index heads, so
that the exact top-k behind it (``models/hybrid.py::index_select``) can
pick the positions the attention reads.  The keys live in the layer's
index-key stream ``[P, 1, ps, ID]``, a context's under its page table.

:func:`index_score_pages` is the one entry point.  On platform ``tpu`` it
is ONE Pallas call (:func:`index_score_pages_pallas`): a grid step holds a
block of queries (all heads of each) and a GROUP of page-table slots,
copied into VMEM through the table by the kernel's own double-buffered
DMAs, runs ``relu(q . k)`` on the MXU with float32 accumulation and the
weighted sum over heads in float32 in VMEM, and writes ``[queries,
positions]`` float32 scores: no private copy of a context's keys and no
``[queries, IH, positions]`` tile in HBM.  Elsewhere, and as the oracle,
the same arithmetic in XLA (:func:`index_scores` over gathered pages).

The call shares no function with the latent ragged call
(``ops/ragged_paged_attention.py``), whose grouping it follows: that one
keeps an online softmax over a region's whole query block, this one has no
state between groups and blocks its queries, and every other stack's
lowered step has to stay the bytes it was (DESIGN.md section 27).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import SUBLANES, _tiled_bytes, vmem_params
from .pallas import on_tpu

F32 = jnp.float32
# queries of one context a grid step scores together (all their heads: a
# ``[block * IH, ID]`` operand), the widest group of page-table slots it
# walks, and the VMEM the group's key buffers and float32 score tiles may
# take (PERF.md section 6, PR 49, step 0)
INDEX_QUERY_BLOCK = 64
# queries whose selection and sparse read are live together: top-k, look-up,
# row gather and ``ops/selected_attention.py``'s call over the gathered rows
# (``hy.indexed_attention``; ``serving/step_account.py`` counts blocks by it)
INDEX_SELECT_BLOCK = 32
INDEX_GROUP_MAX = 32
INDEX_GROUP_VMEM = 40 << 20


def index_scores(q, keys, w):
    """The indexer's score of every (query, cached position) pair,
    float32: ``sum_j w[t, j] relu(q[t, j] . k[s])``, the products
    accumulated in float32.  ``q [n, IH, ID]``, ``w [n, IH]`` float32;
    ``keys`` is ``[S, ID]`` (one row's context, shared by the ``n``
    queries) or ``[n, S, ID]`` (a context a query)."""
    eq = "njd,sd->njs" if keys.ndim == 2 else "njd,nsd->njs"
    s = jnp.einsum(eq, q, keys, preferred_element_type=F32)
    return jnp.einsum("njs,nj->ns", jax.nn.relu(s), w)


def by_blocks(f, arrays, block: int, live=None):
    """``f`` over blocks of ``block`` leading rows of the arrays of
    ``arrays`` (one length ``n``), one block live at a time, the results
    joined: what bounds a step's temporaries by the block and not by the
    chunk.  ``live`` (a traced scalar: the leading rows that are real)
    bounds the WORK too, in the one executable: only the blocks that hold
    one of the first ``live`` rows call ``f``, under a traced trip count
    over a zeroed result, and the rows behind them read zeros."""
    n = arrays[0].shape[0]
    if n <= block:
        return f(arrays)
    pad = -n % block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)  # noqa: E731
                            ).reshape((-1, block) + a.shape[1:])
    blocks = tuple(cut(a) for a in arrays)
    if live is None:
        out = lax.map(f, blocks)
        return out.reshape((-1,) + out.shape[2:])[:n]
    one = jax.eval_shape(f, tuple(a[0] for a in blocks))
    return lax.fori_loop(
        0, (live + block - 1) // block,
        lambda i, out: lax.dynamic_update_slice_in_dim(
            out, f(tuple(a[i] for a in blocks)), i * block, 0),
        jnp.zeros((n + pad,) + one.shape[1:], one.dtype))[:n]


def _group_vmem(q_blk: int, heads: int, pages: int, key_pages):
    """``(shape, dtype)`` entries, as :func:`vmem_params` takes them, of
    what a group of ``pages`` pages holds in VMEM beside the q / w / out
    blocks: both buffers of its key pages and the two float32 tiles (the
    products, and their relu times the head weights)."""
    toks = pages * key_pages.shape[2]
    tile = (q_blk * heads, toks)
    return [((2 * toks, key_pages.shape[-1]), key_pages.dtype),
            (tile, F32), (tile, F32)]


def index_score_blocking(n: int, heads: int, maxp: int, shared: bool,
                         key_pages) -> Tuple[int, int]:
    """``(queries a block, page-table slots a grid step)`` of the scoring
    call — the one rule, read by the kernel wrapper and by
    ``serving/step_account.py`` (the ``index_grid_steps`` counter), from
    what both can see: the call's ``n`` queries, the index heads, the page
    table's width, whether the queries share ONE context (a chunk) or
    have one each (decode rows), and the index-key stream (``[P, 1, ps,
    ID]``, an array or its shape and dtype).

    Queries of one context are scored ``INDEX_QUERY_BLOCK`` at a time
    (fewer, to whole sublanes, where the call has fewer); a decode row is
    a block of its own.  A grid step costs ~0.35 us whatever it fetches
    and a 16 KB page is 64 columns of the MXU, so the group is the largest
    power of two, at most ``INDEX_GROUP_MAX`` and ``maxp``, whose key
    buffers and float32 tiles (:func:`_group_vmem`) fit
    ``INDEX_GROUP_VMEM``."""
    q_blk = min(INDEX_QUERY_BLOCK, -(-n // SUBLANES) * SUBLANES) \
        if shared else 1

    def fits(k):
        return sum(_tiled_bytes(s, d) for s, d in _group_vmem(
            q_blk, heads, k, key_pages)) <= INDEX_GROUP_VMEM

    k = 1
    while 2 * k <= min(INDEX_GROUP_MAX, maxp) and fits(2 * k):
        k *= 2
    return q_blk, k


def _make_kernel(ps: int, pages: int, n_blocks: int, q_blk: int,
                 heads: int, shared: bool):
    """Grid ``(query blocks, groups)``.  A block's q operand is laid HEAD
    MAJOR (row ``j * q_blk + t``: head ``j`` of query ``t``), so that the
    sum over heads adds whole tiles.  A running step starts the copies of
    the NEXT running step's group (the block's next, or the next block's
    first) into the other buffer before it waits for its own; a group past
    the context is skipped and reads 0, like the tail of the last one."""
    cols_g = pages * ps

    def kernel(pt_ref, cl_ref, q_ref, w_ref, k_hbm, o_ref, buf, sem, count):
        i, g = pl.program_id(0), pl.program_id(1)
        row = (lambda b: 0) if shared else (lambda b: b)

        def live_groups(b):
            return (cl_ref[row(b)] + cols_g - 1) // cols_g

        n_g = live_groups(i)

        def copies(b, grp, slot):
            """One DMA a page of group ``grp`` of block ``b``'s context
            into buffer ``slot`` (``b`` None: the same shapes from page 0,
            which is all a wait reads of its descriptor)."""
            return [pltpu.make_async_copy(
                k_hbm.at[0 if b is None else pt_ref[row(b), grp * pages + j],
                         0],
                buf.at[slot, pl.ds(j * ps, ps)], sem.at[slot])
                for j in range(pages)]

        @pl.when(jnp.logical_and(i == 0, g == 0))
        def _first():
            count[0] = 0             # running steps so far: buffer parity

        @pl.when(g < n_g)
        def _group():
            slot = count[0] % 2
            nxt = jnp.minimum(i + 1, n_blocks - 1)
            next_live = jnp.logical_and(i + 1 < n_blocks,
                                        live_groups(nxt) > 0)
            prev_live = jnp.logical_and(
                i > 0, live_groups(jnp.maximum(i - 1, 0)) > 0)
            more = g + 1 < n_g

            @pl.when(jnp.logical_and(g == 0, jnp.logical_not(prev_live)))
            def _cold():             # no step before this one fetched it
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
            s = lax.dot_general(q_ref[...], buf[slot],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
            r = jnp.maximum(s, 0.0) * w_ref[...]   # [heads * q_blk, cols_g]
            if q_blk == 1:
                out = jnp.sum(r, axis=0, keepdims=True)
            else:
                out = jnp.sum(r.reshape(heads, q_blk, cols_g), axis=0)
            cols = g * cols_g + lax.broadcasted_iota(
                jnp.int32, (q_blk, cols_g), 1)
            o_ref[...] = jnp.where(cols < cl_ref[row(i)], out, 0.0)

        @pl.when(g >= n_g)
        def _skipped():
            o_ref[...] = jnp.zeros_like(o_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "interpret", "query_block", "pages_per_step"))
def index_score_pages_pallas(
        iq: jax.Array, iw: jax.Array, key_pages: jax.Array,
        table: jax.Array, ctx_lens: jax.Array, *,
        interpret: Optional[bool] = None,
        query_block: Optional[int] = None,
        pages_per_step: Optional[int] = None) -> jax.Array:
    """The Pallas scoring call (same contract as
    :func:`index_score_pages`).  On the device trace it is
    ``index_score_chunk`` (one table) or ``index_score_decode`` (a table a
    query).  ``query_block`` / ``pages_per_step`` override
    :func:`index_score_blocking` for the kernel's own tests and timings
    only (no serving code passes them: the engine's counters read the
    rule)."""
    n, heads, dim = iq.shape
    ps = key_pages.shape[2]
    shared = table.ndim == 1
    maxp = table.shape[-1]
    if interpret is None:
        interpret = not on_tpu()
    q_blk, kpg = index_score_blocking(n, heads, maxp, shared, key_pages)
    if shared:
        q_blk = query_block or q_blk
    kpg = pages_per_step or kpg
    n_blocks, groups = -(-n // q_blk), -(-maxp // kpg)
    cols_g = kpg * ps
    # a table the group does not divide ends in the trash page's slots
    tables = jnp.pad(table.astype(jnp.int32).reshape(-1, maxp),
                     ((0, 0), (0, groups * kpg - maxp)))
    ctx = jnp.minimum(ctx_lens.astype(jnp.int32).reshape(-1), maxp * ps)
    pad = ((0, n_blocks * q_blk - n),)
    rows = heads * q_blk             # head major inside a block
    q = jnp.pad(iq.astype(key_pages.dtype), pad + ((0, 0), (0, 0))).reshape(
        n_blocks, q_blk, heads, dim).swapaxes(1, 2).reshape(
            n_blocks, rows, dim)
    w = jnp.pad(iw.astype(F32), pad + ((0, 0),)).reshape(
        n_blocks, q_blk, heads).swapaxes(1, 2).reshape(n_blocks, rows, 1)
    name = "index_score_chunk" if shared else "index_score_decode"
    block = lambda i, g, pt, cl: (i, 0, 0)                   # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks, groups),
        in_specs=[pl.BlockSpec((None, rows, dim), block),
                  pl.BlockSpec((None, rows, 1), block),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, q_blk, cols_g),
                               lambda i, g, pt, cl: (i, 0, g)),
        scratch_shapes=[pltpu.VMEM((2, cols_g, dim), key_pages.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    with jax.named_scope(name):
        out = pl.pallas_call(
            _make_kernel(ps, kpg, n_blocks, q_blk, heads, shared),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (n_blocks, q_blk, groups * cols_g), F32),
            compiler_params=vmem_params(
                blocks=[((rows, dim), key_pages.dtype), ((rows, 1), F32),
                        ((q_blk, cols_g), F32)],
                scratch=_group_vmem(q_blk, heads, kpg, key_pages)),
            interpret=interpret,
            name=name,
        )(tables, ctx, q, w, key_pages)
    return out.reshape(n_blocks * q_blk, -1)[:n, :maxp * ps]


def index_score_pages_reference(iq, iw, key_pages, table, ctx_lens):
    """The XLA arithmetic (same contract as :func:`index_score_pages`): a
    context's keys gathered out of the pages a page at a time (a page's
    rows lie together: one 16 KB copy, not 64 of 256 B) — once for the
    queries that share a table, a copy a query where each has its own —
    and :func:`index_scores` over them, 32 queries live at a time."""
    shared = table.ndim == 1

    def keys_of(tab):
        k = key_pages[tab]                       # [.., maxp, 1, ps, ID]
        return k.reshape(tab.shape[:-1] + (-1, k.shape[-1]))

    seen = lambda s, c: jnp.where(                           # noqa: E731
        jnp.arange(s.shape[-1]) < jnp.reshape(c, (-1, 1)), s, 0.0)
    iq = iq.astype(key_pages.dtype)
    if shared:
        keys = keys_of(table)                               # [S, ID]
        return by_blocks(
            lambda a: seen(index_scores(a[0], keys, a[1]), ctx_lens),
            (iq, iw), 32)
    return by_blocks(
        lambda a: seen(index_scores(a[0], keys_of(a[2]), a[1]), a[3]),
        (iq, iw, table, ctx_lens), 32)


def index_score_pages(iq, iw, key_pages, table, ctx_lens, *,
                      use_kernel: Optional[bool] = None):
    """Scores of ``n`` index queries against their contexts' cached keys:
    ``[n, maxp * ps]`` float32, position ``s`` of query ``t`` holding
    ``sum_j iw[t, j] relu(iq[t, j] . key[s])`` (products in the pages'
    dtype, accumulated in float32; relu and head sum in float32) where
    ``s`` lies inside the context and 0 past it.  ``iq [n, IH, ID]`` the
    rotated index queries, ``iw [n, IH]`` float32 head weights,
    ``key_pages [P, 1, ps, ID]`` the layer's index-key stream; ``table``
    is ONE context's page table ``[maxp]`` with ``ctx_lens`` a scalar (a
    chunk: its queries share a context) or ``[n, maxp]`` with ``ctx_lens
    [n]`` (decode rows).  Kernel on platform ``tpu``, the XLA arithmetic
    elsewhere; a kernel error propagates."""
    if on_tpu() if use_kernel is None else use_kernel:
        return index_score_pages_pallas(iq, iw, key_pages, table, ctx_lens)
    return index_score_pages_reference(iq, iw, key_pages, table, ctx_lens)
