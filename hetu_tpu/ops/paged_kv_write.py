"""The serving step's KV write: page-runs of live tokens, not a scatter.

The unified serving step (``serving/decode.py``) adds this step's new
K/V rows to the paged pool ``[P, h, ps, w]`` of every layer.  The host
hands it a per-token write plan ``(token_page, token_off)`` over the
step's STATIC token axis, padding slots aimed at the trash page.  A
scatter over that axis (:func:`paged_kv_write_reference`, the plain
``.at[].set``) pays one serial scatter row per (slot, head) whether the
slot holds a token or not: 3 456 rows a call for the 4 live tokens of a
chat step.

:func:`paged_kv_write` writes what exists.  The token axis is a list of
static REGIONS of equal-width rows (the step's decode, chunk and verify
slots); row ``i`` of the step owns the ``q_lens[i]`` tokens from
``cu_q[i]`` on, at CONSECUTIVE positions of one sequence, so its tokens
fall into page-runs: token ``k`` lies at in-page offset
``(token_off[cu_q[i]] + k) % ps`` of page ``token_page[cu_q[i] + k]``.
:func:`kv_write_plan` cuts every run into the ``tile``-row pieces of the
pool it touches (``tile`` rows = the packed sublane tile of the pool's
dtype: a bf16 pool packs two rows to a 32-bit sublane, so a run that
starts or ends inside a tile is a read-modify-write of that tile, not a
bare one-row copy) and compacts the live pieces to the front; rows with
``q_lens == 0`` and padding slots yield none.  The Pallas call walks the
pieces, one a grid step, for every pool it is given (K and V of a layer
in ONE call): its block index maps read the plan, so the pipeline DMAs
in the pool tile ``[h, tile, w]`` and the two aligned tiles of the new
rows that cover it, the body rotates those onto the tile's rows and
keeps the old rows outside ``[lo, hi)``, and the pipeline DMAs the tile
back.  The pools are aliased to the outputs, so only the touched tiles
move: nothing copies a pool.  The steps after the last live piece keep
its block indices and do nothing — no DMA (their cost: PERF.md, PR 29).

Every live token's row lands bit-equal to the scatter's.  The trash
page is no longer written (the scatter left padding junk there); a step
with no live token at all rewrites its first tile with itself.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import LANES, SUBLANES
from .pallas import on_tpu


def write_tile(pools: Sequence) -> int:
    """Rows of one written piece of ``pools`` (arrays or shape structs
    ``[P, h, ps, w]``): the tallest packed sublane tile among their
    dtypes (8 rows of 32 bits: float32 8, bf16 16, int8 32); the whole
    page where that does not divide the page, or where a pool's rows do
    not fill the 128 lanes (such a page is one padded tile to Mosaic)."""
    page_size = pools[0].shape[2]
    sub = max(SUBLANES * max(1, 4 // jnp.dtype(p.dtype).itemsize)
              for p in pools)
    if page_size % sub or any(p.shape[3] % LANES for p in pools):
        return page_size
    return sub


def paged_kv_write_reference(pools, news, token_page, token_off):
    """The plain scatter: ``news[a] [T, h, w]`` row ``t`` to
    ``pools[a][token_page[t], :, token_off[t]]``, every slot of the
    token axis, padding included (it lands in the trash page)."""
    out = []
    for pool, new in zip(pools, news):
        # one [w] row per (page, head, offset) index: the written window
        # is the pages' minor dim, so at a head_dim that fills the 128
        # lanes XLA scatters in place — a [h, w] window per token makes
        # it re-lay the whole pool out and back (as does a 64-wide head
        # either way: CHANGES.md, PR 21)
        at = (token_page[:, None], jnp.arange(pool.shape[1])[None, :],
              token_off[:, None])
        out.append(pool.at[at].set(new.astype(pool.dtype)))
    return tuple(out)


def kv_write_plan(token_page, token_off, q_lens, cu_q, *,
                  regions: Tuple[Tuple[int, int, int], ...],
                  page_size: int, tile: int):
    """The step's write, as pool pieces: ``(n, page, row, lo, hi, base,
    shift)``, int32, the ``n[0]`` live pieces first.

    ``regions`` lists ``(first_row, rows, width)`` of the static token
    layout.  A row of width ``w`` can touch ``ceil((w + tile - 1) /
    tile)`` tiles wherever it starts; a piece is tile ``row`` of
    ``page`` (pool rows ``[row * tile, (row + 1) * tile)``), of which
    rows ``[lo, hi)`` are this step's tokens.  The new rows come from
    the token axis PADDED BY ``tile`` in front and cut into tiles: tile
    row ``r`` is row ``shift + r`` of padded tiles ``base`` and ``base
    + 1``.  A piece past the live ones repeats the last live one (the
    kernel skips it; equal block indices move nothing); with no live
    piece the first is rows ``[0, 0)`` of the trash page.  Computed once
    a step; every layer's :func:`paged_kv_write` runs the same plan."""
    rows, ms = [], []
    for first, n, width in regions:
        per_row = -(-(width + tile - 1) // tile)
        rows.append(np.repeat(np.arange(first, first + n), per_row))
        ms.append(np.tile(np.arange(per_row), n))
    rows = jnp.asarray(np.concatenate(rows), jnp.int32)
    m = jnp.asarray(np.concatenate(ms), jnp.int32)
    t = token_page.shape[0]
    q, start = q_lens[rows], cu_q[rows]
    off0 = token_off[jnp.clip(start, 0, t - 1)]
    rel = m * tile - off0 % tile        # tile row 0, in tokens of the row
    lo = jnp.clip(-rel, 0, tile)
    hi = jnp.clip(q - rel, 0, tile)
    live = hi > lo
    page = token_page[jnp.clip(start + rel + lo, 0, t - 1)]
    row = (off0 + rel) % page_size // tile
    src = start + rel + tile            # >= 1 on the padded axis
    n = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    order = order[jnp.minimum(jnp.arange(order.shape[0]),
                              jnp.maximum(n, 1) - 1)]
    return (n[None],) + tuple(
        jnp.where(live, x, 0)[order].astype(jnp.int32)
        for x in (page, row, lo, hi, src // tile, src % tile))


def _write_kernel(n_ref, page_ref, row_ref, lo_ref, hi_ref, base_ref,
                  shift_ref, *refs, n_arr: int, tile: int):
    """Grid step ``u``: piece ``u`` of the plan, in every pool."""
    del page_ref, row_ref, base_ref     # read by the block index maps
    u = pl.program_id(0)

    @pl.when(u < jnp.maximum(n_ref[0], 1))
    def _piece():
        lo, hi = lo_ref[u], hi_ref[u]
        back = (2 * tile - shift_ref[u]) % (2 * tile)
        for a in range(n_arr):
            first, second = refs[2 * a], refs[2 * a + 1]
            old, out = refs[2 * n_arr + a], refs[3 * n_arr + a]
            dt = out.dtype
            # rotate in 32 bits (exact both ways)
            wide = dt if dt.itemsize == 4 else (
                jnp.float32 if jnp.issubdtype(dt, jnp.floating)
                else jnp.int32)
            r = lax.broadcasted_iota(jnp.int32, out.shape[2:], 0)
            mine = jnp.logical_and(r >= lo, r < hi)
            for h in range(out.shape[1]):
                win = jnp.concatenate([first[h].astype(wide),
                                       second[h].astype(wide)], axis=0)
                x = pltpu.roll(win, back, 0)[:tile]
                out[0, h] = jnp.where(mine, x.astype(dt), old[0, h])


# jitted for the reason the attention kernels are: the step calls this
# once a layer with the same shapes, and an inner jit is lowered to
# Mosaic once, not once a layer
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def paged_kv_write(pools, news, plan, *, tile: int,
                   interpret: Optional[bool] = None):
    """Write ``news[a] [T, h, w]`` into ``pools[a] [P, h, ps, w]`` along
    ``plan`` (:func:`kv_write_plan` at the same ``tile``); returns the
    pools, updated in place where the caller donates them.  The Mosaic
    call is ``paged_kv_write`` on the device trace."""
    if interpret is None:
        interpret = not on_tpu()
    t = news[0].shape[0]
    padded = -(-t // tile) * tile + 3 * tile    # a window never runs out
    # heads lead, as in the pool: a run is then rows of one [h, rows, w]
    srcs = tuple(jnp.pad(jnp.swapaxes(x.astype(p.dtype), 0, 1),
                         ((0, 0), (tile, padded - tile - t), (0, 0)))
                 for p, x in zip(pools, news))
    n_arr = len(pools)

    def window(second):
        return lambda u, n, page, row, lo, hi, base, shift: (
            0, base[u] + second, 0)

    def piece(u, n, page, row, lo, hi, base, shift):
        return page[u], 0, row[u], 0

    src_specs, pool_specs = [], []
    for p in pools:
        src_specs += [pl.BlockSpec((p.shape[1], tile, p.shape[3]),
                                   window(k)) for k in (0, 1)]
        pool_specs.append(pl.BlockSpec((1, p.shape[1], tile, p.shape[3]),
                                       piece))
    with jax.named_scope("paged_kv_write"):
        out = pl.pallas_call(
            functools.partial(_write_kernel, n_arr=n_arr, tile=tile),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(plan),
                grid=(plan[1].shape[0],),
                in_specs=src_specs + pool_specs, out_specs=pool_specs),
            out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                       for p in pools],
            input_output_aliases={len(plan) + 2 * n_arr + a: a
                                  for a in range(n_arr)},
            interpret=interpret,
            name="paged_kv_write",
        )(*plan, *[s for s in srcs for _ in (0, 1)], *pools)
    return tuple(out)
