"""The routed experts of a serving step: one grouped matmul a layer over
the live assignments, each hit expert's weights read once.

``grouped_experts`` takes the router's choice ``idx, w [T, k]`` over the
step's whole STATIC token axis and computes, for the assignments that
are live and fall on the ``held`` experts stacked in ``w1 [held, L, F]``
/ ``w2 [held, F, L]``, ``sum_j w[t, j] * act(x[t] @ w1[e]) @ w2[e]`` —
nothing for a dead token, nothing for an expert another chip holds,
nothing for an expert no live token chose.  With ``w3 [held, L, F]`` the
experts are gated, ``(act(x w1) * x w3) w2``.  An expert too large for
VMEM whole (``ffn_tile``: the shape decides) is walked in tiles of its
width ``F``: a grid step is then one row block against one tile, the
items of an expert run tile by tile and under a tile block by block, so
each tile of a hit expert's weights still moves once.

The plan (plain XLA, ``moe_dispatch.expert_groups``) sorts the kept
assignments by expert and cuts each expert's group into row blocks of
``block``.  The Pallas call ``moe_grouped_experts`` walks the LIVE
blocks, one a grid step (the grid's bound is the plan's ``n_blocks``:
data, not shape): the block -> expert table is scalar-prefetched and the
weights' block index maps read it, so the pipeline DMAs expert ``e``'s
``[L, F]`` and ``[F, L]`` straight out of the stacked arrays (no
gathered copy), and consecutive blocks of one expert — a skewed expert
has many — keep the block index, so its weights move once.  A grid step
gathers its rows of ``x`` (resident in VMEM, float32) by the sorted
token ids, runs ``act(rows @ w1[e]) @ w2[e]`` with float32 accumulation
(the ``[rows, F]`` hidden never leaves VMEM), and adds each row, times
its combine weight, into the float32 output ``[T, L]`` (resident too:
written back once).

What a block costs: it pushes the expert's whole weights through the MXU
whatever its rows (~7 us at ``1024 x 2688`` on a v5e, against ~13 us to
read them from HBM), so an expert with one block is bound by its bytes
and one with many by the pushes: ``ROW_BLOCK`` is sized for the second
(PERF.md, PR 34).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .moe_dispatch import expert_groups, take_small
from .pallas import on_tpu

F32 = jnp.float32
# rows of one grid step.  One block costs the MXU the same up to ~64 rows,
# so a larger block only halves the blocks of an expert many tokens chose:
# tbt_p95_ms 26.9 / 26.4 / 26.1 / 26.4 at 16 / 32 / 64 / 128 (PERF.md, PR 34)
ROW_BLOCK = 64
LANES = 128
# VMEM the double-buffered weights of one grid step may take: the whole
# expert where it fits (22 MB at 1024 x 2688 x 2 matrices), tiles of its
# width where it does not (201 MB at 4096 x 2048 x 3)
WEIGHT_VMEM = 32 << 20
ACTIVATIONS = {"relu2": lambda h: jnp.square(jax.nn.relu(h)),
               "relu": jax.nn.relu, "gelu": jax.nn.gelu,
               "silu": jax.nn.silu}


def block_rows(load, block: int = ROW_BLOCK):
    """Rows the kernel computes for ``load`` (assignments per expert, any
    shape): every group padded to whole blocks."""
    return ((load + block - 1) // block * block).sum()


def ffn_tile(lat: int, ffn: int, n_mats: int, itemsize: int) -> int:
    """Columns of an expert's width one grid step works on: the whole
    width where two buffers of the expert's ``n_mats`` matrices fit
    ``WEIGHT_VMEM`` (the shape decides), else the width cut into the
    fewest equal tiles of whole lanes that do."""
    per_col = 2 * n_mats * lat * itemsize
    if per_col * ffn <= WEIGHT_VMEM:
        return ffn
    most = max(WEIGHT_VMEM // per_col // LANES, 1) * LANES
    n_tiles = -(-ffn // most)
    return -(-ffn // n_tiles // LANES) * LANES


def _experts_kernel(n_ref, it_e_ref, it_f_ref, start_ref, cnt_ref, tok_ref,
                    wt_ref, x_ref, *refs, activation: str, gated: bool,
                    ffn: int, tile: int):
    """Grid step ``g``: item ``g`` of the plan — one row block of one
    expert against one tile of its width."""
    del it_e_ref                        # read by the block index maps
    if gated:
        w1_ref, w3_ref, w2_ref, out_ref, rows_ref, y_ref = refs
    else:
        (w1_ref, w2_ref, out_ref, rows_ref, y_ref), w3_ref = refs, None
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(g < n_ref[0])
    def _block():
        start, cnt = start_ref[g], cnt_ref[g]

        def gather(r, carry):
            rows_ref[pl.ds(r, 1), :] = x_ref[pl.ds(tok_ref[start + r], 1), :]
            return carry

        lax.fori_loop(0, cnt, gather, 0)
        # rows past cnt hold an earlier block's: computed, never added
        rows = rows_ref[...].astype(w1_ref.dtype)
        hid = ACTIVATIONS[activation](jnp.dot(
            rows, w1_ref[0], preferred_element_type=F32))
        if gated:
            hid = hid * jnp.dot(rows, w3_ref[0], preferred_element_type=F32)
        w2 = w2_ref[0]
        if ffn % tile:
            # the last tile hangs over the width: what lies past it is
            # not the expert's (0 x junk could be NaN: both sides go)
            left = ffn - it_f_ref[g] * tile
            hid = jnp.where(lax.broadcasted_iota(
                jnp.int32, hid.shape, 1) < left, hid, 0.0)
            w2 = jnp.where(lax.broadcasted_iota(
                jnp.int32, w2.shape, 0) < left, w2, jnp.zeros_like(w2))
        y_ref[...] = jnp.dot(hid.astype(w2.dtype), w2,
                             preferred_element_type=F32)

        def add(r, carry):
            at = pl.ds(tok_ref[start + r], 1)
            out_ref[at, :] = out_ref[at, :] + \
                wt_ref[start + r] * y_ref[pl.ds(r, 1), :]
            return carry

        lax.fori_loop(0, cnt, add, 0)


# an inner jit for the reason paged_kv_write is one: the step calls this
# once an expert layer with the same shapes, and it lowers to Mosaic once
@functools.partial(jax.jit, static_argnames=(
    "expert_offset", "activation", "block", "tile", "interpret"))
def grouped_experts(x, idx, w, live, w1, w2, w3=None, *,
                    expert_offset: int = 0, activation: str = "relu2",
                    block: int = ROW_BLOCK, tile: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """``x [T, L]``; ``idx, w [T, k]`` the router's experts (ids over ALL
    routed experts) and float32 combine weights; ``live [T]`` marks real
    tokens; ``w1 [held, L, F]``, ``w2 [held, F, L]`` the experts
    ``expert_offset .. expert_offset + held``; with ``w3 [held, L, F]``
    the experts are gated: ``(act(x w1) * x w3) w2``.  ``tile`` (default
    :func:`ffn_tile`) is the part of ``F`` one grid step works on.
    Returns ``(out [T, L] float32, load [held] int32: live tokens per
    held expert)``."""
    if interpret is None:
        interpret = not on_tpu()
    t, lat = x.shape
    held, _, ffn = w1.shape
    k = idx.shape[-1]
    gated = w3 is not None
    if tile is None:
        tile = ffn_tile(lat, ffn, 3 if gated else 2, w1.dtype.itemsize)
    n_tiles = -(-ffn // tile)
    local = idx - expert_offset
    keep = (local >= 0) & (local < held) & live[:, None]
    plan = expert_groups(local, held, block, keep)
    # an expert's items: tile by tile, and under a tile its row blocks —
    # consecutive items keep the weights' block index as long as they
    # can, so a tile of a hit expert's weights moves once whatever the
    # expert's blocks.  With one tile the items ARE the plan's blocks.
    if n_tiles == 1:
        it_e, n_items = plan.blk_e, plan.n_blocks
        g = jnp.arange(it_e.shape[0], dtype=jnp.int32)
        j = g - take_small(plan.blk_off, it_e)
        it_f = jnp.zeros_like(it_e)
    else:
        blocks = (plan.counts + block - 1) // block
        it_end = jnp.cumsum(blocks) * n_tiles
        n_items = it_end[-1]
        g = jnp.arange(plan.blk_e.shape[0] * n_tiles, dtype=jnp.int32)
        it_e = jnp.minimum(jnp.sum(it_end <= g[:, None], axis=1,
                                   dtype=jnp.int32), held - 1)
        at = g - take_small(it_end - blocks * n_tiles, it_e)
        per = jnp.maximum(take_small(blocks, it_e), 1)
        it_f, j = jnp.minimum(at // per, n_tiles - 1), at % per
    # block j of expert e is rows [j*block, j*block + cnt) of e's group
    start = take_small(plan.src_off, it_e) + j * block
    cnt = jnp.where(g < n_items, jnp.clip(
        take_small(plan.counts, it_e) - j * block, 0, block), 0)
    tok = plan.order // k
    wt = w.reshape(-1).astype(F32)[plan.order]

    # one tile: the weights' block index is the expert alone, as it was
    # before there were tiles
    def up(g, n, it_e, it_f, *_):
        return it_e[g], 0, it_f[g] if n_tiles > 1 else 0

    def down(g, n, it_e, it_f, *_):
        return it_e[g], it_f[g] if n_tiles > 1 else 0, 0

    def whole(g, *_):
        return 0, 0

    ups = [w1, w3] if gated else [w1]
    with jax.named_scope("moe_grouped_experts"):
        out = pl.pallas_call(
            functools.partial(_experts_kernel, activation=activation,
                              gated=gated, ffn=ffn, tile=tile),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=7,
                grid=(jnp.maximum(n_items, 1),),
                in_specs=[pl.BlockSpec((t, lat), whole)]
                + [pl.BlockSpec((1, lat, tile), up)] * len(ups)
                + [pl.BlockSpec((1, tile, lat), down)],
                out_specs=pl.BlockSpec((t, lat), whole),
                scratch_shapes=[pltpu.VMEM((block, lat), F32),
                                pltpu.VMEM((block, lat), F32)]),
            out_shape=jax.ShapeDtypeStruct((t, lat), F32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_limit(t, lat, tile, block, w1.dtype,
                                             len(ups) + 1)),
            interpret=interpret,
            name="moe_grouped_experts",
        )(n_items[None], it_e, it_f, start, cnt, tok, wt,
          x.astype(F32), *ups, w2)
    return out, plan.counts


def _vmem_limit(t: int, lat: int, tile: int, block: int, dtype,
                n_mats: int = 2) -> int:
    """Two buffers of each of the expert's matrices (a tile of each),
    ``x`` and the output (two each), the row scratch and the hidden;
    half as much again."""
    weights = 2 * n_mats * lat * tile * jnp.dtype(dtype).itemsize
    resident = 2 * 2 * t * lat * 4
    work = block * (2 * lat + n_mats * tile) * 4
    return max(int(1.5 * (weights + resident + work)), 16 << 20)
