"""The routed experts of a serving step: one grouped matmul a layer over
the live assignments, each hit expert's weights read once.

``grouped_experts`` takes the router's choice ``idx, w [T, k]`` over the
step's whole STATIC token axis and computes, for the assignments that
are live and fall on the ``held`` experts stacked in ``w1 [held, L, F]``
/ ``w2 [held, F, L]``, ``sum_j w[t, j] * act(x[t] @ w1[e]) @ w2[e]`` —
nothing for a dead token, nothing for an expert another chip holds,
nothing for an expert no live token chose.

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
ACTIVATIONS = {"relu2": lambda h: jnp.square(jax.nn.relu(h)),
               "relu": jax.nn.relu, "gelu": jax.nn.gelu,
               "silu": jax.nn.silu}


def block_rows(load, block: int = ROW_BLOCK):
    """Rows the kernel computes for ``load`` (assignments per expert, any
    shape): every group padded to whole blocks."""
    return ((load + block - 1) // block * block).sum()


def _experts_kernel(n_ref, blk_e_ref, start_ref, cnt_ref, tok_ref, wt_ref,
                    x_ref, w1_ref, w2_ref, out_ref, rows_ref, y_ref, *,
                    activation: str):
    """Grid step ``g``: block ``g`` of the plan."""
    del blk_e_ref                       # read by the block index maps
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
        hid = ACTIVATIONS[activation](jnp.dot(
            rows_ref[...].astype(w1_ref.dtype), w1_ref[0],
            preferred_element_type=F32))
        y_ref[...] = jnp.dot(hid.astype(w2_ref.dtype), w2_ref[0],
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
    "expert_offset", "activation", "block", "interpret"))
def grouped_experts(x, idx, w, live, w1, w2, *, expert_offset: int = 0,
                    activation: str = "relu2", block: int = ROW_BLOCK,
                    interpret: Optional[bool] = None):
    """``x [T, L]``; ``idx, w [T, k]`` the router's experts (ids over ALL
    routed experts) and float32 combine weights; ``live [T]`` marks real
    tokens; ``w1 [held, L, F]``, ``w2 [held, F, L]`` the experts
    ``expert_offset .. expert_offset + held``.  Returns ``(out [T, L]
    float32, load [held] int32: live tokens per held expert)``."""
    if interpret is None:
        interpret = not on_tpu()
    t, lat = x.shape
    held, _, ffn = w1.shape
    k = idx.shape[-1]
    local = idx - expert_offset
    keep = (local >= 0) & (local < held) & live[:, None]
    plan = expert_groups(local, held, block, keep)
    # block g of expert e is rows [j*block, j*block + cnt) of e's group
    g = jnp.arange(plan.blk_e.shape[0], dtype=jnp.int32)
    j = g - take_small(plan.blk_off, plan.blk_e)
    start = take_small(plan.src_off, plan.blk_e) + j * block
    cnt = jnp.where(g < plan.n_blocks, jnp.clip(
        take_small(plan.counts, plan.blk_e) - j * block, 0, block), 0)
    tok = plan.order // k
    wt = w.reshape(-1).astype(F32)[plan.order]

    def expert(g, n, blk_e, *_):
        return blk_e[g], 0, 0

    def whole(g, *_):
        return 0, 0

    with jax.named_scope("moe_grouped_experts"):
        out = pl.pallas_call(
            functools.partial(_experts_kernel, activation=activation),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=6,
                grid=(jnp.maximum(plan.n_blocks, 1),),
                in_specs=[pl.BlockSpec((t, lat), whole),
                          pl.BlockSpec((1, lat, ffn), expert),
                          pl.BlockSpec((1, ffn, lat), expert)],
                out_specs=pl.BlockSpec((t, lat), whole),
                scratch_shapes=[pltpu.VMEM((block, lat), F32),
                                pltpu.VMEM((block, lat), F32)]),
            out_shape=jax.ShapeDtypeStruct((t, lat), F32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_limit(t, lat, ffn, block, w1.dtype)),
            interpret=interpret,
            name="moe_grouped_experts",
        )(plan.n_blocks[None], plan.blk_e, start, cnt, tok, wt,
          x.astype(F32), w1, w2)
    return out, plan.counts


def _vmem_limit(t: int, lat: int, ffn: int, block: int, dtype) -> int:
    """Two buffers of each expert's two matrices, ``x`` and the output
    (two each), the row scratch and the hidden; half as much again."""
    weights = 2 * 2 * lat * ffn * jnp.dtype(dtype).itemsize
    resident = 2 * 2 * t * lat * 4
    work = block * (2 * lat + 2 * ffn) * 4
    return max(int(1.5 * (weights + resident + work)), 16 << 20)
