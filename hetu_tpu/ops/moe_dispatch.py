"""Capacity-free MoE dispatch: assignments grouped by expert.

Shared core of the dropless expert-compute paths (reference
``moe_layer.py:45`` reaches the same dataflow with layout_transform +
AllToAll but *drops* over-capacity tokens; these paths drop none).

:func:`expert_groups` is the one home of the sort / offset arithmetic:
the (token, expert) assignments to compute are sorted by expert and each
expert's group cut into row blocks of ``block``, so every block of rows
multiplies exactly ONE expert's weights.  Shapes are static (``G =
(T*k + E*(block-1)) // block`` blocks: every assignment kept), what is
live is data: the plan's ``n_blocks``.  The integer plumbing carries no
cotangent.  Three users:

* :func:`blocked_group_gemm` (plain XLA, differentiable): pads each
  group to whole blocks, gathers the blocks' weights (``w1[blk_e]``) and
  runs three einsums over ``G`` blocks, ~``k/E`` of the dense
  all-experts FLOPs.  Called by the generation engine's prefill
  (``models/generate.py::_moe_mlp``) and by the training MoE layer's
  ``dispatch_mode="dropless"`` (``nn/moe.py``).
* ``ops/moe_grouped.py::grouped_experts`` (the one kernel,
  ``moe_grouped_experts`` on the device trace; forward only): the
  serving step of a hybrid stack (``models/hybrid.py::moe_routed``).
  Its block -> expert table is scalar-prefetched and the weights' block
  index maps read it, so a hit expert's weights come out of the stacked
  arrays once and nothing gathers them.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


def capacity_tokens(num_tokens: int, num_experts: int, k: int,
                    capacity_factor: float) -> int:
    """Tokens-per-expert capacity the GShard routing math requires:
    ``k * ceil(T/E * cf)``.  Single source of truth shared by the gating
    impls (nn/moe.py) and the analyzer's ``moe-capacity-overprovision``
    rule — a dispatch tensor sized beyond this moves zero-padded bytes
    through the EP all-to-alls."""
    return int(k) * math.ceil(num_tokens / num_experts
                              * float(capacity_factor))


def pick_block_size(n_assign: int, num_experts: int) -> int:
    """Group-GEMM block: large enough to keep the MXU busy, small enough
    that per-expert padding (< E blocks of waste) stays a minor fraction
    of the T*k real assignments."""
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if n_assign >= num_experts * cand:
            return cand
    return 8


class ExpertGroups(NamedTuple):
    """:func:`expert_groups`' plan.  ``order [n]``: the flat assignments
    sorted by expert (kept ones first, token order inside a group);
    ``counts [E]``: kept assignments per expert; ``src_off [E]``: a
    group's start in the sorted order; ``blk_off [E]``: its first block;
    ``blk_e [G]``: a block's expert; ``n_blocks``: the live blocks, a
    scalar (the blocks past them hold nothing)."""
    order: jax.Array
    counts: jax.Array
    src_off: jax.Array
    blk_off: jax.Array
    blk_e: jax.Array
    n_blocks: jax.Array


def expert_groups(ids: jax.Array, num_experts: int, block: int,
                  keep: Optional[jax.Array] = None) -> ExpertGroups:
    """Group the assignments ``ids [T, k]`` (expert of each) by expert in
    row blocks of ``block``.  ``keep [T, k]`` (default: all) marks the
    assignments to compute; the others sort into a sentinel group behind
    every expert's and belong to no block."""
    E, B = num_experts, block
    e_flat = ids.reshape(-1).astype(jnp.int32)
    if keep is not None:
        e_flat = jnp.where(keep.reshape(-1), e_flat, E)
    n = e_flat.shape[0]
    # stable sort by expert keeps token order inside each group
    order = jnp.argsort(e_flat, stable=True).astype(jnp.int32)
    # counted and looked up by comparison against the E experts, not by
    # scatter-add / binary search / gather: a few wide fusions in place
    # of serial ones (PERF.md, PR 34: 0.18 -> 0.07 ms a layer)
    experts = jnp.arange(E, dtype=jnp.int32)
    counts = jnp.sum(e_flat[:, None] == experts, axis=0, dtype=jnp.int32)
    blocks = (counts + B - 1) // B
    blk_end = jnp.cumsum(blocks)
    n_blocks = blk_end[-1]
    G = max((n + E * (B - 1)) // B, 1)              # static upper bound
    # a block lies inside one expert's run of blocks: its expert is the
    # first e whose run ends past it (past the live blocks: the last)
    at = jnp.arange(G, dtype=jnp.int32)
    blk_e = jnp.minimum(jnp.sum(blk_end <= at[:, None], axis=1,
                                dtype=jnp.int32), E - 1)
    return ExpertGroups(order, counts, jnp.cumsum(counts) - counts,
                        blk_end - blocks, blk_e, n_blocks)


def take_small(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a short ``table [E]``, as a compare-and-sum
    over ``[len(idx), E]`` (one fusion; a gather is serial on the TPU)."""
    hit = idx[:, None] == jnp.arange(table.shape[0], dtype=idx.dtype)
    return jnp.sum(jnp.where(hit, table, 0), axis=1, dtype=table.dtype)


def blocked_group_gemm(xt: jax.Array, topi: jax.Array, topv: jax.Array,
                       w1: jax.Array, b1: jax.Array,
                       w2: jax.Array, b2: jax.Array,
                       act: Callable[[jax.Array], jax.Array],
                       block: Optional[int] = None) -> jax.Array:
    """Dropless top-k expert FFN.

    xt: [T, d] tokens; topi/topv: [T, k] expert ids / fp32 gate weights;
    w1: [E, d, f], b1: [E, 1, f], w2: [E, f, d], b2: [E, 1, d].
    Returns the combined output [T, d] in fp32.
    """
    T, d = xt.shape
    E = w1.shape[0]
    k = topi.shape[-1]
    n = T * k
    B = block or pick_block_size(n, E)
    g = expert_groups(topi, E, B)
    e_sorted = topi.reshape(-1)[g.order]
    t_sorted = (g.order // k).astype(jnp.int32)
    w_sorted = topv.reshape(-1).astype(jnp.float32)[g.order]
    # a sorted assignment's row: its group's block-aligned start plus its
    # place in the group (the stable sort keeps token order there)
    pos_in_e = jnp.arange(n, dtype=jnp.int32) - g.src_off[e_sorted]
    dst = (g.blk_off[e_sorted] * B + pos_in_e).astype(jnp.int32)
    blk_e = g.blk_e
    G = blk_e.shape[0]
    n_pad = G * B
    slot_tok = jnp.full((n_pad,), -1, jnp.int32).at[dst].set(t_sorted)
    slot_w = jnp.zeros((n_pad,), jnp.float32).at[dst].set(w_sorted)
    live = slot_tok >= 0
    xg = jnp.where(live[:, None], xt[jnp.clip(slot_tok, 0)], 0.0)
    xg = xg.reshape(G, B, d)
    h = act(jnp.einsum("gbd,gdf->gbf", xg, w1[blk_e]) + b1[blk_e])
    y = jnp.einsum("gbf,gfd->gbd", h, w2[blk_e]) + b2[blk_e]
    y = y.reshape(n_pad, d).astype(jnp.float32) * slot_w[:, None]
    return jnp.zeros((T, d), jnp.float32).at[jnp.clip(slot_tok, 0)].add(
        jnp.where(live[:, None], y, 0.0))
