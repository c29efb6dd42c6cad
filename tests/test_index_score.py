"""The indexer's scoring call (``hetu_tpu/ops/index_score.py``): the Pallas
kernel, interpreted on the CPU, against the oracle ``hy.index_scores`` on
keys the test gathers itself; the blocking rule at the cell's shapes; and
a tiny ``dots3`` engine serving the same tokens through either path."""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_dots3_serving as d3
from hetu_tpu.models import hybrid as hy
from hetu_tpu.models.gpt import LatentGeometry
from hetu_tpu.ops import index_score as ix

HEADS, DIM, PS, PAGES = 4, 16, 8, 40
TOPK = 12


def _inputs(n, maxp, shared, seed=0, dtype=jnp.float32, whole=False):
    """Queries, head weights, a pool of key pages and page tables with
    the pool's pages in a shuffled order (page 0 the trash page, never
    under a context).  ``whole``: small whole numbers, so that every
    product and sum is exact in float32 and equal scores are EXACT ties."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    if whole:
        draw = lambda key, shape, hi: jax.random.randint(  # noqa: E731
            key, shape, -hi, hi + 1).astype(dtype)
        iq, iw = draw(k[0], (n, HEADS, DIM), 2), draw(k[1], (n, HEADS), 2)
        pages = draw(k[2], (PAGES, 1, PS, DIM), 1)
    else:
        iq = jax.random.normal(k[0], (n, HEADS, DIM), dtype)
        iw = jax.random.normal(k[1], (n, HEADS), jnp.float32)
        pages = jax.random.normal(k[2], (PAGES, 1, PS, DIM), dtype)
    order = lambda key: jax.random.permutation(  # noqa: E731
        key, PAGES - 1)[:maxp] + 1
    table = order(k[3]) if shared else jnp.stack(
        [order(kk) for kk in jax.random.split(k[3], n)])
    return iq, iw.astype(jnp.float32), pages, table


def _oracle(iq, iw, pages, table, ctx):
    """``hy.index_scores`` over the contexts' keys, 0 past each context."""
    keys = pages[table].reshape(table.shape[:-1] + (-1, DIM))
    s = hy.index_scores(iq, keys, iw)
    return jnp.where(jnp.arange(s.shape[-1]) < jnp.reshape(ctx, (-1, 1)),
                     s, 0.0)


# name: queries, table slots, one shared table?, contexts, the wrapper's
# blocking overrides (None: the rule's)
CASES = {
    # a chunk: 16 queries of one context that ends mid-page
    "chunk_ends_mid_page": (16, 12, True, 77, {}),
    # two query blocks, the last partly padding, groups of 4 slots
    "chunk_two_query_blocks": (13, 12, True, 96, dict(
        query_block=8, pages_per_step=4)),
    # a context shorter than one group: every later group is skipped
    "chunk_shorter_than_a_group": (16, 12, True, 5, dict(pages_per_step=4)),
    # a table the group does not divide, its trailing slots unused (trash)
    "chunk_trailing_slots_unused": (8, 13, True, 41, dict(pages_per_step=4)),
    # decode rows: a table and a context a query, one of one position
    "decode_rows": (3, 12, False, [77, 1, 96], {}),
    "decode_rows_groups_of_2": (5, 12, False, [77, 9, 50, 16, 17],
                                dict(pages_per_step=2)),
    "decode_rows_shorter_than_a_group": (2, 12, False, [3, 30],
                                         dict(pages_per_step=8)),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_scores_what_the_oracle_scores(name, dtype):
    n, maxp, shared, ctx, kw = CASES[name]
    iq, iw, pages, table = _inputs(n, maxp, shared, dtype=jnp.dtype(dtype))
    if "trailing" in name:
        table = table.at[-(-ctx // PS):].set(0)
    ctx = jnp.asarray(ctx, jnp.int32)
    got = ix.index_score_pages_pallas(iq, iw, pages, table, ctx,
                                      interpret=True, **kw)
    want = _oracle(iq, iw, pages, table, ctx)
    assert got.shape == (n, maxp * PS) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    past = np.arange(maxp * PS) >= np.reshape(np.asarray(ctx), (-1, 1))
    assert not np.asarray(got)[np.broadcast_to(past, got.shape)].any()
    # the dispatching entry point: the XLA arithmetic off the chip, the
    # kernel where asked
    xla = ix.index_score_pages(iq, iw, pages, table, ctx)
    np.testing.assert_allclose(xla, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        ix.index_score_pages(iq, iw, pages, table, ctx, use_kernel=True),
        ix.index_score_pages_pallas(iq, iw, pages, table, ctx,
                                    interpret=True))


@pytest.mark.parametrize("name", ["chunk_ends_mid_page",
                                  "chunk_two_query_blocks", "decode_rows",
                                  "decode_rows_groups_of_2"])
def test_exact_ties_select_the_set_the_oracle_selects(name):
    """Whole-number queries, weights and keys (three values a key lane:
    many keys are equal, and many scores tie exactly): the kernel's scores
    ARE the oracle's, and ``index_select`` picks the same positions."""
    n, maxp, shared, ctx, kw = CASES[name]
    iq, iw, pages, table = _inputs(n, maxp, shared, seed=3, whole=True)
    pages = pages.at[:, :, 1::2].set(pages[:, :, 0::2])   # equal neighbours
    ctx = jnp.asarray(ctx, jnp.int32)
    got = ix.index_score_pages_pallas(iq, iw, pages, table, ctx,
                                      interpret=True, **kw)
    want = _oracle(iq, iw, pages, table, ctx)
    np.testing.assert_array_equal(got, want)
    qpos = (ctx - 1 - jnp.arange(n)[::-1]) if shared else ctx - 1
    qpos = jnp.maximum(qpos, 0)
    mine, valid = hy.index_select(got, qpos, TOPK)
    theirs, valid_t = hy.index_select(want, qpos, TOPK)
    np.testing.assert_array_equal(mine, theirs)
    np.testing.assert_array_equal(valid, valid_t)
    # the ties are there: a query's k-th score stands more than once
    row = np.sort(np.asarray(want[0])[:int(qpos[0]) + 1])[::-1]
    assert (row == row[min(TOPK, len(row)) - 1]).sum() > 1


@pytest.mark.parametrize("n,shared,want", [(256, True, (64, 16)),
                                           (32, False, (1, 32)),
                                           (16, True, (16, 8)),
                                           (5, True, (8, 8))])
def test_the_blocking_rule_at_the_cells_shapes(n, shared, want):
    """A 256-token chunk over 528 slots of 64: 64 queries x 64 heads a
    block, whose two float32 tiles leave room for 16 slots a grid step;
    decode rows a block each, the widest group.  Fewer queries than a
    block: whole sublanes; fewer slots than a group: the table."""
    cell = n > 16
    keys = jax.ShapeDtypeStruct((16645, 1, 64, 128) if cell
                                else (PAGES, 1, PS, DIM), jnp.bfloat16)
    assert ix.index_score_blocking(n, 64 if cell else HEADS,
                                   528 if cell else 12, shared,
                                   keys) == want


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_engine_serves_the_same_tokens_through_the_kernel(dtype):
    """A tiny ``dots3`` engine, prompts below, at and above ``index_topk``,
    the long one prefilled in chunks: the greedy tokens with the scoring
    call interpreted (``use_kernel=True``) are those of the XLA path."""
    _, cfg, state = d3.build(dtype=dtype,
                             std=0.05 if dtype == "bfloat16" else 0.2)
    outs = []
    for use_kernel in (False, True):
        eng = d3.engine(state, cfg, use_kernel=use_kernel)
        hs = [eng.add_request(p, 6) for p in d3.prompts([5, 12, 40])]
        eng.run()
        outs.append([h.out_tokens for h in hs])
        m = eng.metrics_summary()
        assert m["index_grid_steps"] > 0
        assert m["index_key_pages_scored"] >= m["index_grid_steps"]
    assert outs[0] == outs[1] and all(len(o) == 6 for o in outs[0])


# -- a part-filled chunk: the selection and the read follow the live queries --

LIVES = (1, 32, 33, 200, 255, 256)
CHUNK, CTX, LATENT, WIDTH, NH = 256, 300, 32, 48, 4


def _parent_by_blocks(f, arrays, block: int):
    """``by_blocks`` as it stood before it took ``live`` (commit 5ab4529),
    line for line: what ``live=None`` still has to lower to."""
    n = arrays[0].shape[0]
    if n <= block:
        return f(arrays)
    pad = -n % block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)  # noqa: E731
                            ).reshape((-1, block) + a.shape[1:])
    out = jax.lax.map(f, tuple(cut(a) for a in arrays))
    return out.reshape((-1,) + out.shape[2:])[:n]


def _live_blocks(live: int) -> int:
    """Rows of the blocks that hold one of the first ``live`` rows."""
    return -(-live // ix.INDEX_SELECT_BLOCK) * ix.INDEX_SELECT_BLOCK


def _chunk_qpos(live: int):
    """A chunk slot as the engine packs it: the first ``live`` queries hold
    the context's last tokens, the rest position 0."""
    i = np.arange(CHUNK)
    return jnp.asarray(np.where(i < live, CTX - live + i, 0), jnp.int32)


@functools.lru_cache(maxsize=None)
def _chunk_selection():
    """The selection of a 256-query chunk over a context of 300 (38 pages
    of 8, top-12) through ``by_blocks``, jitted once with the live count
    traced (``bounded`` False: the call without one, today's)."""
    iq, iw, pages, table = _inputs(CHUNK, 38, True, seed=5)
    scores = ix.index_score_pages(iq, iw, pages, table,
                                  jnp.asarray(CTX, jnp.int32))

    def select(args):
        pos, valid = hy.index_select(args[0], args[1], TOPK)
        return jnp.where(valid, pos, -1)

    return jax.jit(lambda qpos, live, bounded: ix.by_blocks(
        select, (scores, qpos), ix.INDEX_SELECT_BLOCK,
        live if bounded else None), static_argnames="bounded")


@pytest.mark.parametrize("live", LIVES)
def test_by_blocks_runs_the_blocks_that_hold_a_live_row(live):
    """256 rows in blocks of 32: with ``live`` the blocks up to row ``live
    - 1`` give what the call without it gives (the exact top-k's positions
    here), the blocks behind them zeros."""
    run, qpos = _chunk_selection(), _chunk_qpos(live)
    got = np.asarray(run(qpos, jnp.asarray(live, jnp.int32), bounded=True))
    want = np.asarray(run(qpos, jnp.asarray(live, jnp.int32), bounded=False))
    up = _live_blocks(live)
    assert got.shape == want.shape == (CHUNK, TOPK)
    assert (want[:live] >= 0).all()        # a live query selects all TOPK
    np.testing.assert_array_equal(got[:up], want[:up])
    assert not got[up:].any()


@functools.lru_cache(maxsize=None)
def _chunk_region():
    """One 256-query chunk region of a dsa layer at tiny widths, jitted
    once with the live count traced."""
    geo = LatentGeometry(heads=NH, q_rank=24, latent=LATENT, nope=16, rope=8,
                         v=16, theta=8e7, scale=24 ** -0.5,
                         index_heads=HEADS, index_dim=DIM, index_rope=8,
                         index_topk=TOPK)
    iq, iw, pages, table = _inputs(CHUNK, 38, True, seed=5)
    k = jax.random.split(jax.random.PRNGKey(11), 2)
    pool = jax.random.normal(k[0], (PAGES, 1, PS, WIDTH), jnp.float32)
    q_cat = jax.random.normal(k[1], (CHUNK, NH, WIDTH), jnp.float32)

    def run(qpos, live, bounded: bool):
        return hy.indexed_attention(
            geo, iq, iw, q_cat, qpos, table, (pool, pages),
            use_kernel=False, live=live if bounded else None)

    return jax.jit(run, static_argnames="bounded")


@pytest.mark.parametrize("live", LIVES)
def test_a_chunks_region_reads_for_its_live_blocks_alone(live):
    """``indexed_attention`` over a shared table: the live blocks' outputs
    are the call's without a count bit for bit, the blocks behind them
    zeros (where that call attends over padding)."""
    run, qpos = _chunk_region(), _chunk_qpos(live)
    got = np.asarray(run(qpos, jnp.asarray(live, jnp.int32), bounded=True))
    want = np.asarray(run(qpos, jnp.asarray(live, jnp.int32), bounded=False))
    up = _live_blocks(live)
    assert got.shape == (CHUNK, NH, LATENT) and np.abs(want[:live]).min() > 0
    np.testing.assert_array_equal(got[:up], want[:up])
    assert not got[up:].any() and (up == CHUNK or want[up:].any())


def test_the_chunk_regions_loop_takes_its_trip_count_from_the_live_count():
    """With a count the loop over the region's blocks is a ``while`` whose
    bound is traced, in place of the ``lax.map`` over all eight (a ``scan``
    of length 8: the one left is the XLA scoring arithmetic's, which takes
    no count); without one it is the program it was."""
    run = _chunk_region()
    args = (_chunk_qpos(40), jnp.asarray(40, jnp.int32))
    eight = rf"length={CHUNK // ix.INDEX_SELECT_BLOCK}\b"

    def loops(bounded):
        text = str(jax.make_jaxpr(
            lambda *a: run(*a, bounded=bounded))(*args))
        return text.count("while["), len(re.findall(eight, text))

    assert loops(True) == (1, 1) and loops(False) == (0, 2)
    assert "stablehlo.while" in run.lower(*args, bounded=True).as_text()


def test_by_blocks_without_a_live_count_lowers_to_the_parents_text():
    """``by_blocks(f, arrays, block)`` — the decode rows' region, the
    benchmark's check ``hy._index_positions`` — is the program it was, and
    rows that are no whole blocks come out at their own length."""
    a = jnp.arange(70 * 3, dtype=jnp.float32).reshape(70, 3)
    b = jnp.arange(70, dtype=jnp.int32)
    f = lambda x: x[0] * 2.0 + x[1][:, None]                # noqa: E731
    text = lambda g: jax.jit(                                # noqa: E731
        lambda a, b: g(f, (a, b), 32)).lower(a, b).as_text()
    assert text(ix.by_blocks) == text(_parent_by_blocks)
    bounded = jax.jit(lambda a, b, n: ix.by_blocks(f, (a, b), 32, n))
    want = np.asarray(ix.by_blocks(f, (a, b), 32))
    for live, up in ((1, 32), (32, 32), (33, 64), (65, 70), (70, 70)):
        got = np.asarray(bounded(a, b, live))
        np.testing.assert_array_equal(got[:up], want[:up])
        assert got.shape == want.shape and not got[up:].any()
