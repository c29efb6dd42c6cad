"""The indexer's scoring call (``hetu_tpu/ops/index_score.py``): the Pallas
kernel, interpreted on the CPU, against the oracle ``hy.index_scores`` on
keys the test gathers itself; the blocking rule at the cell's shapes; and
a tiny ``dots3`` engine serving the same tokens through either path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_dots3_serving as d3
from hetu_tpu.models import hybrid as hy
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
