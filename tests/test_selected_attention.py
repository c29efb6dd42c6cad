"""The attention over a dsa layer's selected rows
(``hetu_tpu/ops/selected_attention.py``): the Pallas call, interpreted on
the CPU, against ``hy.selected_attention`` on the rows the test gathers
itself; the blocking rule at the cell's shapes; the counted choice of
path; the page look-up as a product against the gather it replaced; and
``hy.indexed_attention`` through either path over a chunk's shared table
and over decode rows' tables."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import obs
from hetu_tpu.models import hybrid as hy
from hetu_tpu.models.gpt import LatentGeometry
from hetu_tpu.ops import selected_attention as sa
from hetu_tpu.ops.paged_attention import vmem_bytes, vmem_params

# the cell's [32, 128, 640] x [32, 2048, 640] at d_c 512, cut to test size
N, NH, W, K, D_C, SCALE = 8, 8, 256, 256, 128, 192 ** -0.5


def _inputs(n=N, k=K, dtype=jnp.bfloat16, seed=0):
    """An absorbed query (float32, as the step hands it over) and a
    query's own ``k`` gathered rows in the pool's ``dtype``."""
    a, b = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(a, (n, NH, W), jnp.float32),
            jax.random.normal(b, (n, k, W), jnp.float32).astype(dtype))


def _valid(counts, k=K):
    return jnp.arange(k)[None, :] < jnp.asarray(counts)[:, None]


# name: valid positions a query, rows gathered a query, the wrapper's
# blocking override (None: the rule's)
CASES = {
    "every_row_valid": ([K] * N, K, None),
    # a context shorter than the selection: the gather's tail is padding
    "rows_short_of_k": ([K, 1, 17, 128, 129, 255, 200, 64], K, None),
    # a dead query of a live block: the reference's uniform read, finite
    "a_row_with_none": ([K, 0, 5, 0, K, 0, 0, 9], K, None),
    # a table shorter than index_topk: k is what the context holds, no
    # multiple of the lanes
    "k_smaller_than_topk": ([24, 3, 0, 24, 11, 24, 1, 24], 24, None),
    "one_query_a_grid_step": ([K, 0, 5, 77, K, 1, 2, 9], K, 1),
    "the_whole_block_a_grid_step": ([K, 0, 5, 77, K, 1, 2, 9], K, 8),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_attends_what_the_reference_attends(name, dtype):
    counts, k, q_blk = CASES[name]
    q_cat, sel = _inputs(k=k, dtype=jnp.dtype(dtype))
    valid = _valid(counts, k)
    want = hy.selected_attention(q_cat, sel, D_C, valid, SCALE)
    got = sa.selected_attention_pallas(
        q_cat, sel, valid, d_c=D_C, scale=SCALE, interpret=True,
        query_block=q_blk)
    assert got.shape == (N, NH, D_C) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    # the same products in the same dtype, the same float32 softmax
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    dead = [i for i, c in enumerate(counts) if c == 0]
    if dead:
        # no valid position: every gathered row weighs the same
        mean = sel[jnp.asarray(dead)].astype(jnp.float32)[..., :D_C].mean(1)
        np.testing.assert_allclose(
            got[jnp.asarray(dead)], np.broadcast_to(
                mean[:, None], (len(dead), NH, D_C)), rtol=2e-2, atol=2e-2)


def test_a_masked_row_weighs_nothing():
    """What stands in the rows behind a query's valid ones does not reach
    its output: the same result over other padding."""
    q_cat, sel = _inputs()
    valid = _valid([100] * N)
    other = jnp.where(valid[:, :, None], sel, sel[:, ::-1] * 3)
    run = lambda s: sa.selected_attention_pallas(          # noqa: E731
        q_cat, s, valid, d_c=D_C, scale=SCALE, interpret=True)
    np.testing.assert_array_equal(run(sel), run(other))


@pytest.mark.parametrize("n,k,want", [(32, 2048, 1), (32, 256, 8),
                                      (6, 256, 2), (5, 256, 1),
                                      (32, 512, 4)])
def test_the_blocking_rule_at_the_cells_shapes(n, k, want):
    """128 heads over rows of 640 bf16 lanes to 512 latent lanes.  The
    cell's block of 32 queries at 2,048 rows each: ONE query a grid step,
    2.6 MB of rows twice — two would pass Mosaic's default scoped VMEM and
    the call asks for none of its own.  Shorter selections: the most
    queries, a power of two that divides the count, that stay under it."""
    cell = (128, k, 640, 512, jnp.bfloat16)
    assert sa.selected_attention_blocking(n, *cell) == want
    assert n % want == 0
    assert vmem_bytes(*sa._step_vmem(want, *cell)) <= sa.SELECTED_VMEM
    assert vmem_params(*sa._step_vmem(want, *cell)) is None
    if want < min(n & -n, sa.SELECTED_QUERY_MAX):
        assert vmem_bytes(*sa._step_vmem(2 * want, *cell)) > sa.SELECTED_VMEM


@pytest.mark.parametrize("use_kernel,path", [(True, "kernel"),
                                             (False, "xla")])
def test_the_choice_of_path_is_counted_where_it_is_traced(use_kernel, path):
    q_cat, sel = _inputs(n=2, k=16)
    valid = _valid([16, 3], 16)
    obs.reset_counts()
    got = sa.attend_selected(q_cat, sel, D_C, valid, SCALE,
                             xla=hy.selected_attention,
                             use_kernel=use_kernel)
    assert obs.counts("selected_attention_calls") == {(("path", path),): 1}
    np.testing.assert_allclose(
        got, hy.selected_attention(q_cat, sel, D_C, valid, SCALE),
        rtol=1e-5, atol=1e-6)
    # off the TPU the platform's own choice is the XLA arithmetic
    obs.reset_counts()
    sa.attend_selected(q_cat, sel, D_C, valid, SCALE,
                       xla=hy.selected_attention)
    assert obs.counts("selected_attention_calls") == {(("path", "xla"),): 1}


@pytest.mark.parametrize("shared", [True, False])
@pytest.mark.parametrize("top", [16_645, 2 ** 31 // 64 - 1])
def test_the_slots_are_the_page_tables_gather_exactly(shared, top):
    """``selected_slots`` against the ``take_along_axis`` it replaced: one
    shared table or one a query, page ids up to the cell's pool and up to
    the last page whose rows an int32 can number (all four bytes of an id
    in use), positions on a page's first and last row."""
    n, k, maxp, ps = 5, 40, 33, 64
    rng = np.random.default_rng(top)
    table = rng.integers(0, top + 1, size=(1 if shared else n, maxp))
    table[:, :2] = top, 0
    pos = np.sort(rng.integers(0, maxp * ps, size=(n, k)))
    pos[:, :3], pos[:, -1] = (0, ps - 1, ps), maxp * ps - 1
    got = jax.jit(sa.selected_slots, static_argnums=2)(
        jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32), ps)
    want = np.take_along_axis(np.broadcast_to(table, (n, maxp)), pos // ps,
                              axis=1) * ps + pos % ps
    assert got.dtype == jnp.int32 and want.max() < 2 ** 31
    np.testing.assert_array_equal(got, want)


# -- the layer: scores, top-k, gather, and the read through either path -----

HEADS, DIM, PS, PAGES, TOPK = 4, 16, 8, 40, 12
CTX, LATENT, WIDTH = 300, 32, 48
GEO = LatentGeometry(heads=NH, q_rank=24, latent=LATENT, nope=16, rope=8,
                     v=16, theta=8e7, scale=24 ** -0.5, index_heads=HEADS,
                     index_dim=DIM, index_rope=8, index_topk=TOPK)


def _layer(n, shared, dtype):
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    iq = jax.random.normal(k[0], (n, HEADS, DIM), dtype)
    iw = jax.random.normal(k[1], (n, HEADS), jnp.float32)
    keys = jax.random.normal(k[2], (PAGES, 1, PS, DIM), dtype)
    pool = jax.random.normal(k[3], (PAGES, 1, PS, WIDTH), dtype)
    q_cat = jax.random.normal(k[4], (n, NH, WIDTH), jnp.float32)
    order = lambda key: jax.random.permutation(             # noqa: E731
        key, PAGES - 1)[:38] + 1
    table = order(k[5]) if shared else jnp.stack(
        [order(kk) for kk in jax.random.split(k[5], n)])
    return iq, iw, q_cat, table, (pool, keys)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("live", [40, 64])
def test_a_chunks_region_reads_the_same_through_the_kernel(live, dtype):
    """A 64-query chunk slot over one shared table with a ``live`` count:
    the live blocks' outputs through the two Pallas calls (interpreted) are
    the XLA path's, the blocks behind them zeros on both."""
    n = 64
    iq, iw, q_cat, table, pools = _layer(n, True, jnp.dtype(dtype))
    i = np.arange(n)
    qpos = jnp.asarray(np.where(i < live, CTX - live + i, 0), jnp.int32)
    run = jax.jit(lambda use_kernel: hy.indexed_attention(
        GEO, iq, iw, q_cat, qpos, table, pools, use_kernel=use_kernel,
        live=jnp.asarray(live, jnp.int32)), static_argnums=0)
    got, want = np.asarray(run(True)), np.asarray(run(False))
    up = -(-live // 32) * 32
    assert np.abs(want[:live]).min() > 0 and not got[up:].any()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_decode_rows_read_the_same_through_the_kernel(dtype):
    """Decode rows, a table and a context each — one of them shorter than
    ``index_topk``, so some of its gathered rows are padding."""
    n = 5
    iq, iw, q_cat, tables, pools = _layer(n, False, jnp.dtype(dtype))
    qpos = jnp.asarray([CTX - 1, 7, 150, 11, 299], jnp.int32)
    run = lambda use_kernel: hy.indexed_attention(           # noqa: E731
        GEO, iq, iw, q_cat, qpos, tables, pools, use_kernel=use_kernel)
    np.testing.assert_allclose(run(True), run(False), rtol=1e-5, atol=1e-6)
