"""The serving step's KV write as page-runs of live tokens (ISSUE 29).

``ops/paged_kv_write.py`` against the plain scatter it replaces
(``paged_kv_write_reference``, the ``.at[].set`` of every slot of the
token axis): every page but the trash page bit-equal, the trash page
untouched.  Interpret mode; ``test_chip_bringup.py`` holds the Mosaic
lowering and compile of the same call at the benchmark's widths.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from hetu_tpu.ops.paged_kv_write import (kv_write_plan, paged_kv_write,
                                         paged_kv_write_reference,
                                         write_tile)

PS = 64
# the serving layout in small: 4 decode slots, one 256-token chunk slot,
# 4 verify slots of spec_k + 1 = 3 tokens
REGIONS = ((0, 4, 1), (4, 1, 256), (5, 4, 3))
# per row: (tokens this step, position of the first).  Decode rows on an
# odd offset (the packed bf16 row), on a page's last row and on its
# first; an idle decode slot; a chunk that starts and ends mid-page and
# spans 5 pages; verify rows inside a tile, across a tile boundary and
# across a page boundary; an idle verify slot
MIXED = [(1, 5), (0, 0), (1, 63), (1, 64), (256, 1024 + 37),
         (3, 70), (0, 0), (3, 78), (2, 127)]
# short chunk inside one page, from an odd offset to mid-tile
SHORT = [(0, 0), (1, 17), (0, 0), (0, 0), (20, 33),
         (0, 0), (0, 0), (0, 0), (3, 0)]
IDLE = [(0, 0)] * 9               # no live token at all
LAYOUTS = {"mixed": MIXED, "short": SHORT, "idle": IDLE}


def _write_plan(layout, num_pages, rng):
    """The host's plan (``Engine._pack_arrays``): trash page and offset
    0 for padding, a request's own pages for its tokens."""
    rows = sum(n for _, n, _ in REGIONS)
    cu, t = np.zeros(rows + 1, np.int32), 0
    for first, n, width in REGIONS:
        for j in range(n):
            cu[first + j] = t
            t += width
    cu[rows] = t
    q_lens = np.asarray([q for q, _ in layout], np.int32)
    token_page = np.zeros(t, np.int32)
    token_off = np.zeros(t, np.int32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    for r, (q, pos0) in enumerate(layout):
        pages = {}
        for k in range(q):
            pos = pos0 + k
            if pos // PS not in pages:
                pages[pos // PS] = free.pop()
            token_page[cu[r] + k] = pages[pos // PS]
            token_off[cu[r] + k] = pos % PS
    return tuple(jnp.asarray(x) for x in (token_page, token_off, q_lens,
                                          cu))


def _random(rng, shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-120, 120, shape), dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype)


# pools of one call: (heads, row width, dtype) each
FULL_HEAD = lambda kvh, dt: ((kvh, 128, dt),) * 2          # noqa: E731
POOLS = {
    **{f"kvh{kvh}-{jnp.dtype(dt).name}": FULL_HEAD(kvh, dt)
       for kvh in (1, 12) for dt in (jnp.bfloat16, jnp.float32, jnp.int8)},
    # rows that do not fill the lanes are written a page at a time
    "gqa-hd32": ((2, 32, jnp.bfloat16),) * 2,
    "latent-rope": ((1, 256, jnp.bfloat16), (1, 32, jnp.bfloat16)),
    "latent-int8-sidecar": ((1, 256, jnp.int8), (1, 1, jnp.float32)),
    "latent-alone": ((1, 256, jnp.bfloat16),),
}
CASES = [(p, "mixed") for p in POOLS] + [
    ("kvh12-bfloat16", "short"), ("kvh12-bfloat16", "idle"),
    ("kvh1-float32", "short"), ("latent-int8-sidecar", "short"),
    ("gqa-hd32", "idle")]


@pytest.mark.parametrize("pools,layout", CASES)
def test_page_run_write_matches_the_scatter(pools, layout):
    rng = np.random.default_rng(7)
    num_pages = 24
    plan_in = _write_plan(LAYOUTS[layout], num_pages, rng)
    t = plan_in[0].shape[0]
    old = tuple(_random(rng, (num_pages, h, PS, w), dt)
                for h, w, dt in POOLS[pools])
    new = tuple(_random(rng, (t, h, w), dt) for h, w, dt in POOLS[pools])
    tile = write_tile(old)
    dtypes = {jnp.dtype(dt) for _, _, dt in POOLS[pools]}
    if all(w % 128 == 0 for _, w, _ in POOLS[pools]):
        assert tile == max(32 // d.itemsize for d in dtypes)
    else:
        assert tile == PS
    plan = kv_write_plan(*plan_in, regions=REGIONS, page_size=PS,
                         tile=tile)
    want = paged_kv_write_reference(old, new, *plan_in[:2])
    got = paged_kv_write(old, new, plan, tile=tile, interpret=True)
    live = int(np.sum([q for q, _ in LAYOUTS[layout]]))
    assert (int(plan[0][0]) > 0) == (live > 0)
    for g, w_, o in zip(got, want, old):
        assert g.dtype == o.dtype and g.shape == o.shape
        g, w_, o = (np.asarray(x.astype(jnp.float32)) for x in (g, w_, o))
        np.testing.assert_array_equal(g[1:], w_[1:])
        np.testing.assert_array_equal(g[0], o[0])    # trash: not written
        if live:
            assert not np.array_equal(g, o)


def test_plan_counts_tiles_of_page_runs():
    """The plan of the mixed layout at bf16's 16-row tile, by hand: a
    decode token is one piece; the 256-token chunk from offset 37 of a
    page touches 17 tiles (5 page-runs: 27 + 64 + 64 + 64 + 37 tokens,
    the first and the last tile partial); a verify row inside a tile
    is one piece, one across a tile or page boundary two."""
    rng = np.random.default_rng(0)
    plan_in = _write_plan(MIXED, 24, rng)
    n, page, row, lo, hi, base, shift = (
        np.asarray(x) for x in kv_write_plan(
            *plan_in, regions=REGIONS, page_size=PS, tile=16))
    assert n[0] == 3 + 17 + 1 + 2 + 2
    live = slice(0, int(n[0]))
    assert int((hi - lo)[live].sum()) == sum(q for q, _ in MIXED)
    assert (page[live] > 0).all() and (row[live] < PS // 16).all()
    # the first decode token: offset 5 is row 5 of tile 0
    assert (row[0], lo[0], hi[0]) == (0, 5, 6)
    # the chunk's first tile: rows 5..15 of tile 2 of its first page
    assert (row[3], lo[3], hi[3]) == (2, 5, 16)
    # pieces past the live ones repeat the last (same block, no DMA)
    for x in (page, row, base):
        assert (x[int(n[0]):] == x[int(n[0]) - 1]).all()
    assert len({(p, r) for p, r in zip(page[live], row[live])}) == n[0]
