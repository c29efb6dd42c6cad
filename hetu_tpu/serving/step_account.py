"""What a serving step READ: the engine's account of one unified step.

The engine packs a step, calls it, fetches its tokens and commits them;
this module says what the step read, as a pure function of what the step
packed (``rows``, the buffer's ``fields``) and fetched (``out``): nothing
here decides what the engine does next.  It alone knows the kernels'
blocking rules on the host, and it is where a new mixer kind adds a
counter: a name below (every engine registers them all: one schema for
the cluster's merge) and a part that writes it, chosen once from the
configuration.  One layer's reads throughout.
"""
from __future__ import annotations

from functools import cached_property
from typing import Any, Dict

import numpy as np

from ..ops.index_score import INDEX_SELECT_BLOCK, index_score_blocking
from ..ops.moe_grouped import ROW_BLOCK, block_rows
from ..ops.ragged_paged_attention import (kv_call_blocking,
                                          latent_pages_per_grid_step)
from .decode import _regions

COUNTERS = (
    # recurrent state: slots the decode recurrence walked (live decode
    # rows) / held
    "ssm_slots_walked", "ssm_slots_store",
    # mamba1 / gdn: live tokens the chunk form walked / the tokens its chunk
    # slots hold (what a form padded to the slot would walk)
    "ssm_chunk_tokens_walked", "ssm_chunk_tokens_padded",
    # experts: live (token, expert) assignments on the held experts / over
    # all experts; rows the grouped kernel computed
    "moe_assignments_local", "moe_assignments_total", "moe_block_rows",
    # latent (mla): pages attended, counted per row / once where rows share
    # a physical page; grid steps of the latent calls
    "latent_pages_attended", "latent_pages_attended_distinct",
    "latent_grid_steps",
    # full plain K/V layers of a pattern stack, K's (V's are the same
    # again): (page, kv head) pairs / the blocks the call fetches them in
    "kv_page_heads", "kv_page_blocks",
    # indexed (dsa) and window (swa): pairs the indexer scored; positions
    # the attention then read; index-key pages the scoring calls walked (a
    # chunk's once a query block) / their grid steps that ran; blocks of
    # the selection and the sparse read that hold a live query of a chunk
    # row, and so run / that the row's padded slot holds; pages in use in
    # the window / the full space
    "index_pairs_scored", "index_positions_selected",
    "index_key_pages_scored", "index_grid_steps",
    "index_chunk_blocks_live", "index_chunk_blocks_padded",
    "window_pages_held", "full_pages_held",
    # self-drafting: rows one token from emitting / those with a draft
    "decode_rows", "spec_rows",
    # block-wise generation: block forwards computed (a pass of a block
    # each; a fused row is two) / those that were commit passes / the
    # commits that rode in one row with the next block's first pass;
    # positions the denoise passes unmasked;
    # blocks committed (the engine's commit, equal to the commit passes);
    # positions computed in block rows / those masked going in; K/V rows
    # written by denoise passes (overwritten, never read by a later step)
    "block_row_passes", "block_commit_passes", "block_commits_fused",
    "block_tokens_unmasked",
    "blocks_committed", "block_positions", "block_positions_masked",
    "kv_tokens_provisional")
GAUGES = (
    # state slots held; the last step's busiest held expert over the mean
    "state_slots_in_use", "moe_expert_load_peak")


def _capped(st: "_Rows", cap: int) -> int:
    """Sum of ``min(p, cap)`` over every query's positions seen ``p``."""
    total = 0
    for lo, hi in zip(st.pos, st.ctx) if cap else ():
        mid = min(max(lo, cap), hi)
        total += (lo + 1 + mid) * (mid - lo) // 2 + cap * (hi - mid)
    return total


def _pairs(qs, his) -> int:
    """(query, key) pairs inside the causal mask, summed over the rows: a
    row's ``q`` queries, the last at position ``hi``."""
    return sum(q * hi - q * (q - 1) // 2 for q, hi in zip(qs, his))


def _block_pairs(st: "_Rows", b: int) -> int:
    """(query, key) pairs inside the block-wise mask: the query at ``p``
    sees the row's keys up to the end of its own block of ``b``."""
    total = 0
    for lo, hi in zip(st.pos, st.ctx):
        for start in range(lo // b * b, hi, b):
            total += (min(start + b, hi) - max(start, lo)) * \
                min(start + b, hi)
    return total


def _expert_load(load, prefix: str):
    """Rows the grouped kernel computed for ``load [expert layers, held]``
    (a group padded to whole blocks), and the span's attributes."""
    rows = int(block_rows(load))
    return rows, {prefix + "local": int(load.sum()),
                  prefix + "experts_hit": int((load > 0).sum()),
                  prefix + "blocks": rows // ROW_BLOCK}


class _Rows:
    """A step's live rows, read once; what walks the page tables is
    computed when a part asks, and once."""

    def __init__(self, rows, page_tables, page_size: int):
        self.reqs, self.q, self.row = zip(*rows)
        self.pos = [r.pos for r in self.reqs]
        self.ctx = [p + q for p, q in zip(self.pos, self.q)]
        self.pages = [-(-c // page_size) for c in self.ctx]
        self.tables = page_tables

    @cached_property
    def pairs(self) -> int:
        return _pairs(self.q, self.ctx)

    @cached_property
    def distinct_pages(self) -> int:
        """Physical pages under the rows' contexts, a shared one once."""
        return len(np.unique(np.concatenate(
            [self.tables[row, :n] for row, n in zip(self.row, self.pages)])))


class StepAccount:
    """Built once per engine; called after every step, it increments the
    counters and returns a traced step's ``unified_step`` attributes."""

    def __init__(self, cfg, layout, pool, state_store, scheduler,
                 spec_k: int, counters, gauges):
        self.cfg, self.pool, self.state_store = cfg, pool, state_store
        self.counters, self.gauges = counters, gauges
        self.max_batch, self.chunk = scheduler.max_batch, scheduler.chunk
        # one row's recurrent state, ONE layer's, as the store lays it
        self.state_row_bytes = state_store.ssm[0].nbytes // \
            state_store.num_slots if state_store is not None else 0
        self.vbase = scheduler.max_batch + scheduler.prefill_rows
        geo = cfg.mixer_geometry or {}
        self.index_topk = geo["dsa"].index_topk if "dsa" in geo else 0
        self.window = cfg.window_tokens
        self.block, self.mask_id = cfg.diffusion_block, cfg.mask_token_id
        max_pages = layout.fields["page_tables"][1][1]
        regions = _regions(scheduler.max_batch, scheduler.prefill_rows,
                           scheduler.chunk, spec_k, self.block)

        def by_slot(rule):
            # by row slot, each region's own: what the kernel wrapper calls
            table = [1] * layout.n_rows
            for _, row, _, n, width in regions:
                table[row: row + n] = [int(rule(width, n))] * n
            return table

        # kv heads a block of a full plain K/V layer's ragged call holds
        full = next((a for a, i in enumerate(cfg.paged_layers)
                     if cfg.is_hybrid and cfg.stack_pattern[i] == "attention"
                     and not cfg.window_of(i)), None)
        self.kv_block_heads = self.latent_group = None
        self.index_blocks = self.index_group = self.select_blocks = None
        if full is not None:
            pages = pool.k_pages[full]
            self.kv_block_heads = by_slot(lambda width, n: kv_call_blocking(
                width, n * width, cfg.num_heads, pages.dtype, pages,
                max_pages)[-1])
        if cfg.layers_of("mla"):    # pages a grid step of the call covers
            self.latent_group = by_slot(
                lambda width, n: latent_pages_per_grid_step(
                    width, cfg.num_heads, sum(cfg.latent_page_dims),
                    max_pages, (pool.k_pages[0], pool.v_pages[0])))
        if cfg.layers_of("dsa"):
            # a row's query blocks in its region's scoring call, and the
            # page-table slots a grid step of that call walks
            def walk(width, n):
                return index_score_blocking(
                    width if width > 1 else n, geo["dsa"].index_heads,
                    max_pages, width > 1, pool.v_pages[0])

            self.index_blocks = by_slot(
                lambda width, n: -(-width // walk(width, n)[0]))
            self.index_group = by_slot(lambda width, n: walk(width, n)[1])
            # blocks of the selection and the read a chunk slot holds (0:
            # a decode row, whose region is one block whatever is live)
            self.select_blocks = by_slot(
                lambda width, n: -(-width // INDEX_SELECT_BLOCK)
                if width > 1 else 0)
        self.parts = tuple(part for on, part in (
            (cfg.state_mixer, self._state),
            (cfg.state_mixer in ("mamba1", "gdn"), self._scan),
            (cfg.layers_of("moe"), self._moe),
            (full is not None, self._kv),
            (cfg.layers_of("mla"), self._latent),
            (cfg.page_layers is not None, self._index),
            ("draft" in layout.outs, self._self_draft),
            (self.block, self._block)) if on)

    def __call__(self, rows, fields, out, traced: bool) -> Dict[str, Any]:
        if not self.parts:
            return {}
        st = _Rows(rows, fields["page_tables"], self.pool.page_size)
        attrs: Dict[str, Any] = {}
        for part in self.parts:
            attrs.update(part(st, out, traced) or {})
        return attrs if traced else {}

    def _state(self, st, out, traced):
        store = self.state_store
        self.gauges["state_slots_in_use"].set(store.in_use)
        self.counters["ssm_slots_walked"].inc(
            sum(row < self.max_batch for row in st.row))
        self.counters["ssm_slots_store"].inc(store.num_slots)

    def _scan(self, st, out, traced):
        """The walks of a recurrence that carries a chunk row's state in
        its slot (one mamba1 or gdn layer's): the live tokens of the chunk
        rows, their slots' width, the decode rows, the bytes of state the
        walked rows move (in and out, once); and what the attention layers
        beside it read."""
        chunk = [q for q, row in zip(st.q, st.row)
                 if self.max_batch <= row < self.vbase]
        walked, padded = sum(chunk), len(chunk) * self.chunk
        self.counters["ssm_chunk_tokens_walked"].inc(walked)
        self.counters["ssm_chunk_tokens_padded"].inc(padded)
        if not traced:
            return None
        return dict(ssm_chunk_tokens=walked, ssm_chunk_padded=padded,
                    ssm_chunk_rows=len(chunk),
                    ssm_decode_rows=len(st.q) - len(chunk),
                    ssm_state_bytes=2 * len(st.q) * self.state_row_bytes,
                    attn_pairs=st.pairs,
                    kv_pages_distinct=st.distinct_pages)

    def _moe(self, st, out, traced):
        load = out["moe_load"]
        rows, attrs = _expert_load(load, "moe_")
        mean = load.mean()
        peak = attrs["moe_load_peak"] = \
            float(load.max() / mean) if mean else 0.0
        self.gauges["moe_expert_load_peak"].set(peak)
        self.counters["moe_assignments_local"].inc(attrs["moe_local"])
        self.counters["moe_assignments_total"].inc(
            sum(st.q) * self.cfg.moe_top_k * load.shape[0])
        self.counters["moe_block_rows"].inc(rows)
        return attrs

    def _kv(self, st, out, traced):
        """A row's pages times ``kv_heads``, over the heads a block of its
        region's call holds (``ragged_paged_attention.kv_heads_per_block``)."""
        kvh, heads = self.cfg.kv_heads, self.kv_block_heads
        self.counters["kv_page_heads"].inc(sum(st.pages) * kvh)
        self.counters["kv_page_blocks"].inc(sum(
            n * (kvh // heads[row]) for n, row in zip(st.pages, st.row)))

    def _latent(self, st, out, traced):
        """``latent_grid_steps``: a row's pages over the group its region's
        call walks a step, rounded up."""
        pages, distinct = sum(st.pages), st.distinct_pages
        steps = sum(-(-n // self.latent_group[row])
                    for n, row in zip(st.pages, st.row))
        self.counters["latent_pages_attended"].inc(pages)
        self.counters["latent_pages_attended_distinct"].inc(distinct)
        self.counters["latent_grid_steps"].inc(steps)
        return dict(
            latent_ctx_tokens=sum(st.ctx), latent_pages=pages,
            latent_pages_distinct=distinct, latent_grid_steps=steps,
            attn_pairs=st.pairs)

    def _index(self, st, out, traced):
        """``index_selected``: positions the attention reads, ``index_topk``
        a query or all it has.  ``index_key_pages`` / ``index_grid_steps``:
        a row's pages once for each query block of its region's scoring
        call (``ops/index_score.py``), and the grid steps that walk them:
        each block's pages over the group, rounded up.
        ``index_chunk_blocks_padded`` / ``_live``: the blocks of the
        selection and the sparse read a live chunk row's slot holds, and
        those up to its live tokens, the ones that run
        (``hy.indexed_attention``).
        ``index_selected_floor``: DISTINCT positions the host can prove
        (rows whose page tables start with one page share a document and
        may select the same positions, so a group counts its largest
        selection once).  ``window_tokens_distinct``: token slots
        of the distinct window pages (rows that resumed at one boundary
        share its tail)."""
        topk, pool = self.index_topk, self.pool
        selected = _capped(st, topk)
        self.counters["index_pairs_scored"].inc(st.pairs)
        self.counters["index_positions_selected"].inc(selected)
        key_pages = steps = live = padded = 0
        for n, q, row in zip(st.pages, st.q, st.row) if topk else ():
            blocks = self.index_blocks[row]
            key_pages += blocks * n
            steps += blocks * -(-n // self.index_group[row])
            held = self.select_blocks[row]
            if held:
                live += -(-q // INDEX_SELECT_BLOCK)
                padded += held
        self.counters["index_key_pages_scored"].inc(key_pages)
        self.counters["index_grid_steps"].inc(steps)
        if padded:
            self.counters["index_chunk_blocks_live"].inc(live)
            self.counters["index_chunk_blocks_padded"].inc(padded)
        if pool.window is not None:
            self.counters["window_pages_held"].inc(pool.window.in_use)
        self.counters["full_pages_held"].inc(
            pool.num_usable - pool.free_pages)
        if not traced:
            return None
        groups: Dict[int, int] = {}
        for row, c in zip(st.row, st.ctx) if topk else ():
            first = int(st.tables[row, 0])
            groups[first] = max(groups.get(first, 0), min(c, topk))
        return dict(
            index_pairs=st.pairs, index_selected=selected,
            index_key_pages=key_pages, index_grid_steps=steps,
            index_chunk_blocks_live=live, index_chunk_blocks_padded=padded,
            index_selected_floor=sum(groups.values()),
            index_pages_distinct=st.distinct_pages if topk else 0,
            window_pages=sum(len(r.win_pages) for r in st.reqs),
            window_tokens_distinct=pool.page_size * len(
                {pg for r in st.reqs for pg in r.win_pages}),
            window_pairs=_capped(st, self.window))

    def _block(self, st, out, traced):
        """Block rows are the rows in the narrow slots, and the counters
        count BLOCK FORWARDS: B positions of one block through every
        layer.  A plain row is one — a commit pass where its block went in
        with no mask, else a denoise pass, whose K/V rows are provisional.
        A FUSED row (two blocks wide) is two: the commit pass of the block
        it fed clean and the first denoise pass of the next, all masks; no
        forward is dropped, so the passes still sum to what the blocks
        take by the rule.  ``block_commits_fused`` says how often a commit
        rode that way.  ``attn_pairs``: pairs inside the block-wise mask,
        every row of the step (the prompt's chunks run under it too)."""
        b = self.block
        rows = [(r, q, row) for r, q, row in zip(st.reqs, st.q, st.row)
                if row >= self.vbase]
        fused = sum(q > b for _, q, _ in rows)
        passes = len(rows) + fused
        masked = [sum(t == self.mask_id for t in r.block) + q - b
                  for r, q, _ in rows]
        commits = fused + sum(m == 0 for m in masked)
        flags = out["block_flags"]
        unmasked = sum(bin(int(flags[row - self.vbase])).count("1")
                       for (_, _, row), m in zip(rows, masked) if m)
        c = self.counters
        c["block_row_passes"].inc(passes)
        c["block_commit_passes"].inc(commits)
        c["block_commits_fused"].inc(fused)
        c["block_tokens_unmasked"].inc(unmasked)
        c["block_positions"].inc(b * passes)
        c["block_positions_masked"].inc(sum(masked))
        c["kv_tokens_provisional"].inc(b * (passes - commits))
        if not traced:
            return None
        return dict(block_rows=passes, block_commit_rows=commits,
                    block_fused_rows=fused,
                    block_unmasked=unmasked, block_masked=sum(masked),
                    attn_pairs=_block_pairs(st, b),
                    kv_pages_distinct=st.distinct_pages)

    def _self_draft(self, st, out, traced):
        """``decode_rows``: rows one token from emitting whose request has
        emitted before (what a draft is for; not the last row of a preempted
        request's re-prefill, which no step can have drafted for).
        ``mtp_tokens`` / ``mtp_attn_pairs``: positions the module's layer
        keeps, a verify row's up to its last accepted one, and their pairs.
        ``window_keys``: positions inside the windows of each row's queries
        (what a window layer reads by the token, not by the page)."""
        decode = [(r, row) for r, row in zip(st.reqs, st.row)
                  if len(r.tokens) - r.pos == 1 and r.n_generated
                  and not r.resuming]
        verify = {row for r, row in decode
                  if row >= self.vbase and r.spec_drafts}
        self.counters["decode_rows"].inc(len(decode))
        self.counters["spec_rows"].inc(len(verify))
        if not traced:
            return None
        accepted = out["accepted"]
        kept = [1 + int(accepted[row]) if row in verify else q
                for q, row in zip(st.q, st.row)]
        return dict(
            verify_rows=len(verify),
            spec_accepted=int(sum(accepted[row] for row in verify)),
            **_expert_load(out["mtp_load"], "mtp_moe_")[1],
            attn_pairs=st.pairs, mtp_tokens=sum(kept),
            mtp_attn_pairs=_pairs(
                kept, [p + k for p, k in zip(st.pos, kept)]),
            kv_pages_distinct=st.distinct_pages,
            window_keys=sum(min(c, self.window + q - 1)
                            for q, c in zip(st.q, st.ctx)))
