"""Continuous-batching scheduler: admission, token-budget packing,
page budget, preemption.

Every engine step the scheduler (1) admits arrived requests while the
page budget and sequence-slot budget allow, (2) guarantees every
running request a page for its next KV write (preempting the
latest-arrived request — recompute-style eviction — when the pool runs
dry), and (3) **packs** the step's ragged token batch for the single
unified executable (DESIGN.md §12):

- every request one token from emitting (``remaining == 1`` — a decode,
  or the 1-token tail of a chunked prefill: the degenerate case) takes a
  single-token slot.  There are ``max_batch`` of them and at most
  ``max_batch`` live requests, so **every decode advances every step**
  — a long prompt arrival can never stall running decodes;
- remaining budget goes to prefill chunks: the earliest-arrived
  requests still mid-prompt each get one ``chunk`` slot
  (``prefill_rows`` of them per step), Sarathi-style.  A prompt longer
  than ``chunk`` prefills over several steps, interleaved with decodes
  in the SAME executable call.

There are no shape buckets and no per-request prefill executables: the
packed batch always has the same ``max_batch + prefill_rows * chunk``
token shape, so the engine compiles exactly one program no matter the
traffic mix.
"""
from __future__ import annotations

from typing import List, Tuple

from .kv_pool import PagedKVPool
from .request import RUNNING, WAITING, Request, RequestQueue


class Scheduler:
    def __init__(self, pool: PagedKVPool, max_batch: int = 8,
                 chunk: int = 64, prefill_rows: int = 1,
                 prefix_cache=None):
        if prefill_rows < 1:
            raise ValueError(f"prefill_rows must be >= 1, got "
                             f"{prefill_rows}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.pool = pool
        self.max_batch = int(max_batch)
        self.chunk = int(chunk)
        self.prefill_rows = int(prefill_rows)
        # speculative mode (set by the engine): verify_slots dedicated
        # spec_width-wide rows after the chunk slots — one per
        # decode-capable request, so verify bursts NEVER compete with
        # prompt prefills for chunk slots
        self.verify_slots = 0
        self.spec_width = 0
        # a block-wise model (set by the engine from
        # ``cfg.diffusion_block`` / ``cfg.mask_token_id``): the narrow slots
        # are BLOCK slots, two blocks wide, one per sequence slot, and a
        # generating request rides one every step
        self.block = 0
        self.mask_id = None
        # optional serving.prefix_cache.PrefixCache: admission charges
        # only the UNCACHED suffix against the page budget (and counts
        # refcount-0 cached pages as reclaimable), preemption releases
        # shared pages instead of freeing them
        self.cache = prefix_cache

    @property
    def token_budget(self) -> int:
        """Tokens one packed step can carry (the executable's T)."""
        return self.max_batch + self.prefill_rows * self.chunk \
            + self.verify_slots * self.spec_width \
            + self.max_batch * 2 * self.block

    # -- admission -----------------------------------------------------------

    def admit(self, queue: RequestQueue, running: List[Request],
              now: float) -> List[Request]:
        """Pop arrived requests while a sequence slot AND the pages for
        prompt+first-token fit.  FRESH requests stop at the first that
        doesn't fit (FIFO — no small-request overtaking, keeps TTFT
        fair); a PAGE-HOLDING request (disaggregated-handoff adoption:
        pages already attached while WAITING) may overtake a blocked
        head.  That overtake is the deadlock breaker, not a fairness
        leak: a page-holder behind a blocked head means nothing is
        running and nothing will free pages — admitting the holder lets
        it finish and return exactly the pages the head is waiting for.

        With a prefix cache, a candidate is charged only its UNCACHED
        suffix: matched pages come for free, and refcount-0 cached pages
        count as reclaimable budget (the pool's reclaim hook evicts them
        on demand at ``_start``) — except the matched ones themselves,
        which this admission is about to pin."""
        admitted: List[Request] = []
        deferred: List[Request] = []
        if len(running) >= self.max_batch:
            # no row to give: the budget below walks the whole cache
            # index, every step of a full batch over a long backlog
            return admitted
        # free pages + LRU-reclaimable cached pages not yet claimed
        budget = self.pool.free_pages
        if self.cache is not None:
            budget += self.cache.evictable_pages
        pinned = set()
        while len(running) + len(admitted) < self.max_batch:
            req = queue.pop_ready(now)
            if req is None:
                break
            if deferred and not req.pages:
                # fresh-FIFO behind a block: only page-holders may
                # still admit, so skip the match/pin work entirely —
                # under a deep backlog this keeps the scan O(ready),
                # not O(ready x prompt pages)
                deferred.append(req)
                continue
            # an adopted request brings its own pages — charge only
            # what it still lacks.  Cache matching mirrors _start's
            # lookup condition exactly (fresh pos-0 requests only):
            # charging a cached page the start path won't attach would
            # wedge admission the same way ignoring owned pages did
            need = self.pool.pages_for(len(req.tokens) + max(1, self.block)) \
                - len(req.pages)
            new_pins = []
            if self.cache is not None and req.pos == 0 and not req.pages:
                for e in self.cache.match(req.tokens):
                    need -= 1          # cached page: nothing to allocate
                    if e.refs == 0 and e.eid not in pinned:
                        budget -= 1    # ...but it is no longer evictable
                        pinned.add(e.eid)
                        new_pins.append(e.eid)
            need = max(0, need)
            if need > budget:
                # blocked: the scan continues only so page-holders
                # further back can still admit.  The pins THIS
                # candidate took are rolled back — a deferred request
                # must not shrink the budget later page-holders see, or
                # the overtake stops working exactly when nothing is
                # running to free pages
                for eid in new_pins:
                    pinned.discard(eid)
                    budget += 1
                deferred.append(req)
                continue
            budget -= need
            admitted.append(req)
        for req in deferred:
            queue.push(req)            # heap order restores FIFO
        return admitted

    # -- token-budget packing ------------------------------------------------

    def pack(self, running: List[Request]
             ) -> List[Tuple[Request, int, int]]:
        """Assign the step's rows: ``[(request, q_len, row_index)]``.

        Single-token rows (``remaining == 1``) fill slots
        ``[0, max_batch)``; mid-prompt requests fill chunk slots
        ``[max_batch, max_batch + prefill_rows)`` in class-then-arrival
        order (interactive prefills ride before batch ones) with
        ``q_len = min(remaining, chunk)`` — EXACTLY as without spec
        mode: prefill chunks are TTFT-critical and speculation never
        touches them.  In spec mode each decode-ready request with
        staged draft proposals instead takes a DEDICATED verify slot
        (``[max_batch + prefill_rows, max_batch + prefill_rows +
        verify_slots)``, width ``spec_width``) with ``q_len = 1 +
        len(spec_drafts)`` — there is one verify slot per sequence
        slot, so a staged burst always rides and the no-decode-stall
        guarantee is untouched (an unstaged or shed request still gets
        its decode slot).  Requests beyond the chunk slots simply wait
        — they are still RUNNING and keep their pages, they just don't
        ride this step."""
        live = sorted((r for r in running if r.state == RUNNING),
                      key=lambda r: (r.rank, r.arrival_time, r.req_id))
        if self.block:
            return self._pack_blocks(live)
        rows: List[Tuple[Request, int, int]] = []
        verified = set()
        vrow = 0
        vbase = self.max_batch + self.prefill_rows
        for r in live:
            remaining = len(r.tokens) - r.pos
            staged = len(r.spec_drafts)
            if remaining == 1 and staged and vrow < self.verify_slots \
                    and 1 + staged <= self.spec_width:
                rows.append((r, 1 + staged, vbase + vrow))
                vrow += 1
                verified.add(r.req_id)
        slot = 0
        for r in live:
            remaining = len(r.tokens) - r.pos
            if remaining == 1 and r.req_id not in verified \
                    and slot < self.max_batch:
                rows.append((r, 1, slot))
                slot += 1
        chunk_row = 0
        for r in live:
            remaining = len(r.tokens) - r.pos
            if remaining > 1 and chunk_row < self.prefill_rows:
                rows.append((r, min(remaining, self.chunk),
                             self.max_batch + chunk_row))
                chunk_row += 1
        return rows

    def _pack_blocks(self, live: List[Request]
                     ) -> List[Tuple[Request, int, int]]:
        """The rows of a block-wise model's step.  A request whose
        prompt's WHOLE blocks are not all prefilled yet takes a chunk slot
        (``q_len`` up to the last whole block, at most ``chunk``, which is
        a multiple of the block length: a chunk ends where a block ends);
        every other request is GENERATING and rides a block slot every
        step.  Its row is its open block, ``q_len`` = the block length — a
        denoise pass or the block's commit pass, the engine says which —
        or, FUSED, ``q_len`` = two blocks: the block it commits and the
        next one's first denoise pass in one row (the same two forwards
        under the block-wise mask: a committed block's positions do not
        see the next block).  A row is fused where its open block holds no
        mask, the request goes on after this commit (``_goes_on``) and the
        pages under ``pos + 2B`` are at hand (``ensure_decode_pages`` asked
        for them); else it is the plain row.  No row is ever one token
        wide: the decode slots stay idle."""
        b = self.block
        rows: List[Tuple[Request, int, int]] = []
        vbase = self.max_batch + self.prefill_rows
        brow = chunk_row = 0
        for r in live:
            whole = len(r.tokens) // b * b - r.pos
            if whole <= 0:
                fused = self._goes_on(r) and \
                    self.pool.pages_for(r.pos + 2 * b) <= len(r.pages)
                rows.append((r, 2 * b if fused else b, vbase + brow))
                brow += 1
            elif chunk_row < self.prefill_rows:
                rows.append((r, min(whole, self.chunk),
                             self.max_batch + chunk_row))
                chunk_row += 1
        return rows

    def _goes_on(self, req: Request) -> bool:
        """The request's open block holds no mask — its next row is the
        block's commit — and the request opens another block behind it:
        decided from what the host holds before the step.  The commit
        emits the block's tokens that the request did not bring; where
        they reach ``max_new_tokens`` or hold an end-of-sequence id the
        request honours, it ends there."""
        x = req.block
        if x is None or self.mask_id in x:
            return False
        emits = x[len(req.tokens) - req.pos:]
        return req.n_generated + len(emits) < req.max_new_tokens and \
            req.eos_token_id not in emits

    def slot_mix(self, rows: List[Tuple[Request, int, int]]
                 ) -> dict:
        """The step's packing decision as a flat dict — the trace
        plane ends the step's ``engine_step`` span with it, so a
        Perfetto timeline shows exactly how each executable call's
        token budget was split between decode slots and prefill
        chunks."""
        vbase = self.max_batch + self.prefill_rows
        n_decode = sum(1 for _, _, row in rows if row < self.max_batch)
        n_verify = sum(1 for _, _, row in rows if row >= vbase)
        n_block = n_verify if self.block else 0
        n_verify -= n_block
        return {"decode_slots": n_decode,
                "chunk_slots": len(rows) - n_decode - n_verify - n_block,
                "verify_slots": n_verify,
                "block_slots": n_block,
                "spec_tokens": int(sum(len(r.spec_drafts)
                                       for r, _, row in rows
                                       if row >= vbase)),
                "tokens": int(sum(q for _, q, _ in rows)),
                "token_budget": self.token_budget,
                "chunk": self.chunk,
                "prefill_rows": self.prefill_rows}

    # -- decode page budget --------------------------------------------------

    def ensure_decode_pages(self, running: List[Request]
                            ) -> Tuple[List[Request], List[Request]]:
        """Give every running request the pages its next KV writes
        need, evicting lowest-class latest-arrived requests on
        exhaustion.  Returns
        (kept, evicted); evicted requests are already reset to WAITING
        with their pages freed.  Mid-prefill requests were granted their
        whole prompt's pages at admission, so only emitted-token growth
        allocates here — one page per decode step, or up to
        ``ceil((1 + staged drafts) / page_size)`` for a speculative
        verify row (its burst writes ``pos .. pos + spec_len``, which
        may cross a page boundary), or the pages under a block-wise
        model's open block (a block ahead of the committed K/V; the block
        behind it too where the row would be fused, but only from what is
        free).  A page
        squeeze sheds the
        requester's staged drafts FIRST — degrading a burst to a plain
        decode is free, while preempting any request costs its whole
        prefill — and only then falls back to eviction."""
        evicted: List[Request] = []
        kept = sorted(running,
                      key=lambda r: (r.rank, r.arrival_time, r.req_id))
        for req in list(kept):
            if req in evicted:
                continue
            while True:
                # (a block-wise model's pass writes its whole open block)
                need_tokens = req.pos + max(1, self.block) \
                    + len(req.spec_drafts)
                have = len(req.pages) * self.pool.page_size
                if have >= need_tokens:
                    if self._slide_window(req):
                        break          # current pages still have room
                    got = None
                else:
                    got = self.pool.alloc(self.pool.pages_for(need_tokens)
                                          - len(req.pages))
                if got is not None:
                    req.pages.extend(got)
                    req.peak_pages = max(req.peak_pages, len(req.pages))
                    continue
                if req.spec_drafts:
                    req.spec_drafts = []   # shed the burst, keep running
                    continue
                # lowest class first, then latest arrival: a batch
                # straggler is always evicted before any interactive
                # request loses its prefill.  The requester ITSELF is a
                # candidate — a batch request squeezing for a decode
                # page must self-preempt rather than take a page from a
                # higher class (that would be an SLO-class inversion)
                victims = [r for r in kept if r not in evicted]
                victim = max(victims,
                             key=lambda r: (r.rank, r.arrival_time,
                                            r.req_id))
                self.preempt(victim)
                evicted.append(victim)
                if victim is req:
                    break
        kept = [r for r in kept if r not in evicted]
        for req in kept if self.block else ():
            # a commit that would ride with the next block's first pass
            # asks for that block's page too, once every row has what it
            # must have: nobody is preempted for it, and without it the
            # row is the plain commit
            short = self.pool.pages_for(req.pos + 2 * self.block) \
                - len(req.pages)
            got = self.pool.alloc(short) \
                if short > 0 and self._goes_on(req) else None
            if got:
                req.pages.extend(got)
                req.peak_pages = max(req.peak_pages, len(req.pages))
        return kept, evicted

    def _slide_window(self, req: Request) -> bool:
        """The window layers' pages of ``req`` for its next step: let go
        of the pages behind the tail of its last page boundary (every
        coming query's window starts in or after that tail, and the tail
        is what makes the prefix resumable when the request finishes),
        take the pages up to the step's last write (at most a chunk
        ahead; a verify row writes its staged drafts too).  The window
        slides by the tokens COMMITTED — ``pos``, which a burst moves by
        more than one — never by a draft.  False when the window space
        cannot give them (the caller evicts)."""
        ps, pages = self.pool.page_size, self.pool.window
        if pages is None:
            return True
        lo = max(0, req.pos // ps - pages.tail_pages)
        ahead = min(len(req.tokens) + len(req.spec_drafts) - req.pos,
                    self.chunk)
        hi = self.pool.pages_for(req.pos + max(ahead, 1))
        drop = min(max(0, lo - req.win_first), len(req.win_pages))
        if drop:
            pages.release(req.win_pages[:drop])
            del req.win_pages[:drop]
        if not req.win_pages:
            req.win_first = lo
        else:
            req.win_first += drop
        got = pages.alloc(hi - req.win_first - len(req.win_pages))
        if got is None:
            return False
        req.win_pages.extend(got)
        return True

    def preempt(self, req: Request) -> None:
        """Recompute-style eviction: drop KV state, keep the token
        history — re-prefilling ``req.tokens`` (chunked like any other
        prompt) reproduces the sequence exactly (asserted at
        temperature 0 in tests).  Shared prefix-cache pages are
        RELEASED (refcount drop), never freed — other requests and the
        cache index still hold them; only exclusively-owned pages return
        to the free list."""
        self.pool.free(req.pages[req.shared_pages:])
        if self.cache is not None and req.shared_pages:
            self.cache.release(req)
        if req.state_slot is not None:
            # the recurrent state goes with the K/V: the resumed request
            # re-prefills into whatever slot it is given then
            self.pool.state_slots.free(req.state_slot)
            req.state_slot = None
        if req.win_pages:
            self.pool.window.release(req.win_pages)
            req.win_pages, req.win_first = [], 0
        req.pages = []
        req.shared_pages = 0
        req.cached_tokens = 0
        req.spec_drafts = []
        req.block, req.block_pass = None, 0
        req.pos = 0
        req.resuming = True
        req.state = WAITING
        req.n_preemptions += 1
