"""Copy-on-write prefix caching over the paged KV pool.

Under multi-user traffic most requests share a prefix — a system
prompt, a few-shot header, a conversation so far — and the engine used
to recompute that prefill for every arrival.  The paged pool already
stores KV page-granularly and the unified step already consumes an
arbitrary per-request page table, so cached pages can enter a new
request's table with ZERO kernel changes; this module adds the index
that makes the reuse safe.

**Chained page hashing** (vLLM/SGLang style).  A full page of KV at
page index ``i`` is determined by exactly ``tokens[0 : (i+1)*page_size]``
(causality: position ``j``'s K/V depends only on tokens ``<= j``).  The
index therefore keys each cached page by ``(parent_entry_id,
page_tokens)`` — the parent link chains the whole prefix into the key,
so equal keys imply equal full token prefixes (Python's tuple hash does
the chaining; the match is exact, never probabilistic).  Lookups walk
the chain page by page and stop at the first divergence: the longest
cached page-aligned prefix.

**Copy-on-write rules.**  Cached pages are READ-ONLY.  A request that
attaches a cached prefix starts its KV cursor (``pos``) at the cached
boundary, so its per-token KV write plan only ever targets freshly
allocated pages — the first partial or divergent page is always a new
allocation, never a shared one.  The pool tracks a refcount per cached
page (``1 +`` live sharers); the ``cow-page-write`` analysis rule
audits the engine's write-plan tap and fails CI if any live row writes
a cached page at all — refcount 1 (no sharers) is still read-only,
because the index serves the page to future lookups.

**Lookup cap.**  A request's match is capped at
``(len(tokens) - 1) // page_size`` pages: at least one token always
remains uncached, because the engine must still run the final prompt
position through the model to sample the first new token.  Caching is
page-aligned-only on purpose — a partial-page hit would need the tail
of the page recomputed into a *different* physical page, and stitching
two half-pages is exactly the kind of layout change that breaks the
bit-for-bit contract.  Full-page reuse reads identical page contents
through the identical kernel, so temperature-0 outputs are unchanged.

**Insertion** happens when a request FINISHES: every fully-written page
(``(i+1)*page_size <= pos``, generated tokens included — they extend
the token prefix like any other) moves from the request's ownership
into the index at refcount 0; pages whose content is already cached
are freed as duplicates; the partial tail page is freed.

**Eviction** is LRU over refcount-0 entries, leaves first.  Any
request sharing a child page also shares its parents, so
``refcount(parent) >= refcount(child)`` — a refcount-0 entry's whole
subtree is refcount-0 and leaf-first order can always reach it.  The
pool calls :meth:`evict` through its reclaim hook when the free list
runs dry, so cache reclamation happens BEFORE the scheduler falls back
to recompute preemption; a page is removed from the index before it
re-enters the free list, so the index never references a writable page.

**Window layers** (a pool with ``pool.window``).  A window layer keeps a
row's pages only where its window reaches, so a cached chain of full
pages is not enough to resume at its end: the request's first queries
read the ``window - 1`` positions BEFORE the boundary in every window
layer.  An entry can therefore carry a **tail**: the window-space pages
of those positions, one reference each (``WindowPages``), taken over from
the request that finished there.  ``match`` stops at the deepest entry
that has one; a request that resumes there shares the tail's pages
(``Request.win_pages``, a reference each).  Tails are what the window
space's reclaim lets go of, boundaries no request ever resumed from
first, then least recently used; an entry that loses its tail stays a
parent of deeper entries and stops being a place to resume.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .kv_pool import PagedKVPool

ROOT = -1                       # parent id of a first-page entry

#: seed of the content-chained digest hashes (the "hash of the empty
#: prefix") — any fixed 64-bit value works; sharing it between
#: :func:`chain_hash` producers and consumers is what matters
ROOT_HASH = 0x9E3779B97F4A7C15


def chain_hash(parent_hash: int, page_tokens: Sequence[int]) -> int:
    """Content-chained 64-bit page hash: ``H(parent_hash, tokens)``.

    The in-process index chains by ``(parent_eid, tokens)`` tuple keys —
    exact, but entry ids are private to one cache.  The CLUSTER router
    needs a prefix key that two *different* replicas compute
    identically from token content alone, so the exported digest chains
    by hash instead: equal chain hashes imply equal full token prefixes
    up to 64-bit collision odds (~2^-32 across millions of pages —
    fine for *placement*, which is a heuristic; correctness still rides
    the exact in-replica index at admission time)."""
    h = hashlib.blake2b(digest_size=8)
    h.update(int(parent_hash).to_bytes(8, "little", signed=False))
    h.update(np.asarray(list(page_tokens), np.int64).tobytes())
    return int.from_bytes(h.digest(), "little")


def token_chain_hashes(tokens: Sequence[int], page_size: int,
                       max_pages: Optional[int] = None,
                       layout: Sequence[int] = ()) -> List[int]:
    """The chain hashes of every FULL page prefix of ``tokens`` (at most
    ``max_pages``; default caps at ``(len - 1) // page_size`` exactly
    like :meth:`PrefixCache.match` — the final prompt token must always
    run).  ``result[i]`` keys the prefix ``tokens[:(i+1)*page_size]``;
    the router probes replica digests with these.

    ``layout`` salts the chain ROOT (``PagedKVPool.layout_tag``): the
    hashes stay a pure function of token content WITHIN a layout, but a
    latent-KV replica and a full-head replica (or two different page
    layouts generally) can never cross-match — their cached page BYTES
    are incompatible even when the token prefixes agree.  Empty layout
    keeps the raw unsalted chain."""
    ps = int(page_size)
    n = max(0, len(tokens) - 1) // ps
    if max_pages is not None:
        n = min(n, int(max_pages))
    out: List[int] = []
    h = chain_hash(ROOT_HASH, layout) if len(layout) else ROOT_HASH
    for i in range(n):
        h = chain_hash(h, tokens[i * ps:(i + 1) * ps])
        out.append(h)
    return out


@dataclass
class CacheEntry:
    """One cached read-only page: a node in the prefix tree."""
    eid: int                    # unique entry id (the chain link)
    parent: int                 # parent entry id, ROOT for page 0
    tokens: Tuple[int, ...]     # this page's token content
    page: int                   # physical page in the pool
    depth: int                  # page index within its prefix
    last_use: int = 0           # LRU clock (monotonic ticks)
    refs: int = 0               # live requests sharing this page
    children: int = 0           # child entries extending this prefix
    # window-space pages of the positions before this entry's END, oldest
    # first (None: not a place to resume); hits = requests resumed here
    tail: Optional[List[int]] = None
    hits: int = 0


class PrefixCache:
    """Refcounted index of read-only cached pages in a PagedKVPool."""

    def __init__(self, pool: PagedKVPool):
        self.pool = pool
        self.page_size = pool.page_size
        self._index: Dict[Tuple[int, Tuple[int, ...]], CacheEntry] = {}
        self._by_id: Dict[int, CacheEntry] = {}
        # req_id -> the entries it holds references on
        self._attached: Dict[int, List[CacheEntry]] = {}
        self._next_id = 0
        self._tick = 0
        # host-tier hook (serving/slo/host_tier.py): called with
        # (entry, chain_hash) just BEFORE an evicted page returns to
        # the free list — the page is still cached (read-only) at that
        # moment, so the hook can stage its bytes to host RAM.  Leaf-
        # first eviction guarantees the entry's parent chain is still
        # indexed when the hook runs, which is what makes the chain
        # hash computable at all.
        self.on_evict = None
        # window layers: the window space reclaims through ``drop_tails``
        if pool.window is not None:
            pool.window.set_reclaim(self.drop_tails)

    # -- introspection -------------------------------------------------------

    def __len__(self) -> int:
        return len(self._index)

    @property
    def evictable_pages(self) -> int:
        """Pages an eviction sweep could reclaim right now.  Exactly the
        refcount-0 entries: a refcount-0 entry's subtree is refcount-0
        too (sharers of a child share its parents), so leaf-first
        eviction reaches every one of them."""
        return sum(1 for e in self._index.values() if e.refs == 0)

    @property
    def version(self) -> Tuple[int, int]:
        """Cheap change stamp for digest memoization: ``_next_id``
        moves on every insertion and the index size on every eviction,
        so any mutation sequence changes the pair (a dedup'd re-insert
        creates no entry and correctly leaves the digest unchanged)."""
        return (self._next_id, len(self._index))

    def digest(self) -> Dict[int, int]:
        """Compact content-chained snapshot of the cached prefix tree:
        ``{chain_hash: depth + 1}`` — one 64-bit key per cached page,
        position-stamped so a router can read "this replica holds the
        first ``depth+1`` pages of any prompt whose page-``depth`` chain
        hash is ``chain_hash``".  Entries are computed parents-first
        (sorted by depth), so each hash extends its parent's in O(1);
        the whole export is O(cached pages) — tens to hundreds of
        entries, cheap enough to refresh per routing sync.

        The chain ROOT is salted with the pool's ``layout_tag``
        (matching ``token_chain_hashes(..., layout=pool.layout_tag)``):
        digests from replicas with different KV page layouts — latent
        vs full-head, different quantization, different head geometry —
        share no keys, so the router can never place a request on a
        replica whose cached page bytes it could not actually reuse."""
        hashes: Dict[int, int] = {}        # eid -> chain hash
        out: Dict[int, int] = {}
        root = chain_hash(ROOT_HASH, self.pool.layout_tag)
        for e in sorted(self._index.values(), key=lambda e: e.depth):
            parent_h = root if e.parent == ROOT \
                else hashes[e.parent]
            h = chain_hash(parent_h, e.tokens)
            hashes[e.eid] = h
            out[h] = e.depth + 1
        return out

    # -- lookup / attach -----------------------------------------------------

    def _max_match_pages(self, tokens: Sequence[int]) -> int:
        # at least one token must stay uncached: the engine still has to
        # run the last prompt position to sample the first new token
        return max(0, len(tokens) - 1) // self.page_size

    def match(self, tokens: Sequence[int]) -> List[CacheEntry]:
        """Longest chain of cached full pages covering ``tokens`` —
        NO side effects (admission accounting peeks with this)."""
        ps = self.page_size
        out: List[CacheEntry] = []
        parent = ROOT
        for i in range(self._max_match_pages(tokens)):
            e = self._index.get((parent, tuple(tokens[i * ps:(i + 1) * ps])))
            if e is None:
                break
            out.append(e)
            parent = e.eid
        if self.pool.window is not None:
            # resumable only where the window layers' tail is cached
            while out and out[-1].tail is None:
                out.pop()
        return out

    def acquire(self, req) -> List[CacheEntry]:
        """Attach the longest cached prefix to ``req``: refcount every
        matched page (they become unevictable) and touch the LRU clock.
        The caller points the request's page table at ``entry.page`` and
        starts ``pos`` at the cached boundary."""
        entries = self.match(req.tokens)
        if not entries:
            return entries
        self._tick += 1
        for e in entries:
            e.refs += 1
            e.last_use = self._tick
            self.pool.share_page(e.page)
        self._attached[req.req_id] = entries
        if entries[-1].tail is not None:
            # the request reads the boundary's window pages as they are
            # (full pages, never written again): a reference each
            tail = entries[-1].tail
            entries[-1].hits += 1
            self.pool.window.retain(tail)
            req.win_pages = list(tail)
            req.win_first = len(entries) - len(tail)
        return entries

    def release(self, req) -> int:
        """Drop ``req``'s shared references (preemption, admission
        rollback, or the tail of :meth:`on_finish`)."""
        entries = self._attached.pop(req.req_id, [])
        for e in entries:
            e.refs -= 1
            self.pool.unshare_page(e.page)
        return len(entries)

    # -- insertion (request finish) ------------------------------------------

    def on_finish(self, req) -> Tuple[int, int]:
        """Retire a finished request's pages through the cache: insert
        every fully-written owned page, free duplicates and the partial
        tail, release shared references.  Returns
        ``(pages_inserted, pages_freed)``."""
        ps = self.page_size
        shared = self._attached.get(req.req_id, [])
        # pages fully written by the request (pos = next write index)
        full = min(len(req.pages), req.pos // ps)
        parent = shared[-1].eid if shared else ROOT
        inserted = 0
        for i in range(len(shared), full):
            key = (parent, tuple(req.tokens[i * ps:(i + 1) * ps]))
            page = req.pages[i]
            have = self._index.get(key)
            if have is not None:
                # identical content already cached: ours is a duplicate
                self.pool.free([page])
                parent = have.eid
                continue
            self.pool.cache_page(page)
            self._tick += 1
            e = CacheEntry(eid=self._next_id, parent=parent,
                           tokens=key[1], page=page, depth=i,
                           last_use=self._tick)
            self._next_id += 1
            self._index[key] = e
            self._by_id[e.eid] = e
            if parent != ROOT:
                self._by_id[parent].children += 1
            parent = e.eid
            inserted += 1
        tail = req.pages[full:]
        if tail:
            self.pool.free(tail)
        if self.pool.window is not None:
            self._keep_tail(req, full, parent)
        self.release(req)
        freed = (full - len(shared) - inserted) + len(tail)
        req.pages = []
        req.shared_pages = 0
        return inserted, freed

    def _keep_tail(self, req, full: int, last: int) -> None:
        """The finishing request's window pages: those of the positions
        before its last full page's end become that entry's tail (the
        request's references pass to the entry), the rest are let go."""
        window = self.pool.window
        e = self._by_id.get(last) if full else None
        n = min(window.tail_pages, full)
        lo = full - n - req.win_first
        if e is not None and e.tail is None and e.depth == full - 1 \
                and 0 <= lo and lo + n <= len(req.win_pages):
            e.tail = req.win_pages[lo: lo + n]
            window.release(req.win_pages[:lo] + req.win_pages[lo + n:])
        else:
            window.release(req.win_pages)
        req.win_pages, req.win_first = [], 0

    def drop_tails(self, n: int) -> int:
        """Let go of cached boundaries' tails until ``n`` window pages
        came free or none is left: boundaries nothing resumed from first,
        then the least recently used.  Returns the pages freed."""
        window = self.pool.window
        before = window.free_pages
        for e in sorted((e for e in self._index.values()
                         if e.tail is not None),
                        key=lambda e: (e.hits > 0, e.last_use, e.eid)):
            if window.free_pages - before >= n:
                break
            window.release(e.tail)
            e.tail = None
        return window.free_pages - before

    # -- eviction ------------------------------------------------------------

    def evict(self, n: int) -> int:
        """Reclaim up to ``n`` pages: LRU refcount-0 leaves first (each
        removal may expose its parent as the next leaf).  O(entries) per
        page — pools are tens-to-hundreds of pages, and this only runs
        when the free list is already dry."""
        freed = 0
        while freed < n:
            cands = [e for e in self._index.values()
                     if e.refs == 0 and e.children == 0]
            if not cands:
                break
            victim = min(cands, key=lambda e: (e.last_use, e.eid))
            self._remove(victim)
            freed += 1
        return freed

    def chain_hash_of(self, e: CacheEntry) -> int:
        """The entry's layout-salted content chain hash — the same key
        :meth:`digest` exports and :func:`token_chain_hashes` computes
        router-side.  Walks the parent links (all still indexed while
        ``e`` is), so it is usable right up to the moment of
        eviction."""
        chain: List[Tuple[int, ...]] = []
        cur: Optional[CacheEntry] = e
        while cur is not None:
            chain.append(cur.tokens)
            cur = self._by_id.get(cur.parent) if cur.parent != ROOT \
                else None
        h = chain_hash(ROOT_HASH, self.pool.layout_tag)
        for tokens in reversed(chain):
            h = chain_hash(h, tokens)
        return h

    def _remove(self, e: CacheEntry) -> None:
        if self.on_evict is not None:
            # stage BEFORE the index/page bookkeeping: the page is
            # still read-only cached and the parent chain still hashes
            self.on_evict(e, self.chain_hash_of(e))
        if e.tail is not None:
            self.pool.window.release(e.tail)
            e.tail = None
        del self._index[(e.parent, e.tokens)]
        del self._by_id[e.eid]
        if e.parent != ROOT:
            self._by_id[e.parent].children -= 1
        self.pool.uncache_page(e.page)

    # -- host-tier restore ---------------------------------------------------

    def restore(self, parent: int, tokens: Sequence[int], page: int,
                depth: int) -> CacheEntry:
        """Re-insert a page refetched from the host tier: ``page`` is
        freshly allocated and already holds the injected bytes; it
        becomes a refcount-0 cached entry under ``parent`` exactly as
        if :meth:`on_finish` had inserted it.  The caller guarantees
        the key is absent (it probed :meth:`match` first)."""
        key = (parent, tuple(tokens))
        if key in self._index:
            raise ValueError(f"restore of already-cached page at "
                             f"depth {depth}")
        self.pool.cache_page(page)
        self._tick += 1
        e = CacheEntry(eid=self._next_id, parent=parent, tokens=key[1],
                       page=page, depth=depth, last_use=self._tick)
        self._next_id += 1
        self._index[key] = e
        self._by_id[e.eid] = e
        if parent != ROOT:
            self._by_id[parent].children += 1
        return e

    def clear(self) -> None:
        """Evict everything evictable (attached entries survive — live
        requests still read their pages)."""
        self.evict(len(self._index))

    # -- invariants ----------------------------------------------------------

    def check_invariants(self, force: bool = False) -> None:
        """Cache-side bookkeeping invariants (the pool partition has its
        own in ``PagedKVPool.check_invariants``).  Opt-in like the
        pool's: runs only under ``pool.debug`` or ``force``."""
        if not (self.pool.debug or force):
            return
        # one implementation: the protocol verifier's snapshot predicate
        # (analysis/protocol.py) owns the invariant logic; this wrapper
        # keeps the debug/force gating and assert-style reporting every
        # existing call site relies on (imported lazily — the analysis
        # package must stay optional for serving)
        from ..analysis.protocol import cache_index_problems
        problems = cache_index_problems(self, self.pool)
        assert not problems, "; ".join(problems)
