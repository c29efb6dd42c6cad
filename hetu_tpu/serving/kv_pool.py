"""Paged KV-cache pool: preallocated page storage + free-list allocator.

The dense decode cache (``models/generate.py``) holds ``[b, max_len,
kvh, hd]`` per layer — every request pays for the *longest possible*
sequence up front.  The pool instead preallocates ``num_pages`` fixed
``page_size``-token pages per layer and hands them out on demand: a
request holds ``ceil(len/page_size)`` pages, so mixed-length traffic
shares HBM proportionally to what it actually uses (the Ragged Paged
Attention storage layout, PAPERS.md arxiv 2604.15464).

Page 0 is a reserved **trash page**: every padded page-table slot (the
tail of a request's table, dummy batch slots) points at it, so the
jitted step reads through static-shape page tables unconditionally —
reads past ``seq_len`` are masked by the attention op.  It is never
allocated.  The write plan aims padding token slots at it too: the
plain scatter of the CPU path (``ops.paged_kv_write_reference``) writes
them there; the TPU path's page-run write (``ops/paged_kv_write.py``)
writes only tokens that exist and leaves the page alone.

Layout: pages are ``[num_pages, kv_heads, page_size, head_dim]`` — the
trailing ``(page_size, head_dim)`` tile is what the ragged kernel DMAs
per grid step (``ops/ragged_paged_attention.py``).  ``kv_heads``
is the same axis the training stack splits across ``tp``
(nn/parallel.py column-parallel QKV), so a pool built with a mesh
shards pages ``P(None, 'tp', None, None)`` and the decode executable's
per-shard pages line up with the per-shard QKV projections.

Pages live in one of THREE states (``serving/prefix_cache.py`` adds
the third): **free** (on the free list), **allocated** (owned by
exactly one request, writable), or **cached** (owned by the prefix
cache, READ-ONLY, refcounted by live sharers; refcount 0 = evictable).
``alloc`` consults an optional reclaim hook — the prefix cache's LRU
sweep — before failing, so cached pages are transparently recycled
ahead of the scheduler's recompute-preemption fallback.
"""
from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta, selective_scan

TRASH_PAGE = 0

# Process-global protocol sequence counter.  Every record plane the
# analysis event stream merges (pool ops, engine tap, host-tier
# records, transport extract/inject, cluster adoptions/fences) stamps
# its records with the next value at record time, so events from
# DIFFERENT planes interleave in true causal order when
# ``analysis/events.normalize`` merges them — per-plane indices alone
# cannot order a pool free against the host-tier stage that caused it.
_PROTOCOL_SEQ = itertools.count(1)


def protocol_seq() -> int:
    """Next value of the process-global event sequence counter."""
    return next(_PROTOCOL_SEQ)

# page_quant codes for the layout tag (order is part of the tag)
_QUANT_CODES = {None: 0, "int8": 1, "nf4": 2}


def page_shape_bytes(shape: Sequence[int], dtype) -> int:
    """Bytes ONE page of a per-layer page array ``[P, h, ps, w]``
    occupies (i.e. everything but the leading page axis).  The single
    source of truth for KV page sizing: ``PagedKVPool.page_bytes``,
    ``PageTransport`` handoff pricing, engine metrics, and the
    ``analysis/memory.py`` pool predictor all derive from it, so a
    latent (MLA) pool and a full-head pool can never disagree about
    what a page costs."""
    n = 1
    for d in shape[1:]:
        n *= int(d)
    return n * jnp.dtype(dtype).itemsize


class StateSlotStore:
    """Fixed-size recurrent state beside the K/V pages: one SLOT per
    running sequence for every state-space layer.

    A page holds a few tokens' K/V and a sequence grows into more of
    them; a slot holds a sequence's WHOLE recurrent state (the conv's
    last ``K - 1`` inputs and the float32 scan state of each state-space
    layer: ``[heads, head_dim, state]`` for mamba2, ``[state, channels /
    128, 128]`` for mamba1 — the layout its scan walks,
    ``ops.selective_scan.state_shape`` — ``[heads / p, key_dim, p *
    value_dim]`` for gdn, ``ops.gated_delta.state_shape``) and never grows.  The engine owns
    the store beside the pool and moves a request's slot with its pages:
    allocated at admission, freed at finish, at preemption (recompute:
    the state is dropped and the sequence re-prefilled) and at abort.
    There is no zeroing pass: the unified step starts a row whose first
    token sits at position 0 from zeros whatever its slot holds, so a
    slot's old content cannot reach the sequence that takes it next.
    A decode step reads and writes, in place, the scan state of the slots
    that have a live decode row (``ops.ssd.ssd_decode_slots``,
    ``ops.selective_scan.selective_scan_slots``,
    ``ops.gated_delta.gated_delta_slots``) and of no
    other: a slot that is free, or whose sequence is waiting or
    prefilling, keeps its bytes untouched.

    ``conv`` / ``ssm`` are tuples of per-layer arrays ``[slots, K - 1,
    conv_dim]`` / ``[slots, *state_shape]``; the jitted step takes them
    donated and returns them (``set_arrays``)."""

    def __init__(self, num_layers: int, num_slots: int, conv_kernel: int,
                 conv_dim: int, state_shape: Sequence[int],
                 conv_dtype=jnp.float32):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_layers, self.num_slots = int(num_layers), int(num_slots)
        self.conv: Tuple[jax.Array, ...] = tuple(
            jnp.zeros((num_slots, conv_kernel - 1, conv_dim), conv_dtype)
            for _ in range(num_layers))
        self.ssm: Tuple[jax.Array, ...] = tuple(
            jnp.zeros((num_slots,) + tuple(state_shape), jnp.float32)
            for _ in range(num_layers))
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        self._owner: Dict[int, int] = {}          # slot -> req_id
        self.allocs = 0
        # ``(seq, op, [slot])`` on the pool's own sequence counter
        self.event_log: List[Tuple[int, str, List[int]]] = []

    @property
    def in_use(self) -> int:
        return len(self._owner)

    @property
    def slot_bytes(self) -> int:
        """Bytes ONE slot holds across all layers."""
        return sum(page_shape_bytes(a.shape, a.dtype)
                   for a in self.conv + self.ssm)

    def alloc(self, req_id: int) -> Optional[int]:
        """A free slot for ``req_id``, or None when every slot is held."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._owner[slot] = int(req_id)
        self.allocs += 1
        self.event_log.append((protocol_seq(), "slot_alloc", [slot]))
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._owner:
            raise ValueError(f"double free / foreign state slot {slot}")
        del self._owner[slot]
        self._free.append(slot)
        self.event_log.append((protocol_seq(), "slot_free", [slot]))

    def owner(self, slot: int) -> Optional[int]:
        return self._owner.get(slot)

    def set_arrays(self, conv, ssm) -> None:
        self.conv, self.ssm = tuple(conv), tuple(ssm)

    def problems(self) -> List[str]:
        """Free and held slots partition ``range(num_slots)``."""
        out = []
        free, held = set(self._free), set(self._owner)
        if len(free) != len(self._free):
            out.append("a state slot is on the free list twice")
        if free & held:
            out.append(f"state slots both free and held: {sorted(free & held)}")
        if free | held != set(range(self.num_slots)):
            out.append("state slots leaked or invented: "
                       f"{sorted(set(range(self.num_slots)) ^ (free | held))}")
        return out


def state_store_layout(cfg, kind: str):
    """``(conv taps, conv channels, per-slot state shape)`` of the store a
    pattern with recurrent mixer ``kind`` (``models.gpt.STATE_MIXERS``)
    keeps: the channels its conv runs over, and its state as its
    recurrence walks it."""
    if kind == "mamba2":
        return (cfg.mamba_conv_kernel, cfg.mamba_conv_dim,
                (cfg.mamba_num_heads, cfg.mamba_head_dim,
                 cfg.mamba_state_dim))
    if kind == "mamba1":
        return (cfg.mamba_conv_kernel, cfg.mamba1_inner,
                selective_scan.state_shape(cfg.mamba1_inner,
                                           cfg.mamba_state_dim))
    if kind == "gdn":
        return (cfg.linear_conv_kernel, cfg.linear_conv_dim,
                gated_delta.state_shape(
                    cfg.linear_value_heads, cfg.linear_key_dim,
                    cfg.linear_value_dim))
    raise ValueError(f"no state store is laid out for mixer {kind!r}")


class WindowPages:
    """The page-id space of the window layers: pages counted by
    reference, so that a row and a cached boundary can hold one page.

    A window layer's query reads the ``window`` positions up to itself,
    so a row needs that layer's pages only where its next queries' windows
    reach (``window_table_pages``), whatever its context: the engine drops
    a row's page once every query still to come starts behind it and
    allocates ahead of the write cursor.  A cached prefix is resumable
    only with the window pages of its tail (``PrefixCache``: the entry at
    the boundary holds a reference on each); a row that resumes there
    holds references on the same pages until its window has slid past
    them.  A page returns to the free list when its last holder lets go.
    Page 0 is the trash page, as in the pool."""

    def __init__(self, num_pages: int, tail_pages: int = 0):
        if num_pages < 2:
            raise ValueError(f"window pages must be >= 2, got {num_pages}")
        self.num_pages = int(num_pages)
        # pages behind a page boundary that a request resuming there
        # reads (``window_tail_pages``): what a row keeps and a cached
        # boundary's tail holds
        self.tail_pages = int(tail_pages)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        # fn(n_short): lets go of cached boundaries' tails (the prefix
        # cache's sweep); alloc calls it before failing
        self._reclaim: Optional[Callable[[int], int]] = None

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._refs)

    def set_reclaim(self, fn: Optional[Callable[[int], int]]) -> None:
        self._reclaim = fn

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages at one reference each, or None (no partial grant)."""
        if n > len(self._free) and self._reclaim is not None:
            self._reclaim(n - len(self._free))
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for pg in pages:
            self._refs[pg] = 1
        return pages

    def retain(self, pages: Sequence[int]) -> None:
        for pg in pages:
            if pg not in self._refs:
                raise ValueError(f"retain of free window page {pg}")
            self._refs[pg] += 1

    def release(self, pages: Sequence[int]) -> None:
        for pg in pages:
            left = self._refs.get(pg, 0) - 1
            if left < 0:
                raise ValueError(f"release of free window page {pg}")
            if left:
                self._refs[pg] = left
            else:
                del self._refs[pg]
                self._free.append(pg)

    def reset(self) -> None:
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._refs = {}

    def problems(self) -> List[str]:
        """Free and held pages partition ``range(1, num_pages)``."""
        free, held = set(self._free), set(self._refs)
        out = []
        if len(free) != len(self._free) or free & held:
            out.append("a window page is free twice, or free and held")
        if free | held != set(range(1, self.num_pages)):
            out.append("window pages leaked or invented")
        return out


def window_tail_pages(window: int, page_size: int) -> int:
    """Pages of a window layer that a request resuming at a page boundary
    reads behind it: the ``window - 1`` positions before the boundary."""
    return -(-(window - 1) // page_size)


def window_table_pages(window: int, chunk: int, page_size: int) -> int:
    """Page-table slots a row needs in a window layer.  A row keeps the
    tail of its last page boundary (so that its prefix is resumable there
    when it finishes) and writes at most ``chunk`` positions from a cursor
    less than a page past that boundary."""
    return window_tail_pages(window, page_size) + \
        (page_size + chunk - 2) // page_size + 1


class PagedKVPool:
    """Free-list page allocator over per-layer k/v page arrays.

    Two layouts share every allocator/bookkeeping path:

    - **full-head** (default): k and v pages are both
      ``[P, kv_heads, ps, head_dim]``.
    - **latent** (MLA, ``latent_dim`` set): k_pages hold ONE compressed
      stream ``[P, 1, ps, latent_dim]`` and v_pages carry the decoupled
      rotated key ``[P, 1, ps, rope_dim]`` (width 0 for learned
      positions).  With ``quant`` set (int8/nf4, learned-position MLA
      only), k_pages store codes (int8, or packed uint8 at
      ``latent_dim // 2``) and v_pages become the per-token fp32 absmax
      sidecar ``[P, 1, ps, 1]``.

    - **by layer** (``layers``: one ``models.gpt.PageLayer`` a paged
      layer): latent pages whose widths differ from layer to layer, in
      two page-id spaces.  ``k_pages[a]`` is paged layer ``a``'s ONE
      stream ``[P, 1, ps, latent + rope]``, ``c_kv | k_r`` (and the zero
      lanes that fill the rotary part to 128) side by side, so that a
      token's row is one gather; ``v_pages`` holds, for the layers that
      have an indexer and in their order, the index-key stream ``[P, 1,
      ps, index]``: the indexer's key of each token beside the latent,
      under the same page ids.  A plain K/V layer (``kv_heads`` > 0) has
      the full-head form in both: ``k_pages[a]`` its K, and ``v_pages``,
      in the layers' order, its V, ``[P, kv_heads, ps, head_dim]``.  A
      "full" layer's arrays have
      ``num_pages`` pages and are addressed by a request's ``pages`` as
      ever; a "window" layer's have ``window_pages`` pages of an id space
      of their own (``self.window``), of which a request holds only what
      its window reaches (``Request.win_pages``).

    Page-table math, the allocator, CoW refcounts, and the prefix cache
    never look inside a page, so they compose with any layout; only
    ``page_bytes`` / ``layout_tag`` observe the difference.
    """

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 kv_heads: int, head_dim: int, dtype=jnp.float32,
                 mesh=None, kv_axis: str = "tp", debug: bool = False,
                 latent_dim: Optional[int] = None, rope_dim: int = 0,
                 quant: Optional[str] = None, layers=None,
                 window_pages: int = 0, window_tokens: int = 0):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is the "
                             f"reserved trash page), got {num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if quant is not None:
            if quant not in ("int8", "nf4"):
                raise ValueError(f"page quant must be int8|nf4, "
                                 f"got {quant!r}")
            if latent_dim is None or rope_dim:
                raise ValueError("page quantization requires the latent "
                                 "(MLA) layout with rope_dim == 0 — the "
                                 "v-page slot carries the absmax sidecar")
            if quant == "nf4" and latent_dim % 2:
                raise ValueError(f"nf4 pages need even latent_dim, got "
                                 f"{latent_dim}")
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(dtype)
        self.latent_dim = None if latent_dim is None else int(latent_dim)
        self.rope_dim = int(rope_dim)
        self.quant = quant
        self.layers = None if layers is None else tuple(layers)
        self.window: Optional[WindowPages] = None
        if self.layers is not None:
            if quant is not None or mesh is not None or \
                    len(self.layers) != num_layers:
                raise ValueError("a pool laid out by layer holds one "
                                 "PageLayer a paged layer, unquantized, on "
                                 "one device")
            if any(l.space == "window" for l in self.layers):
                self.window = WindowPages(window_pages, window_tail_pages(
                    window_tokens, page_size))
        if latent_dim is not None:
            if quant == "int8":
                k_shape = (num_pages, 1, page_size, self.latent_dim)
                k_dtype = jnp.dtype(jnp.int8)
            elif quant == "nf4":
                k_shape = (num_pages, 1, page_size, self.latent_dim // 2)
                k_dtype = jnp.dtype(jnp.uint8)
            else:
                k_shape = (num_pages, 1, page_size, self.latent_dim)
                k_dtype = self.dtype
            # rope stream, or the per-token absmax sidecar when quantized
            v_w = 1 if quant else self.rope_dim
            v_shape = (num_pages, 1, page_size, v_w)
            v_dtype = jnp.dtype(jnp.float32) if quant else self.dtype
        else:
            k_shape = v_shape = (num_pages, kv_heads, page_size, head_dim)
            k_dtype = v_dtype = self.dtype
        self.sharding = None
        if mesh is not None and kv_axis in getattr(mesh, "axis_names", ()):
            from jax.sharding import NamedSharding, PartitionSpec as P
            tp = mesh.shape[kv_axis]
            # the latent stream has no head axis to split — replicate
            if latent_dim is None and kv_heads % tp == 0:
                self.sharding = NamedSharding(
                    mesh, P(None, kv_axis, None, None))

        def make(shape, dt):
            z = jnp.zeros(shape, dt)
            return jax.device_put(z, self.sharding) if self.sharding \
                else z

        if self.layers is None:
            self.k_pages: Tuple[jax.Array, ...] = tuple(
                make(k_shape, k_dtype) for _ in range(num_layers))
            self.v_pages: Tuple[jax.Array, ...] = tuple(
                make(v_shape, v_dtype) for _ in range(num_layers))
        else:
            def stream(l, width):
                n = window_pages if l.space == "window" else num_pages
                shape = (n, l.kv_heads, page_size, l.head_dim) \
                    if l.kv_heads else (n, 1, page_size, width)
                return jnp.zeros(shape, self.dtype)

            # a layer's second array: a K/V layer's V, a latent layer's
            # index keys
            self.k_pages = tuple(stream(l, l.latent + l.rope)
                                 for l in self.layers)
            self.v_pages = tuple(stream(l, l.index) for l in self.layers
                                 if l.index or l.kv_heads)
        # LIFO free list: recently-freed pages are re-issued first (their
        # HBM is hot); page 0 reserved
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._allocated = set()
        # read-only pages owned by the prefix cache: page -> live sharers
        # (refcount() reports 1 + sharers; 0 sharers = LRU-evictable)
        self._cached: Dict[int, int] = {}
        # invoked by alloc() when the free list can't cover a request:
        # fn(n_short) reclaims up to n_short cached pages (LRU sweep)
        self._reclaim: Optional[Callable[[int], int]] = None
        # times a reclaim hook CLAIMED more/fewer pages than actually
        # landed on the free list (alloc verifies the delta; a lying
        # hook falls through to preemption instead of IndexError)
        self.reclaim_shortfalls = 0
        # O(num_pages) invariant rebuilds are opt-in: tests/engines set
        # debug=True (or pass force=) — bench/production paths skip them
        self.debug = bool(debug)
        # append-only op log ``(seq, op, pages)`` — the page plane of
        # the analysis event stream (analysis/events.py normalizes it
        # into page.alloc/free/cache/... events).  Always on: one tuple
        # append per allocator op is noise next to the page bookkeeping
        # itself, and a conditional log would make the protocol lint
        # silently vacuous on production-configured pools.
        self.event_log: List[Tuple[int, str, List[int]]] = []
        # the recurrent-state slots of a hybrid stack, attached by the
        # engine that owns both (None: every layer keeps K/V)
        self.state_slots: Optional[StateSlotStore] = None

    # -- allocator -----------------------------------------------------------

    @property
    def num_usable(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._allocated)

    @property
    def utilization(self) -> float:
        return self.used_pages / self.num_usable

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` KV entries."""
        return -(-int(n_tokens) // self.page_size)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages; None (no partial grant) when the pool
        can't satisfy the request — the scheduler's eviction signal.
        When a reclaim hook is installed (the prefix cache's LRU sweep),
        a dry free list triggers it BEFORE giving up: cached refcount-0
        pages are recycled ahead of recompute preemption.

        The hook's CLAIMED count is never trusted: only pages that
        actually landed on the free list satisfy the request, so a
        lying/partial sweep degrades to a clean ``None`` (the caller's
        preemption path) instead of a short grant.  A mismatch between
        claim and delivery is recorded in ``reclaim_shortfalls`` —
        it means the reclaim hook's accounting is broken."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free) and self._reclaim is not None:
            before = len(self._free)
            claimed = self._reclaim(n - before)
            delivered = len(self._free) - before
            if claimed is not None and int(claimed) != delivered:
                self.reclaim_shortfalls += 1
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._allocated.update(pages)
        if pages:
            self.event_log.append((protocol_seq(), "alloc", list(pages)))
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for pg in pages:
            if pg not in self._allocated:
                raise ValueError(f"double free / foreign page {pg}")
            self._allocated.remove(pg)
            self._free.append(pg)
        pages = list(pages)
        if pages:
            self.event_log.append((protocol_seq(), "free", pages))

    # -- cached (read-only, refcounted) pages --------------------------------

    def set_reclaim(self, fn: Optional[Callable[[int], int]]) -> None:
        """Install the cache's LRU sweep: ``fn(n)`` frees up to ``n``
        refcount-0 cached pages; ``alloc`` calls it before failing."""
        self._reclaim = fn

    @property
    def cached_pages(self) -> int:
        return len(self._cached)

    def refcount(self, pg: int) -> int:
        """0 = free, 1 = exclusively owned (allocated, or cached with no
        sharer), 1+n = cached and shared by n live requests.  A KV write
        plan may only ever target refcount-1 ALLOCATED pages — the
        ``cow-page-write`` analysis rule audits exactly this."""
        if pg in self._cached:
            return 1 + self._cached[pg]
        return 1 if pg in self._allocated else 0

    def cache_page(self, pg: int) -> None:
        """allocated -> cached (refcount 0): the finishing request hands
        the fully-written page to the prefix cache, read-only from here."""
        if pg not in self._allocated:
            raise ValueError(f"cannot cache non-allocated page {pg}")
        self._allocated.remove(pg)
        self._cached[pg] = 0
        self.event_log.append((protocol_seq(), "cache", [pg]))

    def share_page(self, pg: int) -> None:
        """A live request attached this cached page to its page table."""
        if pg not in self._cached:
            raise ValueError(f"cannot share non-cached page {pg}")
        self._cached[pg] += 1
        self.event_log.append((protocol_seq(), "share", [pg]))

    def unshare_page(self, pg: int) -> None:
        if self._cached.get(pg, 0) < 1:
            raise ValueError(f"unshare of page {pg} with no sharers")
        self._cached[pg] -= 1
        self.event_log.append((protocol_seq(), "unshare", [pg]))

    def uncache_page(self, pg: int) -> None:
        """cached (refcount 0) -> free: the cache evicted the entry; the
        index entry must already be gone so no lookup can hand the page
        out again after it becomes writable."""
        if pg not in self._cached:
            raise ValueError(f"cannot uncache non-cached page {pg}")
        if self._cached[pg] != 0:
            raise ValueError(f"evicting cached page {pg} with "
                             f"{self._cached[pg]} live sharers")
        del self._cached[pg]
        self._free.append(pg)
        self.event_log.append((protocol_seq(), "uncache", [pg]))

    def reset(self, clear_pages: bool = False) -> None:
        """Return the pool to its post-construction allocator state.

        The rebuilt free-list must EXCLUDE the reserved trash page 0 —
        a naive ``range(num_pages)`` rebuild would hand page 0 to the
        next request and real KV writes would land in the padding sink
        (every padded page-table slot points there).  Regression-tested:
        alloc-after-reset can never return page 0.

        ``clear_pages`` additionally zeroes the page storage (off by
        default: allocator reuse does not require wiping HBM, and stale
        KV beyond ``seq_len`` is masked by the attention op anyway).
        """
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._allocated = set()
        self._cached = {}
        self.event_log = [(protocol_seq(), "reset", [])]
        if self.window is not None:
            self.window.reset()
        if clear_pages:
            self.k_pages = tuple(jnp.zeros_like(p) for p in self.k_pages)
            self.v_pages = tuple(jnp.zeros_like(p) for p in self.v_pages)

    def check_invariants(self, force: bool = False) -> None:
        """Allocator bookkeeping invariants: free/allocated/cached
        PARTITION the usable pages (pairwise disjoint, nothing leaked or
        invented), trash page never issued, cached refcounts
        non-negative.  Rebuilding the sets is O(num_pages), so the check
        is OPT-IN: a no-op unless the pool was built with ``debug=True``
        (tests, debug engines) or ``force=True`` is passed — bench and
        production paths skip it on every scheduling storm."""
        if not (self.debug or force):
            return
        # one implementation: the protocol verifier's snapshot predicate
        # (analysis/protocol.py) owns the invariant logic; this wrapper
        # keeps the debug/force gating and assert-style reporting every
        # existing call site relies on (imported lazily — the analysis
        # package must stay optional for serving)
        from ..analysis.protocol import page_partition_problems
        problems = page_partition_problems(
            self.num_pages, self._free, self._allocated, self._cached)
        if self.state_slots is not None:
            problems = list(problems) + self.state_slots.problems()
        if self.window is not None:
            problems = list(problems) + self.window.problems()
        assert not problems, "; ".join(problems)

    # -- accounting ----------------------------------------------------------

    @property
    def is_latent(self) -> bool:
        return self.latent_dim is not None or self.layers is not None

    def _arrays(self, space: str):
        """The page arrays of one page-id space (every array of a pool
        not laid out by layer is "full")."""
        if self.layers is None:
            return self.k_pages + self.v_pages if space == "full" else ()
        second = [l for l in self.layers if l.index or l.kv_heads]
        return tuple(a for a, l in zip(self.k_pages, self.layers)
                     if l.space == space) + \
            tuple(a for a, l in zip(self.v_pages, second)
                  if l.space == space)

    def page_array_shapes(self) -> Tuple[Tuple[Tuple[int, ...], ...],
                                         Tuple[Tuple[int, ...], ...]]:
        """Actual per-layer (k, v) page-array shapes — what the jitted
        executables see, and what ``analysis/memory.py`` classifies as
        kv-page operands.  Derived from the live arrays, never from the
        constructor attrs, so it is correct for every layout."""
        return (tuple(tuple(p.shape) for p in self.k_pages),
                tuple(tuple(p.shape) for p in self.v_pages))

    @property
    def page_bytes(self) -> int:
        """HBM bytes one page holds across k+v and all layers, summed
        from the ACTUAL page arrays via :func:`page_shape_bytes` (the
        one shared helper — transport pricing and metrics read this
        property, so they can never disagree with the real layout)."""
        return sum(page_shape_bytes(p.shape, p.dtype)
                   for p in self._arrays("full"))

    @property
    def window_page_bytes(self) -> int:
        """HBM bytes one page id of the window space holds across the
        window layers (0 without them)."""
        return sum(page_shape_bytes(p.shape, p.dtype)
                   for p in self._arrays("window"))

    @property
    def kv_bytes_per_token(self) -> int:
        """KV bytes ONE cached token costs across all layers (page
        bytes amortized over the page's token slots)."""
        return self.page_bytes // self.page_size

    @property
    def layout_tag(self) -> Tuple[int, ...]:
        """Compact int tuple identifying the page LAYOUT (not contents):
        two pools agree on this iff a page extracted from one can be
        injected into the other and read back identically.  Salted into
        the prefix-cache digest so a latent replica and a full-head
        replica can never cross-match in the router."""
        if self.layers is not None:
            return (2, self.dtype.itemsize) + tuple(
                x for l in self.layers
                for x in (int(l.space == "window"), l.latent, l.rope,
                          l.index, l.kv_heads, l.head_dim))
        if self.is_latent:
            return (1, self.latent_dim, self.rope_dim,
                    _QUANT_CODES[self.quant], self.dtype.itemsize)
        return (0, self.kv_heads, self.head_dim, 0, self.dtype.itemsize)

    def set_pages(self, k_pages, v_pages) -> None:
        """Install updated page arrays (the jitted executables return new
        arrays; the pool is the single owner of the live version)."""
        self.k_pages = tuple(k_pages)
        self.v_pages = tuple(v_pages)
