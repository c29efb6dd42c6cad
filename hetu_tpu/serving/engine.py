"""Continuous-batching inference engine over the paged KV pool.

The serving half of the roadmap: where ``models.generate`` runs ONE
static batch to completion, the engine runs an admission loop — every
``step()`` it admits arrived requests, packs ALL live work (prefill
chunks + decode tokens) into one ragged token batch, runs the single
**unified executable** (``serving/decode.build_unified_step_fn``), and
streams each emitted token to its request, retiring/evicting under the
page budget.  Late-arriving requests join mid-flight; short requests
leave without waiting for long ones; long prompts prefill in
``chunk_size`` slices so they never stall running decodes.

One executable, compiled once (DESIGN.md §12): there is no prefill
bucket grid and no per-batch-size decode program — ``compile_count``
is 1 regardless of traffic, asserted by the CI recompile guard.  Its
control data crosses the host-device boundary once each way
(``serving/decode.StepLayout``): ``fn(params, packed, k_pages, v_pages,
*states)`` takes the step's small host arrays as ONE int32 buffer — one
transfer a step, counter ``h2d_copies`` — and returns, before the pools
and states, ONE int32 vector — the tokens and, beside them, the hybrid
family's expert load / a speculative build's accepted lengths; one
fetch a step, counter ``d2h_fetches``.

Determinism contract: at temperature 0 every request's output equals a
solo ``generate()`` run — batching, paging, chunked prefill, admission
order, and even preemption (recompute eviction) change WHEN a token is
computed, never WHAT it is.  Sampled modes (temperature / top-k /
top-p) run ON DEVICE keyed by ``(seed, position)``, so replays are
deterministic too and the engine only ever fetches ``[rows]`` int32 —
``host_logit_fetches`` stays 0 on any traffic mix.

Speculative decoding (``serving/spec.py``, DESIGN.md §20, opt-in via
``Engine(spec=SpecConfig(...))``): a shallow draft model — or, with
``SpecConfig(self_draft=1)``, the stack's own MTP module inside the step
(DESIGN.md §28: the step's ``draft`` output is staged as the row's draft
of the next step) — proposes ``k``
greedy tokens per decode-ready request each step; the scheduler packs
them as dedicated ``k + 1``-token ragged VERIFY rows (structurally
prefill chunks) and the unified executable's on-device accept head
returns the longest-accepted-prefix length plus a bonus token per row
— up to ``k + 1`` tokens committed per call, temp-0 output still
bit-for-bit, ``host_logit_fetches`` still 0, and the draft's three
fixed-shape programs join the compile-count guard.

Block-wise generation (DESIGN.md §29, a model with
``cfg.diffusion_block``): a generating request's tip is its OPEN BLOCK
(``Request.block``: known ids and the mask id), the scheduler gives it a
block slot every step, the step's block head says which masked positions
the pass unmasks and with what (``DenoiseRule``: two numbers a row cover
the three published rules), and ``_commit_block`` applies them; a block
with no mask left goes in once more as its COMMIT pass, whose K/V stands,
and its tokens are emitted in position order — 0 to B tokens a row a step.
Where the request goes on and the next block's page is at hand, that
commit rides in one FUSED row with the next block's first denoise pass
(2B positions: the block's clean tokens, then B masks): under the
block-wise mask the committed half sees nothing of the next block, so it
is the same forward "of its own" (``configs/sdar30b-pp8.json``
``assumed.d_commit``), and a generating row costs two steps a block under
the two-pass rule, not three.
Counters ``block_row_passes`` / ``block_commit_passes`` /
``block_commits_fused`` /
``block_tokens_unmasked`` / ``blocks_committed`` / ``block_positions`` /
``block_positions_masked`` / ``kv_tokens_provisional``
(``serving/step_account.py``: they count block forwards, a fused row two).

Prefix reuse (``serving/prefix_cache.py``, on by default): finished
requests' fully-written pages enter a chained-hash index; a new request
whose page-aligned token prefix is cached attaches those pages
read-only (copy-on-write — its KV write plan starts past them) and
prefills only the uncached suffix.  When the pool runs dry, an LRU
sweep over refcount-0 cached pages reclaims space BEFORE recompute
preemption.  Cache-hit and cache-cold runs are bit-for-bit identical
at temperature 0: the kernel reads identical page contents either way.

Observability (utils/metrics.py instruments): counters
``tokens_generated``/``prefill_tokens``/``kv_tokens_written``/
``requests_completed``/
``preemptions``/``decode_steps``/``prefill_chunks``/``step_calls``/
``prefix_cache_hits``/``prefix_cache_misses``/
``prefix_cache_tokens_saved``/``prefix_cache_evictions``,
gauges ``batch_occupancy``/``page_utilization``/``queue_depth``,
histograms ``ttft``/``tbt``/``tpot``/``request_latency`` (ttft/tbt are
Prometheus-bucketed for per-stage latency dashboards) — with the no-op
fallback when disabled.  ``metrics_summary()`` adds the derived
``prefix_cache_hit_rate`` and the live ``prefix_cache_pages`` count.
``metrics_text()`` renders everything as Prometheus text exposition.

Trace plane (hetu_tpu/obs, DESIGN.md §15): under an installed tracer
every request gets a complete lifecycle timeline on its own track —
``enqueue`` instant, ``queued``/``running`` state spans that tile
[submit, finish] gaplessly across preemptions, ``admit`` (page
accounting), ``prefix_cache_hit``, per-chunk ``prefill_chunk`` spans
with their token-budget slice, per-token instants, ``preempt`` and
``finish`` — plus a ``unified_step`` span per executable call (``exec=``
names the registered executable; ``obs.reconcile()`` joins the analysis
plane's predictions on it) and, around the whole of ``step()``,
an ``engine_step`` span (``step=`` the engine's step index; it ends with
the queue's load and the scheduler's packing decision, ``slot_mix``)
tiled by its host phases
``step.admit`` (admission, prefix-cache match, draft
staging), ``step.pages`` (decode pages, preemption), ``step.pack``
(packing decision + host arrays), ``step.tap`` (the analysis tap's
copy), ``step.h2d`` (the one host-to-device transfer), ``step.dispatch``
(the compiled call up to its return), ``step.fetch`` (the host waits for
the device here) and ``step.commit`` (pages, counters, per-row commit,
stream callbacks, gauges).  These real-time spans are mirrored into the
jax profiler's trace (``hetu:`` prefix).  Two retroactive spans, host
tracer only, size what the phases hide: ``pack_arrays`` (the packed
host arrays, inside ``step.pack``; ``rows``, ``page_slots``) and
``account`` (what the step spends on its counters and span attributes,
``serving/step_account``, inside ``step.commit``); with ``unified_step``
they carry the same ``step=`` as their ``engine_step``.  While a tracer is set
through ``set_tracer`` the collector's runs are ``gc`` spans on track
``runtime`` (``obs/tracer.py``).  The default tracer is the
shared no-op: every emission site guards on ``tracer.enabled``.

A clock that is always on, tracing or not: ``step()`` reads the clock at
entry, round the compiled call (``t0``, ``t1``) and at exit, and adds to
the counters ``host_before_s`` (entry to ``t0``), ``call_s`` (``t0`` to
``t1``: copies in, the call, the fetch), ``host_after_s`` (``t1`` to
exit) and ``between_steps_s`` (the previous exit to this entry, counted
only when requests were running at that exit).  ``slow_step_s`` is the
part of a step's wall (its ``between`` share and entry to exit) beyond
``SLOW_STEP_FACTOR`` x the mean wall of the steps before it since the
reset; ``Engine.slow_steps`` keeps the ``SLOW_STEPS_KEPT`` longest steps
since the reset (index, seconds since the reset, the four parts, rows,
tokens, full collections in it), and a step that enters it beyond the
rule is one warning on logger ``hetu_tpu.serving``.
"""
from __future__ import annotations

import gc
import logging
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import _Params
from ..models.gpt import GPTConfig
from ..obs.tracer import get_tracer
from ..ops.pallas import on_tpu
from ..utils.metrics import make_instrument, render_prometheus
from .decode import StepLayout, build_unified_step_fn
from .kv_pool import (TRASH_PAGE, PagedKVPool, StateSlotStore,
                      protocol_seq, state_store_layout, window_table_pages)
from .prefix_cache import PrefixCache
from .request import (FINISHED, RUNNING, DenoiseRule, Request,
                      RequestQueue)
from .scheduler import Scheduler
from .spec import SpecConfig, SpecDecoder
from .step_account import COUNTERS, GAUGES, StepAccount

# default Prometheus-style latency bounds (seconds) for ttft/tbt; tests
# and benches with a synthetic clock pass their own
DEFAULT_LATENCY_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                           10.0)

# the always-on clock's rule for a slow step: a wall beyond this many
# times the mean wall of the steps before it since the reset; and how
# many of the longest steps ``Engine.slow_steps`` keeps
SLOW_STEP_FACTOR = 10.0
SLOW_STEPS_KEPT = 8

log = logging.getLogger("hetu_tpu.serving")


class Engine:
    def __init__(self, state: Dict[str, Any], cfg: GPTConfig,
                 num_pages: int = 64, page_size: int = 64,
                 max_batch: int = 8, max_model_len: Optional[int] = None,
                 chunk_size: Optional[int] = 64, prefill_rows: int = 1,
                 mesh=None, use_kernel: Optional[bool] = None,
                 metrics: bool = True,
                 latency_buckets: Optional[Sequence[float]] = None,
                 time_fn: Optional[Callable[[], float]] = None,
                 name: str = "serving", analysis_tap: bool = True,
                 prefix_cache: bool = True, debug: bool = False,
                 tracer=None, step_fn: Optional[Callable] = None,
                 spec: Optional[SpecConfig] = None,
                 page_quant: Optional[str] = None,
                 host_tier=None, window_pages: Optional[int] = None,
                 early_fetch: bool = False,
                 denoise: Optional[DenoiseRule] = None):
        self.cfg = cfg
        self.name = name
        # start the step's one device-to-host copy when the call is
        # enqueued, not when the host asks for the tokens: the copy then
        # follows the execution on the device's own queue, and the fetch
        # waits for one event where it waited for the execution, woke,
        # asked for the copy and waited again (ROADMAP S3)
        self.early_fetch = bool(early_fetch)
        # runtime trace plane (hetu_tpu/obs): None follows the ambient
        # tracer (obs.install_tracer / obs.trace), which defaults to the
        # shared no-op — every emission site below guards on
        # ``tr.enabled`` so disabled tracing stays out of the hot loop
        self._tracer = tracer
        # the tracer whose collector hook ``set_tracer`` registered
        self._gc_watched = None
        # traced steps only: the open ``step.*`` child of the
        # ``engine_step`` span and the attributes that span ends with
        self._phase_sp = None
        self._step_attrs: Dict[str, Any] = {}
        # ring buffer of recent packed-step layouts (rows + page tables),
        # consumed by the trash-page-write lint (hetu_tpu/analysis)
        self.tap: Optional[deque] = deque(maxlen=128) if analysis_tap \
            else None
        # engine-plane request-lifecycle events (req.queued / req.admit
        # / req.finish) for the analysis event stream.  Preempt/rewind
        # ride the tap and adopt rides the cluster's adoption records,
        # so every transition is emitted by exactly one plane.
        self.protocol_log: List[Dict[str, Any]] = []
        # a new engine owns its analysis namespace: stale handles from a
        # discarded same-name engine would otherwise mix dead pool
        # snapshots into analyze_registered(name) — and pin that
        # engine's KV pool in the process-global registry forever
        from ..graph.graph import clear_executables
        clear_executables(f"{self.name}/")
        self.params = _Params(state, cfg).s      # normalized key view
        if max_model_len is None:
            max_model_len = (num_pages - 1) * page_size
            if cfg.position == "learned":
                # never past the wpe table: an out-of-range position
                # gather clamps silently to the last row
                max_model_len = min(max_model_len, cfg.max_seq_len)
        self.max_model_len = int(max_model_len)
        self.max_pages_per_seq = -(-self.max_model_len // page_size)
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.debug = bool(debug)
        # MLA latent layout (DESIGN.md §21): pages hold ONE compressed
        # [latent_dim] stream per token (plus the shared rope stream /
        # quant-scale sidecar) instead of kv_heads x head_dim — the
        # whole serving stack above the pool is layout-generic
        if page_quant is not None and not cfg.is_mla:
            raise ValueError("page_quant requires an MLA config "
                             "(kv_latent_dim set)")
        self.page_quant = page_quant
        # a hybrid stack (cfg.layer_pattern) keeps K/V for its attention
        # layers only and a recurrent-state slot per running sequence for
        # its recurrent (models.gpt.STATE_MIXERS) layers.  What is not built
        # for recurrent state is refused here, not run wrong: a cached prefix would need the
        # state AT the cached boundary, a rejected draft a roll-back
        self.hybrid = cfg.is_hybrid
        kind = cfg.state_mixer if self.hybrid else None
        if kind:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True is not built for a stack with "
                    f"recurrent ({kind}) layers: a cached page prefix "
                    "carries no state snapshot — pass prefix_cache=False")
            if spec is not None:
                raise ValueError(
                    "speculative decoding is not built for a stack with "
                    f"recurrent ({kind}) layers: a rejected draft cannot "
                    "be rolled back out of the state")
        # a block-wise model (cfg.diffusion_block, DESIGN.md §29): a
        # generating request's step is its open block, denoised in passes
        # under ``denoise`` (the engine's one rule) and then committed.
        # What is not built with it is refused here, by name
        self.block = cfg.diffusion_block
        if denoise is not None and not self.block:
            raise ValueError("denoise= describes block-wise generation "
                             "(cfg.diffusion_block); this model emits one "
                             "position after another")
        if self.block:
            self.denoise = (denoise or DenoiseRule()).check(self.block)
            if spec is not None:
                raise ValueError(
                    "speculative decoding is not built with block-wise "
                    "generation (diffusion_block): a block row's step is "
                    "its open block, there is no next token to draft")
            if mesh is not None:
                raise ValueError(
                    "a mesh is not built with block-wise generation "
                    "(diffusion_block): the block head's selection and the "
                    "block-wise mask are built for one device's pool")
            if prefix_cache and page_size % self.block:
                raise ValueError(
                    f"prefix_cache=True with block-wise generation needs "
                    f"page_size % diffusion_block == 0 (page {page_size}, "
                    f"block {self.block}): a cached page's K/V then "
                    "depends on the tokens up to its own end, which its "
                    "hash covers — pass prefix_cache=False")
        self.self_draft = spec is not None and bool(spec.self_draft)
        if spec is not None and self.hybrid and not self.self_draft:
            raise ValueError(
                "a pattern stack speculates with its own MTP module "
                "(SpecConfig(self_draft=1)): a separate draft model's "
                "verify rows are built for the plain block only")
        if self.self_draft and prefix_cache:
            raise ValueError(
                "prefix_cache=True is not built with self-drafting: the "
                "MTP layer's K/V at a cached boundary's last position "
                "embeds the token BEHIND the boundary, which the page's "
                "hash does not cover — pass prefix_cache=False")
        latent_dim, rope_dim = cfg.latent_page_dims
        # window layers (cfg.window_tokens keys a query) keep a row's
        # pages only where its window reaches: a page-id space of their
        # own, sized for every row's reach and, by default, as much again
        # for the tails of cached boundaries (DESIGN.md §27)
        self.window = cfg.window_tokens
        chunk = max_model_len if chunk_size is None \
            else min(int(chunk_size), max_model_len)
        self.window_table_pages = window_table_pages(
            self.window, chunk, page_size) if self.window else 0
        if self.window:
            if host_tier or mesh is not None:
                raise ValueError(
                    "window layers are built without the host tier and a "
                    "sharded pool: neither moves a row's window pages "
                    "with its full ones")
            need = int(max_batch) * self.window_table_pages + 1
            window_pages = 2 * need if window_pages is None \
                else int(window_pages)
            if window_pages < need:
                raise ValueError(
                    f"window_pages {window_pages} cannot hold {max_batch} "
                    f"rows x {self.window_table_pages} pages of reach")
        self.pool = PagedKVPool(len(cfg.paged_layers),
                                num_pages, page_size,
                                cfg.kv_heads, cfg.head_dim, dtype,
                                mesh=mesh, debug=debug,
                                latent_dim=latent_dim, rope_dim=rope_dim,
                                quant=page_quant, layers=cfg.page_layers,
                                window_pages=window_pages or 0,
                                window_tokens=self.window)
        self.state_store: Optional[StateSlotStore] = None
        if kind:
            # by kind (kv_pool.state_store_layout): the taps and channels
            # of its conv (a mamba1 layer's runs over its x alone, a gdn
            # layer's over q | k | v) and its state as its recurrence
            # walks it: [heads, head_dim, state] for mamba2, [state,
            # channels / 128, 128] for mamba1, [heads / 2, key_dim, 2 x
            # value_dim] for gdn (two heads side by side fill the
            # lanes).  One layout a store, so one kind a pattern; what a
            # slot costs a sequence follows from it: 27.4 MB at the
            # widths of olmohybrid-pp2 (12 layers), 9.3 at jamba2-3b's
            # (26 layers)
            self.state_store = StateSlotStore(
                len(cfg.layers_of(kind)), int(max_batch),
                *state_store_layout(cfg, kind), conv_dtype=dtype)
            self.pool.state_slots = self.state_store
        # copy-on-write prefix reuse: finished requests' full pages are
        # indexed by chained token hash; _start attaches the longest
        # cached prefix so prefill skips straight to the cached boundary
        self.prefix_cache: Optional[PrefixCache] = \
            PrefixCache(self.pool) if prefix_cache else None
        if self.prefix_cache is not None:
            self.pool.set_reclaim(self._reclaim_cached_pages)
        # chunk_size=None: whole-prompt chunks (bounded by what a
        # sequence can ever hold) — the "infinite chunk" configuration
        self.scheduler = Scheduler(self.pool, max_batch=max_batch,
                                   chunk=chunk,
                                   prefill_rows=prefill_rows,
                                   prefix_cache=self.prefix_cache)
        # kernel or gather-dense reference: chosen from the platform,
        # exactly as the ops' own dispatchers choose (ops/pallas)
        self.use_kernel = on_tpu() if use_kernel is None \
            else bool(use_kernel)
        self.queue = RequestQueue()
        self.running: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self._time_fn = time_fn or time.monotonic
        self._next_id = 0
        self.steps = 0
        self._calls = 0
        self._reset_clock()
        # host logits round-trips actually paid: sampling (greedy AND
        # temperature/top-k/top-p) runs on device and moves [rows]
        # int32s per step — this stays 0 on every traffic mix
        self.host_logit_fetches = 0
        m = metrics
        self.counters = {k: make_instrument("counter", k, m) for k in
                         ("tokens_generated", "prefill_tokens",
                          "kv_tokens_written",
                          "requests_completed", "preemptions",
                          "decode_steps", "prefill_chunks",
                          "step_calls",
                          # transfers of the step's control data: one
                          # buffer in, one array out, a call
                          "h2d_copies", "d2h_fetches",
                          # prefix cache: hits/misses count request
                          # starts with/without a cached prefix;
                          # tokens_saved = prefill tokens skipped;
                          # evictions = cached pages LRU-reclaimed
                          "prefix_cache_hits", "prefix_cache_misses",
                          "prefix_cache_tokens_saved",
                          "prefix_cache_evictions",
                          # speculative decoding: draft tokens proposed
                          # / accepted (committed), bonus tokens riding
                          # verify rows (always present so the cluster
                          # Prometheus merge sees a uniform schema;
                          # zero on non-spec engines)
                          "spec_proposed", "spec_accepted",
                          "spec_bonus_tokens",
                          # drafts a self-drafting step staged for the next
                          "spec_drafted",
                          # SLO traffic plane (serving/slo): per-class
                          # admission/preemption counts and the host
                          # KV tier's page moves — always present (zero
                          # without a host tier / on default-class
                          # traffic) so the cluster merge stays uniform
                          "admitted_interactive", "admitted_standard",
                          "admitted_batch", "preempted_interactive",
                          "preempted_standard", "preempted_batch",
                          "host_evictions", "host_hits",
                          "host_refetch_bytes",
                          # state slots handed out (zero without state layers)
                          "state_slot_allocs",
                          # what the steps read (serving/step_account)
                          *COUNTERS,
                          # the always-on clock (header): seconds of
                          # step() before / in / after the compiled call
                          # and between two steps with requests running;
                          # the part of slow steps' walls beyond the rule
                          "host_before_s", "call_s", "host_after_s",
                          "between_steps_s", "slow_step_s")}
        self.gauges = {k: make_instrument("gauge", k, m) for k in
                       ("batch_occupancy", "page_utilization",
                        "queue_depth",
                        # KV footprint (satellite of DESIGN.md §21):
                        # bytes of page storage per cached token —
                        # static per layout — and bytes held by
                        # currently-allocated pages; both derive from
                        # kv_pool.page_shape_bytes so the lint /
                        # transport / metrics planes can never disagree
                        "kv_bytes_per_token", "kv_bytes_in_use",
                        # live host-tier page count (0 without one)
                        "host_pages", *GAUGES)}
        self.gauges["kv_bytes_per_token"].set(
            self.pool.kv_bytes_per_token)
        lb = list(latency_buckets if latency_buckets is not None
                  else DEFAULT_LATENCY_BUCKETS)
        self.histograms = {
            "ttft": make_instrument("histogram", "ttft", m, buckets=lb),
            "tbt": make_instrument("histogram", "tbt", m, buckets=lb),
            "tpot": make_instrument("histogram", "tpot", m),
            "request_latency": make_instrument("histogram",
                                               "request_latency", m),
        }
        # host-RAM tier for cold prefix-cache pages (serving/slo,
        # DESIGN.md §22): pass a HostTier instance, True (defaults),
        # or an int page capacity.  Evicted refcount-0 cached pages
        # stage to host instead of dropping; a chain-hash hit refetches
        # them bit-exact through PageTransport.inject, priced.
        self.host_tier = None
        if host_tier:
            if self.prefix_cache is None:
                raise ValueError("host_tier requires prefix_cache=True")
            from .slo.host_tier import HostTier
            ht = host_tier if isinstance(host_tier, HostTier) else (
                HostTier() if host_tier is True
                else HostTier(int(host_tier)))
            ht.bind(self.pool, self.prefix_cache,
                    counters=self.counters, gauges=self.gauges,
                    tracer_fn=lambda: self.tracer,
                    time_fn=self._time_fn)
            self.host_tier = ht
        # speculative decoding (serving/spec.py, DESIGN.md §20): a
        # draft model proposes spec_k greedy tokens per decode-ready
        # request; the scheduler packs them as verify rows and the
        # unified executable's on-device accept head returns
        # accepted_len + a bonus token per row
        self.spec: Optional[SpecDecoder] = None
        self.spec_k = 0
        if spec is not None:
            self.spec_k = int(spec.k)
            if not self.self_draft:
                self.spec = SpecDecoder(spec, cfg, self.scheduler.max_batch,
                                        self.max_model_len, self.spec_k)
            self.scheduler.verify_slots = self.scheduler.max_batch
            self.scheduler.spec_width = self.spec_k + 1
        self.scheduler.block = self.block
        self.scheduler.mask_id = cfg.mask_token_id
        # THE executable: fixed (max_seqs, chunk, prefill_rows) shapes,
        # compiled exactly once — no bucket grid, no per-request prefill.
        # ``step_fn`` lets N identically-shaped engines (cluster
        # replicas) share ONE jitted program: the jit cache keys on
        # argument shapes, so the whole replica fleet compiles once.
        self._compiled: Dict[str, Callable] = {
            "unified": step_fn if step_fn is not None
            else build_unified_step_fn(
                cfg, self.scheduler.max_batch, self.scheduler.chunk,
                self.scheduler.prefill_rows, self.max_pages_per_seq,
                page_size, use_kernel=self.use_kernel,
                spec_k=self.spec_k, page_quant=page_quant)}
        if self.spec is not None:
            # the draft programs join the jit-cache compile guard: a
            # silent draft retrace trips compile_count just like a
            # unified-step retrace would
            self._compiled.update(self.spec.compiled)
        # the static packed layout (decode slots, prefill chunk slots,
        # then -- spec mode -- one (k+1)-wide verify slot per
        # decode-capable request) and the step's two buffers: from the
        # shapes alone, so a shared ``step_fn`` and this engine agree
        s, r, ck = (self.scheduler.max_batch, self.scheduler.prefill_rows,
                    self.scheduler.chunk)
        self.layout = StepLayout(cfg, s, ck, r, self.max_pages_per_seq,
                                 self.spec_k, page_size=page_size)
        # what each step read, from its own inputs and outputs
        self.account = StepAccount(cfg, self.layout, self.pool,
                                   self.state_store, self.scheduler,
                                   self.spec_k, self.counters, self.gauges)
        self._register_for_analysis()

    # -- submission ----------------------------------------------------------

    def add_request(self, prompt_ids: Sequence[int], max_new_tokens: int,
                    temperature: float = 0.0, top_k: int = 0,
                    top_p: float = 0.0, seed: int = 0,
                    eos_token_id: Optional[int] = None,
                    arrival_time: Optional[float] = None,
                    stream_cb: Optional[Callable] = None,
                    slo_class: str = "standard") -> Request:
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.block and self.cfg.mask_token_id in prompt:
            raise ValueError(
                f"the mask id {self.cfg.mask_token_id} in a prompt: a "
                "position fed as it reads as not yet known")
        total = len(prompt) + int(max_new_tokens)
        if total > self.max_model_len:
            raise ValueError(
                f"prompt+max_new_tokens = {total} exceeds max_model_len "
                f"{self.max_model_len}")
        if self.pool.pages_for(total) > self.pool.num_usable:
            raise ValueError(
                f"request needs {self.pool.pages_for(total)} pages; pool "
                f"has {self.pool.num_usable} — it could never run")
        now = self._now()
        req = Request(req_id=self._next_id, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), seed=int(seed),
                      eos_token_id=eos_token_id,
                      arrival_time=now if arrival_time is None
                      else float(arrival_time), stream_cb=stream_cb,
                      slo_class=slo_class)
        req.submit_time = max(now, req.arrival_time)
        req.trace_t0 = req.submit_time      # queued segment opens here
        self._next_id += 1
        self.queue.push(req)
        self.protocol_log.append({"ev": "req.queued",
                                  "key": f"req:{req.req_id}",
                                  "seq": protocol_seq()})
        tr = self.tracer
        if tr.enabled:
            tr.instant("enqueue", track=f"req {req.req_id}",
                       ts=req.submit_time, req=req.req_id,
                       prompt_tokens=len(prompt),
                       max_new_tokens=int(max_new_tokens),
                       slo_class=req.slo_class,
                       queue_depth=len(self.queue))
        return req

    def adopt_request(self, prompt: Sequence[int],
                      generated: Sequence[int], max_new_tokens: int,
                      pages: Optional[Sequence[int]] = None,
                      pos: int = 0, temperature: float = 0.0,
                      top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                      eos_token_id: Optional[int] = None,
                      arrival_time: Optional[float] = None,
                      stream_cb: Optional[Callable] = None,
                      slo_class: str = "standard") -> Request:
        """Admit a MID-FLIGHT request: ``generated`` tokens already
        sampled elsewhere and (optionally) ``pages`` in THIS engine's
        pool already holding KV for positions ``[0, pos)`` — the
        disaggregated prefill→decode handoff entry point
        (``serving/cluster``): a prefill replica finishes the prompt,
        the transport copies its pages into this pool, and decode
        resumes here from ``pos`` without recomputing the prefill.

        The adopted request rides the normal admission path (WAITING →
        ``_start`` grants any additional pages → packed steps), so
        backpressure, preemption and tracing all behave normally; a
        preemption falls back to local re-prefill of the full
        accumulated sequence, which reproduces the identical
        continuation at temperature 0 (and under the position-keyed
        sampler for every mode).  Sampling params must match the
        original request or the continuation diverges by design."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        generated = [int(t) for t in
                     np.asarray(generated, np.int64).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if len(generated) >= max_new_tokens:
            raise ValueError("request already finished: "
                             f"{len(generated)} >= {max_new_tokens}")
        if self.window:
            raise ValueError("adoption is not built for window layers: a "
                             "handoff carries no window pages")
        if self.block:
            raise ValueError("adoption is not built with block-wise "
                             "generation: a handoff carries no open block")
        total = len(prompt) + int(max_new_tokens)
        if total > self.max_model_len:
            raise ValueError(
                f"prompt+max_new_tokens = {total} exceeds max_model_len "
                f"{self.max_model_len}")
        if self.pool.pages_for(total) > self.pool.num_usable:
            # same guard as add_request: a request the pool can never
            # hold would otherwise defer at admission forever
            raise ValueError(
                f"request needs {self.pool.pages_for(total)} pages; pool "
                f"has {self.pool.num_usable} — it could never run")
        pages = list(pages or ())
        pos = int(pos)
        if pos and self.state_store is not None:
            raise ValueError(
                "a mid-flight hand-off (pos > 0) carries K/V pages but no "
                "recurrent state: adopt with pos=0 and let it re-prefill")
        if pos > len(prompt) + len(generated):
            raise ValueError(f"pos {pos} past the accumulated tokens")
        if pos and len(pages) < self.pool.pages_for(pos):
            raise ValueError(
                f"pages cover {len(pages) * self.pool.page_size} tokens "
                f"but pos is {pos}")
        now = self._now()
        req = Request(req_id=self._next_id, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), seed=int(seed),
                      eos_token_id=eos_token_id,
                      arrival_time=now if arrival_time is None
                      else float(arrival_time), stream_cb=stream_cb,
                      slo_class=slo_class)
        req.tokens = prompt + generated
        req.out_tokens = list(generated)
        req.pages = pages
        req.peak_pages = len(pages)
        req.pos = pos
        req.submit_time = max(now, req.arrival_time)
        req.trace_t0 = req.submit_time
        self._next_id += 1
        self.queue.push(req)
        self.protocol_log.append({"ev": "req.queued",
                                  "key": f"req:{req.req_id}",
                                  "seq": protocol_seq()})
        tr = self.tracer
        if tr.enabled:
            tr.instant("adopt", track=f"req {req.req_id}",
                       ts=req.submit_time, req=req.req_id,
                       prompt_tokens=len(prompt),
                       generated_tokens=len(generated), pos=pos,
                       handoff_pages=len(pages),
                       queue_depth=len(self.queue))
        return req

    # -- loop ----------------------------------------------------------------

    def _now(self) -> float:
        return self._time_fn()

    @property
    def tracer(self):
        """The effective tracer: the injected one, else the ambient
        global (usually ``NULL_TRACER`` — the no-op)."""
        return self._tracer if self._tracer is not None else get_tracer()

    def set_tracer(self, tracer) -> None:
        """Swap the engine's tracer live (None reverts to following the
        ambient global) — lets a service toggle tracing on a running
        engine, and a traced and an untraced run share one executable.
        While a tracer is set here the collector's runs are its ``gc``
        spans (``SpanTracer.watch_gc``); the hook goes with the tracer."""
        if self._gc_watched is not None:
            self._gc_watched.unwatch_gc()
            self._gc_watched = None
        self._tracer = tracer
        if tracer is not None and tracer.watch_gc():
            self._gc_watched = tracer

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.running)

    def step(self) -> int:
        """One engine iteration: admit, pack prefill chunks + decodes
        into ONE ragged batch, run the unified executable.  Returns the
        number of tokens emitted."""
        now = self._now()
        tr = self.tracer
        # (t0, t1, rows, tokens) of the step's compiled call, if it makes one
        self._call: Optional[Tuple[float, float, int, int]] = None
        if not tr.enabled:
            produced = self._step(tr, now)
            self._clock_step(now, self._now())
            return produced
        # host phases on the profiler's clock (obs/tracer.py mirrors
        # begin/end into jax.profiler): one ``engine_step`` parent whose
        # ``step.*`` children tile it, so a device idle gap can be put
        # down to the phase the host was in.  The finally closes the
        # parent even when the body raises (ending it discards any open
        # child), so a failing step never corrupts the nesting stack.
        step_sp = tr.begin("engine_step", track="engine", ts=now,
                           step=self.steps)
        try:
            produced = self._step(tr, now)
        finally:
            t = self._now()
            if self._phase_sp is not None:
                tr.end(self._phase_sp, ts=t)
                self._phase_sp = None
            tr.end(step_sp, ts=t, **self._step_attrs)
        self._clock_step(now, t)
        return produced

    def _reset_clock(self) -> None:
        """The always-on clock's state since the reset (header)."""
        self._clocked = 0                   # steps booked, and the
        self._wall_s = 0.0                  # sum of their walls
        self._reset_at = self._now()
        # the last step's exit, if requests were running then
        self._last_exit: Optional[float] = None
        self._gc_full = gc.get_stats()[2]["collections"]
        self.slow_steps: List[Dict[str, Any]] = []

    def _clock_step(self, entry: float, exit_: float) -> None:
        """Book one step's wall under the four parts of the always-on
        clock, and keep the longest steps (header)."""
        c = self.counters
        between = 0.0
        if self._last_exit is not None:
            between = entry - self._last_exit
            c["between_steps_s"].inc(between)
        # an open-loop caller that sleeps to the next arrival with an
        # empty engine is not a stall
        self._last_exit = exit_ if self.running else None
        t0, t1, rows, tokens = self._call or (exit_, exit_, 0, 0)
        c["host_before_s"].inc(t0 - entry)
        c["call_s"].inc(t1 - t0)
        c["host_after_s"].inc(exit_ - t1)
        wall = between + (exit_ - entry)
        # (no rule before a step has taken time: the first step after a
        # reset, a test's clock that stands still inside a step)
        mean = self._wall_s / self._clocked if self._wall_s > 0.0 else 0.0
        over = wall - SLOW_STEP_FACTOR * mean if mean else 0.0
        self._clocked += 1
        self._wall_s += wall
        full, self._gc_full = self._gc_full, \
            gc.get_stats()[2]["collections"]
        slow = self.slow_steps
        if over > 0.0:
            c["slow_step_s"].inc(over)
        elif len(slow) == SLOW_STEPS_KEPT and wall <= slow[-1]["wall_s"]:
            return
        parts = {"between_steps_s": between, "host_before_s": t0 - entry,
                 "call_s": t1 - t0, "host_after_s": exit_ - t1}
        rec = {"step": self.steps - 1, "at_s": entry - self._reset_at,
               "wall_s": wall, **parts, "rows": rows, "tokens": tokens,
               "full_collections": self._gc_full - full}
        slow.append(rec)
        slow.sort(key=lambda r: -r["wall_s"])
        del slow[SLOW_STEPS_KEPT:]
        if over > 0.0 and rec in slow:
            log.warning(
                "%s: step %d, %.1f s after the reset, took %.3f s, %.3f s "
                "beyond %g x the mean step (%.4f s); most of it in %s "
                "(between_steps %.3f, host_before %.3f, call %.3f, "
                "host_after %.3f s); rows %d, tokens %d, full "
                "collections in it %d", self.name, rec["step"],
                rec["at_s"], wall, over, SLOW_STEP_FACTOR, mean,
                max(parts, key=parts.get), between, t0 - entry, t1 - t0,
                exit_ - t1, rows, tokens, rec["full_collections"])

    def _enter_phase(self, tr, name: str,
                     t: Optional[float] = None) -> float:
        """Close the open ``step.*`` child of ``engine_step`` and open
        ``name`` at the same instant ``t`` (now unless given), which is
        returned.  Traced steps only."""
        if t is None:
            t = self._now()
        if self._phase_sp is not None:
            tr.end(self._phase_sp, ts=t)
        self._phase_sp = tr.begin(name, track="engine", ts=t)
        return t

    def _step(self, tr, now: float) -> int:
        traced = tr.enabled
        if traced:
            self._step_attrs = {"rows": 0, "tokens": 0}
            self._enter_phase(tr, "step.admit", now)
        for req in self.scheduler.admit(self.queue, self.running, now):
            self._start(req)
        live = [r for r in self.running if r.state == RUNNING]
        if self.spec is not None:
            self._stage_spec(live)
        if traced:
            self._enter_phase(tr, "step.pages")
        kept, evicted = self.scheduler.ensure_decode_pages(live)
        for req in evicted:
            self.running.remove(req)
            self.queue.push(req)
            self.counters["preemptions"].inc()
            self.counters[f"preempted_{req.slo_class}"].inc()
            if self.spec is not None:
                # a preempted request leaves the running set: free its
                # draft slot (the cache is stale anyway — resuming
                # re-prefills into a fresh slot).  Releasing, not just
                # invalidating, keeps slot holders ⊆ running, so the
                # admit-overtake path can never exhaust the slot pool
                self.spec.release(req)
            if self.tap is not None:
                # the rewind lint's validity tracking: preemption drops
                # every written KV slot (the pages themselves returned
                # to the pool)
                self.tap.append({"kind": "kv_drop", "req": req.req_id,
                                 "seq": protocol_seq()})
            t = self._now()
            if tr.enabled:
                # the running segment ends here; a fresh queued segment
                # opens at the SAME timestamp (gapless state tiling)
                tr.complete("running", req.trace_t0, t - req.trace_t0,
                            track=f"req {req.req_id}", req=req.req_id)
                tr.instant("preempt", track=f"req {req.req_id}", ts=t,
                           req=req.req_id,
                           n_preemptions=req.n_preemptions,
                           pos_lost=len(req.tokens))
            req.trace_t0 = t
        if traced:
            self._enter_phase(tr, "step.pack")
        rows = self.scheduler.pack(kept)
        if traced:
            # queue_depth counts every queued request, future arrivals
            # included; queue_due only those admission left waiting
            self._step_attrs.update(
                running=len(self.running), queue_depth=len(self.queue),
                free_pages=self.pool.free_pages,
                queue_due=self.queue.due(now))
            if rows:
                self._step_attrs.update(self.scheduler.slot_mix(rows))
        produced = self._run_unified(rows) if rows else 0
        if traced and not rows:
            self._enter_phase(tr, "step.commit")
        if self.debug:
            self.pool.check_invariants()
            if self.prefix_cache is not None:
                self.prefix_cache.check_invariants()
        self.steps += 1
        self.gauges["batch_occupancy"].set(
            len(self.running) / self.scheduler.max_batch)
        self.gauges["page_utilization"].set(self.pool.utilization)
        self.gauges["queue_depth"].set(len(self.queue))
        self.gauges["kv_bytes_per_token"].set(
            self.pool.kv_bytes_per_token)
        self.gauges["kv_bytes_in_use"].set(
            (self.pool.num_usable - self.pool.free_pages)
            * self.pool.page_bytes)
        if self.host_tier is not None:
            self.gauges["host_pages"].set(self.host_tier.host_pages)
        return produced

    def run(self, max_steps: Optional[int] = None
            ) -> Dict[int, List[int]]:
        """Drive until idle (or ``max_steps``); returns
        {req_id: generated tokens} for everything finished so far."""
        while self.has_work:
            if max_steps is not None and self.steps >= max_steps:
                break
            self.step()
        return {rid: list(r.out_tokens)
                for rid, r in self.finished.items()}

    @property
    def compile_count(self) -> int:
        """Compiled program count, read from the REAL jit cache when the
        runtime exposes it — a silent retrace (shape/dtype/weak-type
        drift in the packed arrays) shows up here and trips the CI
        recompile guard, which a structural ``len(_compiled)`` never
        could.  Falls back to one per built executable."""
        n = 0
        for fn in self._compiled.values():
            try:
                n += int(fn._cache_size())
            except Exception:
                n += 1
        return n

    @property
    def executable_calls(self) -> int:
        """Unified-step invocations — engine state (a plain counter), so
        it stays correct under ``metrics=False``."""
        return self._calls

    # -- admission / lifecycle -----------------------------------------------

    def _reclaim_cached_pages(self, n: int) -> int:
        """The pool's reclaim hook: LRU-sweep refcount-0 cached pages
        when the free list runs dry — BEFORE the scheduler falls back to
        recompute preemption."""
        freed = self.prefix_cache.evict(n)
        if freed:
            self.counters["prefix_cache_evictions"].inc(freed)
            tr = self.tracer
            if tr.enabled:
                tr.instant("prefix_cache_evict", track="engine",
                           ts=self._now(), pages_freed=freed,
                           pages_wanted=n)
        return freed

    def _start(self, req: Request) -> None:
        """Move an admitted request to RUNNING: attach the longest
        cached prefix (copy-on-write — the shared pages enter the page
        table read-only and ``pos`` starts at the cached boundary, so
        the KV write plan and the token budget only ever see the
        uncached suffix), then grant the pages the rest of its
        accumulated tokens need.  Prefill itself is chunked over
        subsequent packed steps; there is no prefill call here."""
        looked_up = self.prefix_cache is not None and req.pos == 0 \
            and not req.pages
        if looked_up:
            if self.host_tier is not None:
                # extend the device-cache match with host-tier pages
                # FIRST: restored pages join the index, so the acquire
                # below attaches the deeper chain through the normal
                # copy-on-write path (a dry pool simply stops the
                # restore — the suffix recomputes like any miss)
                self.host_tier.refetch(req.tokens)
            entries = self.prefix_cache.acquire(req)
            if entries:
                req.pages = [e.page for e in entries]
                req.shared_pages = len(entries)
                req.pos = len(entries) * self.pool.page_size
                req.cached_tokens = req.pos
        need = self.pool.pages_for(len(req.tokens)) - len(req.pages)
        pages = self.pool.alloc(need)
        if pages is not None and self.state_store is not None:
            # the recurrent-state slot comes with the pages or not at all
            req.state_slot = self.state_store.alloc(req.req_id)
            if req.state_slot is None:
                self.pool.free(pages)
                pages = None
            else:
                self.counters["state_slot_allocs"].inc()
        tr = self.tracer
        if pages is None:
            if tr.enabled:
                # stays queued: the open queued segment keeps running
                tr.instant("admit_defer", track=f"req {req.req_id}",
                           ts=self._now(), req=req.req_id,
                           pages_needed=need,
                           free_pages=self.pool.free_pages)
            # admission over-committed (another _start this step evicted
            # a cached page the budget counted on): roll back and retry
            # next step — never crash the loop on a page race.  Counters
            # deliberately untouched: the retried start is the SAME
            # logical start, not a second hit/miss.  Only the cache
            # attach this call made is undone — an ADOPTED request
            # (handoff pages pre-attached, pos past the prompt) keeps
            # its pages and cursor for the retry
            if looked_up:
                if self.prefix_cache is not None and req.shared_pages:
                    self.prefix_cache.release(req)
                self._free_window_pages(req)
                req.pages = []
                req.shared_pages = 0
                req.cached_tokens = 0
                req.pos = 0
            self.queue.push(req)
            return
        if looked_up:
            if req.shared_pages:
                self.counters["prefix_cache_hits"].inc()
                self.counters["prefix_cache_tokens_saved"].inc(
                    req.cached_tokens)
            else:
                self.counters["prefix_cache_misses"].inc()
        req.pages = req.pages + pages
        req.peak_pages = max(req.peak_pages, len(req.pages))
        req.state = RUNNING
        self.counters[f"admitted_{req.slo_class}"].inc()
        self.running.append(req)
        self.protocol_log.append({"ev": "req.admit",
                                  "key": f"req:{req.req_id}",
                                  "seq": protocol_seq()})
        t = self._now()
        if tr.enabled:
            # close the queued segment and open running at the same
            # instant; the admission decision carries its page math
            tr.complete("queued", req.trace_t0, t - req.trace_t0,
                        track=f"req {req.req_id}", req=req.req_id,
                        preemptions=req.n_preemptions)
            tr.instant("admit", track=f"req {req.req_id}", ts=t,
                       req=req.req_id, pages_granted=need,
                       pages_total=len(req.pages),
                       cached_pages=req.shared_pages,
                       free_pages=self.pool.free_pages,
                       batch=len(self.running))
            if looked_up and req.shared_pages:
                tr.instant("prefix_cache_hit", track=f"req {req.req_id}",
                           ts=t, req=req.req_id,
                           cached_tokens=req.cached_tokens,
                           shared_pages=req.shared_pages)
        req.trace_t0 = t

    def abort_all(self) -> List[int]:
        """Abort every queued + running request: owned pages return to
        the free list, shared prefix-cache references are released,
        nothing enters ``finished``.  The re-admission path for a
        fenced cluster replica — its re-routed work already lives on
        survivors, so whatever this engine still holds is stale by
        definition.  Returns the aborted engine request ids."""
        victims = list(self.queue.requests())
        victims.extend(self.running)
        for req in victims:
            self.pool.free(req.pages[req.shared_pages:])
            if self.prefix_cache is not None and req.shared_pages:
                self.prefix_cache.release(req)
            if self.spec is not None:
                self.spec.release(req)
            self._free_state_slot(req)
            self._free_window_pages(req)
            req.pages = []
            req.shared_pages = 0
            req.cached_tokens = 0
            req.spec_drafts = []
            req.block, req.block_pass = None, 0
            req.pos = 0
            req.state = FINISHED          # terminal, but never collected
        self.queue.clear()
        self.running.clear()
        if self.debug:
            self.pool.check_invariants()
            if self.prefix_cache is not None:
                self.prefix_cache.check_invariants()
        return [r.req_id for r in victims]

    def _free_window_pages(self, req: Request) -> None:
        if req.win_pages:
            self.pool.window.release(req.win_pages)
            req.win_pages, req.win_first = [], 0

    def _free_state_slot(self, req: Request) -> None:
        if req.state_slot is not None:
            self.state_store.free(req.state_slot)
            req.state_slot = None

    def _stage_spec(self, live: List[Request]) -> None:
        """Draft-propose for every decode-ready request that can still
        profit from speculation (≥ 2 tokens left to emit): ONE batched
        draft call per engine step, drafts staged on the requests for
        the scheduler to pack as verify rows."""
        cands = []
        k_effs: Dict[int, int] = {}
        for r in sorted(live, key=lambda r: (r.arrival_time, r.req_id)):
            if r.state != RUNNING or r.spec_drafts or r.done:
                continue
            if len(r.tokens) - r.pos != 1:
                continue               # mid-prefill: nothing to draft
            k_eff = min(self.spec_k,
                        r.max_new_tokens - r.n_generated - 1)
            if k_eff < 1:
                continue               # last token: plain decode is it
            cands.append(r)
            k_effs[r.req_id] = k_eff
        if not cands:
            return
        tr = self.tracer
        t0 = self._now()
        drafts = self.spec.stage(cands, k_effs, tracer=tr, now=t0)
        dt = self._now() - t0
        total = 0
        for r in cands:
            r.spec_drafts = drafts.get(r.req_id, [])
            total += len(r.spec_drafts)
        self.counters["spec_proposed"].inc(total)
        if tr.enabled and total:
            tr.complete("draft", t0, dt, track="engine",
                        requests=len(cands), proposed=total,
                        k=self.spec_k)

    # -- the unified step ----------------------------------------------------

    def _pack_arrays(self, rows: List[Tuple[Request, int, int]]):
        """Host-side marshalling of the packed step into ONE int32
        buffer (``serving/decode.StepLayout``): flat token arrays +
        per-row ragged descriptors + per-row sampling params.  Returns
        the buffer and its fields as views on it.  A verify
        row's fed tokens are the committed tail plus its staged drafts
        (``qlen = 1 + spec_len``), written through the SAME trash-page-
        safe per-token KV write plan as any prefill chunk.  The buffer
        is a fresh one every step: the CPU backend may alias a NumPy
        array it was given, so a reused one would rewrite the step
        before it."""
        lay = self.layout
        ps = self.pool.page_size
        vbase = self.scheduler.max_batch + self.scheduler.prefill_rows
        packed = np.zeros(lay.size, np.int32)
        f = lay.views(packed)
        f["token_page"].fill(TRASH_PAGE)
        f["page_tables"].fill(TRASH_PAGE)
        if self.self_draft:
            f["next_tok"].fill(-1)
        # (the window fields start at 0, which is that space's trash page)
        tokens, token_pos = f["tokens"], f["token_pos"]
        token_page, token_off = f["token_page"], f["token_off"]
        for req, qlen, row in rows:
            if req.state_slot is not None:
                f["state_slots"][row] = req.state_slot
            start = int(lay.cu_q[row])
            pos = np.arange(req.pos, req.pos + qlen)
            if self.block and row >= vbase:
                # a generating row: its open block, and this pass's rule
                # (a block with no mask left is committed: nothing to
                # pick); a fused row's second half is the next block, all
                # masks, under ITS first pass's rule
                fused = qlen > self.block
                tokens[start:start + qlen] = req.block + \
                    [self.cfg.mask_token_id] * (qlen - self.block)
                if fused or self.cfg.mask_token_id in req.block:
                    f["unmask_k"][row], f["unmask_tau"][row] = \
                        self.denoise.unmask(
                            self.block, 0 if fused else req.block_pass)
                else:
                    f["unmask_tau"][row] = 2.0
            else:
                seq = req.tokens if not (row >= vbase and req.spec_drafts) \
                    else req.tokens + req.spec_drafts
                tokens[start:start + qlen] = seq[req.pos:req.pos + qlen]
            token_pos[start:start + qlen] = pos
            pages = self._page_array(req)
            token_page[start:start + qlen] = pages[pos // ps]
            token_off[start:start + qlen] = pos % ps
            if self.window:
                # the row's reach in the window layers: its table, the
                # position of the table's first slot, the write plan
                wp = np.asarray(req.win_pages, np.int32)
                f["win_tables"][row, :len(wp)] = wp
                f["win_base"][row] = req.win_first * ps
                f["win_token_page"][start:start + qlen] = \
                    wp[pos // ps - req.win_first]
            f["q_lens"][row] = qlen
            f["page_tables"][row, :len(pages)] = pages
            f["ctx_lens"][row] = req.pos + qlen
            f["temps"][row] = req.temperature
            f["top_ps"][row] = req.top_p
            f["top_ks"][row] = req.top_k
            f["seeds"][row] = req.seed
            if row >= vbase and req.spec_drafts:
                f["spec_lens"][row] = len(req.spec_drafts)
            if self.self_draft and req.pos + qlen < len(req.tokens):
                f["next_tok"][row] = req.tokens[req.pos + qlen]
        return packed, f

    @staticmethod
    def _page_array(req: Request) -> np.ndarray:
        """``req.pages`` as int32, kept on the request from step to step:
        the list only grows in place (``extend``) or is replaced by
        another, so the same list object is the same table as far as the
        array goes, and a decode step converts the page it gained, not
        the hundreds under a long context."""
        pages = req.pages
        src, arr = req.page_array or (None, ())
        if src is not pages or len(arr) > len(pages):
            arr = np.asarray(pages, np.int32)
        elif len(arr) < len(pages):
            arr = np.concatenate(
                [arr, np.asarray(pages[len(arr):], np.int32)])
        else:
            return arr
        req.page_array = (pages, arr)
        return arr

    def _run_unified(self, rows: List[Tuple[Request, int, int]]) -> int:
        s = self.scheduler.max_batch
        vbase = s + self.scheduler.prefill_rows
        for req, qlen, row in rows:
            if row < vbase and req.spec_drafts:
                # packed outside its verify slot (defensive: with one
                # dedicated slot per sequence this shouldn't happen) —
                # this row commits a token the drafts never saw, so
                # they are stale and dropped before the step
                req.spec_drafts = []
            if self.block and row >= vbase and req.block is None:
                # the prompt's whole blocks are in: open the next block with
                # what is left of the tokens, the rest not yet known
                known = req.tokens[req.pos:]
                req.block = known + [self.cfg.mask_token_id] * (
                    self.block - len(known))
        tr = self.tracer
        traced = tr.enabled
        tp = self._now() if traced else 0.0
        packed, fields = self._pack_arrays(rows)
        page_tables, ctx_lens = fields["page_tables"], fields["ctx_lens"]
        kv_tokens = sum(q for _, q, _ in rows)   # every fed token's KV
        if traced:
            t = self._now()
            tr.complete("pack_arrays", tp, t - tp, track="engine",
                        step=self.steps, rows=len(rows),
                        page_slots=sum(len(r.pages) for r, _, _ in rows))
            self._enter_phase(tr, "step.tap", t)
        if self.tap is not None:
            self.tap.append({
                "kind": "unified",
                "seq": protocol_seq(),
                "rows": [(row, req.pos, qlen) for req, qlen, row in rows],
                # per-request read extent for the spec-rewind-leak lint:
                # this step WRITES [pos, pos+qlen) and READS [0, ctx) —
                # a read past the valid-KV watermark (stale slots left
                # by a rewind, not yet re-written) is a leak
                "reads": [(req.req_id, req.pos, qlen,
                           int(ctx_lens[row]))
                          for req, qlen, row in rows],
                "page_tables": page_tables.copy(),
                # refcount snapshot of the read-only cached pages: the
                # cow-page-write lint flags any live row whose write
                # plan targets a page in this snapshot (membership =
                # cached = read-only, whatever the sharer count)
                # (``pool.refcount`` of a cached page, without a call a
                # page: a long document's cache is thousands of them)
                "refcounts": {pg: 1 + n
                              for pg, n in self.pool._cached.items()}})
        t0 = self._enter_phase(tr, "step.h2d") if traced else self._now()
        # the step's ONE host-to-device transfer; placed as any
        # uncommitted array, so a sharded pool has it where it wants it
        packed_dev = jax.device_put(packed)
        self.counters["h2d_copies"].inc()
        states = ()
        if self.hybrid:
            st = self.state_store
            states = (st.conv, st.ssm) if st is not None else ((), ())
        if traced:
            self._enter_phase(tr, "step.dispatch")
        # the call returns once the executable is enqueued; the host
        # waits for the device in the fetch below
        out, new_k, new_v, *new_states = self._compiled["unified"](
            self.params, packed_dev, self.pool.k_pages, self.pool.v_pages,
            *states)
        if self.early_fetch:
            out.copy_to_host_async()
        if traced:
            self._enter_phase(tr, "step.fetch")
        # the ONE device-to-host fetch: [rows] int32 tokens and, beside
        # them, the expert load (hybrid) / accepted lengths (spec)
        out = self.layout.split(np.asarray(out))
        self.counters["d2h_fetches"].inc()
        toks = out["next_tokens"]
        accs = out.get("accepted")
        drafts = out.get("draft")
        t1 = self._now()
        dt = t1 - t0
        self._call = (t0, t1, len(rows), kv_tokens)
        if traced:
            self._enter_phase(tr, "step.commit", t1)
        self.pool.set_pages(new_k, new_v)
        if self.state_store is not None:
            self.state_store.set_arrays(*new_states)
        # what the step spends on the engine's own counters and span
        # attributes (traced steps put the ``account`` span round it)
        ta = self._now() if traced else 0.0
        # classify by SLOT, not q_len: a chunk_size=1 prefill chunk is
        # still a prefill chunk, and a verify row is neither
        n_decode = sum(1 for _, _, row in rows if row < s)
        n_chunk = sum(1 for _, _, row in rows if s <= row < vbase)
        attrs = self.account(rows, fields, out, traced)
        self._calls += 1
        self.counters["step_calls"].inc()
        self.counters["kv_tokens_written"].inc(kv_tokens)
        if traced:
            self._step_attrs.update(rows=len(rows), tokens=kv_tokens)
            tr.complete("account", ta, self._now() - ta, track="engine",
                        step=self.steps)
            # the span every reconciliation row hangs off: exec= names
            # the registered ExecutableHandle (obs.reconcile looks the
            # static predictions up by it at report time)
            tr.complete("unified_step", t0, dt, track="engine",
                        exec=f"{self.name}/unified", step=self.steps,
                        rows=len(rows), tokens=kv_tokens,
                        h2d_bytes=packed.nbytes, **attrs)
        if n_decode:
            self.counters["decode_steps"].inc()
        self.counters["prefill_chunks"].inc(n_chunk)
        produced = 0
        if self.block:
            # plain lists once a step, not an array scalar a position
            blocks = (out["block_tokens"].tolist(),
                      out["block_flags"].tolist(),
                      out["block_conf"].view(np.float32).tolist()
                      if self.tap is not None else None)
        for req, qlen, row in rows:
            if self.block and row >= vbase:
                produced += self._commit_block(req, blocks, row - vbase, dt)
                if qlen > self.block:
                    # a fused row: the commit above, then the next block
                    # opened and its first pass's picks, in that order
                    req.block = [self.cfg.mask_token_id] * self.block
                    self._commit_block(req, blocks, row - vbase, dt)
                continue
            pre = max(0, min(qlen, req.prompt_len - req.pos))
            if pre:
                self.counters["prefill_tokens"].inc(pre)
                if tr.enabled:
                    tr.complete("prefill_chunk", t0, dt,
                                track=f"req {req.req_id}",
                                req=req.req_id, q_len=qlen,
                                prefill_tokens=pre, pos=req.pos,
                                budget_slice=qlen,
                                cached_skip=req.cached_tokens)
            if row >= vbase and req.spec_drafts:
                produced += self._commit_verify(
                    req, int(accs[row]), int(toks[row]), t0, dt)
            else:
                req.pos += qlen
                if self.block or req.pos != len(req.tokens):
                    continue       # (a block-wise model's chunk emits nothing)
                self._emit(req, int(toks[row]))  # the row reached its tip:
                produced += 1                    # commit the sample
                req.resuming = False
                self._observe_token(req, row < s, dt)
                self._maybe_finish(req)
            if drafts is not None and req.state == RUNNING:
                # the MTP module's proposal behind the token just
                # committed: the row's draft of its next step
                req.spec_drafts = [int(drafts[row])]
                self.counters["spec_drafted"].inc()
        return produced

    def _observe_token(self, req: Request, decode_slot: bool,
                       dt: float) -> None:
        """Latency bookkeeping + trace instant for ONE emitted token."""
        tr = self.tracer
        now = self._now()
        if tr.enabled:
            tr.instant("token", track=f"req {req.req_id}", ts=now,
                       req=req.req_id, n=req.n_generated,
                       decode_slot=bool(decode_slot))
        if req.first_token_time is None:
            req.first_token_time = now
            self.histograms["ttft"].observe(now - req.submit_time)
        else:
            self.histograms["tbt"].observe(
                now - (req.last_token_time or now))
            self.histograms["tpot"].observe(dt)
        req.last_token_time = now

    def _commit_block(self, req: Request, blocks: Tuple[list, list, Any],
                      slot: int, dt: float) -> int:
        """The outcome of one forward of a block row.  A DENOISE pass (the
        block went in with masks): the positions the step's selection
        flagged take its tokens; nothing of the pass's K/V is kept — the
        next pass overwrites it, the context has not moved.  A COMMIT pass
        (no mask went in): its K/V stands, the block's tokens that the
        request did not bring itself are emitted in position order (those
        past ``max_new_tokens`` or behind an end-of-sequence token are
        dropped and the request ends), the context moves by the block and
        the next block opens at the request's next step — or, in a FUSED
        row, in this one: the row fed the committed block and the next one
        all masks, so the caller applies the commit, opens the next block
        and calls again for its first denoise pass (the step's selection
        is that pass's).  Under the block-wise mask the committed half is
        the forward "of its own" that a plain commit is (the family's
        ``store_kv``): tokens are emitted AT the commit either way.
        ``blocks``: the
        step's ``block_tokens`` / ``block_flags`` / ``block_conf`` as lists
        (the last only while the analysis tap is on), of each slot's open
        block — a plain commit's own, which selects nothing.  Returns the
        tokens emitted."""
        b, mask = self.block, self.cfg.mask_token_id
        x = req.block
        toks, flags = blocks[0][slot], blocks[1][slot]
        masked = [j for j in range(b) if x[j] == mask]
        picked = [j for j in masked if flags >> j & 1]
        if self.tap is not None:
            conf = blocks[2][slot]
            req.denoise_log.append((
                req.pos, tuple(x), tuple(picked),
                tuple(toks[j] for j in picked),
                tuple(conf[j] for j in masked)))
        if masked:
            for j in picked:
                x[j] = toks[j]
            req.block_pass += 1
            return 0
        known = len(req.tokens) - req.pos      # what the request brought
        self.counters["prefill_tokens"].inc(
            max(0, min(b, req.prompt_len - req.pos)))
        emitted = 0
        for tok in x[known:]:
            if req.done:
                break
            self._emit(req, tok)
            emitted += 1
            self._observe_token(req, False, dt)
        if known + emitted == b:
            # (a block cut short stays out of ``pos``: its K/V saw tokens
            # the request does not hold, and no page with it is cached)
            req.pos += b
        req.block, req.block_pass = None, 0
        req.resuming = False
        self.counters["blocks_committed"].inc()
        self._maybe_finish(req)
        return emitted

    def _commit_verify(self, req: Request, accepted: int,
                       bonus: int, t0: float, dt: float) -> int:
        """Commit a verify row's outcome: the accepted draft prefix
        plus the bonus token, capped by ``max_new_tokens``/EOS, then
        rewind ``pos`` to the accepted boundary.  Rejected positions'
        KV slots beyond the boundary are STALE — they are re-written by
        the next burst before anything can read them (the write plan
        covers every fed position ahead of the attention, and
        ``ctx_lens`` never reaches past the written extent; the
        ``spec-rewind-leak`` lint audits exactly this from the tap).
        Returns the number of requests that emitted (0 or 1)."""
        drafts = req.spec_drafts
        spec_len = len(drafts)
        n0 = len(req.tokens)
        committed_drafts = 0
        emitted = 0
        for i, tok in enumerate(drafts[:accepted] + [bonus]):
            if req.n_generated >= req.max_new_tokens:
                break
            self._emit(req, int(tok))
            emitted += 1
            if i < accepted:
                committed_drafts += 1
            self._observe_token(req, False, dt)
            if req.eos_token_id is not None and \
                    int(tok) == req.eos_token_id:
                break
        # rewind: the first spec_len - committed_drafts fed positions
        # past the boundary hold rejected/stale KV; the next verify
        # burst (or re-prefill) re-writes them in place
        req.pos = n0 + committed_drafts
        req.spec_drafts = []
        if self.tap is not None:
            req.verify_log.append((n0, tuple(drafts), accepted))
        self.counters["spec_accepted"].inc(committed_drafts)
        if emitted > committed_drafts:
            self.counters["spec_bonus_tokens"].inc()
        tr = self.tracer
        if tr.enabled:
            tr.complete("verify", t0, dt, track=f"req {req.req_id}",
                        req=req.req_id, proposed=spec_len,
                        accepted=accepted, committed=emitted)
            tr.instant("spec_accept", track=f"req {req.req_id}",
                       ts=self._now(), req=req.req_id, n=committed_drafts,
                       bonus=int(emitted > committed_drafts))
        if self.tap is not None and committed_drafts < spec_len:
            self.tap.append({"kind": "spec_rewind", "req": req.req_id,
                             "seq": protocol_seq(),
                             "valid_upto": int(req.pos),
                             "written_upto": int(n0 + spec_len)})
        self._maybe_finish(req)
        return 1 if emitted else 0

    # -- sampling / retirement ----------------------------------------------

    def _emit(self, req: Request, token: int) -> None:
        """Commit the next token — ALWAYS sampled on device by the
        unified executable (greedy argmax bit-for-bit with solo
        ``generate()``; temperature/top-k/top-p keyed by
        ``(seed, position)`` for batching-independent replays)."""
        tok = int(token)
        req.tokens.append(tok)
        req.out_tokens.append(tok)
        self.counters["tokens_generated"].inc()
        if req.stream_cb is not None:
            req.stream_cb(req, tok)

    def _maybe_finish(self, req: Request) -> None:
        if not req.done:
            return
        if self.spec is not None:
            self.spec.release(req)
            req.spec_drafts = []
        if self.prefix_cache is not None:
            # fully-written pages enter the cache index (refcount 0,
            # LRU-evictable); duplicates and the partial tail are freed;
            # shared references released
            self.prefix_cache.on_finish(req)
        else:
            self.pool.free(req.pages)
        self._free_window_pages(req)
        self._free_state_slot(req)
        req.pages = []
        req.state = FINISHED
        req.finish_time = self._now()
        self.protocol_log.append({"ev": "req.finish",
                                  "key": f"req:{req.req_id}",
                                  "seq": protocol_seq()})
        tr = self.tracer
        if tr.enabled:
            tr.complete("running", req.trace_t0,
                        req.finish_time - req.trace_t0,
                        track=f"req {req.req_id}", req=req.req_id)
            tr.instant("finish", track=f"req {req.req_id}",
                       ts=req.finish_time, req=req.req_id,
                       new_tokens=req.n_generated,
                       preemptions=req.n_preemptions,
                       peak_pages=req.peak_pages)
        req.trace_t0 = req.finish_time
        if req in self.running:
            self.running.remove(req)
        self.finished[req.req_id] = req
        self.counters["requests_completed"].inc()
        self.histograms["request_latency"].observe(
            req.finish_time - req.submit_time)

    # -- analysis ------------------------------------------------------------

    def _register_for_analysis(self) -> None:
        """Expose the unified executable to the static analyzer
        (hetu_tpu/analysis): abstract arg specs are fully determined by
        the engine's fixed layout, so the handle can lower without
        running."""
        from ..graph.graph import register_executable
        sds = lambda a: jax.ShapeDtypeStruct(np.shape(a),  # noqa: E731
                                             np.asarray(a).dtype) \
            if not hasattr(a, "aval") else jax.ShapeDtypeStruct(a.shape,
                                                                a.dtype)
        params = jax.tree_util.tree_map(sds, self.params)
        # k and v page stacks differ in shape (and dtype) under the MLA
        # latent layout — build each spec from its own arrays
        k_pages = tuple(sds(p) for p in self.pool.k_pages)
        v_pages = tuple(sds(p) for p in self.pool.v_pages)
        args = (params, self.layout.abstract(), k_pages, v_pages)
        if self.hybrid:
            st = self.state_store
            args += (tuple(sds(a) for a in st.conv),
                     tuple(sds(a) for a in st.ssm)) if st is not None \
                else ((), ())
        meta = {
            "kind": "serving_unified",
            "mesh_axes": {},
            # model weights ride in as closed-over inputs: replicated by
            # design on the single-device path (trainable=False keeps
            # replicated-large-param quiet; a tp-sharded pool analysis
            # would annotate pspecs here)
            "params": [],
            # single-device (or fully explicit) program: NO collective
            # may appear that the inventory doesn't list
            "allowed_gspmd": {} if self.pool.sharding is None else None,
            "scalar_fetches": 0,
            "serving": lambda: {"pool": self.pool,
                                "page_size": self.pool.page_size,
                                "tap": list(self.tap or ()),
                                # the page + engine-request planes of
                                # the protocol event stream
                                "pool_log": list(self.pool.event_log),
                                "protocol": list(self.protocol_log)},
        }
        if self.host_tier is not None:
            # host-tier page-move records for the host-offload-unpriced
            # rule; engines without a host tier stay out of scope
            meta["host_offload"] = \
                lambda: list(self.host_tier.records)
        if self.pool.sharding is None:
            # per-edge claim: the single-device serving path predicts
            # ZERO comm edges — any emitted collective is unexplained
            # by construction (a tp-sharded pool would declare its
            # attention/head reduction edges here instead)
            meta["pspec_edges"] = []
        register_executable(f"{self.name}/unified",
                            self._compiled["unified"], args, meta)

    def unregister_analysis(self) -> None:
        """Drop this engine's executables from the analysis registry.

        Registration closes over the engine (pool snapshot hook), so a
        long-running service that retires engines must call this (or
        reuse the name — construction clears its own namespace) to let
        the pool's HBM/host arrays be collected."""
        from ..graph.graph import clear_executables
        clear_executables(f"{self.name}/")

    # -- observability -------------------------------------------------------

    def metrics_text(self) -> str:
        """Prometheus text exposition of every engine instrument
        (``utils.metrics.render_prometheus``): counters and gauges
        as-is, histograms as ``_bucket``/``_sum``/``_count`` — ready
        for a /metrics scrape endpoint."""
        insts: Dict[str, Any] = {}
        insts.update(self.counters)
        insts.update(self.gauges)
        insts.update(self.histograms)
        return render_prometheus(insts)

    def reset_metrics(self) -> None:
        """Zero every counter/gauge/histogram AND the step counter (the
        compiled executable and all request state stay) — lets a bench
        separate the compile-bearing first trace from steady-state
        serving.  ``steps`` and ``executable_calls`` reset too, so
        ``run(max_steps=...)`` and the call count describe the trace
        since the reset, not the engine's lifetime (``compile_count``
        deliberately does NOT reset — compiles are lifetime state)."""
        self.steps = 0
        self._calls = 0
        self._reset_clock()
        for d in (self.counters, self.gauges, self.histograms):
            for k, inst in list(d.items()):
                if inst.__class__.__name__ == "_NullInstrument":
                    continue
                kw = {"buckets": list(inst.buckets)} \
                    if getattr(inst, "buckets", None) else {}
                d[k] = make_instrument(inst.__class__.__name__.lower(),
                                       k, True, **kw)
        if self.gauges["kv_bytes_per_token"].__class__.__name__ \
                != "_NullInstrument":
            # layout-static: re-seed rather than read 0 until a step
            self.gauges["kv_bytes_per_token"].set(
                self.pool.kv_bytes_per_token)

    def metrics_summary(self) -> Dict[str, Any]:
        out = {k: c.value for k, c in self.counters.items()}
        out.update({k: g.value for k, g in self.gauges.items()})
        for k, h in self.histograms.items():
            out[k] = h.summary()
        out["ttft_buckets"] = self.histograms["ttft"].bucket_counts()
        out["tbt_buckets"] = self.histograms["tbt"].bucket_counts()
        out["compile_count"] = self.compile_count
        out["executable_calls"] = self.executable_calls
        out["host_logit_fetches"] = self.host_logit_fetches
        # prefix cache: request-level hit rate since the last
        # reset_metrics (warm a shared header, reset, replay: 1.0)
        hits = self.counters["prefix_cache_hits"].value
        miss = self.counters["prefix_cache_misses"].value
        out["prefix_cache_hit_rate"] = hits / max(hits + miss, 1.0)
        out["prefix_cache_pages"] = self.pool.cached_pages
        # speculative decoding: draft hit rate + emitted tokens per
        # executable call since the last reset (non-spec engines report
        # rate 0 / plain 1-token-per-emitting-row cadence)
        prop = self.counters["spec_proposed"].value
        out["spec_accept_rate"] = \
            self.counters["spec_accepted"].value / max(prop, 1.0)
        out["accepted_per_step"] = (
            (self.counters["spec_accepted"].value +
             self.counters["spec_bonus_tokens"].value) /
            max(self.counters["step_calls"].value, 1.0))
        return out
