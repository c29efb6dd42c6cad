"""Serving subsystem: paged KV pool, paged attention, continuous batching.

The load-bearing contract: at temperature 0, the paged engine —
batching, paging, late admission, preemption and all — produces
BIT-FOR-BIT the tokens of a solo dense-cache ``generate()`` run.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.models.generate import generate
from hetu_tpu.ops.paged_attention import paged_attention_reference
from hetu_tpu.serving import (Engine, PagedKVPool, RequestQueue, TRASH_PAGE)
from hetu_tpu.utils.metrics import (Counter, Gauge, Histogram,
                                    NULL_INSTRUMENT, make_instrument)


def _build_state(cfg, seed=3):
    ht.set_seed(seed)
    with ht.graph("eager", create_new=True):
        model = GPTLMHeadModel(cfg)
        model.logits(np.zeros((1, 4), np.int32))
        state = {k: np.asarray(v) for k, v in model.state_dict().items()}
    return state


def _solo(state, cfg, prompt, n_new):
    return np.asarray(generate(state, cfg,
                               np.asarray([prompt], np.int32), n_new,
                               temperature=0.0))[0, len(prompt):].tolist()


def _make_engine(state, cfg, **kw):
    clock = [0.0]
    kw.setdefault("time_fn", lambda: clock[0])
    kw.setdefault("debug", True)        # invariant checks on in tests
    eng = Engine(state, cfg, **kw)
    eng._test_clock = clock
    return eng


def _drain(eng, check=True):
    while eng.has_work:
        eng.step()
        eng._test_clock[0] += 1.0
        if check:
            eng.pool.check_invariants()


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

def test_pool_alloc_free_invariants():
    pool = PagedKVPool(num_layers=2, num_pages=9, page_size=8,
                       kv_heads=2, head_dim=16, debug=True)
    assert pool.num_usable == 8 and pool.free_pages == 8
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert len(a) == 3 and len(b) == 4
    assert TRASH_PAGE not in a + b          # trash page never issued
    assert len(set(a + b)) == 7             # no double allocation
    pool.check_invariants()
    # OOM: no partial grant, state untouched
    assert pool.alloc(2) is None
    assert pool.free_pages == 1
    pool.free(a)
    pool.check_invariants()
    assert pool.free_pages == 4
    with pytest.raises(ValueError, match="double free"):
        pool.free([a[0]])
    assert pool.pages_for(1) == 1 and pool.pages_for(8) == 1 \
        and pool.pages_for(9) == 2


def test_pool_rejects_degenerate_shapes():
    with pytest.raises(ValueError, match="num_pages"):
        PagedKVPool(1, 1, 8, 2, 16)


def test_pool_reset_never_reissues_trash_page():
    """Regression: reset() must rebuild the free-list EXCLUDING the
    reserved trash page 0 — a range(num_pages) rebuild would hand page 0
    to the next request and real KV writes would land in the padding
    sink.  Alloc-after-reset can never return page 0."""
    pool = PagedKVPool(num_layers=2, num_pages=9, page_size=8,
                       kv_heads=2, head_dim=16, debug=True)
    pool.alloc(5)
    pool.reset()
    assert pool.free_pages == pool.num_usable == 8
    assert pool.used_pages == 0
    # drain the ENTIRE pool: page 0 must never surface
    got = pool.alloc(pool.num_usable)
    assert got is not None and TRASH_PAGE not in got
    assert sorted(got) == list(range(1, pool.num_pages))
    pool.check_invariants()
    # reset with live allocations: old handles are forgotten, page 0
    # still reserved, invariants hold
    pool.reset(clear_pages=True)
    pool.check_invariants()
    assert float(jnp.sum(jnp.abs(pool.k_pages[0]))) == 0.0
    again = pool.alloc(pool.num_usable)
    assert TRASH_PAGE not in again
    pool.check_invariants()


def test_pool_tp_sharding_spec(devices8):
    from hetu_tpu.parallel import create_mesh
    mesh = create_mesh({"tp": 2}, devices8[:2])
    pool = PagedKVPool(num_layers=1, num_pages=4, page_size=8,
                       kv_heads=4, head_dim=8, mesh=mesh)
    assert pool.sharding is not None
    spec = pool.sharding.spec
    assert tuple(spec) == (None, "tp", None, None)
    assert pool.k_pages[0].sharding == pool.sharding


# ---------------------------------------------------------------------------
# paged attention op
# ---------------------------------------------------------------------------

def _scatter_dense_to_pages(k_dense, page_table, ps, num_pages):
    """[B, S, kvh, hd] dense -> pages, via each request's page table."""
    b, s, kvh, hd = k_dense.shape
    pages = np.zeros((num_pages, kvh, ps, hd), k_dense.dtype)
    for bi in range(b):
        for t in range(s):
            pages[page_table[bi, t // ps], :, t % ps] = k_dense[bi, t]
    return pages


def test_paged_attention_matches_dense_sdpa():
    """Gather-via-page-table attention == dense attention over the same
    (ragged) histories, for GQA and non-contiguous page tables."""
    rng = np.random.RandomState(0)
    B, nh, kvh, hd, ps = 3, 8, 2, 16, 8
    seq_lens = np.array([13, 5, 24], np.int32)
    maxp = 3
    # non-contiguous, per-request page ids; tail slots -> trash
    page_table = np.array([[4, 9, 0], [2, 0, 0], [7, 1, 5]], np.int32)
    num_pages = 12
    S = maxp * ps
    k_dense = rng.randn(B, S, kvh, hd).astype(np.float32)
    v_dense = rng.randn(B, S, kvh, hd).astype(np.float32)
    q = jnp.asarray(rng.randn(B, nh, hd), jnp.float32)
    kp = jnp.asarray(_scatter_dense_to_pages(k_dense, page_table, ps,
                                             num_pages))
    vp = jnp.asarray(_scatter_dense_to_pages(v_dense, page_table, ps,
                                             num_pages))

    got = paged_attention_reference(q, kp, vp, jnp.asarray(page_table),
                                    jnp.asarray(seq_lens))

    # dense oracle, one request at a time over its true history
    g = nh // kvh
    for bi in range(B):
        L = seq_lens[bi]
        k = np.repeat(k_dense[bi, :L], g, axis=1)       # [L, nh, hd]
        v = np.repeat(v_dense[bi, :L], g, axis=1)
        qb = np.asarray(q)[bi]                          # [nh, hd]
        s = np.einsum("hd,lhd->hl", qb, k) / np.sqrt(hd)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hl,lhd->hd", p, v)
        np.testing.assert_allclose(np.asarray(got)[bi], want,
                                   rtol=1e-5, atol=1e-5)


def test_paged_attention_rejects_bad_shapes():
    q = jnp.zeros((2, 4, 16))
    kp = jnp.zeros((4, 2, 8, 16))
    pt = jnp.zeros((2, 2), jnp.int32)
    sl = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="head_dim"):
        paged_attention_reference(jnp.zeros((2, 4, 8)), kp, kp, pt, sl)
    with pytest.raises(ValueError, match="divisible"):
        paged_attention_reference(jnp.zeros((2, 3, 16)), kp, kp, pt, sl)
    with pytest.raises(ValueError, match="seq_lens"):
        paged_attention_reference(q, kp, kp, pt, jnp.zeros((3,),
                                                           jnp.int32))


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

CFG_KW = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64, sp=False, dropout=0.0)


def test_engine_matches_solo_generate_mixed_lengths():
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg)
    prompts = [[5, 17, 2, 9], [1, 1, 4, 88, 7, 3, 2], [3, 2, 1]]
    want = [_solo(state, cfg, pr, 8) for pr in prompts]
    eng = _make_engine(state, cfg, num_pages=16, page_size=16,
                       max_batch=4)
    reqs = [eng.add_request(pr, 8, arrival_time=0.0) for pr in prompts]
    _drain(eng)
    for i, r in enumerate(reqs):
        assert r.out_tokens == want[i], \
            f"req {i}: {r.out_tokens} != solo {want[i]}"
    assert eng.pool.used_pages == 0            # everything returned


def test_late_arriving_request_identical_to_solo():
    """A request admitted MID-FLIGHT (others already decoding) produces
    exactly its solo-run tokens — continuous batching changes when a
    token is computed, never what it is."""
    cfg = GPTConfig(position="rotary", norm="rmsnorm",
                    activation="swiglu", **CFG_KW)
    state = _build_state(cfg, seed=5)
    early = [[5, 17, 2, 9, 1, 1], [7, 3, 2, 9]]
    late = [42, 13, 8]
    want_late = _solo(state, cfg, late, 10)
    want_early = [_solo(state, cfg, pr, 14) for pr in early]

    eng = _make_engine(state, cfg, num_pages=24, page_size=8,
                       max_batch=4)
    reqs = [eng.add_request(pr, 14, arrival_time=0.0) for pr in early]
    late_req = eng.add_request(late, 10, arrival_time=4.0)  # mid-decode
    _drain(eng)
    assert late_req.first_token_time >= 4.0    # really arrived late
    assert late_req.out_tokens == want_late
    for r, w in zip(reqs, want_early):
        assert r.out_tokens == w


def test_oom_eviction_preserves_determinism():
    """Pool too small for all requests at once: the scheduler preempts
    (recompute eviction), invariants hold every step, and every request
    still reproduces its solo tokens."""
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg, seed=11)
    prompts = [[5, 17, 2, 9, 33, 12, 8, 1], [1, 1, 4, 44], [3, 2, 1, 9]]
    want = [_solo(state, cfg, pr, 12) for pr in prompts]
    eng = _make_engine(state, cfg, num_pages=7, page_size=8,
                       max_batch=4)
    reqs = [eng.add_request(pr, 12, arrival_time=float(i))
            for i, pr in enumerate(prompts)]
    _drain(eng)
    assert eng.counters["preemptions"].value >= 1, \
        "test should exercise eviction; enlarge prompts if not"
    for i, r in enumerate(reqs):
        assert r.out_tokens == want[i]
    assert eng.pool.used_pages == 0


def test_engine_rejects_impossible_request():
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg)
    eng = _make_engine(state, cfg, num_pages=4, page_size=8,
                       max_batch=2)
    with pytest.raises(ValueError, match="exceeds max_model_len"):
        eng.add_request(list(range(1, 30)), 40)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.add_request([1, 2], 0)
    with pytest.raises(ValueError, match="empty"):
        eng.add_request([], 4)


def test_engine_streaming_and_eos():
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg, seed=2)
    prompt = [5, 17, 2, 9]
    full = _solo(state, cfg, prompt, 10)
    eos = full[3]                               # stop after 4 tokens
    streamed = []
    eng = _make_engine(state, cfg, num_pages=16, page_size=16,
                       max_batch=2)
    req = eng.add_request(prompt, 10, eos_token_id=eos,
                          stream_cb=lambda r, t: streamed.append(t))
    _drain(eng)
    assert req.out_tokens == full[:4]
    assert streamed == req.out_tokens           # every token streamed


def test_engine_single_unified_executable():
    """Requests with assorted prompt lengths and a fluctuating live set
    run through ONE compiled executable — the unified ragged
    prefill+decode step.  There is no bucket grid to grow."""
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg, seed=4)
    eng = _make_engine(state, cfg, num_pages=32, page_size=8,
                       max_batch=4, chunk_size=8)
    rng = np.random.RandomState(0)
    for i in range(7):
        pr = [int(t) for t in rng.randint(1, 90, size=rng.randint(2, 14))]
        eng.add_request(pr, 6, arrival_time=float(i))
    _drain(eng)
    assert eng.compile_count == 1
    assert set(eng._compiled) == {"unified"}
    assert eng.executable_calls == eng.metrics_summary()["step_calls"]
    assert eng.executable_calls >= 1


def test_engine_metrics_advance_and_disable():
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg, seed=6)
    eng = _make_engine(state, cfg, num_pages=16, page_size=16,
                       max_batch=2)
    eng.add_request([5, 17, 2], 5, arrival_time=0.0)
    eng.add_request([1, 9, 4, 2], 5, arrival_time=0.0)
    _drain(eng)
    m = eng.metrics_summary()
    assert m["tokens_generated"] == 10
    assert m["prefill_tokens"] == 7
    assert m["requests_completed"] == 2
    assert m["decode_steps"] >= 4
    assert m["ttft"]["count"] == 2
    assert m["tpot"]["count"] == 8
    assert m["request_latency"]["p50"] > 0
    # disabled engines run on the shared no-op instrument
    eng2 = _make_engine(state, cfg, num_pages=16, page_size=16,
                        max_batch=2, metrics=False)
    eng2.add_request([5, 17, 2], 3, arrival_time=0.0)
    _drain(eng2, check=False)
    assert eng2.counters["tokens_generated"] is NULL_INSTRUMENT
    assert eng2.metrics_summary()["tokens_generated"] == 0.0


def test_admission_respects_step_page_budget():
    """Two requests that EACH fit the free pool but not TOGETHER: the
    scheduler must admit one and hold the other (regression: admit()
    compared every candidate against the same pool.free_pages and
    over-admitted, crashing _prefill's reservation assert)."""
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg, seed=13)
    want = [_solo(state, cfg, pr, 3)
            for pr in ([5, 17, 2, 9, 33, 12, 8, 1, 7],
                       [1, 1, 4, 44, 9, 2, 6, 3, 5])]
    # 4 usable pages of 4 tokens; each 9-token prompt needs 3 pages
    eng = _make_engine(state, cfg, num_pages=5, page_size=4,
                       max_batch=4)
    reqs = [eng.add_request([5, 17, 2, 9, 33, 12, 8, 1, 7], 3,
                            arrival_time=0.0),
            eng.add_request([1, 1, 4, 44, 9, 2, 6, 3, 5], 3,
                            arrival_time=0.0)]
    _drain(eng)
    for r, w in zip(reqs, want):
        assert r.out_tokens == w


def test_prompt_filling_entire_page_table():
    """A request filling its entire (non-power-of-two-wide) page table:
    chunked prefill must scatter exactly the real tokens' KV (v1
    regression: the bucketed prefill's clamped pt_row[j] gather
    silently overwrote the last real page with padding KV — the
    per-token write plan makes phantom pages impossible by
    construction, but the full-table scenario stays covered)."""
    cfg = GPTConfig(position="rotary", norm="rmsnorm",
                    activation="silu", num_kv_heads=2, **CFG_KW)
    state = _build_state(cfg, seed=14)
    # 12 usable pages of 4 tokens (maxp=12, not a power of two);
    # 45-token prompt + 3 new = 48 tokens = exactly 12 pages
    prompt = [int(t) for t in
              np.random.RandomState(3).randint(1, 90, size=45)]
    want = _solo(state, cfg, prompt, 3)
    eng = _make_engine(state, cfg, num_pages=13, page_size=4,
                       max_batch=2)
    assert eng.max_pages_per_seq == 12
    req = eng.add_request(prompt, 3, arrival_time=0.0)
    _drain(eng)
    assert req.out_tokens == want


def test_requeue_preserves_fifo_for_equal_arrivals():
    """A request pushed back (didn't fit) must keep its place ahead of
    same-arrival-time peers (regression: the heap tiebreaker was
    insertion order, so a re-push overtook)."""
    from hetu_tpu.serving.request import Request
    q = RequestQueue()
    a = Request(req_id=0, prompt=[1], max_new_tokens=1, arrival_time=0.0)
    b = Request(req_id=1, prompt=[1], max_new_tokens=1, arrival_time=0.0)
    q.push(a)
    q.push(b)
    got = q.pop_ready(1.0)
    assert got is a
    q.push(a)                                  # didn't fit: push back
    assert q.pop_ready(1.0) is a               # still first, no overtake


def test_learned_positions_bound_by_wpe_table():
    """max_model_len must never exceed the learned-position table (an
    out-of-range wpe gather clamps silently instead of failing)."""
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", vocab_size=97, hidden_size=32,
                    num_layers=1, num_heads=4, max_seq_len=20, sp=False,
                    dropout=0.0)
    state = _build_state(cfg, seed=15)
    eng = _make_engine(state, cfg, num_pages=8, page_size=8,
                       max_batch=2)
    assert eng.max_model_len == 20             # not rounded up to 24
    with pytest.raises(ValueError, match="exceeds max_model_len"):
        eng.add_request(list(range(1, 16)), 10)


def test_request_queue_arrival_order_gating():
    from hetu_tpu.serving.request import Request
    q = RequestQueue()
    a = Request(req_id=0, prompt=[1], max_new_tokens=1, arrival_time=5.0)
    b = Request(req_id=1, prompt=[1], max_new_tokens=1, arrival_time=1.0)
    q.push(a)
    q.push(b)
    assert q.pop_ready(0.5) is None             # nothing has arrived
    assert q.pop_ready(2.0) is b                # earliest arrival first
    assert q.pop_ready(2.0) is None             # a hasn't arrived yet
    assert q.pop_ready(5.0) is a
    assert not q


def test_sampling_on_device_skips_logits_roundtrip():
    """ALL sampling modes run inside the unified executable: an
    all-greedy workload AND a mixed greedy/temperature batch both fetch
    only [rows] int32s — host_logit_fetches stays 0 — while greedy rows
    remain bit-for-bit with solo generate()."""
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg, seed=21)
    prompts = [[5, 17, 2, 9], [3, 2, 1]]
    want = [_solo(state, cfg, pr, 6) for pr in prompts]

    eng = _make_engine(state, cfg, num_pages=16, page_size=16,
                       max_batch=4)
    reqs = [eng.add_request(pr, 6, arrival_time=0.0) for pr in prompts]
    _drain(eng)
    assert eng.host_logit_fetches == 0          # argmax stayed on device
    assert eng.metrics_summary()["host_logit_fetches"] == 0
    for r, w in zip(reqs, want):
        assert r.out_tokens == w

    eng2 = _make_engine(state, cfg, num_pages=16, page_size=16,
                        max_batch=4)
    g_req = eng2.add_request(prompts[0], 6, arrival_time=0.0)
    s_req = eng2.add_request(prompts[1], 6, temperature=1.0, seed=3,
                             arrival_time=0.0)
    _drain(eng2)
    assert eng2.host_logit_fetches == 0         # sampled row too
    assert g_req.out_tokens == want[0]          # greedy peer untouched
    assert len(s_req.out_tokens) == 6


# ---------------------------------------------------------------------------
# metrics instruments (satellite)
# ---------------------------------------------------------------------------

def test_metrics_instruments():
    c = Counter("tok")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge("occ")
    g.set(0.75)
    assert g.value == 0.75
    h = Histogram("ttft")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    assert h.count == 5 and h.mean == 22.0
    assert h.percentile(50) == 3.0
    assert h.percentile(100) == 100.0
    # linear interpolation between ranks (rank 3.96 over [1,2,3,4,100]),
    # not the old nearest-index snap to 100.0
    assert h.summary()["p99"] == pytest.approx(96.16)
    # factory + no-op fallback
    assert isinstance(make_instrument("histogram", "x"), Histogram)
    n = make_instrument("counter", "x", enabled=False)
    assert n is NULL_INSTRUMENT
    n.inc(); n.observe(3.0); n.set(1.0)         # all swallow silently
    assert n.value == 0.0 and n.percentile(99) == 0.0
    assert n.summary()["p90"] == 0.0            # indexable, not {}
    assert n.bucket_counts() == {"+Inf": 0}
    with pytest.raises(ValueError, match="unknown instrument"):
        make_instrument("summary")


def test_histogram_buckets_count_overflow_in_inf_and_sum():
    """Observations ABOVE the last bucket bound must still land in
    +Inf, count and sum (dropping the overflow tail would hide exactly
    the tail latencies a histogram exists to expose)."""
    h = Histogram("ttft", buckets=[0.1, 1.0])
    for v in [0.05, 0.5, 0.7, 5.0]:             # 5.0 > last bound
        h.observe(v)
    assert h.count == 4
    assert h.total == pytest.approx(6.25)       # overflow in the sum
    bc = h.bucket_counts()
    assert bc["0.1"] == 1
    assert bc["1.0"] == 3                       # cumulative
    assert bc["+Inf"] == 4                      # overflow counted
    # cumulative counts always close at the observation count
    assert bc["+Inf"] == h.count
    # percentiles still see the overflow observation
    assert h.percentile(100) == 5.0
    # bucketless histogram: everything is +Inf, count still closes
    h2 = Histogram("tpot")
    h2.observe(3.0)
    assert h2.bucket_counts() == {"+Inf": 1}
