"""MLA compressed latent KV on the paged pool (ISSUE 16).

Covers the tentpole contracts:

- **converter** — ``mla_state_from`` emits the weight-absorbed schema
  (q / kv_a / k_up / v_up, no fused qkv) and is EXACT when the stacked
  per-head ``[W_k; W_v]`` rank fits the latent dim;
- **latent serving bit-for-bit** — a latent engine under the
  adversarial trace (small pool, chunked prefill, late arrivals,
  preemption asserted non-vacuous) reproduces latent solo
  ``generate()`` at temperature 0, for learned AND rotary (decoupled
  rope) configs;
- **composition, not forks** — prefix-cache CoW (warm hit vs cold
  bitwise, LRU eviction under pressure), speculative verify rows
  (temp-0 and sampled bitwise vs a non-spec latent engine), and
  disaggregated handoff/adoption (cluster vs monolithic bitwise) all
  ride latent pages unchanged;
- **layout safety** — ``PageTransport.inject`` refuses a cross-layout
  page stream; the prefix digest is layout-salted so latent and
  full-head replicas never cross-match;
- **quantized pages** — int8/nf4 latent pages (row absmax, one scale
  per cached token) round-trip within their error bounds and serve
  deterministically;
- **kernel parity** — the latent Pallas kernel (interpret mode on CPU)
  against the gather-dense latent reference, rope and quant variants
  included;
- **observability** — ``kv_bytes_per_token`` / ``kv_bytes_in_use``
  gauges, pool layout tags, and ``analysis/memory`` recognizing latent
  page shapes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.models.generate import generate
from hetu_tpu.models.gpt import draft_state_from, mla_config, mla_state_from
from hetu_tpu.ops.quantization import dequantize_rows, quantize_rows
from hetu_tpu.ops.ragged_paged_attention import (
    latent_ragged_paged_attention_pallas,
    latent_ragged_paged_attention_reference)
from hetu_tpu.serving import Engine, EngineCluster
from hetu_tpu.serving.kv_pool import PagedKVPool, page_shape_bytes
from hetu_tpu.serving.prefix_cache import token_chain_hashes
from hetu_tpu.serving.spec import SpecConfig

CFG_KW = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64, sp=False, dropout=0.0)


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
    kw.setdefault("debug", True)
    eng = Engine(state, cfg, **kw)
    eng._test_clock = clock
    return eng


def _drain(eng, check=True):
    guard = 0
    while eng.has_work:
        eng.step()
        eng._test_clock[0] += 1.0
        guard += 1
        assert guard < 500, "engine failed to drain"
        if check:
            eng.pool.check_invariants()


@pytest.fixture(scope="module")
def mla():
    """Learned-position base checkpoint plus its latent conversion
    (d_c=16: a real 4x page compression, NOT full-rank — every serving
    contract below is vs the LATENT solo generate(), the bitwise
    reference the engine must reproduce)."""
    cfg = GPTConfig(position="learned", norm="layernorm",
                    activation="gelu", **CFG_KW)
    state = _build_state(cfg, seed=3)
    lstate, lcfg = mla_state_from(state, cfg, kv_latent_dim=16)
    return state, cfg, lstate, lcfg


@pytest.fixture(scope="module")
def mla_rot():
    """Rotary base plus latent conversion with a decoupled rope stream
    (d_r=4): pages carry latent + rotated-key sidecars."""
    cfg = GPTConfig(position="rotary", norm="rmsnorm",
                    activation="swiglu", **CFG_KW)
    state = _build_state(cfg, seed=7)
    rstate, rcfg = mla_state_from(state, cfg, kv_latent_dim=16,
                                  kv_rope_dim=4)
    return rstate, rcfg


# ---------------------------------------------------------------------------
# config + converter
# ---------------------------------------------------------------------------


def test_config_validation_and_converter_schema(mla):
    state, cfg, lstate, lcfg = mla
    with pytest.raises(ValueError):
        GPTConfig(kv_rope_dim=8, **CFG_KW)      # rope dim needs MLA
    assert lcfg.is_mla and not cfg.is_mla
    assert lcfg.rope_dim == 0                   # learned: no rope stream
    assert mla_config(cfg, 16).kv_latent_dim == 16
    # weight-absorbed schema replaces the fused qkv per layer
    assert not any(".attn.qkv." in k for k in lstate)
    for i in range(cfg.num_layers):
        assert lstate[f"h{i}.attn.kv_a.weight"].shape == \
            (16, cfg.hidden_size)
        assert lstate[f"h{i}.attn.k_up.weight"].shape == \
            (cfg.num_heads, cfg.head_dim, 16)
        assert lstate[f"h{i}.attn.v_up.weight"].shape == \
            (cfg.num_heads, cfg.head_dim, 16)
    # rotary MLA pins the decoupled rope width
    rcfg = mla_config(GPTConfig(position="rotary", norm="rmsnorm",
                                activation="swiglu", **CFG_KW), 16,
                      kv_rope_dim=4)
    assert rcfg.rope_dim == 4


def test_converter_exact_when_rank_fits_latent(mla):
    """d_c = hidden: the stacked [W_k; W_v] SVD keeps every singular
    value, so the latent model IS the full-head model (fp rounding
    aside) — greedy decodes agree token for token."""
    state, cfg, _, _ = mla
    lstate, lcfg = mla_state_from(state, cfg,
                                  kv_latent_dim=cfg.hidden_size)
    rng = np.random.RandomState(2)
    for n in (5, 13, 22):
        pr = [int(t) for t in rng.randint(1, 90, size=n)]
        assert _solo(lstate, lcfg, pr, 10) == _solo(state, cfg, pr, 10)


# ---------------------------------------------------------------------------
# latent serving: the temp-0 bitwise acceptance trace
# ---------------------------------------------------------------------------


def test_latent_temp0_bitwise_under_pressure(mla):
    """The acceptance criterion: a latent engine on a tiny pool (forces
    recompute eviction, asserted non-vacuous), 4-token chunks, late
    arrivals — bit-for-bit the latent solo generate() run for every
    request."""
    _, _, lstate, lcfg = mla
    prompts = [[5, 17, 2, 9, 33, 12, 8, 1], [1, 1, 4, 44],
               [3, 2, 1, 9, 6, 5, 4]]
    want = [_solo(lstate, lcfg, pr, 10) for pr in prompts]
    eng = _make_engine(lstate, lcfg, num_pages=7, page_size=8,
                       max_batch=4, chunk_size=4)
    assert eng.pool.is_latent
    # d_c * f32 * num_layers (page_bytes spans every layer's stream)
    assert eng.pool.kv_bytes_per_token == 16 * 4 * lcfg.num_layers
    reqs = [eng.add_request(pr, 10, arrival_time=float(2 * i))
            for i, pr in enumerate(prompts)]
    _drain(eng)
    assert eng.counters["preemptions"].value >= 1, \
        "trace should exercise eviction; shrink the pool if not"
    for r, w in zip(reqs, want):
        assert r.out_tokens == w
    assert eng.pool.used_pages == 0
    assert eng.compile_count == 1
    assert eng.host_logit_fetches == 0


def test_latent_rotary_serving_bitwise(mla_rot):
    """Rotary MLA: the decoupled rope sidecar rides the v-page slot and
    serving still matches latent solo decode bit-for-bit."""
    rstate, rcfg = mla_rot
    rng = np.random.RandomState(4)
    prompts = [[int(t) for t in rng.randint(1, 90, size=n)]
               for n in (19, 4, 11)]
    want = [_solo(rstate, rcfg, pr, 6) for pr in prompts]
    eng = _make_engine(rstate, rcfg, num_pages=24, page_size=8,
                       max_batch=4, chunk_size=8)
    assert eng.pool.rope_dim == 4
    assert eng.pool.v_pages[0].shape[-1] == 4
    reqs = [eng.add_request(pr, 6, arrival_time=0.0) for pr in prompts]
    _drain(eng)
    for r, w in zip(reqs, want):
        assert r.out_tokens == w
    assert eng.compile_count == 1


# ---------------------------------------------------------------------------
# composition: prefix-cache CoW on latent pages
# ---------------------------------------------------------------------------


def test_latent_prefix_hit_vs_cold_bitwise(mla):
    """Shared-header burst through (a) a cold latent engine with the
    cache off and (b) a warm latent engine serving the header off
    cached pages: outputs match each other AND latent solo exactly."""
    _, _, lstate, lcfg = mla
    rng = np.random.RandomState(2)
    header = [int(t) for t in rng.randint(1, 90, size=16)]
    prompts = [header + [int(t) for t in rng.randint(1, 90, size=n)]
               for n in (3, 7, 5)]
    want = [_solo(lstate, lcfg, pr, 6) for pr in prompts]
    cold = _make_engine(lstate, lcfg, num_pages=24, page_size=8,
                        max_batch=4, chunk_size=8, prefix_cache=False)
    cold_reqs = [cold.add_request(p, 6, arrival_time=0.0)
                 for p in prompts]
    _drain(cold)
    assert cold.metrics_summary()["prefix_cache_hits"] == 0
    warm = _make_engine(lstate, lcfg, num_pages=24, page_size=8,
                        max_batch=4, chunk_size=8)
    warm.add_request(prompts[0], 6, arrival_time=0.0)
    _drain(warm)
    assert warm.pool.cached_pages > 0
    reqs = [warm.add_request(p, 6, arrival_time=warm._test_clock[0])
            for p in prompts]
    _drain(warm)
    for r, c, w in zip(reqs, cold_reqs, want):
        assert r.out_tokens == w
        assert c.out_tokens == w
    assert all(r.cached_tokens >= 16 for r in reqs)
    assert warm.compile_count == 1


def test_latent_prefix_eviction_and_preemption_pressure(mla):
    """The hard case with the cache ON: a pool small enough to force
    BOTH LRU cache eviction and recompute preemption (each asserted
    non-vacuous), shared headers, late arrivals — still bit-for-bit."""
    _, _, lstate, lcfg = mla
    rng = np.random.RandomState(8)
    header = [int(t) for t in rng.randint(1, 90, size=8)]
    prompts = [header + [int(t) for t in rng.randint(1, 90, size=n)]
               for n in (9, 2, 13, 5)]
    want = [_solo(lstate, lcfg, pr, 8) for pr in prompts]
    eng = _make_engine(lstate, lcfg, num_pages=7, page_size=8,
                       max_batch=3, chunk_size=4)
    eng.add_request(header + prompts[0][8:10], 2, arrival_time=0.0)
    _drain(eng)
    reqs = [eng.add_request(pr, 8, arrival_time=eng._test_clock[0] + i)
            for i, pr in enumerate(prompts)]
    _drain(eng)
    m = eng.metrics_summary()
    assert m["preemptions"] >= 1, \
        "trace should exercise preemption; shrink the pool if not"
    assert m["prefix_cache_evictions"] >= 1, \
        "trace should exercise cache eviction"
    assert m["prefix_cache_hits"] >= 1
    for r, w in zip(reqs, want):
        assert r.out_tokens == w
    assert eng.pool.used_pages == 0


# ---------------------------------------------------------------------------
# composition: speculative decoding verifies on latent pages
# ---------------------------------------------------------------------------


def test_latent_spec_bitwise_vs_nonspec_engine(mla):
    """MLA target + MLA self-draft: spec verify rows ride the latent
    unified step and outputs (greedy AND seeded-sampled rows) equal the
    non-spec latent engine token for token."""
    _, _, lstate, lcfg = mla
    dstate, dcfg = draft_state_from(lstate, lcfg, 1)
    assert dcfg.is_mla
    rng = np.random.RandomState(2)
    prompts = [[int(t) for t in rng.randint(1, 90, size=n)]
               for n in (23, 4, 17)]
    outs = {}
    for spec in (None, SpecConfig(dstate, dcfg, k=3)):
        eng = _make_engine(lstate, lcfg, num_pages=24, page_size=8,
                           max_batch=4, chunk_size=8, spec=spec)
        reqs = [eng.add_request(p, 8, arrival_time=float(2 * i))
                for i, p in enumerate(prompts)]
        sampled = eng.add_request(prompts[0], 8, temperature=0.7,
                                  top_p=0.9, top_k=40, seed=123,
                                  arrival_time=1.0)
        _drain(eng)
        assert eng.host_logit_fetches == 0
        if spec is not None:
            m = eng.metrics_summary()
            assert m["spec_accepted"] > 0, "speculation never engaged"
        outs[spec is None] = [r.out_tokens for r in reqs] + \
            [sampled.out_tokens]
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# composition: disaggregated handoff + adoption on latent pages
# ---------------------------------------------------------------------------


def test_latent_disaggregated_cluster_bitwise(mla):
    """Prefill on one latent replica, pages streamed to a latent decode
    replica, outputs bit-for-bit the monolithic latent engine — and
    every handoff is priced at the LATENT page size."""
    from hetu_tpu.serving.decode import build_unified_step_fn
    _, _, lstate, lcfg = mla
    shape = dict(page_size=8, max_batch=4, chunk_size=8,
                 prefill_rows=1, max_model_len=56)
    fn = build_unified_step_fn(
        lcfg, shape["max_batch"], shape["chunk_size"],
        shape["prefill_rows"],
        -(-shape["max_model_len"] // shape["page_size"]),
        shape["page_size"], use_kernel=False)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 97, size=n).tolist()
               for n in (26, 18, 12, 22)]
    NEW = 8
    clock = [0.0]
    mono = Engine(lstate, lcfg, num_pages=12, name="mla_mono",
                  debug=True, time_fn=lambda: clock[0], step_fn=fn,
                  **shape)
    for i, p in enumerate(prompts):
        mono.add_request(p, NEW, arrival_time=float(i))
    while mono.has_work:
        mono.step()
        clock[0] += 1.0
    want = {i: list(mono.finished[i].out_tokens)
            for i in range(len(prompts))}
    assert want[0] == _solo(lstate, lcfg, prompts[0], NEW)

    cclock = [0.0]
    cl = EngineCluster(lstate, lcfg, step_fn=fn, num_replicas=2,
                       mode="disaggregated", num_prefill=1,
                       num_pages=12, name="mla_disagg",
                       coordinator=False, debug=True, ttl=3600.0,
                       time_fn=lambda: cclock[0], **shape)
    try:
        reqs = [cl.add_request(p, NEW, arrival_time=float(i))
                for i, p in enumerate(prompts)]
        n = 0
        while cl.has_work:
            cl.step()
            cclock[0] += 1.0
            n += 1
            assert n < 500, "cluster did not drain"
        ms = cl.metrics_summary()
        assert ms["cluster_handoffs"] == len(prompts)
        pb = cl.replicas[0].engine.pool.page_bytes
        # ps * d_c * f32 * layers: handoffs priced at LATENT page size
        assert pb == 8 * 16 * 4 * lcfg.num_layers
        for rec in cl.transport.records:
            assert rec["payload_bytes"] == rec["pages"] * pb
            assert rec["predicted_s"] > 0
        for r in reqs:
            assert r.out_tokens == want[r.req_id], \
                (r.req_id, r.out_tokens, want[r.req_id])
    finally:
        cl.close()


def test_transport_rejects_cross_layout_injection():
    """A latent page stream may not land in a full-head pool (or any
    other layout): inject() raises before touching destination KV."""
    from hetu_tpu.serving.cluster.transport import LocalPageTransport
    lat = PagedKVPool(num_layers=1, num_pages=4, page_size=4,
                      kv_heads=2, head_dim=4, latent_dim=8)
    full = PagedKVPool(num_layers=1, num_pages=4, page_size=4,
                      kv_heads=2, head_dim=4)
    tr = LocalPageTransport()
    staged = tr.extract(lat, lat.alloc(1))
    assert staged["layout"] == lat.layout_tag
    with pytest.raises(ValueError, match="layout mismatch"):
        tr.inject(full, staged, full.alloc(1), 0, 1, epoch=0)
    # same-layout injection lands and is priced at latent page bytes
    lat2 = PagedKVPool(num_layers=1, num_pages=4, page_size=4,
                       kv_heads=2, head_dim=4, latent_dim=8)
    rec = tr.inject(lat2, staged, lat2.alloc(1), 0, 1, epoch=0)
    assert rec["payload_bytes"] == lat.page_bytes


def test_chain_hash_layout_salt_diverges():
    """Layout-salted chain hashes share NO stamps with unsalted (or
    other-layout) hashes — a latent replica's digest can never match a
    full-head replica's prompt pages in the router."""
    toks = list(range(1, 33))
    plain = token_chain_hashes(toks, 8)
    lat = token_chain_hashes(toks, 8, layout=(1, 16, 0, 0, 4))
    full = token_chain_hashes(toks, 8, layout=(0, 4, 8, 0, 4))
    assert not set(plain) & set(lat)
    assert not set(lat) & set(full)
    assert lat == token_chain_hashes(toks, 8, layout=(1, 16, 0, 0, 4))


# ---------------------------------------------------------------------------
# pool layout + quantized pages
# ---------------------------------------------------------------------------


def test_pool_layouts_tags_and_bytes():
    kw = dict(num_layers=2, num_pages=6, page_size=4, kv_heads=2,
              head_dim=8)
    full = PagedKVPool(**kw)
    lat = PagedKVPool(latent_dim=16, **kw)
    rope = PagedKVPool(latent_dim=16, rope_dim=4, **kw)
    q8 = PagedKVPool(latent_dim=16, quant="int8", **kw)
    q4 = PagedKVPool(latent_dim=16, quant="nf4", **kw)
    # every layout gets a distinct tag (the digest salt / decode-cache key)
    tags = [p.layout_tag for p in (full, lat, rope, q8, q4)]
    assert len(set(tags)) == 5
    # page_bytes is THE shared helper applied to the live array shapes
    for p in (full, lat, rope, q8, q4):
        ks, vs = p.page_array_shapes()
        want = sum(page_shape_bytes(s, a.dtype)
                   for s, a in zip(ks, p.k_pages)) + \
            sum(page_shape_bytes(s, a.dtype)
                for s, a in zip(vs, p.v_pages))
        assert p.page_bytes == want
        assert p.kv_bytes_per_token * p.page_size == p.page_bytes
    L = kw["num_layers"]
    assert full.kv_bytes_per_token == 2 * 2 * 8 * 4 * L   # 2 streams
    assert lat.kv_bytes_per_token == 16 * 4 * L
    assert rope.kv_bytes_per_token == (16 + 4) * 4 * L
    assert q8.kv_bytes_per_token == (16 + 4) * L          # codes + scale
    assert q4.kv_bytes_per_token == (8 + 4) * L
    assert q8.k_pages[0].dtype == jnp.int8
    assert q4.k_pages[0].shape[-1] == 8                # packed pairs
    assert q8.v_pages[0].shape[-1] == 1                # absmax sidecar
    # the quant gate: latent-only, rope-free, even width
    with pytest.raises(ValueError):
        PagedKVPool(quant="int8", **kw)                # no latent
    with pytest.raises(ValueError):
        PagedKVPool(latent_dim=16, rope_dim=4, quant="int8", **kw)
    with pytest.raises(ValueError):
        PagedKVPool(latent_dim=15, quant="nf4", **kw)  # odd width


def test_quantize_rows_roundtrip_bounds():
    rng = np.random.RandomState(0)
    x = rng.randn(6, 16).astype(np.float32) * np.asarray(
        [0.1, 1.0, 10.0, 0.01, 3.0, 0.0], np.float32)[:, None]
    for quant, bound in (("int8", 1.0 / 127), ("nf4", 0.18)):
        codes, absmax = quantize_rows(jnp.asarray(x), quant)
        got = np.asarray(dequantize_rows(codes, absmax, quant, 16))
        err = np.abs(got - x).max(-1)
        tol = np.abs(x).max(-1) * bound + 1e-7
        assert (err <= tol).all(), (quant, err, tol)
    assert np.all(got[-1] == 0)                        # zero row exact


def test_quantized_latent_engine_deterministic(mla):
    """int8 latent pages: two fresh engines emit identical tokens (the
    quant path is deterministic end to end); nf4 serves the same trace;
    page_quant without MLA is refused."""
    state, cfg, lstate, lcfg = mla
    rng = np.random.RandomState(5)
    prompts = [[int(t) for t in rng.randint(1, 90, size=n)]
               for n in (14, 6)]
    runs = []
    for _ in range(2):
        eng = _make_engine(lstate, lcfg, num_pages=16, page_size=8,
                           max_batch=2, chunk_size=8, page_quant="int8")
        reqs = [eng.add_request(p, 8, arrival_time=0.0)
                for p in prompts]
        _drain(eng)
        assert eng.pool.quant == "int8"
        runs.append([r.out_tokens for r in reqs])
    assert runs[0] == runs[1]
    assert all(len(t) == 8 for t in runs[0])
    e4 = _make_engine(lstate, lcfg, num_pages=16, page_size=8,
                      max_batch=2, chunk_size=8, page_quant="nf4")
    r4 = [e4.add_request(p, 8, arrival_time=0.0) for p in prompts]
    _drain(e4)
    assert all(len(r.out_tokens) == 8 for r in r4)
    with pytest.raises(ValueError, match="MLA"):
        Engine(state, cfg, num_pages=8, page_size=8, max_batch=2,
               page_quant="int8")


# ---------------------------------------------------------------------------
# latent kernel parity (interpret mode)
# ---------------------------------------------------------------------------


# (max_q, q_lens, ctx_lens, rows sharing physical pages) over 8-token
# pages and 11 page-table slots: a decode call and a chunk call, each
# with padding rows (q_lens 0) at the start, in the middle and at the
# end, a context that ends inside a group, a row holding fewer pages
# than any group > 1 (one page), and two rows naming the same pages
_LATENT_CALLS = {
    "decode": (1, [1, 1, 0, 1, 1, 1, 0], [13, 80, 0, 6, 33, 80, 0],
               (1, 5)),
    "chunk": (8, [0, 1, 5, 0, 0, 6, 8, 8], [0, 13, 70, 0, 0, 6, 88, 88],
              (6, 7)),
}


@pytest.mark.parametrize("group", [None, 1, 2, 4, 8, 16])
@pytest.mark.parametrize("call", list(_LATENT_CALLS))
@pytest.mark.parametrize("quant,d_c,d_r", [
    (None, 16, 4), (None, 16, 0), ("int8", 16, 0), ("nf4", 16, 0),
    (None, 128, 128), ("int8", 128, 0)])
def test_latent_kernel_matches_reference(quant, d_c, d_r, call, group):
    """Pallas latent ragged kernel (interpret mode) against the
    gather-dense latent reference, by the group of page-table slots a
    grid step covers (None: the rule's own; 11 slots are a multiple of
    no group but 1), rope sidecar and quantized-page variants.  Streams
    128 wide reach VMEM by the kernel's own DMAs, narrower ones through
    page-table-indexed blocks: both ways, and both in one call."""
    rng = np.random.RandomState(0)
    nh, num_pages, ps, maxp = 4, 40, 8, 11
    max_q, q_lens, ctx_lens, (first, twin) = _LATENT_CALLS[call]
    s = len(q_lens)
    cu = np.zeros(s + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    t = int(cu[-1])
    q = jnp.asarray(rng.randn(t, nh, d_c + d_r), jnp.float32)
    lat = rng.randn(num_pages, 1, ps, d_c).astype(np.float32)
    scale_pages = None
    if quant:
        codes, absmax = quantize_rows(jnp.asarray(lat), quant)
        c_pages, scale_pages = codes, absmax
    else:
        c_pages = jnp.asarray(lat)
    r_pages = jnp.asarray(rng.randn(num_pages, 1, ps, d_r),
                          jnp.float32) if d_r else None
    pt = np.zeros((s, maxp), np.int32)
    for i in range(s):
        need = -(-ctx_lens[i] // ps)
        pt[i, :need] = rng.permutation(np.arange(1, num_pages))[:need]
    pt[twin] = pt[first]             # one document under two rows
    args = (jnp.asarray(np.asarray(q_lens, np.int32)), jnp.asarray(cu),
            jnp.asarray(pt), jnp.asarray(np.asarray(ctx_lens, np.int32)))
    kw = dict(max_q=max_q, softmax_scale=(d_c + d_r) ** -0.5,
              scale_pages=scale_pages, quant=quant, latent_dim=d_c)
    ref = latent_ragged_paged_attention_reference(
        q, c_pages, r_pages, *args, **kw)
    got = latent_ragged_paged_attention_pallas(
        q, c_pages, r_pages, *args, interpret=True, pages_per_step=group,
        **kw)
    mask = np.zeros(t, bool)
    for i in range(s):
        mask[int(cu[i]):int(cu[i]) + int(q_lens[i])] = True
    np.testing.assert_allclose(np.asarray(got)[mask],
                               np.asarray(ref)[mask],
                               rtol=2e-5, atol=2e-5)


def test_latent_group_rule_follows_the_calls_shapes():
    """The one rule at the latent cell's shapes (32 heads, 272 slots of
    64 tokens, a 256 | 128 bf16 pool, q 384 wide): a decode
    region takes the cap, a 256-token chunk what its score tile leaves
    of the budget, a short table no more than it has."""
    from hetu_tpu.ops.ragged_paged_attention import (
        LATENT_GROUP_MAX, latent_pages_per_grid_step)
    pool = (jax.ShapeDtypeStruct((8138, 1, 64, 256), jnp.bfloat16),
            jax.ShapeDtypeStruct((8138, 1, 64, 128), jnp.bfloat16))
    rule = lambda max_q, maxp=272: latent_pages_per_grid_step(  # noqa: E731
        max_q, 32, 384, maxp, pool)
    assert rule(1) == LATENT_GROUP_MAX
    assert 1 < rule(256) < rule(1)
    assert rule(4096) == 1
    assert [rule(1, maxp) for maxp in (1, 2, 3, 5)] == [1, 2, 2, 4]


@pytest.mark.parametrize("fixture", ["mla", "mla_rot"])
def test_latent_kernel_backed_step_end_to_end(fixture, request):
    """The latent call site goes through the same per-region helper as
    the full-head one: the whole executable with the latent kernel
    (interpret mode) serves the dense-fallback executable's greedy
    tokens, decode rows, chunked prompts and a late arrival included."""
    lstate, lcfg = request.getfixturevalue(fixture)[-2:]
    prompts = [[5, 17, 2, 9, 33, 12, 8, 1, 4, 6, 7], [3, 2, 1]]
    outs = {}
    for uk in (False, True):
        eng = _make_engine(lstate, lcfg, num_pages=9, page_size=8,
                           max_batch=2, chunk_size=8, use_kernel=uk)
        reqs = [eng.add_request(p, 5, arrival_time=float(2 * i))
                for i, p in enumerate(prompts)]
        _drain(eng)
        outs[uk] = [r.out_tokens for r in reqs]
    assert outs[False] == outs[True]


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_kv_byte_gauges_and_analysis_shapes(mla):
    _, _, lstate, lcfg = mla
    eng = _make_engine(lstate, lcfg, num_pages=8, page_size=8,
                       max_batch=2, chunk_size=8)
    eng.add_request([5, 17, 2, 9, 1, 3, 4, 8, 11], 4, arrival_time=0.0)
    _drain(eng)
    m = eng.metrics_summary()
    assert m["kv_bytes_per_token"] == eng.pool.kv_bytes_per_token == 128
    want = (eng.pool.num_usable - eng.pool.free_pages) * \
        eng.pool.page_bytes
    assert m["kv_bytes_in_use"] == want
    text = eng.metrics_text()
    assert "kv_bytes_per_token" in text and "kv_bytes_in_use" in text
    # analysis/memory classifies latent (and sidecar) page shapes
    from hetu_tpu.analysis.memory import _kv_page_shapes
    shapes = _kv_page_shapes({"pool": eng.pool})
    assert eng.pool.k_pages[0].shape in shapes
    assert eng.pool.v_pages[0].shape in shapes
    q8 = PagedKVPool(num_layers=1, num_pages=4, page_size=4,
                     kv_heads=2, head_dim=4, latent_dim=8, quant="int8")
    shapes = _kv_page_shapes({"pool": q8})
    assert (4, 1, 4, 8) in shapes and (4, 1, 4, 1) in shapes
