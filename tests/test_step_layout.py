"""The serving step's control data crosses the host-device boundary once
each way (``serving/decode.StepLayout``): one packed int32 buffer in, one
int32 vector out, for the four builds of the step — dense, speculative
(``spec_k`` 2), a hybrid stack with recurrent-state slots, a hybrid stack
over the latent page pool.  Tiny widths, float32, the CPU.

The pinned tokens were served by the parent of PR 38 (thirteen separate
arrays in, two fetches out) on the same requests with sampling ON, so the
two float32 fields are shown to travel bit for bit.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

import test_hybrid_serving as hybrid_tests
import test_mistral4_serving as latent_tests
import test_spec_decode as dense_tests
from hetu_tpu.models import GPTConfig, draft_state_from
from hetu_tpu.obs.tracer import SpanTracer
from hetu_tpu.serving import Engine, SpecConfig

FAMILIES = ("dense", "spec2", "hybrid", "latent")


def _dense():
    cfg = GPTConfig(position="learned", norm="layernorm", activation="gelu",
                    **dense_tests.CFG_KW)
    return dense_tests._build_state(cfg, seed=11), cfg


def _hybrid():
    """``*EMEM`` at its own tests' tiny widths: state slots."""
    _, cfg, state = hybrid_tests.build("*EMEM")
    return state, cfg


def _latent():
    """``(L, E) x 2`` at its own tests' tiny widths: the latent pool."""
    _, cfg, state = latent_tests.build()
    return state, cfg


def make_engine(family: str, **kw) -> Engine:
    """A tiny engine of the family on a clock that stands still."""
    kw = {"num_pages": 48, "page_size": 8, "max_batch": 3, "chunk_size": 8,
          "max_model_len": 64, "debug": True, "use_kernel": False,
          "time_fn": lambda: 0.0, "name": f"layout_{family}", **kw}
    if family in ("dense", "spec2"):
        state, cfg = _dense()
        if family == "spec2":
            kw["spec"] = SpecConfig(*draft_state_from(state, cfg, 1), k=2)
    elif family == "hybrid":
        state, cfg = _hybrid()
        kw["prefix_cache"] = False
    else:
        state, cfg = _latent()
    return Engine(state, cfg, **kw)


# (prompt length, temperature, top_p, top_k, seed, arrives at step)
TRAFFIC = ((19, 0.7, 0.9, 0, 11, 0), (5, 0.0, 0.0, 0, 0, 0),
           (1, 1.3, 0.0, 5, 7, 2), (30, 0.9, 0.6, 12, 123456789, 4))
NEW_TOKENS = 10


def serve(eng: Engine, traffic=TRAFFIC):
    """Serve ``traffic`` (late arrivals join mid-flight, so consecutive
    steps hold different rows); the requests in order."""
    rng = np.random.RandomState(0)
    vocab = eng.cfg.vocab_size
    reqs, step = [], 0
    pending = [(t, rng.randint(1, vocab, t[0]).tolist()) for t in traffic]
    while pending or eng.has_work:
        while pending and pending[0][0][5] <= step:
            (_, temp, top_p, top_k, seed, _), prompt = pending.pop(0)
            reqs.append(eng.add_request(
                prompt, NEW_TOKENS, temperature=temp, top_p=top_p,
                top_k=top_k, seed=seed))
        eng.step()
        step += 1
        assert step < 400, "engine failed to drain"
    return reqs


# served by the parent of PR 38 (``serve(make_engine(family))``)
_DENSE = [
    [75, 12, 7, 69, 94, 4, 33, 84, 38, 32],
    [55, 25, 87, 38, 88, 82, 33, 51, 13, 44],
    [24, 33, 40, 87, 38, 74, 30, 44, 44, 45],
    [31, 39, 60, 39, 68, 38, 35, 84, 84, 51],
]
PINNED = {
    "dense": _DENSE,
    "spec2": _DENSE,          # a draft changes when, never what
    "hybrid": [
        [46, 1, 2, 6, 9, 86, 124, 114, 46, 48],
        [13, 25, 26, 8, 51, 98, 90, 16, 62, 68],
        [96, 98, 18, 116, 101, 64, 20, 96, 43, 94],
        [45, 73, 37, 58, 29, 67, 118, 13, 52, 30],
    ],
    "latent": [
        [95, 194, 158, 103, 146, 237, 251, 86, 89, 139],
        [169, 157, 164, 148, 14, 4, 134, 223, 53, 185],
        [145, 194, 81, 46, 169, 31, 164, 74, 170, 169],
        [111, 65, 44, 97, 64, 134, 254, 165, 242, 235],
    ],
}


@pytest.mark.parametrize("family", FAMILIES)
def test_engine_serves_the_same_tokens_with_sampling_on(family):
    """Temperature, top-p, top-k and a seed per request: the tokens are
    those the thirteen-array step served, so ``temps`` / ``top_ps`` reach
    the sampler bit for bit through the int32 buffer."""
    reqs = serve(make_engine(family))
    assert [r.out_tokens for r in reqs] == PINNED[family]


@pytest.mark.parametrize("family", FAMILIES)
def test_one_copy_in_and_one_fetch_out_a_step(family):
    """``h2d_copies`` == ``d2h_fetches`` == ``step_calls``, and every
    ``unified_step`` span carries the buffer's ``h2d_bytes``."""
    eng = make_engine(family, tracer=SpanTracer())
    serve(eng)
    c = eng.metrics_summary()
    assert c["h2d_copies"] == c["d2h_fetches"] == c["step_calls"] > 10
    steps = [e for e in eng.tracer.events() if e.name == "unified_step"]
    assert len(steps) == c["step_calls"]
    assert {e.attrs["h2d_bytes"] for e in steps} == {4 * eng.layout.size}
    assert eng.compile_count == len(eng._compiled)


@pytest.mark.parametrize("family", FAMILIES)
def test_layout_round_trips_every_field_bit_for_bit(family):
    """Host views -> one buffer -> the step's slices: every field comes
    out as it went in (the float fields by bit pattern, NaN payloads and
    negative zero included), the fields tile the buffer with no gap and
    no overlap, and the output vector splits into what was joined."""
    lay = make_engine(family).layout
    want = {"dense": set(), "spec2": {"spec_lens"},
            "hybrid": {"state_slots"}, "latent": {"state_slots"}}[family]
    assert set(lay.fields) - {
        "tokens", "token_pos", "token_page", "token_off", "q_lens",
        "page_tables", "ctx_lens", "temps", "top_ps", "top_ks",
        "seeds"} == want
    rng = np.random.RandomState(1)
    buf = np.full(lay.size, -1, np.int32)
    sent = {}
    for name, view in lay.views(buf).items():
        bits = rng.randint(-2**31, 2**31 - 1, view.shape,
                           dtype=np.int64).astype(np.int32)
        if name in lay.F32:
            assert view.dtype == np.float32
            bits.reshape(-1)[:4] = np.array(
                [0.7, -0.0, np.nan, 1e-42], np.float32).view(np.int32)[
                    :bits.size]
            view[...] = bits.view(np.float32)
        else:
            assert view.dtype == np.int32
            view[...] = bits
        sent[name] = bits
    assert sum(b.size for b in sent.values()) == lay.size
    assert np.array_equal(buf, np.concatenate(
        [b.reshape(-1) for b in sent.values()]))      # tiled, in order
    # (the step unpacks by position: the order is part of the format)
    assert list(lay.unpack(buf)) == list(sent)
    got = jax.jit(lay.unpack)(buf)
    for name, a in got.items():
        assert a.shape == sent[name].shape
        assert a.dtype == (np.float32 if name in lay.F32 else np.int32)
        assert np.array_equal(np.asarray(a).view(np.int32), sent[name]), name
    # cu_q: each row's first token, from the regions alone
    assert lay.cu_q.shape == (lay.n_rows + 1,)
    assert lay.cu_q[0] == 0 and lay.cu_q[-1] == lay.n_tokens
    assert np.all(np.diff(lay.cu_q) > 0)
    # the way out
    outs = {name: rng.randint(0, 1000, shape).astype(np.int32)
            for name, (_, shape) in lay.outs.items()}
    vec = np.asarray(jax.jit(lambda o: lay.join(**o))(outs))
    assert vec.shape == (lay.out_size,) and vec.dtype == np.int32
    back = lay.split(vec)
    assert list(back) == list(outs)
    for name in outs:
        assert np.array_equal(back[name], outs[name]), name


def test_a_fresh_buffer_every_step():
    """The CPU backend may alias a NumPy array it was given, so the
    packed buffer is never reused: two consecutive steps with different
    rows hold two buffers, and the requests read what two fresh engines
    serve them alone."""
    eng = make_engine("dense")
    bufs = []
    pack = eng._pack_arrays

    def spy(rows):
        out = pack(rows)
        bufs.append((out[0], [row for _, _, row in rows]))
        return out

    eng._pack_arrays = spy
    a, b = serve(eng, TRAFFIC[:1] + TRAFFIC[2:3])
    assert any(r0 != r1 for (_, r0), (_, r1) in zip(bufs, bufs[1:]))
    for (b0, _), (b1, _) in zip(bufs, bufs[1:]):
        assert not np.shares_memory(b0, b1)
    for req, traffic in ((a, TRAFFIC[0]), (b, TRAFFIC[2])):
        # the same prompt: ``serve`` draws them in order from one seed
        alone = make_engine("dense")
        alone.add_request(req.prompt, NEW_TOKENS, temperature=traffic[1],
                          top_p=traffic[2], top_k=traffic[3],
                          seed=traffic[4])
        alone.run()
        assert alone.finished[0].out_tokens == req.out_tokens


@pytest.mark.parametrize("family", ("dense", "hybrid"))
def test_the_built_function_by_hand_through_the_layout(family):
    """``build_unified_step_fn`` called outside an engine: the arguments
    are ``(params, packed, pools, *states)`` with ``packed`` filled through
    ``StepLayout.views``, and the first token of a whole-prompt chunk is
    the one the engine serves."""
    from hetu_tpu.serving.decode import StepLayout, build_unified_step_fn
    eng = make_engine(family)
    prompt = [5, 17, 2, 9, 33, 12]
    req = eng.add_request(prompt, 1)
    eng.run()
    sch = eng.scheduler
    fn = build_unified_step_fn(
        eng.cfg, sch.max_batch, sch.chunk, sch.prefill_rows,
        eng.max_pages_per_seq, eng.pool.page_size, use_kernel=False)
    lay = StepLayout(eng.cfg, sch.max_batch, sch.chunk, sch.prefill_rows,
                     eng.max_pages_per_seq)
    assert (lay.size, lay.out_size) == (eng.layout.size,
                                        eng.layout.out_size)
    packed = np.zeros(lay.size, np.int32)
    f = lay.views(packed)
    row, n = sch.max_batch, len(prompt)           # the first chunk slot
    start = int(lay.cu_q[row])
    f["tokens"][start:start + n] = prompt
    f["token_pos"][start:start + n] = np.arange(n)
    f["token_page"][start:start + n] = 1          # page 1, offsets 0..n-1
    f["token_off"][start:start + n] = np.arange(n)
    f["q_lens"][row] = f["ctx_lens"][row] = n
    f["page_tables"][row, 0] = 1
    fresh = make_engine(family, name=f"layout_{family}_hand")
    st = fresh.state_store
    states = () if not fresh.hybrid else (st.conv, st.ssm)
    out, *_ = fn(fresh.params, packed, fresh.pool.k_pages,
                 fresh.pool.v_pages, *states)
    assert lay.split(np.asarray(out))["next_tokens"][row] == \
        req.out_tokens[0]


# ---------------------------------------------------------------------------
# the blocks a step's plain K/V ragged calls fetch (PR 44)
# ---------------------------------------------------------------------------

def _kexaone(**kw):
    """The window / full K/V stack with its MTP module, self-drafting, at
    its own tests' tiny widths (2 kv heads)."""
    import test_kexaone_serving as kx
    _, cfg, state = kx.build()
    return kx.engine(state, cfg, name="layout_kexaone", **kw), kx


def _kv_counters(eng):
    c = eng.metrics_summary()
    return c["kv_page_heads"], c["kv_page_blocks"]


@pytest.mark.parametrize("family", FAMILIES)
def test_kv_page_blocks_are_counted_where_the_rule_decides(family):
    """A pattern stack's full plain K/V layers take the rule, which at
    tiny widths puts every kv head of a slot in one block in every
    region: one full layer's K, from the contexts the step packed.  The
    dense builder's call is pinned and a latent stack has no such call:
    neither counts."""
    eng = make_engine(family)
    serve(eng)
    heads, blocks = _kv_counters(eng)
    if family != "hybrid":
        assert eng.account.kv_block_heads is None \
            and (heads, blocks) == (0, 0)
        return
    assert set(eng.account.kv_block_heads) == {eng.cfg.kv_heads}
    assert blocks > 0 and heads == eng.cfg.kv_heads * blocks


def test_kv_page_blocks_follow_each_regions_heads_a_block(monkeypatch):
    """``kv_page_heads / kv_page_blocks`` is the heads a block of the
    row's region holds, from the function the kernel wrapper calls: with
    the budget cut so that the chunk region's call holds one head a block
    and the verify region's two, a chunk row's pages count a block a head
    and a verify row's a block a slot."""
    import importlib
    from hetu_tpu.serving.decode import _regions
    rpa = importlib.import_module("hetu_tpu.ops.ragged_paged_attention")
    eng, kx = _kexaone()
    kx.serve(eng, kx.prompts(kx.SHAPES[:2]))
    heads, blocks = _kv_counters(eng)
    assert blocks > 0 and heads == 2 * blocks        # the uncut budget
    monkeypatch.setattr(rpa, "KV_HEADS_VMEM", 1 << 20)
    eng, _ = _kexaone()
    sch, pages = eng.scheduler, eng.pool.k_pages[3]  # layer 3: full
    want = {}
    for tag, row, _, n, width in _regions(sch.max_batch, sch.prefill_rows,
                                          sch.chunk, eng.spec_k):
        want[tag] = rpa.kv_call_blocking(
            width, n * width, eng.cfg.num_heads, pages.dtype, pages,
            eng.max_pages_per_seq)[-1]
        assert set(eng.account.kv_block_heads[row: row + n]) == {want[tag]}
    assert want == {"decode": 2, "chunk": 1, "verify": 2}
    seen = []
    account = eng.account
    eng.account = lambda rows, *step: seen.append(
        [(r.pos + q, row) for r, q, row in rows]) or account(rows, *step)
    kx.serve(eng, kx.prompts(kx.SHAPES[:2]))
    heads, blocks = _kv_counters(eng)
    ps, vbase = eng.pool.page_size, sch.max_batch + sch.prefill_rows
    by_slot = [(-(-ctx // ps), row) for step in seen for ctx, row in step]
    assert heads == 2 * sum(n for n, _ in by_slot)
    # a chunk row's pages a block a head, a verify (or decode) row's a slot
    assert blocks == sum(n * 2 if sch.max_batch <= row < vbase else n
                         for n, row in by_slot)
    assert 1.0 < heads / blocks < 2.0


# ---------------------------------------------------------------------------
# what a step read (``serving/step_account``), recounted by brute force
# ---------------------------------------------------------------------------

def _account_engine(stack: str) -> Engine:
    """A traced tiny engine of each stack, at its own tests' widths."""
    if stack == "dots3":
        import test_dots3_serving as d3
        _, cfg, state = d3.build()
        return d3.engine(state, cfg, tracer=SpanTracer(), name="account_d3")
    if stack == "kexaone":
        return _kexaone(tracer=SpanTracer())[0]
    return make_engine({"mistral4": "latent"}.get(stack, stack),
                       tracer=SpanTracer())


def _recount(eng: Engine, step: dict) -> tuple:
    """One step's ``(counters, gauges, span attributes)`` from the rows of
    the tap (slot, position, query length; the page tables) and what the
    spy saw beside them: sets of pages as Python sets, pairs by loops."""
    from hetu_tpu.ops.index_score import (INDEX_SELECT_BLOCK,
                                          index_score_blocking)
    from hetu_tpu.ops.moe_grouped import ROW_BLOCK
    from hetu_tpu.ops.ragged_paged_attention import (
        kv_call_blocking, latent_pages_per_grid_step)
    from hetu_tpu.serving.decode import _regions
    cfg, sch, pool = eng.cfg, eng.scheduler, eng.pool
    ps, maxp = pool.page_size, eng.max_pages_per_seq
    vbase = sch.max_batch + sch.prefill_rows
    width = {row + i: w for _, row, _, n, w in _regions(
        sch.max_batch, sch.prefill_rows, sch.chunk, eng.spec_k)
        for i in range(n)}
    rows, tables = step["rows"], step["page_tables"]
    held = {row: [int(tables[row, j]) for j in range(maxp)
                  if j * ps < pos + q] for row, pos, q in rows}
    every = set().union(*held.values())

    def pairs(lo, n, reach=None):
        # (query, key) pairs of n queries from position lo, each reading
        # the keys up to itself (the last ``reach`` of them)
        return sum(1 for x in range(lo, lo + n) for k in range(x + 1)
                   if reach is None or x - k < reach)

    causal = sum(pairs(pos, q) for _, pos, q in rows)
    c, g, a = {}, {}, {}
    if cfg.layers_of("mamba2"):
        g["state_slots_in_use"] = step["slots"]
        c["ssm_slots_walked"] = sum(row < sch.max_batch for row, _, _ in rows)
        c["ssm_slots_store"] = eng.state_store.num_slots

    def experts(load, tag):
        blocks = sum(-(-int(n) // ROW_BLOCK) for layer in load for n in layer)
        return blocks, {f"{tag}_local": sum(int(n) for l in load for n in l),
                        f"{tag}_experts_hit": sum(
                            1 for l in load for n in l if n > 0),
                        f"{tag}_blocks": blocks}

    if cfg.layers_of("moe"):
        load = step["out"]["moe_load"]
        blocks, attrs = experts(load, "moe")
        a.update(attrs)
        a["moe_load_peak"] = g["moe_expert_load_peak"] = \
            float(load.max() * load.size / max(a["moe_local"], 1))
        c["moe_assignments_local"] = a["moe_local"]
        c["moe_assignments_total"] = sum(q for _, _, q in rows) \
            * cfg.moe_top_k * len(cfg.layers_of("moe"))
        c["moe_block_rows"] = blocks * ROW_BLOCK
    full = [n for n, i in enumerate(cfg.paged_layers)
            if cfg.is_hybrid and cfg.stack_pattern[i] == "attention"
            and not cfg.window_of(i)]
    if full:
        kv = pool.k_pages[full[0]]
        c["kv_page_heads"] = cfg.kv_heads * sum(map(len, held.values()))
        c["kv_page_blocks"] = sum(
            len(held[row]) * (cfg.kv_heads // kv_call_blocking(
                width[row], (sch.max_batch if row < sch.max_batch else 1)
                * width[row], cfg.num_heads, kv.dtype, kv, maxp)[-1])
            for row in held)
    if cfg.layers_of("mla"):
        a.update(latent_ctx_tokens=sum(pos + q for _, pos, q in rows),
                 latent_pages=sum(map(len, held.values())),
                 latent_pages_distinct=len(every), attn_pairs=causal,
                 latent_grid_steps=sum(
                     -(-len(held[row]) // latent_pages_per_grid_step(
                         width[row], cfg.num_heads,
                         sum(cfg.latent_page_dims), maxp,
                         (pool.k_pages[0], pool.v_pages[0])))
                     for row in held))
        c.update(latent_pages_attended=a["latent_pages"],
                 latent_pages_attended_distinct=len(every),
                 latent_grid_steps=a["latent_grid_steps"])
    if cfg.page_layers is not None:
        topk = cfg.mixer_geometry["dsa"].index_topk \
            if cfg.layers_of("dsa") else 0
        docs = {}
        for row, pos, q in rows if topk else ():
            docs[held[row][0]] = max(docs.get(held[row][0], 0),
                                     min(pos + q, topk))
        wins = [pg for pages in step["win_pages"] for pg in pages]
        # every query block of a row's scoring call walks the row's pages,
        # a group of slots a grid step; a chunk slot's selection and read
        # run its blocks of queries up to the row's live tokens
        key_pages = grid = blocks_live = blocks_padded = 0
        for row, _, q in rows if topk else ():
            q_blk, group = index_score_blocking(
                width[row] if width[row] > 1 else sch.max_batch,
                cfg.mixer_geometry["dsa"].index_heads, maxp, width[row] > 1,
                pool.v_pages[0])
            for _ in range(0, width[row], q_blk):
                key_pages += len(held[row])
                grid += len(range(0, len(held[row]), group))
            if sch.max_batch <= row < vbase:
                blocks_live += len(range(0, q, INDEX_SELECT_BLOCK))
                blocks_padded += len(range(0, width[row],
                                           INDEX_SELECT_BLOCK))
        a.update(index_pairs=causal, index_key_pages=key_pages,
                 index_grid_steps=grid,
                 index_chunk_blocks_live=blocks_live,
                 index_chunk_blocks_padded=blocks_padded,
                 index_selected=sum(pairs(pos, q, topk) for _, pos, q in rows)
                 if topk else 0,
                 index_selected_floor=sum(docs.values()),
                 index_pages_distinct=len(every) if topk else 0,
                 window_pages=len(wins),
                 window_tokens_distinct=ps * len(set(wins)),
                 window_pairs=sum(pairs(pos, q, cfg.window_tokens)
                                  for _, pos, q in rows))
        c.update(index_pairs_scored=causal,
                 index_positions_selected=a["index_selected"],
                 index_key_pages_scored=key_pages, index_grid_steps=grid,
                 index_chunk_blocks_live=blocks_live,
                 index_chunk_blocks_padded=blocks_padded,
                 window_pages_held=step["window_in_use"],
                 full_pages_held=step["full_in_use"])
    if eng.self_draft:
        decode = [row for (row, _, _), d in zip(rows, step["decode"]) if d]
        verify = [row for (row, _, _), d, v in zip(
            rows, step["decode"], step["drafted"])
            if d and v and row >= vbase]
        acc = step["out"]["accepted"]
        kept = {row: 1 + int(acc[row]) if row in verify else q
                for row, _, q in rows}
        c.update(decode_rows=len(decode), spec_rows=len(verify))
        a.update(experts(step["out"]["mtp_load"], "mtp_moe")[1],
                 verify_rows=len(verify),
                 spec_accepted=sum(int(acc[row]) for row in verify),
                 attn_pairs=causal, mtp_tokens=sum(kept.values()),
                 mtp_attn_pairs=sum(pairs(pos, kept[row])
                                    for row, pos, _ in rows),
                 kv_pages_distinct=len(every),
                 window_keys=sum(len({
                     k for x in range(pos, pos + q) for k in range(x + 1)
                     if x - k < cfg.window_tokens}) for _, pos, q in rows))
    return c, g, a


@pytest.mark.parametrize(
    "stack", ("dense", "hybrid", "mistral4", "dots3", "kexaone"))
def test_the_account_is_what_the_steps_rows_read(stack):
    """Every counter and gauge ``serving/step_account`` writes and every
    attribute it gives the ``unified_step`` span, against a recount from
    the tap's rows: two waves of requests, the second two rows on one
    document (shared pages where the stack keeps a prefix cache).  The
    dense step is accounted nothing."""
    from hetu_tpu.serving.step_account import COUNTERS, GAUGES
    eng = _account_engine(stack)
    account, seen = eng.account, []

    def spy(rows, fields, out, traced):
        st, win = eng.state_store, eng.pool.window
        seen.append(dict(
            out={k: v.copy() for k, v in out.items()},
            win_pages=[list(r.win_pages) for r, _, _ in rows],
            decode=[len(r.tokens) - r.pos == 1 and r.n_generated > 0
                    and not r.resuming for r, _, _ in rows],
            drafted=[bool(r.spec_drafts) for r, _, _ in rows],
            slots=st.in_use if st is not None else 0,
            window_in_use=win.in_use if win is not None else 0,
            full_in_use=eng.pool.num_usable - eng.pool.free_pages))
        return account(rows, fields, out, traced)

    eng.account = spy
    rng = np.random.RandomState(5)

    def draw(n):
        return rng.randint(1, eng.cfg.vocab_size, n).tolist()

    doc = draw(20)
    for prompt, new in ((draw(19), 6), (draw(5), 9), (doc, 4)):
        eng.add_request(prompt, new)
    eng.run()
    for tail in ([3, 4, 5], [7]):
        eng.add_request(doc + tail, 5)
    eng.run()
    taps = [t for t in eng.tap if t["kind"] == "unified"]
    spans = [e.attrs for e in eng.tracer.events() if e.name == "unified_step"]
    assert len(taps) == len(seen) == len(spans) > 10
    base = {"exec", "step", "rows", "tokens", "h2d_bytes"}
    counters = dict.fromkeys(COUNTERS, 0)
    gauges = dict.fromkeys(GAUGES, 0)
    for tap, step, span in zip(taps, seen, spans):
        c, g, a = _recount(eng, {**tap, **step})
        for k, v in c.items():
            counters[k] += v
        gauges.update(g)
        assert {k: span[k] for k in set(span) - base} == a
    m = eng.metrics_summary()
    assert {k: m[k] for k in counters} == counters
    assert {k: m[k] for k in gauges} == pytest.approx(gauges)
    if stack == "dense":
        assert not any(counters.values()) and not any(gauges.values())
    else:
        assert any(counters.values())
    if eng.prefix_cache is not None and stack != "dense":
        assert m["prefix_cache_hits"] >= 1


# ---------------------------------------------------------------------------
# a block-wise model's step (PR 48): the block slots on the verify slots'
# scaffolding, two more fields in, three more arrays out
# ---------------------------------------------------------------------------

def _sdar(**kw):
    """The block-diffusion stack at its own tests' tiny widths."""
    import test_sdar_serving as sd
    cfg, state, _ = sd.build()
    return sd.engine(cfg, state, name="layout_sdar",
                     time_fn=lambda: 0.0, **kw), sd


def test_block_slots_stand_where_the_verify_slots_stand():
    """The block region is the narrow region behind the chunk slots:
    ``max_batch`` rows of ``2 B`` tokens (a plain row fills the first B, a
    fused row all of them), tagged ``block``, the layout's last; the decode
    and chunk regions are where every build has them."""
    from hetu_tpu.serving.decode import _chunk_slots, _regions
    eng, sd = _sdar()
    sch, b = eng.scheduler, eng.cfg.diffusion_block
    regions = _regions(sch.max_batch, sch.prefill_rows, sch.chunk, 0, b)
    assert [r[0] for r in regions] == ["decode", "chunk", "block"]
    assert regions[:2] == _regions(sch.max_batch, sch.prefill_rows,
                                   sch.chunk, 0)
    # the same rows and tokens as the verify slots of a build with drafts
    # of 2 B - 1
    verify = _regions(sch.max_batch, sch.prefill_rows, sch.chunk, 2 * b - 1)
    assert regions[2][1:] == verify[2][1:] and verify[2][0] == "verify"
    assert regions[2][4] == 2 * b
    assert _chunk_slots(sch.max_batch, sch.prefill_rows, sch.chunk, 0, b) \
        == _chunk_slots(sch.max_batch, sch.prefill_rows, sch.chunk, 2 * b - 1)
    lay = eng.layout
    assert lay.n_rows == 2 * sch.max_batch + sch.prefill_rows
    assert lay.n_tokens == sch.max_batch * (1 + 2 * b) + sch.chunk
    assert sch.token_budget == lay.n_tokens
    assert {"unmask_k", "unmask_tau"} <= set(lay.fields)
    assert "spec_lens" not in lay.fields and "next_tok" not in lay.fields
    assert list(lay.outs) == ["next_tokens", "moe_load", "block_tokens",
                              "block_flags", "block_conf"]
    # the head's work is the open block's: B positions a slot, not 2 B
    assert lay.outs["block_tokens"][1] == (sch.max_batch, b) == \
        lay.outs["block_conf"][1]


@pytest.mark.parametrize("spec_k", [0, 1, 3])
def test_slots_without_a_block_are_what_they_were(spec_k):
    """``block=0`` (every ``spec_k`` a configuration uses: none, the MTP
    module's 1, a draft model's 3): the slots and regions written out by
    hand, as they were before a block slot was two blocks wide."""
    from hetu_tpu.serving.decode import _chunk_slots, _regions
    s, r, chunk = 4, 2, 16
    slots = [(4, 4, 16), (5, 20, 16)]
    regions = [("decode", 0, 0, 4, 1), ("chunk", 4, 4, 2, 16)]
    if spec_k:
        w = spec_k + 1
        slots += [(6 + j, 36 + j * w, w) for j in range(4)]
        regions.append(("verify", 6, 36, 4, w))
    for args in ((s, r, chunk, spec_k), (s, r, chunk, spec_k, 0)):
        assert _chunk_slots(*args) == slots
        assert _regions(*args) == regions


def test_a_block_step_is_one_buffer_each_way_and_the_rule_rides_it():
    """One transfer each way a step; ``unmask_k`` / ``unmask_tau`` are the
    schedule's count for the pass and 2.0 under the static rule, 0 / 2.0 on
    a plain commit pass, the next block's first pass's on a fused row; the
    float travels by its bit pattern."""
    eng, sd = _sdar()
    h = eng.add_request(sd.prompts([9])[0], 6)
    seen = []
    real = eng._compiled["unified"]

    def spy(params, packed, *rest):
        f = eng.layout.views(np.asarray(packed).copy())
        rows = [r for r in range(eng.layout.n_rows) if f["q_lens"][r]]
        seen.append([(r, int(f["q_lens"][r]), int(f["unmask_k"][r]),
                      float(f["unmask_tau"][r])) for r in rows])
        return real(params, packed, *rest)
    eng._compiled["unified"] = spy
    eng.run()
    c = eng.metrics_summary()
    assert c["h2d_copies"] == c["d2h_fetches"] == c["step_calls"] == \
        len(seen)
    vbase = eng.scheduler.max_batch + eng.scheduler.prefill_rows
    # 9 tokens: two whole blocks in a chunk, then block 2 opens with one
    # prompt token: masks 3 -> passes (2, 1), then its commit FUSED with
    # block 3's first pass (8 positions under that pass's count, 2); block
    # 3's second pass and, the request ending there, a plain commit.
    assert seen[0] == [(eng.scheduler.max_batch, 8, 0, 0.0)]
    assert [s[0][1:] for s in seen[1:]] == [
        (4, 2, 2.0), (4, 2, 2.0), (8, 2, 2.0), (4, 2, 2.0), (4, 0, 2.0)]
    assert all(s[0][0] == vbase for s in seen[1:])
    assert len(h.out_tokens) == 6


@pytest.mark.parametrize("rule,k,tau", [
    ("low_confidence_static", 1, 2.0), ("sequential", -1, 2.0),
    ("low_confidence_dynamic", 1, 0.9)])
def test_the_three_rules_are_two_numbers(rule, k, tau):
    from hetu_tpu.serving import DenoiseRule
    r = DenoiseRule(steps=3, rule=rule)
    assert [r.unmask(4, t) for t in range(3)] == \
        [(2 * k, tau), (k, tau), (k, tau)]


def test_the_block_head_selects_on_the_device():
    """``_block_head`` alone, on logits built by hand: rank by confidence
    with ties to the lower position, the sequential rule by position, the
    threshold whatever the rank, never the mask id, nothing on a commit —
    over each slot's OPEN block: the first half of a plain row's slot, the
    second half of a fused one's, whatever the other half holds."""
    import jax.numpy as jnp
    from hetu_tpu.serving.decode import _block_head
    eng, sd = _sdar()
    cfg, mask = eng.cfg, eng.cfg.mask_token_id
    v, h = cfg.vocab_size, cfg.hidden_size
    # a head whose row t is e_t, and hidden states that score one token
    head = jnp.zeros((v, h)).at[jnp.arange(h), jnp.arange(h)].set(1.0)
    p = lambda name: head if name == "lm_head.weight" else None  # noqa: E731
    peak = lambda tok, s: jnp.zeros((h,)).at[tok].set(s)         # noqa: E731
    loud = [peak(20, 30.0)] * 4
    # slot 0 a plain row: its block, then four dead positions; slot 1 a
    # fused row: the committed block, then the open one
    x = jnp.stack([peak(3, 9.0), peak(4, 5.0), peak(5, 9.0), peak(6, 7.0)]
                  + loud + loud +
                  [peak(7, 1.0), peak(8, 2.0), peak(9, 3.0), peak(10, 4.0)])
    tokens = jnp.asarray([mask, mask, mask, 11] + [mask] * 4 +
                         [12, 13, 14, 15] + [mask] * 4)
    q_lens = jnp.asarray([4, 8])
    live = jnp.asarray([True] * 4 + [False] * 4 + [True] * 8)
    zeros = jnp.zeros((2,))
    sampling = (zeros, zeros, jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), jnp.int32))

    def head_of(p, x, ks, taus):
        return _block_head(cfg, p, x, tokens, jnp.arange(16), live, q_lens,
                           sampling, jnp.asarray(ks, jnp.int32),
                           jnp.asarray(taus, jnp.float32))

    def picks(ks, taus):
        out = head_of(p, x, ks, taus)
        return (np.asarray(out["block_tokens"]).tolist(),
                [int(f) for f in np.asarray(out["block_flags"])])
    toks, flags = picks([1, 2], [2.0, 2.0])
    # row 0: positions 0 and 2 tie at the top: the lower; 3 is not masked.
    # row 1: the two most confident are the last two
    assert flags == [0b0001, 0b1100]
    assert toks == [[3, 4, 5, 11], [7, 8, 9, 10]]
    assert picks([-1, -2], [2.0, 2.0])[1] == [0b0001, 0b0011]   # by position
    conf = np.asarray(head_of(p, x, [0, 0], [2.0, 2.0])[
        "block_conf"]).view(np.float32)
    assert picks([0, 0], [2.0, 2.0])[1] == [0, 0]               # a commit
    tau = float((conf[0, 1] + conf[0, 0]) / 2)
    assert picks([1, 1], [tau, 2.0])[1] == [0b0101, 0b1000]     # above tau
    # the mask id is never the choice, however it scores
    shout = head.at[mask, 0].set(50.0)
    out = head_of(lambda name: shout if name == "lm_head.weight" else None,
                  x.at[:, 0].add(0.5), [4, 4], [2.0, 2.0])
    assert np.asarray(out["block_tokens"]).tolist() == \
        [[3, 4, 5, 11], [7, 8, 9, 10]]
