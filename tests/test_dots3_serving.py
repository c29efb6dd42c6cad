"""An indexed / window latent-attention stack with a leading dense layer
(``model_type: dots3_note``: per published layer a "dsa" or "swa" mixer,
then "mlp" or "moe") through the serving engine, against the plain float32
reference (``benchmark/reference_dots3.py``: NOT absorbed, no cache, the
selection and the window as masks), at tiny widths on the CPU with seeded
random weights.

Tolerances, each with its reason:

* ``GAP_F32`` 1e-4 — float32 system against the float32 reference, in
  logit units of the reference (a served greedy token's logit below the
  reference's best, teacher-forced).  The two differ by reassociation only
  (absorbed against decompressed attention, gathered selection against a
  mask, paged window against a band, grouped experts against a per-expert
  loop): ~1e-6 at these widths.  A wrong mask, window edge, rotation,
  rescale, gate or share reads 0.05-1: the controls below read the
  reference with ``index_topk`` or the window off by one position set and
  have to fail the same tolerance.
* ``GAP_BF16`` 0.03 — the bf16 system against the float32 reference at
  hidden 64, 5 x 2 layers, weights of std 0.05: 8 mantissa bits, and a
  selection that may swap two positions at the 12th place; the served
  tokens read 0 to 0.01 below the reference's best, the float8 reading of
  the same sequences (every weight matrix and every mixer's input and
  output rounded to e4m3) reads 0.05-0.4, which has to fail it.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
import reference_dots3 as ref  # noqa: E402

from hetu_tpu import obs  # noqa: E402
from hetu_tpu.models import hybrid as hy  # noqa: E402
from hetu_tpu.serving import Engine  # noqa: E402
from hetu_tpu.serving.kv_pool import (WindowPages,  # noqa: E402
                                      window_table_pages, window_tail_pages)

GAP_F32 = 1e-4
GAP_BF16 = 0.03
VOCAB = 256
TOPK, WINDOW = 12, 13
TYPES = ["full_attention", "full_attention", "sliding_attention",
         "sliding_attention", "sliding_attention"]


def published(**kw) -> dict:
    """A tiny ``dots3_note`` config under the published keys: the two
    latent geometries differ in every size (full 4 heads, latent 32, 16 |
    8 | 16; window 2 heads, latent 48, 24 | 8 | 16), ``index_topk`` 12 and
    a 13-key window (both below the contexts), one leading dense layer, 8
    routed experts of which 4 are held from offset 2, top-3, one shared."""
    d = dict(model_type="dots3_note", hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=5, vocab_size=VOCAB,
             layer_types=list(TYPES), max_position_embeddings=4096,
             hidden_act="silu", rms_norm_eps=1e-5, tie_word_embeddings=False,
             kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7,
             rope_scaling=None, swa_kv_lora_rank=48, swa_q_lora_rank=24,
             swa_num_attention_heads=2, swa_num_key_value_heads=2,
             swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
             swa_v_head_dim=16, swa_rope_theta=50000,
             sliding_window_size=WINDOW, attention_gate_type="headwise",
             swa_attention_gate_type="headwise",
             apply_mla_qkv_lora_rescale=True, index_n_heads=4,
             index_head_dim=16, index_topk=TOPK, first_k_dense_replace=1,
             intermediate_size=96, moe_intermediate_size=48,
             moe_layer_freq=1, n_routed_experts=4, moe_router_outputs=8,
             expert_offset=2, n_shared_experts=1, norm_topk_prob=True,
             num_experts_per_tok=3, routed_scaling_factor=1,
             scoring_func="sigmoid", topk_method="noaux_tc", dtype="float32")
    d.update(kw)
    return d


def build(seed: int = 3, std: float = 0.2, **kw):
    pub = published(**kw)
    cfg = hy.dots3_config(pub, init_std=std)
    return pub, cfg, hy.init_state(cfg, seed, router_bias_std=0.1)


def engine(state, cfg, **kw):
    kw = {"num_pages": 64, "page_size": 8, "max_batch": 3, "chunk_size": 16,
          "max_model_len": 96, "prefix_cache": True, "debug": True,
          "use_kernel": False, **kw}
    return Engine(state, cfg, **kw)


def prompts(lengths, seed: int = 0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).tolist() for n in lengths]


def gaps(pub, state, prompt, out, new: int = 8):
    return ref.greedy_logit_gaps(state, prompt + list(out), len(prompt),
                                 ref.spec_from_config(pub), 96, new)


def _sub(state, prefix):
    return {k[len(prefix):]: v for k, v in state.items()
            if k.startswith(prefix)}


# -- (a) engine against reference: chunks, then decode through the pools -----

@pytest.mark.parametrize("dtype,use_kernel,tol", [
    ("float32", False, GAP_F32), ("float32", True, GAP_F32),
    ("bfloat16", True, GAP_BF16)])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(
        dtype, use_kernel, tol):
    """Logits, not tokens.  One batch holds contexts below (5 + 6), at
    (prompt 12: the first decoded query is the 13th position) and above
    (40 + 8) ``index_topk`` 12 and the 13-key window; the long prompt is
    prefilled in chunks of 16 and all three decode through the full-space
    pages, the index keys and the window space."""
    pub, cfg, state = build(dtype=dtype, std=0.05 if dtype == "bfloat16"
                            else 0.2)
    eng = engine(state, cfg, use_kernel=use_kernel, prefix_cache=False)
    ps = prompts([5, 12, 40])
    hs = [eng.add_request(p, 8) for p in ps]
    eng.run()
    assert eng.compile_count == 1 and eng.state_store is None
    worst = max(max(gaps(pub, state, p, h.out_tokens))
                for p, h in zip(ps, hs))
    assert all(len(h.out_tokens) == 8 for h in hs) and worst <= tol, worst
    assert eng.pool.window.in_use == 0 and eng.pool.used_pages == 0
    if dtype == "bfloat16":
        spec = ref.spec_from_config(pub)
        low = max(max(ref.lowp_choice_gaps(
            state, p + list(h.out_tokens), len(p), spec, 96, 8))
            for p, h in zip(ps, hs))
        assert low > tol, low     # the float8 reading fails the tolerance


def paged_mixer(cfg, state, i: int, u):
    """Layer ``i``'s attention mixer as the serving step computes it, on
    ONE sequence ``u`` [T, H] fed as a single chunk row: the latents (and
    index keys) written into pages, the absorbed attention over the
    selection or the window, the gate, the out projection."""
    kind = cfg.layer_pattern[i]
    geo, t, ps = cfg.geometry(kind), u.shape[0], 8
    pages = -(-t // ps)
    cos, sin, _ = hy.mla_rotary_tables(cfg, pages * ps, geo)
    pos = jnp.arange(t)
    q, c_kv, k_r, c_q = hy.latent_in(cfg, state, i, u, geo)
    q_rot = hy.mla_rotate(cfg, q[..., geo.nope:], cos[:t], sin[:t],
                          geo.interleave)
    k_rot = hy.mla_rotate(cfg, k_r, cos[:t], sin[:t], geo.interleave)
    lanes = geo.rope_lanes - geo.rope
    q_cat = jnp.pad(hy.mla_absorb_q(cfg, state, i, q, q_rot, geo.nope),
                    ((0, 0), (0, 0), (0, lanes)))

    def paged(x, width):        # [T, w] -> pages 1.. of [P, 1, ps, width]
        x = jnp.pad(x, ((0, pages * ps - t), (0, width - x.shape[1])))
        return jnp.concatenate([jnp.zeros((1, 1, ps, width), x.dtype),
                                x.reshape(pages, 1, ps, width)])

    table = jnp.arange(1, pages + 1)
    pool = paged(jnp.concatenate([c_kv, k_rot], -1),
                 geo.latent + geo.rope_lanes)
    if kind == "dsa":
        iq, iw = hy.index_queries(state, i, u, c_q, geo)
        ik = hy.rotate_index(hy.index_keys(state, i, u), cos[:t], sin[:t])
        o = hy.indexed_attention(
            geo, hy.rotate_index(iq, cos[:t], sin[:t]), iw, q_cat, pos,
            table, (pool, paged(ik, geo.index_dim)), block=16)
    else:
        o = hy.window_attention(geo, q_cat, pos, table, 0, pool)
    attn = hy.mla_absorb_out(cfg, state, i, o, jnp.float32)
    attn = attn.reshape(t, geo.heads, geo.v) * \
        hy.head_gate(state, i, u)[:, :, None]
    return attn.reshape(t, -1) @ state[f"h{i}.attn.out.weight"].T


@pytest.mark.parametrize("layer,kind,change", [
    (2, "full", dict(index_topk=TOPK - 1)),
    (2, "full", dict(apply_mla_qkv_lora_rescale=False)),
    (4, "window", dict(sliding_window_size=WINDOW - 1)),
    (4, "window", dict(swa_rope_theta=10000))])
def test_the_paged_mixers_equal_the_references_masks(layer, kind, change):
    """Tensor against tensor, 40 positions: the gathered selection against
    the reference's selection mask, the paged window against its band,
    absorbed against decompressed, within 2e-5 of the largest entry; and
    the control: a reference that selects one position fewer, sees one key
    fewer, drops the rescale or turns with another base lies 100 x
    further, so the agreement is not the tolerance's slack."""
    pub, cfg, state = build()
    u = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)
    p = ref._f32(_sub(state, f"h{layer}.attn."))

    def theirs(pub):
        spec = ref.spec_from_config(pub)
        member = ref.select(u, p, spec)[1] if kind == "full" else None
        return ref.attention(u, p, spec, kind, member)

    with jax.default_matmul_precision("highest"):
        mine = paged_mixer(cfg, state, layer, u)
        want, other = theirs(pub), theirs(published(**change))
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(mine - want).max()) <= 2e-5 * top
    assert float(jnp.abs(mine - other).max()) > 2e-3 * top


# -- (b) the prefix cache over two page spaces --------------------------------

def test_a_hit_at_a_document_boundary_equals_the_cold_run():
    """A request that resumes at a cached document's end reads the window
    layers' tail (the 12 positions before the boundary) and the full
    layers' latents AND index keys out of the cache: same tokens, same
    logits as a cold engine that prefills the whole prompt."""
    pub, cfg, state = build()
    (doc,) = prompts([48], seed=5)
    p = doc + [7, 8, 9, 10, 11]
    cold = engine(state, cfg, name="cold")
    h0 = cold.add_request(p, 8)
    cold.run()
    warm = engine(state, cfg, name="warm")
    warm.add_request(doc + [1, 2, 3], 2)
    warm.run()
    tail = [e.tail for e in warm.prefix_cache._index.values()
            if e.tail is not None]
    assert tail == [[e for e in tail[0]]] and len(tail[0]) == \
        window_tail_pages(WINDOW, 8) == 2
    assert warm.pool.window.in_use == 2
    h1 = warm.add_request(p, 8)
    warm.step()
    req = warm.running[0]
    assert req.pos >= 48 and req.cached_tokens == 48
    assert req.win_first == 4                # the tail's two pages, shared
    warm.run()
    assert list(h1.out_tokens) == list(h0.out_tokens)
    assert max(gaps(pub, state, p, h1.out_tokens)) <= GAP_F32
    m = warm.metrics_summary()
    assert m["prefix_cache_tokens_saved"] == 48
    warm.pool.check_invariants(force=True)
    warm.prefix_cache.check_invariants(force=True)


def test_a_prefix_without_its_window_tail_is_not_resumed():
    """The window space lets go of tails first; a chain whose boundary lost
    its tail is still a parent of deeper entries but no place to resume:
    the next request prefills from 0 and still agrees."""
    pub, cfg, state = build()
    (doc,) = prompts([48], seed=6)
    eng = engine(state, cfg)
    eng.add_request(doc + [1, 2, 3], 2)
    eng.run()
    assert len(eng.prefix_cache.match(doc + [9] * 5)) == 6
    assert eng.prefix_cache.drop_tails(100) == 2
    assert eng.pool.window.in_use == 0
    assert eng.prefix_cache.match(doc + [9] * 5) == []
    p = doc + [4, 5, 6]
    h = eng.add_request(p, 4)
    eng.run()
    assert eng.metrics_summary()["prefix_cache_tokens_saved"] == 0
    assert max(gaps(pub, state, p, h.out_tokens, 4)) <= GAP_F32


def test_tails_nobody_resumed_from_go_before_a_hot_documents():
    """A finished request leaves a tail at its own end, which nothing may
    ever resume from; the window space's sweep drops those before the tail
    of a boundary that requests did resume from, however recent they
    are."""
    _, cfg, state = build()
    (doc,) = prompts([48], seed=7)
    eng = engine(state, cfg, max_model_len=128)
    eng.add_request(doc + [1, 2, 3], 2)
    eng.run()
    for k in range(3):                       # three hits, three new tails
        eng.add_request(doc + prompts([20], seed=10 + k)[0], 4)
        eng.run()
    tails = sorted((e.hits, e.depth) for e in
                   eng.prefix_cache._index.values() if e.tail is not None)
    assert tails[-1] == (3, 5) and [h for h, _ in tails[:-1]] == [0, 0, 0]
    eng.prefix_cache.drop_tails(2 * 3)
    left = [(e.hits, e.depth) for e in eng.prefix_cache._index.values()
            if e.tail is not None]
    assert left == [(3, 5)]


# -- (c) window pages are bounded by the window, not by the context -----------

def test_window_pages_held_stay_bounded_as_the_context_grows():
    _, cfg, state = build()
    eng = engine(state, cfg, prefix_cache=False, max_model_len=96,
                 tracer=obs.SpanTracer())
    assert eng.window_table_pages == window_table_pages(WINDOW, 16, 8) == 5
    (p,) = prompts([60])
    h = eng.add_request(p, 30)
    held, full = [], []
    while eng.has_work:
        eng.step()
        if eng.running:
            held.append(len(eng.running[0].win_pages))
            full.append(len(eng.running[0].pages))
    assert len(h.out_tokens) == 30 and max(full) == 11       # 89 written
    assert max(held) <= eng.window_table_pages
    assert held[-1] <= 3                     # decode: the tail + the cursor
    steps = [e for e in eng.tracer.events() if e.name == "unified_step"]
    assert max(e.attrs["window_pages"] for e in steps) <= 5
    m = eng.metrics_summary()
    assert 0 < m["window_pages_held"] < m["full_pages_held"]


def test_the_window_space_counts_references():
    w = WindowPages(6)
    a = w.alloc(3)
    w.retain(a[:2])
    w.release(a)
    assert w.in_use == 2 and w.free_pages == 3 and not w.problems()
    assert w.alloc(4) is None
    w.release(a[:2])
    assert w.in_use == 0 and sorted(w.alloc(5)) == [1, 2, 3, 4, 5]
    with pytest.raises(ValueError, match="free window page"):
        WindowPages(4).release([2])


# -- (d) what the step says it did --------------------------------------------

def test_the_span_carries_the_indexers_and_the_windows_counts():
    """One row, prompt 40 in chunks of 16 then decode: ``index_pairs`` are
    the causal pairs, ``index_selected`` is ``index_topk`` a query once the
    context passes it, ``window_pairs`` the keys inside the windows."""
    _, cfg, state = build()
    eng = engine(state, cfg, prefix_cache=False, tracer=obs.SpanTracer())
    (p,) = prompts([40])
    eng.add_request(p, 3)
    eng.run()
    steps = [e.attrs for e in eng.tracer.events()
             if e.name == "unified_step"]
    spans = [(0, 16), (16, 32), (32, 40), (40, 41), (41, 42)]
    for a, (lo, hi) in zip(steps, spans):
        q = np.arange(lo, hi) + 1
        assert a["index_pairs"] == q.sum()
        assert a["index_selected"] == np.minimum(q, TOPK).sum()
        assert a["window_pairs"] == np.minimum(q, WINDOW).sum()
        assert a["index_selected_floor"] == min(hi, TOPK)
        assert a["index_pages_distinct"] == -(-hi // 8)
    m = eng.metrics_summary()
    assert m["index_pairs_scored"] == sum(a["index_pairs"] for a in steps)
    assert m["index_positions_selected"] == \
        sum(a["index_selected"] for a in steps)


def test_an_untraced_step_counts_what_a_traced_one_does():
    """The counters are closed-form sums; the walk over the page tables is
    the span's alone.  Both engines count the same pairs and selections."""
    _, cfg, state = build()
    ps = prompts([40, 7, 23])
    totals = []
    for tracer in (obs.SpanTracer(), None):
        eng = engine(state, cfg, tracer=tracer)
        for p in ps:
            eng.add_request(p, 5)
        eng.run()
        m = eng.metrics_summary()
        totals.append([m[k] for k in (
            "index_pairs_scored", "index_positions_selected",
            "window_pages_held", "full_pages_held")])
    assert totals[0] == totals[1] and all(v > 0 for v in totals[0])


@pytest.mark.parametrize("early", [False, True])
def test_the_fetch_started_at_dispatch_serves_the_same_tokens(early):
    _, cfg, state = build()
    eng = engine(state, cfg, early_fetch=early)
    hs = [eng.add_request(p, 6) for p in prompts([40, 9, 30, 17], seed=4)]
    eng.run()
    plain = engine(state, cfg)
    want = [plain.add_request(p, 6) for p in prompts([40, 9, 30, 17], seed=4)]
    plain.run()
    assert [h.out_tokens for h in hs] == [h.out_tokens for h in want]


def test_the_kept_page_array_follows_the_requests_list():
    """``Engine._page_array`` keeps ``req.pages`` as int32 between steps:
    the same list grown in place gains its tail, another list (a restart, a
    preemption) is read anew, and the packed tables agree with the lists."""
    from hetu_tpu.serving.request import Request
    req = Request(req_id=0, prompt=[1], max_new_tokens=1)
    req.pages = [4, 9]
    a = Engine._page_array(req)
    assert a.tolist() == [4, 9] and Engine._page_array(req) is a
    req.pages.extend([2, 7])
    b = Engine._page_array(req)
    assert b.tolist() == [4, 9, 2, 7] and b.dtype == np.int32
    req.pages = req.pages + [5]
    assert Engine._page_array(req).tolist() == [4, 9, 2, 7, 5]
    req.pages = []
    assert Engine._page_array(req).tolist() == []
    # through the engine, preemptions included: a pool too small for three
    # rows' contexts evicts, and every step's table is the list's
    _, cfg, state = build()
    eng = engine(state, cfg, num_pages=14, prefix_cache=False)
    for p in prompts([30, 28, 26], seed=6):
        eng.add_request(p, 12)
    pack = eng._pack_arrays

    def checked(rows):
        packed, f = pack(rows)
        for req, _, row in rows:
            assert f["page_tables"][row, :len(req.pages)].tolist() == \
                req.pages
        return packed, f

    eng._pack_arrays = checked
    eng.run()
    assert eng.metrics_summary()["preemptions"] > 0


def test_a_full_batch_admits_nothing_and_leaves_the_queue():
    _, cfg, state = build()
    eng = engine(state, cfg, max_batch=2)
    for p in prompts([20, 20, 20, 20], seed=7):
        eng.add_request(p, 4)
    eng.step()
    assert len(eng.running) == 2 and len(eng.queue) == 2
    assert eng.scheduler.admit(eng.queue, eng.running, 1e9) == []
    assert len(eng.queue) == 2
    out = eng.run()
    assert len(out) == 4


def test_rows_on_one_document_are_one_group_of_the_selection_floor():
    _, cfg, state = build()
    (doc,) = prompts([48], seed=8)
    eng = engine(state, cfg, max_model_len=128, tracer=obs.SpanTracer())
    eng.add_request(doc + [1, 2, 3], 2)
    eng.run()
    for k in range(3):
        eng.add_request(doc + prompts([9], seed=20 + k)[0], 6)
    eng.run()
    last = [e.attrs for e in eng.tracer.events()
            if e.name == "unified_step" and e.attrs["rows"] == 3][-1]
    assert last["tokens"] == 3 and last["index_selected"] == 3 * TOPK
    assert last["index_selected_floor"] == TOPK      # one document
    # the three rows still share the pages of the boundary they resumed at
    assert last["window_tokens_distinct"] < 8 * last["window_pages"]


# -- (e) the indexer, the shares, the translation -----------------------------

def test_the_programs_selection_is_the_references_in_float32():
    """``hy.index_positions`` (the step's indexer arithmetic on a whole
    sequence) against ``ref.select`` on the same layer input: the same
    positions for every query; a query with fewer than ``index_topk``
    positions behind it selects them all."""
    pub, cfg, state = build()
    spec = ref.spec_from_config(pub)
    u = jax.random.normal(jax.random.PRNGKey(1), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        theirs, member = ref.select(u, ref._f32(_sub(state, "h2.attn.")),
                                    spec)
        theirs = np.asarray(theirs)
        mine = np.asarray(hy.index_positions(cfg, state, 2, u,
                                             np.arange(40)))
    assert ref.select_overlap(mine, theirs, 40) == 1.0
    # the packed mask names the same positions as the list
    mask = np.unpackbits(np.asarray(member), axis=-1, count=40).astype(bool)
    assert all(sorted(row[row != 40]) == list(np.flatnonzero(m))
               for row, m in zip(theirs, mask))
    assert sorted(theirs[5][theirs[5] != 40]) == list(range(6))
    assert (theirs[30] != 40).sum() == TOPK and theirs[30].max() <= 30
    assert ref.select_overlap(mine[:, :6], theirs, 40) == 1.0
    assert ref.select_overlap(np.full((40, TOPK), 39), theirs, 40) < 0.1


@pytest.mark.parametrize("n,s,k", [(5, 96, 12), (4, 300, 64),
                                   (2, 4224, 2048), (3, 128, 128),
                                   (3, 1000, 2048), (2, 33792, 2048)])
def test_the_sort_free_selection_is_top_ks_set(n, s, k):
    """``hy.index_select`` against ``lax.top_k`` on scores with many exact
    ties (every 7th equal), zeros of both signs, a query that sees
    everything and one that sees 4 positions: the same set, ties to the
    lower position, at widths that are and are not whole 128-lane
    blocks."""
    rng = np.random.RandomState(s)
    sc = rng.randn(n, s).astype(np.float32)
    sc[:, ::7] = sc[:, 3:4]
    sc[0, :50], sc[0, 10:20] = 0.0, -0.0
    qpos = rng.randint(0, s, n)
    qpos[0], qpos[-1] = s - 1, 3
    pos, valid = hy.index_select(jnp.asarray(sc), jnp.asarray(qpos), k)
    seen = np.arange(s)[None] <= qpos[:, None]
    val, idx = jax.lax.top_k(jnp.where(seen, sc, -jnp.inf), min(k, s))
    for r in range(n):
        want = sorted(np.asarray(idx[r])[np.asarray(val[r]) > -np.inf])
        assert list(np.asarray(pos[r])[np.asarray(valid[r])]) == want
    assert int(valid[-1].sum()) == 4


def test_the_reference_reads_a_tail_as_it_reads_the_whole():
    """``tail``: only the positions the read ones depend on are computed
    (a window layer's reach, a full layer's keys from everywhere); the
    logits are the whole evaluation's."""
    pub, _, state = build()
    spec = ref.spec_from_config(pub)
    (ids,) = prompts([90], seed=4)
    assert ref.first_needed(spec, 80) == [0, 0] + [44] * 8
    pos = [80, 85, 89]
    whole = ref.logits(state, ids, spec, positions=pos)
    seen = []
    part = ref.logits(state, ids, spec, positions=pos, tail=10,
                      probe=lambda i, u, sel, first: seen.append(
                          (i, u.shape[0], sel.shape[0], first)))
    assert seen == [(0, 90, 90, 0), (2, 90, 46, 44)]
    assert float(jnp.abs(part - whole).max()) <= 1e-5 * \
        float(jnp.abs(whole).max())
    # a shared document's pass, once: the request computes its own 26
    # positions in the first full layer, 46 of them known, in the second
    known = ref.document_state(state, ids[:64], spec)
    assert list(known) == [2] and known[2].shape == (64, 64)
    # (both full layers compute the rows the second needs: one compiled
    # shape; the first reads 46 - 26 rows nobody needs)
    assert ref.first_needed(spec, 80, {2: 64}) == [44] * 10
    seen.clear()
    mine = ref.logits(state, ids, spec, positions=pos, tail=10, known=known,
                      probe=lambda i, u, sel, first: seen.append(
                          (i, u.shape[0], sel.shape[0], first)))
    assert seen == [(0, 90, 46, 44), (2, 90, 46, 44)]
    assert float(jnp.abs(mine - whole).max()) <= 1e-5 * \
        float(jnp.abs(whole).max())
    # compiled ahead over shapes alone: the same calls, the same numbers,
    # and nothing left for the evaluation itself to compile
    ref._AHEAD.clear()
    kept = ref.compile_ahead(state, spec, doc_len=64, pad_to=90, max_new=3,
                             tail=10)
    assert kept == len(ref._AHEAD) >= 9
    ran = []
    for key, compiled in list(ref._AHEAD.items()):
        ref._AHEAD[key] = lambda *a, c=compiled, k=key: (
            ran.append(k[0]), c(*a))[1]
    again = ref.logits(state, ids, spec, positions=pos, tail=10,
                       known=ref.document_state(state, ids[:64], spec))
    assert float(jnp.abs(again - mine).max()) == 0.0
    assert len(ran) == 6 + 26 and set(ran) == {
        "_embed", "_behind", "_normed", "select_positions", "_mix", "_rows",
        "_head"}
    ref._AHEAD.clear()


def test_the_eight_shares_and_the_shared_expert_once_give_the_uncut_layer():
    """Expert parallelism without the exchange: each of 8 shares routes
    over all 8 experts (sigmoid scores, top-3 of score + bias) and computes
    its own one; their routed parts, with the shared expert counted once,
    add up to the uncut reference's layer."""
    pub, cfg, state = build(n_routed_experts=8, expert_offset=0)
    u = jax.random.normal(jax.random.PRNGKey(0), (9, 64), jnp.float32)
    live = jnp.ones((9,), bool)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(u, _sub(state, "h3.moe."), ref.spec_from_config(pub))
        total, loads = 0.0, []
        for share in range(8):
            part = dataclasses.replace(cfg, experts_held=1,
                                       expert_offset=share)
            params = {k: v for k, v in state.items()
                      if share == 0 or ".shared." not in k}
            for n in ("w1", "w2", "w3"):
                params[f"h3.moe.experts.{n}"] = \
                    state[f"h3.moe.experts.{n}"][share: share + 1]
            out, load = hy.latent_moe(part, params, 3, u, live)
            total = total + out
            loads.append(int(load.sum()))
    assert np.abs(np.asarray(total - whole)).max() \
        <= 2e-5 * float(jnp.abs(whole).max())
    assert sum(loads) == 9 * 3          # every assignment on exactly one share


def test_the_translation_keeps_the_published_widths():
    pub = published()
    cfg = hy.dots3_config(pub)
    assert cfg.layer_pattern == ("dsa", "mlp", "dsa", "moe", "swa", "moe",
                                 "swa", "moe", "swa", "moe")
    full, win = cfg.geometry("dsa"), cfg.geometry("swa")
    assert (full.heads, full.latent, full.nope, full.rope, full.v,
            full.q_rank) == (4, 32, 16, 8, 16, 24)
    assert (win.heads, win.latent, win.nope, win.rope, win.v) == \
        (2, 48, 24, 8, 16)
    assert (full.index_heads, full.index_dim, full.index_topk, full.window,
            win.index_topk, win.window) == (4, 16, TOPK, 0, 0, WINDOW)
    assert full.q_rescale == pytest.approx((64 / 24) ** 0.5)
    assert full.kv_rescale == pytest.approx(2 ** 0.5)
    assert win.kv_rescale == pytest.approx((64 / 48) ** 0.5)
    assert full.scale == pytest.approx(24 ** -0.5) and full.gate and win.gate
    assert (full.theta, win.theta) == (8e7, 50000.0)
    assert cfg.paged_layers == (0, 2, 4, 6, 8) and cfg.window_tokens == WINDOW
    assert [(l.space, l.latent, l.rope, l.index) for l in cfg.page_layers] \
        == [("full", 32, 128, 16)] * 2 + [("window", 48, 128, 0)] * 3
    pool = engine(hy.init_state(cfg, 0), cfg).pool
    assert [a.shape[-1] for a in pool.k_pages] == [160, 160, 176, 176, 176]
    assert [a.shape for a in pool.v_pages] == [(64, 1, 8, 16)] * 2
    assert pool.k_pages[2].shape[0] == pool.window.num_pages
    assert (cfg.num_experts, cfg.held_experts, cfg.expert_offset,
            cfg.moe_top_k, cfg.moe_router) == (8, 4, 2, 3, "sigmoid_bias")
    shapes = hy.param_shapes(cfg)
    assert shapes["h0.attn.index.q.weight"] == (4 * 16, 24)
    assert shapes["h0.attn.gate.weight"] == (4, 64)
    assert shapes["h4.attn.k_up.weight"] == (2, 24, 48)
    assert "h4.attn.index.k.weight" not in shapes
    assert shapes["h1.mlp.gate.weight"] == (96, 64)
    assert shapes["h3.moe.experts.w3"] == (4, 64, 48)
    assert shapes["h3.moe.router.bias"] == (8,)


@pytest.mark.parametrize("change,word", [
    (dict(n_group=2), "group-limited"), (dict(topk_group=2), "group-limited"),
    (dict(scoring_func="softmax"), "sigmoid scores"),
    (dict(attention_gate_type="elementwise"), "headwise"),
    (dict(rope_scaling={"type": "yarn"}), "scaled rotary"),
    (dict(norm_topk_prob=False), "renormalised"),
    (dict(layer_types=TYPES[:4]), "layer_types")])
def test_the_translation_refuses_what_it_would_have_to_guess(change, word):
    with pytest.raises(ValueError, match=word):
        hy.dots3_config(published(**change))


def test_what_is_not_built_for_window_layers_is_refused():
    from hetu_tpu.models.gpt import GPTConfig
    pub, cfg, state = build()
    with pytest.raises(ValueError, match="window layers are built without"):
        engine(state, cfg, host_tier=True)
    with pytest.raises(ValueError, match="cannot hold 3 rows"):
        engine(state, cfg, window_pages=8)
    with pytest.raises(ValueError, match="adoption is not built"):
        engine(state, cfg).adopt_request([1, 2, 3], [4], 4, pages=[1], pos=3)
    with pytest.raises(ValueError, match="mixer_geometry"):
        dataclasses.replace(cfg, mixer_geometry=None)
    with pytest.raises(ValueError, match="one page pool holds one layout"):
        GPTConfig(num_layers=2, layer_pattern=("mla", "dsa"),
                  kv_latent_dim=8, mixer_geometry=cfg.mixer_geometry)
    with pytest.raises(ValueError, match="needs ffn_hidden_size"):
        GPTConfig(num_layers=1, layer_pattern=("mlp",))
    a = engine(state, cfg, name="a").pool.layout_tag
    b = engine(state, dataclasses.replace(cfg, mixer_geometry={
        **cfg.mixer_geometry, "swa": cfg.geometry("swa")._replace(
            latent=32)}), name="b").pool.layout_tag
    assert a != b and a[0] == 2


# -- (g) a part-filled chunk pays for its live query blocks only --------------

def test_part_filled_chunks_serve_the_tokens_of_every_block_run(monkeypatch):
    """Chunks of 64 (two blocks of the selection and the read): prompts of
    70, 33, 64 and 100 tokens end in chunks of 6, 33, 64 and 36 live
    tokens.  The greedy tokens are those of the same engine whose dsa mixer
    never hears of the live count and runs every block (the parent's), and
    the account counts the live blocks against those the slots hold."""
    _, cfg, state = build()
    kw = dict(chunk_size=64, max_model_len=128, num_pages=96,
              prefix_cache=False)
    ps = prompts([70, 33, 64, 100], seed=4)

    def serve():
        eng = engine(state, cfg, **kw)
        hs = [eng.add_request(p, 5) for p in ps]
        eng.run()
        assert eng.compile_count == 1
        return [h.out_tokens for h in hs], eng.metrics_summary()

    outs, m = serve()
    # 64 + 6, 33, 64, 64 + 36: six chunk rows of two blocks; 2 + 1, 2, 2, 2 + 2
    assert (m["index_chunk_blocks_padded"],
            m["index_chunk_blocks_live"]) == (12, 11)
    every_block, counts = hy.indexed_attention, []

    def parents(*a, live=None, **k):
        counts.append(live)
        return every_block(*a, **k)

    monkeypatch.setattr(hy, "indexed_attention", parents)
    theirs, m_p = serve()
    # each full layer traced its decode region and its chunk slot through
    # it: the slot was handed its row's count (a scalar), the decode rows
    # theirs, which the per-row tables take no notice of
    assert sum(c.ndim == 0 for c in counts) == len(counts) // 2 > 0
    assert outs == theirs and all(len(o) == 5 for o in outs)
    assert m_p["index_chunk_blocks_live"] == 11     # the account is the host's
