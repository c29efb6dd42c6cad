"""Hybrid stacks (Mamba-2 / attention / latent MoE, one mixer a layer)
through the serving engine, against the plain float32 reference
(``benchmark/reference_hybrid.py``), at tiny widths on the CPU with seeded
random weights.

Tolerances, each with its reason:

* ``GAP_F32`` 1e-4 — float32 system against the float32 reference, in
  logit units of the reference (a served greedy token's logit below the
  reference's best, teacher-forced).  The two differ by reassociation
  only (chunked matmul scan against a sequential one, the dense expert
  mix against a per-expert loop, paged against whole-sequence attention):
  ~1e-6 at these widths; a wrong mask, group, state or share reads 0.1-1.
* ``TENSOR_F32`` 2e-5 (relative to the tensor's largest entry) — the same
  pair compared tensor against tensor (scan outputs, final states, the
  expert layer).
* ``GAP_BF16`` 0.006 — the bf16 system against the float32 reference.
  bf16 keeps 8 mantissa bits; at these widths (hidden 64, 5 layers) the
  served tokens read 0 to 0.0016 below the reference's best over 12
  requests, so 0.006 leaves ~4x.  The float8 reading of the same
  comparison (3 mantissa bits, the nearest precision below: the tokens
  the reference itself picks with every weight matrix and every mixer's
  input and output rounded to e4m3, scaled per tensor) reads 0-0.045 a
  sequence, 0.045 over the 8 sequences of
  ``test_lower_precision_fails_the_tolerance``, which has to fail it.
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
import reference_hybrid as ref  # noqa: E402

from hetu_tpu.models import hybrid as hy  # noqa: E402
from hetu_tpu.ops import ssd  # noqa: E402
from hetu_tpu.ops.moe_grouped import ROW_BLOCK, grouped_experts  # noqa: E402
from hetu_tpu.serving import Engine  # noqa: E402
from hetu_tpu.serving.kv_pool import StateSlotStore  # noqa: E402
from hetu_tpu.serving.spec import SpecConfig  # noqa: E402

GAP_F32 = 1e-4
TENSOR_F32 = 2e-5
GAP_BF16 = 0.006
VOCAB = 128


def published(pattern: str, **kw) -> dict:
    """A tiny ``nemotron_h`` config under the published keys: 16 routed
    experts of which 4 are held from offset 4, top-6, latent 32."""
    d = dict(hybrid_override_pattern=pattern, num_hidden_layers=len(pattern),
             hidden_size=64, num_attention_heads=4, head_dim=16,
             num_key_value_heads=2, vocab_size=VOCAB,
             max_position_embeddings=256, mlp_hidden_act="relu2",
             layer_norm_epsilon=1e-5, tie_word_embeddings=False,
             mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
             ssm_state_size=16, conv_kernel=4, chunk_size=8,
             n_routed_experts=4, moe_router_outputs=16, expert_offset=4,
             num_experts_per_tok=6, routed_scaling_factor=5,
             moe_intermediate_size=24, moe_latent_size=32,
             moe_shared_expert_intermediate_size=48, n_shared_experts=1,
             dtype="float32")
    d.update(kw)
    return d


def build(pattern: str, seed: int = 3, **kw):
    pub = published(pattern, **kw)
    cfg = hy.hybrid_config(pub)
    # a router bias that matters: selection and weights come apart
    return pub, cfg, hy.init_state(cfg, seed, router_bias_std=0.05)


def engine(state, cfg, **kw):
    kw = {"num_pages": 64, "page_size": 8, "max_batch": 4, "chunk_size": 8,
          "prefix_cache": False, "debug": True, "use_kernel": False, **kw}
    return Engine(state, cfg, **kw)


def prompts(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).tolist() for n in lens]


def worst_gap(pub, state, prompt, out) -> float:
    spec = ref.spec_from_config(pub)
    return max(ref.greedy_logit_gaps(state, prompt + out, len(prompt), spec,
                                     pad_to=64, max_new=16))


# -- the mixers' arithmetic against the reference, tensor for tensor ----------

def test_chunked_scan_and_recurrence_agree_with_the_sequential_scan():
    """``ssd_chunk_scan`` (from a non-zero state, a ragged tail) and
    ``ssd_decode_step`` against the token-by-token recurrence."""
    rng = np.random.RandomState(1)
    t, h, p, g, n, q = 24, 8, 8, 2, 16, 8
    x = rng.randn(t, h, p).astype(np.float32)
    dt = np.log1p(np.exp(rng.randn(t, h))).astype(np.float32) * 0.3
    a = -np.exp(rng.rand(h)).astype(np.float32)
    b, c = (rng.randn(t, g, n).astype(np.float32) for _ in range(2))
    d = rng.randn(h).astype(np.float32)
    s0 = rng.randn(h, p, n).astype(np.float32)
    length = 19
    s, ys = s0.copy(), []
    for i in range(length):
        bi, ci = (np.repeat(v[i], h // g, axis=0) for v in (b, c))
        s = s * np.exp(dt[i] * a)[:, None, None] + \
            (dt[i][:, None] * x[i])[:, :, None] * bi[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", s, ci) + d[:, None] * x[i])
    y, s_end = ssd.ssd_chunk_scan(x, dt, a, b, c, d, s0, q, length)
    scale = np.abs(np.stack(ys)).max()
    assert np.abs(np.asarray(y)[:length] - np.stack(ys)).max() \
        <= TENSOR_F32 * scale
    assert np.abs(np.asarray(s_end) - s).max() <= TENSOR_F32 * np.abs(s).max()
    # one token for each of 3 rows
    rows = np.stack([s0, 2 * s0, -s0])
    y1, new = ssd.ssd_decode_step(x[:3], dt[:3], a, b[:3], c[:3], d, rows)
    for r in range(3):
        one_y, one_s = ssd.ssd_chunk_scan(x[r:r + 1], dt[r:r + 1], a,
                                          b[r:r + 1], c[r:r + 1], d,
                                          rows[r], 1)
        assert np.allclose(y1[r], one_y[0], atol=1e-5)
        assert np.allclose(new[r], one_s, atol=1e-5)


@pytest.mark.parametrize("n_live", [0, 1, 12, 64])
def test_the_walk_updates_the_live_slots_and_touches_no_other(n_live):
    """``ssd_decode_slots`` against ``ssd_decode_step`` over the whole
    store: 64 slots of which ``n_live`` are live, scattered and listed in
    any order, some of them fresh.  Live slots: ``y`` and the new state
    to float32 tolerance; every other slot bit-identical to what it held
    and its ``y`` zero; a fresh slot's old content (NaN here) reaches
    neither ``y`` nor the new state."""
    rng = np.random.RandomState(10 + n_live)
    s_n, h, p, g, n = 64, 8, 8, 2, 16
    x = rng.randn(s_n, h, p).astype(np.float32)
    dt = np.log1p(np.exp(rng.randn(s_n, h))).astype(np.float32) * 0.3
    a = -np.exp(rng.rand(h)).astype(np.float32)
    b, c = (rng.randn(s_n, g, n).astype(np.float32) for _ in range(2))
    d = rng.randn(h).astype(np.float32)
    store = rng.randn(s_n, h, p, n).astype(np.float32)
    live_ids = rng.permutation(s_n)[:n_live]
    live = np.zeros(s_n, bool)
    live[live_ids] = True
    fresh = live & (rng.rand(s_n) < 0.3)
    if n_live:
        fresh[live_ids[0]] = True
    store[fresh] = np.nan
    # the list the step builds: live first, the rest the last live one
    slots, n_arr = ssd.live_slot_list(jnp.asarray(live))
    assert int(n_arr[0]) == n_live
    assert sorted(np.asarray(slots)[:n_live]) == sorted(live_ids)
    assert set(np.asarray(slots)[n_live:]) <= \
        {int(np.asarray(slots)[max(n_live, 1) - 1])}
    # ... and the same slots in another order
    order = rng.permutation(live_ids) if n_live else np.zeros(1, np.int64)
    shuffled = np.concatenate(
        [order, np.full(s_n - len(order), order[-1])]).astype(np.int32)
    want_y, want = ssd.ssd_decode_step(
        x, dt, a, b, c, d, np.where(fresh[:, None, None, None], 0.0, store))
    want_y, want = np.asarray(want_y), np.asarray(want)
    for lst in (slots, jnp.asarray(shuffled)):
        y, new = ssd.ssd_decode_slots(x, dt, a, b, c, d, jnp.asarray(store),
                                      lst, n_arr, jnp.asarray(fresh))
        y, new = np.asarray(y), np.asarray(new)
        assert np.array_equal(new[~live].view(np.uint32),
                              store[~live].view(np.uint32))
        assert not y[~live].any()
        if n_live:
            assert np.isfinite(y[live]).all() and np.isfinite(new[live]).all()
            assert np.abs(y[live] - want_y[live]).max() \
                <= TENSOR_F32 * np.abs(want_y[live]).max()
            assert np.abs(new[live] - want[live]).max() \
                <= TENSOR_F32 * np.abs(want[live]).max()


def test_causal_conv_carries_its_tail_across_a_ragged_chunk():
    rng = np.random.RandomState(2)
    k, ch = 4, 6
    x = rng.randn(11, ch).astype(np.float32)
    w, b = rng.randn(k, ch).astype(np.float32), rng.randn(ch).astype(np.float32)
    pad = np.concatenate([np.zeros((k - 1, ch), np.float32), x])
    want = sum(pad[j: j + 11] * w[j] for j in range(k)) + b
    tail = jnp.zeros((k - 1, ch), jnp.float32)
    # 5 valid tokens of an 8-wide slot, then the other 6
    y1, tail = ssd.causal_conv(jnp.pad(x[:5], ((0, 3), (0, 0))), w, b, tail, 5)
    y2, tail = ssd.causal_conv(x[5:], w, b, tail)
    got = np.concatenate([np.asarray(y1)[:5], np.asarray(y2)])
    assert np.abs(got - want).max() <= 1e-5
    assert np.array_equal(np.asarray(tail), x[-(k - 1):])


def test_the_four_shares_and_the_shared_expert_once_give_the_uncut_layer():
    """Expert parallelism without the exchange: each of 4 shares routes
    over all 16 experts and computes its own 4; their routed parts, with
    the shared expert counted once, add up to the uncut reference."""
    pub, cfg, state = build("E", n_routed_experts=16, expert_offset=0)
    u = jax.random.normal(jax.random.PRNGKey(0), (9, 64), jnp.float32)
    live = jnp.ones((9,), bool)
    spec = ref.spec_from_config(pub)
    with jax.default_matmul_precision("highest"):
        whole = ref.latent_moe(u, {k[len("h0.moe."):]: v for k, v in
                                   state.items() if k.startswith("h0.moe.")},
                               spec)
        total, loads = 0.0, []
        for share in range(4):
            part = dataclasses.replace(cfg, experts_held=4,
                                       expert_offset=4 * share)
            params = {k: v for k, v in state.items()
                      if share == 0 or ".shared." not in k}
            for n in ("w1", "w2"):
                params[f"h0.moe.experts.{n}"] = \
                    state[f"h0.moe.experts.{n}"][4 * share: 4 * share + 4]
            out, load = hy.latent_moe(part, params, 0, u, live)
            total = total + out
            loads.append(int(load.sum()))
    assert np.abs(np.asarray(total - whole)).max() \
        <= TENSOR_F32 * float(jnp.abs(whole).max())
    assert sum(loads) == 9 * 6          # every assignment on exactly one share


def _dense_mix(cfg, params, lat, idx, w, live):
    """The arithmetic the grouped routed part replaced: every held expert
    on every token, mixed by combine weights that are zero where an
    expert was not chosen or the token is dead."""
    local = idx - cfg.expert_offset
    ok = (local >= 0) & (local < cfg.held_experts) & live[:, None]
    rows = jnp.arange(idx.shape[0])[:, None]
    wd = jnp.zeros((idx.shape[0], cfg.held_experts), jnp.float32).at[
        rows, jnp.clip(local, 0, cfg.held_experts - 1)].add(
            jnp.where(ok, w, 0.0))
    hid = jnp.square(jax.nn.relu(jnp.einsum(
        "tl,elf->tef", lat, params["h0.moe.experts.w1"])))
    return jnp.einsum("tef,efl->tl", hid * wd[..., None],
                      params["h0.moe.experts.w2"]), (wd > 0).sum(0)


# decode slots 0..8, one 32-token chunk slot behind them; a case is (live
# tokens, published keys, router bias by expert); 16 routed experts, top-6
_DEC, _CHUNK = 8, 32
_ROUTED_CASES = {
    # (a) decode rows only, the chunk region dead
    "decode_rows_chunk_dead": (list(range(5)), {}, {}),
    # (b) a chunk beside the decode rows
    "chunk_and_decode_rows": ([0, 2, 3] + list(range(_DEC, _DEC + 27)), {},
                              {}),
    # (c) one held expert chosen by every token: many blocks of one expert
    "one_expert_takes_every_token": (
        list(range(_DEC + _CHUNK)), {}, {5: 10.0}),
    # every assignment held here: the static worst case, T * k rows
    "every_assignment_held": (
        list(range(_DEC + _CHUNK)),
        {"n_routed_experts": 16, "expert_offset": 0}, {}),
    # (d) no assignment on a held expert
    "nothing_held_here": (list(range(_DEC + _CHUNK)), {},
                          {4: -10.0, 5: -10.0, 6: -10.0, 7: -10.0}),
    # (e) the first and the last share
    "offset_0": ([1, 4] + list(range(_DEC, _DEC + 9)),
                 {"expert_offset": 0}, {}),
    "offset_12": ([1, 4] + list(range(_DEC, _DEC + 9)),
                  {"expert_offset": 12}, {}),
    # (f) dead tokens that all point at held expert 6
    "dead_tokens_point_at_a_held_expert": ([0, 1, _DEC, _DEC + 1], {},
                                           {6: 10.0}),
}


@pytest.mark.parametrize("case", list(_ROUTED_CASES))
def test_grouped_routed_experts_equal_the_dense_mix_and_the_reference(case):
    """The routed part as the serving step runs it (``moe_route_down`` ->
    ``moe_routed`` over the whole token axis, kernel interpreted) against
    the dense every-expert mix and against the reference's per-expert
    loop, float32: live rows agree, dead rows are exactly zero, the load
    counts live tokens only."""
    live_at, keys, bias = _ROUTED_CASES[case]
    pub, cfg, state = build("E", **keys)
    rb = state["h0.moe.router.bias"]
    for e, b in bias.items():
        rb = rb.at[e].set(b)
    state = {**state, "h0.moe.router.bias": rb}
    n = _DEC + _CHUNK
    u = jax.random.normal(jax.random.PRNGKey(1), (n, 64), jnp.float32)
    live = jnp.zeros((n,), bool).at[jnp.asarray(live_at)].set(True)
    no_shared = {k: v for k, v in state.items() if ".shared." not in k}
    with jax.default_matmul_precision("highest"):
        idx, w, lat = hy.moe_route_down(cfg, state, 0, u)
        r, load = hy.moe_routed(cfg, state, 0, lat, idx, w, live)
        want, want_load = _dense_mix(cfg, state, lat, idx, w, live)
        # 8-row blocks: a group of 40 tokens is then five blocks of one
        # expert, where the step's own block holds it in one
        fine, fine_load = grouped_experts(
            lat, idx, w, live, state["h0.moe.experts.w1"],
            state["h0.moe.experts.w2"], expert_offset=cfg.expert_offset,
            activation=cfg.activation, block=8)
        whole = ref.latent_moe(
            u, {k[len("h0.moe."):]: v for k, v in state.items()
                if k.startswith("h0.moe.")},
            ref.spec_from_config(pub), shared=False)
        got = hy.moe_up_shared(cfg, no_shared, 0, u, r)
    r, load, dead = np.asarray(r), np.asarray(load), ~np.asarray(live)
    assert np.array_equal(load, np.asarray(want_load))
    assert not r[dead].any()
    scale = max(float(jnp.abs(want).max()), 1e-6)
    assert np.abs(r - np.asarray(want)).max() <= TENSOR_F32 * scale
    assert np.abs(np.asarray(fine) - np.asarray(want)).max() \
        <= TENSOR_F32 * scale
    assert np.array_equal(np.asarray(fine_load), load)
    whole, got = np.asarray(whole), np.asarray(got)
    assert np.abs(got - whole)[~dead].max() \
        <= TENSOR_F32 * max(np.abs(whole).max(), 1e-6)
    if case == "nothing_held_here":
        assert not r.any() and not load.any()
    if case == "one_expert_takes_every_token":
        assert load[5 - cfg.expert_offset] == n
    if case == "every_assignment_held":
        assert load.sum() == n * cfg.moe_top_k
    if case == "dead_tokens_point_at_a_held_expert":
        # the dead tokens chose expert 6 too; only the live ones count,
        # and the live rows are what they are with no dead token beside
        assert load[6 - cfg.expert_offset] == len(live_at)
        at = jnp.asarray(live_at)
        alone, alone_load = hy.moe_routed(
            cfg, state, 0, lat[at], idx[at], w[at], live[at])
        assert np.array_equal(np.asarray(alone_load), load)
        assert np.abs(np.asarray(alone) - r[np.asarray(live_at)]).max() \
            <= TENSOR_F32 * scale


# -- through the engine --------------------------------------------------------

@pytest.mark.parametrize("pattern", ["M", "*", "E", "*EMEM"])
def test_prefill_then_decode_agrees_with_the_reference(pattern):
    """Chunked prefill, then decode through pages and state slots, three
    requests batched: every served token within ``GAP_F32`` logits of the
    reference's full forward pass; one executable."""
    pub, cfg, state = build(pattern)
    eng = engine(state, cfg)
    ps = prompts((19, 5, 1))
    reqs = [eng.add_request(p, 8) for p in ps]
    eng.run()
    assert eng.compile_count == 1
    for r, p in zip(reqs, ps):
        assert len(r.out_tokens) == 8
        assert worst_gap(pub, state, p, r.out_tokens) <= GAP_F32
    st = eng.state_store
    assert (st is None) == ("M" not in pattern)
    assert eng.pool.num_layers == pattern.count("*")
    if st is not None:
        assert st.in_use == 0 and not st.problems()
        assert eng.counters["state_slot_allocs"].value == 3
    if "E" in pattern:
        c = eng.metrics_summary()
        assert 0 < c["moe_assignments_local"] < c["moe_assignments_total"]


def test_engine_serves_the_same_tokens_and_counts_the_kernels_rows():
    """The tiny hybrid cell through the grouped routed experts: the
    tokens are those the dense every-expert mix served (pinned from the
    parent of PR 34, greedy, float32), the load counters are what they
    were, and ``moe_block_rows`` counts each hit expert's group padded
    to whole row blocks."""
    from hetu_tpu.obs.tracer import SpanTracer
    pub, cfg, state = build("*EMEM")
    eng = engine(state, cfg, tracer=SpanTracer())
    ps = prompts((19, 5, 1, 30))
    reqs = [eng.add_request(p, 12) for p in ps]
    eng.run()
    assert [r.out_tokens for r in reqs] == [
        [125, 26, 23, 119, 52, 109, 35, 73, 78, 17, 52, 24],
        [104, 108, 53, 97, 51, 6, 23, 30, 126, 64, 59, 116],
        [66, 57, 97, 101, 83, 33, 121, 120, 72, 52, 9, 97],
        [113, 78, 19, 109, 117, 72, 96, 89, 13, 29, 74, 9]]
    c = eng.metrics_summary()
    assert c["moe_assignments_local"] == 318
    assert c["moe_assignments_total"] == 1188
    assert c["moe_block_rows"] >= c["moe_assignments_local"] > 0
    assert c["moe_block_rows"] % ROW_BLOCK == 0
    steps = [e for e in eng.tracer.events() if e.name == "unified_step"]
    assert steps and sum(e.attrs["moe_blocks"] for e in steps) \
        * ROW_BLOCK == c["moe_block_rows"]


def test_a_step_counts_the_slots_its_recurrence_walked():
    """Three requests decoding while a fourth prefills: that step's
    recurrence walks three of the store's four slots (the chunk row's
    state goes through the chunked scan), the counters say so, and every
    served token stays within ``GAP_F32`` of the reference."""
    pub, cfg, state = build("*EMEM")
    eng = engine(state, cfg)
    ps = prompts((5, 3, 1, 19))
    reqs = [eng.add_request(p, 8) for p in ps[:3]]
    for _ in range(3):
        eng.step()
    assert all(r.n_generated >= 1 for r in reqs)
    reqs.append(eng.add_request(ps[3], 8))
    before = eng.metrics_summary()
    eng.step()
    after = eng.metrics_summary()
    delta = {k: after[k] - before[k] for k in (
        "ssm_slots_walked", "ssm_slots_store", "prefill_chunks")}
    assert eng.state_store.num_slots == 4
    assert delta == {"ssm_slots_walked": 3, "ssm_slots_store": 4,
                     "prefill_chunks": 1}
    eng.run()
    c = eng.metrics_summary()
    assert 0 < c["ssm_slots_walked"] < c["ssm_slots_store"]
    assert c["ssm_slots_store"] == 4 * c["step_calls"]
    for r, p in zip(reqs, ps):
        assert worst_gap(pub, state, p, r.out_tokens) <= GAP_F32


@pytest.mark.parametrize("chunk", [5, 32, None])
def test_chunk_boundaries_change_neither_tokens_nor_state(chunk):
    """A 27-token prompt fed as chunks of 5, of 32 (one ragged chunk) and
    whole: the same tokens as the reference and the same recurrent state
    after the last token (conv tail and scan state of both mamba2
    layers), within float32 reassociation."""
    pub, cfg, state = build("*EMEM")
    p = prompts((27,), seed=5)[0]

    def serve(chunk_size):
        eng = engine(state, cfg, chunk_size=chunk_size, max_batch=2)
        r = eng.add_request(p, 6)
        eng.run()
        st = eng.state_store            # slot 0: the freed slot keeps it
        return r.out_tokens, [np.asarray(a[0]) for a in st.conv + st.ssm]

    out, states = serve(chunk)
    assert worst_gap(pub, state, p, out) <= GAP_F32
    out8, states8 = serve(8)
    assert out == out8
    for a, b in zip(states, states8):
        assert np.abs(a - b).max() <= TENSOR_F32 * max(np.abs(b).max(), 1.0)


def test_a_reused_slot_starts_from_zeros():
    """No zeroing pass: a row whose first token sits at position 0 starts
    from zeros whatever its slot holds.  Slot 0, left dirty by a first
    request (and then overwritten with large values), serves a second
    request exactly as a new engine does."""
    pub, cfg, state = build("*EMEM")
    a, b = prompts((13, 9), seed=7)
    eng = engine(state, cfg, max_batch=1)
    eng.add_request(a, 5)
    eng.run()
    st = eng.state_store
    assert st.in_use == 0 and any(float(jnp.abs(s).max()) > 0 for s in st.ssm)
    st.set_arrays([c + 100 for c in st.conv], [s + 100 for s in st.ssm])
    second = eng.add_request(b, 5)
    eng.run()
    fresh = engine(state, cfg, max_batch=1)
    want = fresh.add_request(b, 5)
    fresh.run()
    assert second.state_slot is None and second.out_tokens == want.out_tokens
    assert worst_gap(pub, state, b, second.out_tokens) <= GAP_F32


def test_preemption_drops_the_slot_and_resuming_reproduces_the_tokens():
    """A pool too small for all the requests' decode pages: someone is
    preempted (recompute: pages and slot go back), resumes in whatever
    slot is free then, and every request still serves the reference's
    tokens.  The invariants of pages and slots hold at every step
    (``debug=True`` checks both)."""
    pub, cfg, state = build("*EMEM")
    ps = prompts((14, 15, 13), seed=11)
    eng = engine(state, cfg, num_pages=8, page_size=8, max_batch=3)
    reqs = [eng.add_request(p, 12) for p in ps]
    eng.run()
    assert eng.counters["preemptions"].value >= 1
    assert eng.counters["state_slot_allocs"].value >= 4
    for r, p in zip(reqs, ps):
        assert worst_gap(pub, state, p, r.out_tokens) <= GAP_F32
    assert eng.state_store.in_use == 0
    assert eng.pool.free_pages == eng.pool.num_usable
    eng.pool.check_invariants(force=True)


def test_abort_returns_every_slot():
    pub, cfg, state = build("MM")
    eng = engine(state, cfg)
    for p in prompts((9, 4, 6)):
        eng.add_request(p, 50)
    for _ in range(3):
        eng.step()
    assert eng.state_store.in_use == 3
    assert eng.gauges["state_slots_in_use"].value == 3
    assert len(eng.abort_all()) == 3
    assert eng.state_store.in_use == 0 and not eng.state_store.problems()


def test_slot_store_keeps_its_invariants_under_the_protocol_chaos_trace():
    """The protocol gate's seeded chaos trace (admissions, preemptions,
    crashes that requeue, sheds, finishes, on two replicas), replayed on
    one slot store per replica: a request holds exactly one slot from
    admit to preempt / requeue / shed / finish, and free and held slots
    partition the store after every event."""
    from hetu_tpu.analysis.protocol import fuzz_trace
    events = fuzz_trace(seed=0, n_events=300)
    store = StateSlotStore(2, 8, 4, 12, (2, 4, 4))
    held = {}
    for e in events:
        if e.kind == "req.admit" and e.key not in held:
            held[e.key] = store.alloc(int(e.key.split(":")[1]))
            assert held[e.key] is not None
        elif e.kind in ("req.preempt", "req.queued", "req.shed",
                        "req.finish") and e.key in held:
            store.free(held.pop(e.key))
        assert not store.problems()
        assert store.in_use == len(held)
        assert {store.owner(s) for s in held.values()} == \
            {int(k.split(":")[1]) for k in held}
    assert sum(1 for e in events if e.kind == "req.admit") >= 10
    with pytest.raises(ValueError, match="double free"):
        store.free(7 if store.owner(7) is None else 99)


def test_engine_chaos_keeps_pages_and_slots_consistent():
    """Random arrivals on a squeezed pool with an abort in the middle,
    ``debug=True`` checking the partition of pages AND slots every step."""
    pub, cfg, state = build("M*")
    clock = [0.0]
    eng = engine(state, cfg, num_pages=10, max_batch=3,
                 time_fn=lambda: clock[0])
    rng = np.random.RandomState(4)
    for i in range(14):
        eng.add_request(rng.randint(0, VOCAB, rng.randint(1, 20)).tolist(),
                        int(rng.randint(1, 10)),
                        arrival_time=float(rng.randint(0, 12)))
    for step in range(400):
        if not eng.has_work:
            break
        eng.step()
        clock[0] += 1.0
        assert eng.state_store.in_use == len(eng.running)
        if step == 9:
            eng.abort_all()
    assert not eng.has_work and eng.state_store.in_use == 0
    assert eng.compile_count == 1


# -- what is not built is refused ---------------------------------------------

def test_prefix_cache_with_recurrent_layers_is_refused():
    pub, cfg, state = build("*EMEM")
    with pytest.raises(ValueError, match="prefix_cache=True is not built"):
        Engine(state, cfg, num_pages=16, page_size=8, use_kernel=False)
    # an attention + expert stack holds no recurrent state: it may cache
    _, cfg2, state2 = build("*E")
    eng = Engine(state2, cfg2, num_pages=16, page_size=8, use_kernel=False)
    assert eng.prefix_cache is not None and eng.state_store is None


def test_speculation_with_recurrent_layers_is_refused():
    pub, cfg, state = build("*EMEM")
    spec = SpecConfig(draft_state=state, draft_cfg=cfg, k=2)
    with pytest.raises(ValueError, match="speculative decoding is not built"):
        Engine(state, cfg, num_pages=16, page_size=8, prefix_cache=False,
               spec=spec, use_kernel=False)
    eng = engine(state, cfg)
    with pytest.raises(ValueError, match="no recurrent state"):
        eng.adopt_request([1, 2, 3], [4], 4, pages=[1], pos=3)


def test_config_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="layer_pattern must name"):
        hy.hybrid_config(published("*EM"), layer_pattern=("moe", "conv", "x"))
    with pytest.raises(ValueError, match="experts held"):
        hy.hybrid_config(published("E", expert_offset=14))
    with pytest.raises(ValueError, match="group-limited"):
        hy.hybrid_config(published("E", n_group=2))
    from hetu_tpu.models import GPTConfig
    with pytest.raises(ValueError, match="expert layer of a layer_pattern"):
        GPTConfig(num_experts=4, moe_router="sigmoid_bias")


def test_softmax_router_is_the_plain_blocks_rule():
    """``moe_router="softmax"`` in a pattern stack routes as
    ``generate._moe_route`` does: top-k of the softmax, its values the
    weights, no renormalisation."""
    from hetu_tpu.models.generate import _moe_route
    cfg = hy.hybrid_config(published("E"), moe_router="softmax",
                           moe_router_scale=1.0)
    u = jax.random.normal(jax.random.PRNGKey(1), (7, 64), jnp.float32)
    wr = jax.random.normal(jax.random.PRNGKey(2), (16, 64), jnp.float32)
    idx, w = hy.moe_route(cfg, wr, jnp.zeros(16), u)
    _, topv, topi = _moe_route(cfg, wr, u[None])
    assert np.array_equal(np.asarray(idx), np.asarray(topi[0]))
    assert np.allclose(w, topv[0], atol=1e-6)


# -- precision ----------------------------------------------------------------

def test_bf16_serving_stays_inside_its_tolerance():
    pub, cfg, state = build("*EMEM", dtype="bfloat16")
    eng = engine(state, cfg)
    ps = prompts((21, 6))
    reqs = [eng.add_request(p, 10) for p in ps]
    eng.run()
    for r, p in zip(reqs, ps):
        assert worst_gap(pub, state, p, r.out_tokens) <= GAP_BF16


def test_lower_precision_fails_the_tolerance():
    """The comparison is tight enough to tell a precision below bf16: the
    tokens the reference itself picks computed as a float8 deployment
    would lie further than ``GAP_BF16`` below its float32 best, somewhere
    in 8 sequences."""
    pub, cfg, state = build("*EMEM", dtype="bfloat16")
    spec = ref.spec_from_config(pub)
    worst = 0.0
    for p in prompts((40,) * 8, seed=9):
        worst = max(worst, max(ref.lowp_choice_gaps(
            state, p, 8, spec, pad_to=64, max_new=32)))
    assert worst > GAP_BF16


def test_a_plain_config_means_what_it_meant():
    """No pattern: attention in every layer, K/V for every layer, no
    state store, and the dense step's signature."""
    from hetu_tpu.models import GPTConfig
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4)
    assert not cfg.is_hybrid and cfg.layers_of("attention") == (0, 1)
    assert cfg.layers_of("mamba2") == () and cfg.layers_of("moe") == ()
    assert cfg.held_experts == 0
