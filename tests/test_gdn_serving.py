"""The gated-delta / attention stack (``model_type: olmo_hybrid``) through
the serving engine, against the plain float32 reference
(``benchmark/reference_olmo_hybrid.py``), at the configuration's ``tiny``
sizes on the CPU with seeded random weights.

Tolerances, each with its reason:

* ``GAP_F32`` 1e-4 — float32 system against the float32 reference, in
  logit units of the reference (a served greedy token's logit below the
  reference's best, teacher-forced).  The two differ by reassociation only
  (a triangular solve a block of 64 tokens and a rank-one update a decode
  step against one ``lax.scan``, paged against whole-sequence attention):
  ~1e-6 at these widths; a wrong mask, carried state, norm place or tail
  reads 0.1-1.
* ``TENSOR_F32`` 2e-5 (relative to the tensor's largest entry) — the same
  pair compared tensor against tensor (outputs, final states): float32
  sums of 8 to a few hundred terms in another order.  A state kept in
  bf16, ``alpha`` left out of the correction ``v - alpha S^T k`` or the
  factor 2 on ``beta`` each read 1e-3 to 1 of the scale
  (``test_a_dropped_term_or_a_bf16_state_is_beyond_the_tolerance``).
* ``init_std`` 0.2 in place of the configuration's 0.02, and the norm
  weights, ``dt_bias`` and ``A_log`` perturbed: at hidden 64 the layers
  then carry the stream, the decay differs head by head, and no term of
  the mixer is a 0 or a 1 that hides it.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
import reference_olmo_hybrid as ref  # noqa: E402

from hetu_tpu.models import hybrid as hy  # noqa: E402
from hetu_tpu.models.gpt import STATE_MIXERS, GPTConfig  # noqa: E402
from hetu_tpu.ops import gated_delta as gd  # noqa: E402
from hetu_tpu.ops.ssd import live_slot_list  # noqa: E402
from hetu_tpu.serving import Engine  # noqa: E402
from hetu_tpu.serving.spec import SpecConfig  # noqa: E402

GAP_F32 = 1e-4
TENSOR_F32 = 2e-5
F32 = jnp.float32


def published(tiny: bool = True) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmohybrid-pp2.json")) as f:
        pub = json.load(f)
    if tiny:
        pub = {**pub, **{k: v for k, v in pub["tiny"].items()
                         if k != "serve"}}
    return pub


def build(seed: int = 5, **kw):
    pub = {**published(), **kw}
    cfg = hy.olmo_hybrid_config(pub, dtype="float32", init_std=0.2)
    state = hy.init_state(cfg, seed)
    key = jax.random.key(seed + 1)
    for name in sorted(state):
        if name.endswith(("norm.weight", "gdn.dt_bias", "gdn.A_log")):
            key, k = jax.random.split(key)
            state[name] = state[name] + 0.3 * jax.random.normal(
                k, state[name].shape, state[name].dtype)
    return pub, cfg, state


def engine(state, cfg, **kw):
    kw = {"num_pages": 64, "page_size": 8, "max_batch": 4, "chunk_size": 8,
          "prefix_cache": False, "debug": True, "use_kernel": False, **kw}
    return Engine(state, cfg, **kw)


def prompts(lens, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, n).tolist() for n in lens]


def worst_gap(pub, state, prompt, out) -> float:
    spec = ref.spec_from_config(pub)
    return max(ref.greedy_logit_gaps(state, prompt + list(out), len(prompt),
                                     spec, pad_to=96, max_new=16))


def close(got, want, tol=TENSOR_F32):
    scale = float(jnp.abs(want).max()) or 1.0
    assert float(jnp.abs(got - want).max()) <= tol * scale


def delta_case(t: int, h: int = 4, dk: int = 8, dv: int = 16, slots: int = 4,
               neg: bool = True, seed: int = 0):
    """Unit keys with a common component (as ``silu`` leaves them: the
    solve's matrix is far from the identity), decays from 1 down to
    ``exp(-1.5)`` a token, ``beta`` over its whole range."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), F32)  # noqa: E731
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa
    return dict(
        q=unit(f(t, h, dk)) * dk ** -0.5, k=unit(f(t, h, dk) + 0.5),
        v=f(t, h, dv),
        alpha=jnp.exp(-1.5 * jnp.asarray(rng.random((t, h)), F32)),
        beta=jax.nn.sigmoid(2 * f(t, h)) * (2.0 if neg else 1.0),
        store=f(slots, h, dk, dv))


def run_chunk(k, slot, length, fresh, p=None):
    h, dv = k["v"].shape[1:]
    p = p or gd.heads_packed(h, dv)
    o, new = gd.gated_delta_chunk(
        k["q"], k["k"], k["v"], k["alpha"], k["beta"],
        gd.pack_state(k["store"], p), slot, length, fresh, interpret=True)
    return o, gd.unpack_state(new, p)


# -- the two kernel forms against the token-by-token recurrence ---------------

@pytest.mark.parametrize("length,fresh,neg", [
    (256, False, True), (100, False, True), (64, True, True),
    (37, False, False), (1, True, False), (1, False, True)])
def test_chunk_form_equals_the_recurrence_and_moves_one_slot(length, fresh,
                                                             neg):
    """Interpreted, four blocks of 64: the run's live tokens against
    ``lax.scan``, output and final state; a token past ``length`` leaves
    the state as it was (``beta`` 0, ``alpha`` 1); a fresh row starts from
    zeros whatever its slot holds; no other slot moves."""
    k = delta_case(256, neg=neg)
    s0 = jnp.where(fresh, 0.0, k["store"][2])
    want_o, want_s = gd.gated_delta_reference(
        k["q"], k["k"], k["v"], k["alpha"], k["beta"], s0, length)
    o, new = run_chunk(k, 2, length, fresh)
    close(o[:length], want_o[:length])
    close(new[2], want_s)
    others = jnp.asarray([0, 1, 3])
    assert (np.asarray(new[others]) == np.asarray(k["store"][others])).all()
    # a block wholly past the row's length gives zeros
    assert not np.asarray(o[-(-length // 64) * 64:]).any()


@pytest.mark.parametrize("p", [1, 2])
def test_a_state_carried_over_three_chunks_equals_one_pass(p):
    """150 tokens in chunk slots of 64 (the last part-filled), the state
    carried in its slot from chunk to chunk, in both layouts of the store
    (a head a block; two heads side by side on the lanes)."""
    k = delta_case(192, seed=3)
    want_o, want_s = gd.gated_delta_reference(
        k["q"], k["k"], k["v"], k["alpha"], k["beta"], k["store"][1], 150)
    store, outs = gd.pack_state(k["store"], p), []
    for at in range(0, 192, 64):
        n = max(0, min(64, 150 - at))
        sl = slice(at, at + 64)
        o, store = gd.gated_delta_chunk(
            k["q"][sl], k["k"][sl], k["v"][sl], k["alpha"][sl],
            k["beta"][sl], store, 1, n, False, interpret=True)
        outs.append(o[:n])
    close(jnp.concatenate(outs), want_o[:150])
    close(gd.unpack_state(store, p)[1], want_s)


@pytest.mark.parametrize("live", [(1, 0, 1, 1), (0, 0, 0, 0), (0, 1, 0, 0),
                                  (1, 1, 1, 1)])
@pytest.mark.parametrize("neg", [True, False])
def test_decode_form_walks_the_live_slots_and_no_other(live, neg):
    k = delta_case(4, neg=neg, seed=1)
    live = jnp.asarray(live, bool)
    fresh = jnp.asarray([0, 0, 1, 0], bool)
    slots, n_live = live_slot_list(live)
    p = gd.heads_packed(4, 16)
    o, new = gd.gated_delta_slots(
        k["q"], k["k"], k["v"], k["alpha"], k["beta"],
        gd.pack_state(k["store"], p), slots, n_live, fresh, interpret=True)
    new = gd.unpack_state(new, p)
    for s in range(4):
        if not bool(live[s]):
            assert not np.asarray(o[s]).any()
            assert (np.asarray(new[s]) == np.asarray(k["store"][s])).all()
            continue
        row = slice(s, s + 1)
        want_o, want_s = gd.gated_delta_reference(
            k["q"][row], k["k"][row], k["v"][row], k["alpha"][row],
            k["beta"][row], jnp.where(fresh[s], 0.0, k["store"][s]))
        close(o[s], want_o[0])
        close(new[s], want_s)


@pytest.mark.parametrize("fault", ["alpha_out_of_the_correction",
                                   "beta_without_its_factor",
                                   "bf16_state"])
def test_a_dropped_term_or_a_bf16_state_is_beyond_the_tolerance(fault):
    """What ``TENSOR_F32`` is there to catch reads hundreds of times over
    it, on the output of 64 tokens."""
    k = delta_case(64, seed=2)
    s0 = k["store"][0]
    want, _ = gd.gated_delta_reference(k["q"], k["k"], k["v"], k["alpha"],
                                       k["beta"], s0)
    s, outs = s0, []
    for t in range(64):
        a, b = k["alpha"][t][:, None, None], k["beta"][t][:, None]
        if fault == "beta_without_its_factor":
            b = b / 2
        read = s if fault == "alpha_out_of_the_correction" else a * s
        u = b * (k["v"][t] - jnp.einsum("hkv,hk->hv", read, k["k"][t]))
        s = a * s + k["k"][t][:, :, None] * u[:, None, :]
        if fault == "bf16_state":
            s = s.astype(jnp.bfloat16).astype(F32)
        outs.append(jnp.einsum("hkv,hk->hv", s, k["q"][t]))
    err = float(jnp.abs(jnp.stack(outs) - want).max())
    assert err > 50 * TENSOR_F32 * float(jnp.abs(want).max())


def test_the_recurrence_is_the_references_own():
    """``ops.gated_delta.gated_delta_reference`` (what the kernels are held
    to above) against ``reference_olmo_hybrid.recurrence``, from a carried
    state."""
    k = delta_case(40, seed=4)
    o, s = gd.gated_delta_reference(k["q"], k["k"], k["v"], k["alpha"],
                                    k["beta"], k["store"][1])
    want_o, want_s = ref.recurrence(k["q"], k["k"], k["v"], k["alpha"],
                                    k["beta"], k["store"][1])
    close(o, want_o)
    close(s, want_s)


def test_state_layout_packs_two_heads_where_one_does_not_fill_the_lanes():
    assert gd.state_shape(30, 96, 192) == (15, 96, 384)
    assert gd.state_shape(32, 128, 128) == (32, 128, 128)
    assert gd.state_shape(3, 8, 16) == (3, 8, 16)
    s = jnp.arange(2 * 4 * 3 * 5, dtype=F32).reshape(2, 4, 3, 5)
    packed = gd.pack_state(s, 2)
    assert packed.shape == (2, 2, 3, 10)
    assert (np.asarray(packed[1, 0, :, 5:]) == np.asarray(s[1, 1])).all()
    assert (np.asarray(gd.unpack_state(packed, 2)) == np.asarray(s)).all()


# -- the mixer against the reference, tensor for tensor -----------------------

def _mixer(cfg, w, u, conv, ssm, slot, length, fresh):
    proj = u @ w.in_proj.T
    o, conv, ssm = hy.gdn_chunk(cfg, w, proj, conv, ssm, slot, length, fresh)
    y = hy.gdn_gate_norm(cfg, w, o, hy.gdn_split(cfg, proj)[1], F32)
    return y @ w.out_proj.T, conv, ssm


def _stores(cfg, slots=3, seed=9):
    rng = np.random.default_rng(seed)
    conv = jnp.asarray(rng.standard_normal(
        (slots, cfg.linear_conv_kernel - 1, cfg.linear_conv_dim)), F32)
    ssm = jnp.asarray(rng.standard_normal((slots,) + gd.state_shape(
        cfg.linear_value_heads, cfg.linear_key_dim, cfg.linear_value_dim)),
        F32)
    return conv, ssm


@pytest.mark.parametrize("neg", [True, False])
def test_gdn_mixer_alone_equals_the_reference(neg):
    pub, cfg, state = build(linear_allow_neg_eigval=neg)
    w = hy.GdnWeights(state, 0)
    u = jnp.asarray(np.random.default_rng(1).standard_normal(
        (32, cfg.hidden_size)), F32)
    conv, ssm = _stores(cfg)
    out, _, _ = _mixer(cfg, w, u, conv, ssm, 1, 32, True)
    p = {k[len("h0.gdn."):]: v for k, v in state.items()
         if k.startswith("h0.gdn.")}
    with jax.default_matmul_precision("highest"):
        want = ref.gated_delta_net(u, p, ref.spec_from_config(pub))
    close(out, want)


def test_chunks_carry_the_state_and_padding_leaves_it_alone():
    """A run cut into chunks of 8 (the last part-filled, padded to the
    slot) equals one pass over the whole run, output and final state; a
    chunk's tokens past ``length`` change neither the matrix state nor the
    conv tail."""
    pub, cfg, state = build()
    w = hy.GdnWeights(state, 0)
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (21, cfg.hidden_size)), F32)
    conv, ssm = _stores(cfg)
    whole, conv_w, ssm_w = _mixer(cfg, w, jnp.pad(u, ((0, 11), (0, 0))), conv,
                                  ssm, 2, 21, True)
    outs, c, s = [], conv, ssm
    for at in range(0, 21, 8):
        piece = jnp.pad(u[at: at + 8], ((0, max(0, at + 8 - 21)), (0, 0)))
        o, c, s = _mixer(cfg, w, piece, c, s, 2, min(8, 21 - at), at == 0)
        outs.append(o[: min(8, 21 - at)])
    close(jnp.concatenate(outs), whole[:21])
    close(s[2], ssm_w[2])
    close(c[2], conv_w[2])
    assert (np.asarray(s[:2]) == np.asarray(ssm[:2])).all()
    assert (np.asarray(c[:2]) == np.asarray(conv[:2])).all()
    # a chunk with no live token at all moves nothing
    _, c0, s0 = _mixer(cfg, w, u[:8], c, s, 2, 0, False)
    assert (np.asarray(s0) == np.asarray(s)).all()
    assert (np.asarray(c0) == np.asarray(c)).all()


# -- through the engine -------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(use_kernel):
    """Chunked prefill with the state carried chunk to chunk, then decode
    through pages and state slots, four requests batched: every served
    token within ``GAP_F32`` logits of the reference's full forward pass
    (post-norm sublayers, a whole-width QK-norm, no rotation); one
    executable; the served tokens differ by request."""
    pub, cfg, state = build()
    eng = engine(state, cfg, use_kernel=use_kernel)
    ps = prompts((37, 5, 1, 18), cfg.vocab_size)
    reqs = [eng.add_request(p, 8) for p in ps]
    eng.run()
    assert eng.compile_count == 1
    for r, p in zip(reqs, ps):
        assert len(r.out_tokens) == 8
        assert worst_gap(pub, state, p, r.out_tokens) <= GAP_F32
    assert len({tuple(r.out_tokens) for r in reqs}) == 4
    assert len(set(reqs[0].out_tokens)) > 4
    st = eng.state_store
    assert st.in_use == 0 and not st.problems()
    assert len(st.ssm) == len(cfg.layers_of("gdn")) == 6
    assert st.ssm[0].shape == (4, 2, 8, 32)
    assert st.conv[0].shape == (4, 3, cfg.linear_conv_dim)
    assert eng.pool.num_layers == 2
    c = eng.metrics_summary()
    # a one-token prompt rides a decode slot: the decode walk takes it
    assert c["ssm_chunk_tokens_walked"] == 37 + 5 + 18
    assert c["ssm_chunk_tokens_padded"] == 8 * (5 + 1 + 3)
    assert c["ssm_slots_walked"] == 4 * 7 + 1


def test_the_norm_place_and_the_qk_norm_width_are_read_by_the_step():
    """The same weights under a pre-norm step, or a head-wise QK-norm's
    arithmetic, serve other tokens than the reference's: the two
    properties reach the one step builder."""
    pub, cfg, state = build()
    (p,) = prompts((20,), cfg.vocab_size, seed=4)
    import dataclasses
    pre = dataclasses.replace(cfg, norm_position="pre")
    eng = engine(state, pre)
    r = eng.add_request(p, 8)
    eng.run()
    assert worst_gap(pub, state, p, r.out_tokens) > 100 * GAP_F32


def test_a_preempted_row_is_recomputed_and_its_slot_reused():
    """A pool too small for all the requests' decode pages: someone is
    preempted (recompute: pages and slot go back), another request takes
    the slot, the preempted one re-prefills into whatever slot is free
    then, and every request still serves the reference's tokens."""
    pub, cfg, state = build()
    ps = prompts((14, 15, 13, 9), cfg.vocab_size, seed=11)
    eng = engine(state, cfg, num_pages=8, page_size=8, max_batch=3)
    reqs = [eng.add_request(p, 12) for p in ps]
    eng.run()
    assert eng.counters["preemptions"].value >= 1
    assert eng.counters["state_slot_allocs"].value >= 5
    for r, p in zip(reqs, ps):
        assert worst_gap(pub, state, p, r.out_tokens) <= GAP_F32
    assert eng.state_store.in_use == 0
    assert eng.pool.free_pages == eng.pool.num_usable
    eng.pool.check_invariants(force=True)


@pytest.mark.parametrize("what", ["prefix_cache", "speculation"])
def test_the_engine_refuses_what_recurrent_state_cannot_do(what):
    """The refusals of the other recurrent stacks, naming this kind."""
    pub, cfg, state = build()
    if what == "prefix_cache":
        with pytest.raises(ValueError, match=r"prefix_cache=True is not "
                           r"built for a stack with recurrent \(gdn\) "
                           r"layers: a cached page prefix carries no state"):
            Engine(state, cfg, num_pages=16, page_size=8, use_kernel=False)
    else:
        spec = SpecConfig(draft_state=state, draft_cfg=cfg, k=2)
        with pytest.raises(ValueError, match=r"speculative decoding is not "
                           r"built for a stack with recurrent \(gdn\) "
                           r"layers: a rejected draft cannot be rolled"):
            Engine(state, cfg, num_pages=16, page_size=8,
                   prefix_cache=False, spec=spec, use_kernel=False)


@pytest.mark.parametrize("other", [k for k in STATE_MIXERS if k != "gdn"])
def test_one_pattern_holds_one_kind_of_recurrent_mixer(other):
    with pytest.raises(ValueError, match="one kind of recurrent mixer "
                       rf"\({' / '.join(STATE_MIXERS)}\)"):
        GPTConfig(num_layers=2, layer_pattern=("gdn", other))


# -- the translation ----------------------------------------------------------

def test_olmo_hybrid_config_reads_the_published_keys():
    """Three linear layers then a full one, four times; every published
    layer is its mixer and then a dense MLP; the head is untied; the
    parameter count from shapes alone (nothing is allocated) is 4.10 B for
    the stage and the published 7.43 B for the whole model."""
    pub = published(tiny=False)
    cfg = hy.olmo_hybrid_config(pub)
    assert len(cfg.layer_pattern) == 32
    assert cfg.layer_pattern[1::2] == ("mlp",) * 16
    assert cfg.layer_pattern[0::2] == ("gdn", "gdn", "gdn", "attention") * 4
    assert cfg.state_mixer == "gdn"
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (30, 30, 128)
    assert (cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim,
            cfg.linear_value_dim, cfg.linear_conv_kernel,
            cfg.linear_neg_eigval) == (30, 30, 96, 192, 4, True)
    assert cfg.linear_conv_dim == 11520
    assert cfg.position == "none" and not cfg.tie_embeddings
    assert cfg.norm_position == "post" and cfg.attn_qk_norm_full
    shapes = hy.param_shapes(cfg)
    assert shapes["h0.gdn.in_proj.weight"] == (17340, 3840)
    assert shapes["h0.gdn.conv.weight"] == (4, 11520)
    assert shapes["h6.attn.q_norm.weight"] == (3840,)
    assert shapes["lm_head.weight"] == (100352, 3840)
    count = lambda sh, *pre: sum(                            # noqa: E731
        int(np.prod(s)) for k, s in sh.items() if k.startswith(pre or ""))
    assert count(shapes) == 4_100_788_944
    assert count(shapes, "h0.", "h1.") == 215_570_172    # linear + its MLP
    assert count(shapes, "h6.", "h7.") == 185_809_920    # full + its MLP
    assert count(shapes, "wte.", "lm_head.") == 770_703_360
    whole = hy.olmo_hybrid_config({
        **pub, "num_hidden_layers": 32, "layer_types": pub["layer_types"] * 2})
    assert count(hy.param_shapes(whole)) == 7_430_870_688
    assert pub["reduced"] == ["num_hidden_layers", "layer_types"]
    # the state a sequence holds: 12 layers x 30 x 96 x 192 float32
    assert 12 * int(np.prod(gd.state_shape(30, 96, 192))) * 4 == 26_542_080


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("linear_num_key_heads", 15),
    ("rope_parameters", {"rope_theta": 500000.0}),
    ("layer_types", ["sliding_attention"] * 8)])
def test_olmo_hybrid_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        hy.olmo_hybrid_config({**published(), key: value})


def test_init_follows_the_published_gated_deltanet_initialiser():
    pub = published()
    cfg = hy.olmo_hybrid_config(pub, dtype="float32")
    state = hy.init_state(cfg, 3)
    a = np.exp(np.asarray(state["h0.gdn.A_log"]))
    assert 0 < a.min() and a.max() <= 16
    dt = np.asarray(jax.nn.softplus(state["h0.gdn.dt_bias"]))
    assert 0.001 <= dt.min() and dt.max() <= 0.1001
    assert state["h0.gdn.A_log"].dtype == jnp.float32
    assert "h0.gdn.conv.bias" not in state and "lm_head.weight" in state
    assert (np.asarray(state["h0.gdn.norm.weight"]) == 1).all()
