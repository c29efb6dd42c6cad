"""The Mamba-1 / attention stack (``model_type: jamba`` with dense
feed-forwards) through the serving engine, against the plain float32
reference (``benchmark/reference_jamba.py``), at the configuration's
``tiny`` sizes on the CPU with seeded random weights.

Tolerances, each with its reason:

* ``GAP_F32`` 1e-4 — float32 system against the float32 reference, in
  logit units of the reference (a served greedy token's logit below the
  reference's best, teacher-forced).  The two differ by reassociation only
  (the scan walked a chunk at a time in a kernel against one ``lax.scan``,
  paged against whole-sequence attention): ~1e-6 at these widths; a wrong
  mask, carried state, norm or tail reads 0.1-1.
* ``TENSOR_F32`` 2e-5 (relative to the tensor's largest entry) — the same
  pair compared tensor against tensor (mixer outputs, scan outputs, final
  states): float32 sums of 16 to a few hundred terms in another order.
* ``init_std`` 0.2 in place of the configuration's 0.02: at hidden 64 a
  tied head under 0.02 scores the token just fed ~40 logits over every
  other (the residual stream IS its embedding, the layers add ~1e-3 of
  it), and any greedy comparison passes whatever the mixers compute; at
  0.2 the layers carry the stream and the served tokens differ request by
  request (at the published widths 0.02 does the same: PERF.md, PR 53).
  Conv bias, norm weights and ``D`` are perturbed too, so that no term of
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
import reference_jamba as ref  # noqa: E402

from hetu_tpu.models import hybrid as hy  # noqa: E402
from hetu_tpu.ops.ragged_paged_attention import (  # noqa: E402
    ragged_paged_attention_pallas, ragged_paged_attention_reference)
from hetu_tpu.ops import selective_scan as ss  # noqa: E402
from hetu_tpu.ops.ssd import live_slot_list  # noqa: E402
from hetu_tpu.serving import Engine  # noqa: E402
from hetu_tpu.serving.spec import SpecConfig  # noqa: E402

GAP_F32 = 1e-4
TENSOR_F32 = 2e-5
F32 = jnp.float32


def published(tiny: bool = True) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        pub = json.load(f)
    if tiny:
        pub = {**pub, **{k: v for k, v in pub["tiny"].items()
                         if k != "serve"}}
    return pub


def build(seed: int = 5, **kw):
    pub = published()
    cfg = hy.jamba_config(pub, dtype="float32", init_std=0.2, **kw)
    state = hy.init_state(cfg, seed)
    key = jax.random.key(seed + 1)
    for name in sorted(state):
        if name.endswith(("conv.bias", "norm.weight", "mamba.D")):
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


def scan_case(t: int, ch: int = 256, n: int = 16, slots: int = 4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), F32)  # noqa: E731
    return dict(x=f(t, ch), dt=jax.nn.softplus(f(t, ch) - 1.0),
                a=-jnp.exp(0.5 * f(n, ch)), b=f(t, n), c=f(t, n), d=f(ch),
                store=f(slots, *ss.state_shape(ch, n)))


# -- the kernel against the jax.numpy recurrence, in the store's layout -------

@pytest.mark.parametrize("length,fresh", [(128, False), (77, False),
                                          (5, True), (64, True)])
def test_chunk_kernel_equals_the_recurrence_and_moves_one_slot(length, fresh):
    """Interpreted, two token blocks: the run's live tokens against
    ``lax.scan``; a token past ``length`` gives zeros and leaves the state
    as it was; a fresh row starts from zeros whatever its slot holds; no
    other slot moves."""
    k = scan_case(128)
    n, ch = k["a"].shape
    s0 = jnp.where(fresh, 0.0, k["store"][2].reshape(n, ch))
    want_y, want_h = ss.selective_scan_reference(
        k["x"], k["dt"], k["a"], k["b"], k["c"], k["d"], s0, length)
    y, new = ss.selective_scan_chunk(
        k["x"], k["dt"], k["a"], k["b"], k["c"], k["d"], k["store"], 2,
        length, fresh, interpret=True)
    close(y[:length], want_y[:length])
    assert not np.asarray(y[length:]).any()
    close(new[2].reshape(n, ch), want_h)
    others = jnp.asarray([0, 1, 3])
    assert (np.asarray(new[others]) == np.asarray(k["store"][others])).all()


@pytest.mark.parametrize("live", [(1, 0, 1, 1), (0, 0, 0, 0), (0, 1, 0, 0),
                                  (1, 1, 1, 1)])
def test_decode_kernel_walks_the_live_slots_and_no_other(live):
    k = scan_case(4)
    n, ch = k["a"].shape
    live = jnp.asarray(live, bool)
    fresh = jnp.asarray([0, 0, 1, 0], bool)
    slots, n_live = live_slot_list(live)
    y, new = ss.selective_scan_slots(
        k["x"], k["dt"], k["a"], k["b"], k["c"], k["d"], k["store"], slots,
        n_live, fresh, interpret=True)
    for s in range(4):
        if not bool(live[s]):
            assert not np.asarray(y[s]).any()
            assert (np.asarray(new[s]) == np.asarray(k["store"][s])).all()
            continue
        s0 = jnp.where(fresh[s], 0.0, k["store"][s].reshape(n, ch))
        want_y, want_h = ss.selective_scan_reference(
            k["x"][s:s + 1], k["dt"][s:s + 1], k["a"], k["b"][s:s + 1],
            k["c"][s:s + 1], k["d"], s0)
        close(y[s], want_y[0])
        close(new[s].reshape(n, ch), want_h)


def test_the_recurrence_is_the_references_own():
    """``ops.selective_scan.selective_scan_reference`` (what the kernel is
    held to above) against ``reference_jamba.recurrence``, from a carried
    state."""
    k = scan_case(40)
    n, ch = k["a"].shape
    s0 = k["store"][1].reshape(n, ch)
    y, h = ss.selective_scan_reference(k["x"], k["dt"], k["a"], k["b"],
                                       k["c"], k["d"], s0)
    want_y, want_h = ref.recurrence(k["x"], k["dt"], k["b"], k["c"], k["a"],
                                    k["d"], s0)
    close(y, want_y)
    close(h, want_h)


def test_state_layout_is_refused_where_it_does_not_tile():
    assert ss.state_shape(5120, 16) == (16, 40, 128)
    assert ss.state_shape(128, 16) == (16, 1, 128)
    with pytest.raises(ValueError, match="128 lanes"):
        ss.state_shape(1536, 16)
    with pytest.raises(ValueError, match="128 lanes"):
        ss.state_shape(96, 16)


# -- the mixer against the reference, tensor for tensor -----------------------

def _mixer(cfg, w, u, conv, ssm, slot, length, fresh):
    xz = u @ w.in_proj.T
    y, conv, ssm = hy.mamba1_chunk(cfg, w, xz, conv, ssm, slot, length, fresh)
    return hy.mamba1_gate(y, xz[:, cfg.mamba1_inner:], F32) @ w.out_proj.T, \
        conv, ssm


def _stores(cfg, slots=3, seed=9):
    rng = np.random.default_rng(seed)
    conv = jnp.asarray(rng.standard_normal(
        (slots, cfg.mamba_conv_kernel - 1, cfg.mamba1_inner)), F32)
    ssm = jnp.asarray(rng.standard_normal((slots,) + ss.state_shape(
        cfg.mamba1_inner, cfg.mamba_state_dim)), F32)
    return conv, ssm


def test_mamba1_mixer_alone_equals_the_reference():
    pub, cfg, state = build()
    w = hy.Mamba1Weights(state, 0)
    u = jnp.asarray(np.random.default_rng(1).standard_normal(
        (24, cfg.hidden_size)), F32)
    conv, ssm = _stores(cfg)
    out, _, _ = _mixer(cfg, w, u, conv, ssm, 1, 24, True)
    p = {k[len("h0.mamba."):]: v for k, v in state.items()
         if k.startswith("h0.mamba.")}
    with jax.default_matmul_precision("highest"):
        want = ref.mamba1(u, p, ref.spec_from_config(pub))
    close(out, want)


def test_chunks_carry_the_state_and_padding_leaves_it_alone():
    """A run cut into chunks of 8 (the last part-filled, padded to the
    slot) equals one pass over the whole run, output and final state; a
    chunk's tokens past ``length`` change neither the scan state nor the
    conv tail."""
    pub, cfg, state = build()
    w = hy.Mamba1Weights(state, 0)
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (21, cfg.hidden_size)), F32)
    conv, ssm = _stores(cfg)
    whole, conv_w, ssm_w = _mixer(cfg, w, jnp.pad(u, ((0, 3), (0, 0))), conv,
                                  ssm, 2, 21, True)
    outs, c, s = [], conv, ssm
    for at in range(0, 21, 8):
        piece = jnp.pad(u[at: at + 8], ((0, max(0, at + 8 - 21)), (0, 0)))
        o, c, s = _mixer(cfg, w, piece, c, s, 2, min(8, 21 - at), at == 0)
        outs.append(o[: min(8, 21 - at)])
    close(jnp.concatenate(outs), whole[:21])
    close(s[2], ssm_w[2])
    close(c[2], conv_w[2])
    # the other slots are where they were
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
    token within ``GAP_F32`` logits of the reference's full forward pass;
    one executable; the served tokens differ by request (the comparison is
    not one of a token with itself)."""
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
    assert len(st.ssm) == len(cfg.layers_of("mamba1")) == 6
    assert st.ssm[0].shape == (4, 16, 1, 128)
    assert st.conv[0].shape == (4, 3, cfg.mamba1_inner)
    assert eng.pool.num_layers == 2
    c = eng.metrics_summary()
    # a one-token prompt rides a decode slot: the decode walk takes it
    assert c["ssm_chunk_tokens_walked"] == 37 + 5 + 18
    assert c["ssm_chunk_tokens_padded"] == 8 * (5 + 1 + 3)
    assert c["ssm_slots_walked"] == 4 * 7 + 1


def test_a_preempted_row_is_recomputed_to_the_same_tokens():
    """A pool too small for all the requests' decode pages: someone is
    preempted (recompute: pages and slot go back), re-prefills into
    whatever slot is free then, and every request still serves the
    reference's tokens."""
    pub, cfg, state = build()
    ps = prompts((14, 15, 13), cfg.vocab_size, seed=11)
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


@pytest.mark.parametrize("what", ["prefix_cache", "speculation"])
def test_the_engine_refuses_what_recurrent_state_cannot_do(what):
    """The refusals of a ``mamba2`` stack, for ``mamba1`` too, under the
    same message."""
    pub, cfg, state = build()
    if what == "prefix_cache":
        with pytest.raises(ValueError, match=r"prefix_cache=True is not "
                           r"built for a stack with recurrent \(mamba1\) "
                           r"layers: a cached page prefix carries no state"):
            Engine(state, cfg, num_pages=16, page_size=8, use_kernel=False)
    else:
        spec = SpecConfig(draft_state=state, draft_cfg=cfg, k=2)
        with pytest.raises(ValueError, match=r"speculative decoding is not "
                           r"built for a stack with recurrent \(mamba1\) "
                           r"layers: a rejected draft cannot be rolled"):
            Engine(state, cfg, num_pages=16, page_size=8,
                   prefix_cache=False, spec=spec, use_kernel=False)


# -- 20 query heads on one key/value head -------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True, "split"])
def test_twenty_query_heads_on_one_kv_head_equal_plain_attention(
        use_kernel, monkeypatch):
    """The ragged call at the published head geometry (20 query heads, ONE
    K/V head, 128 lanes; a group that divides no packed tile): three decode
    rows and a 24-token chunk row over shuffled pages, against plain causal
    attention on the gathered keys.  bf16 pools and queries as served:
    2e-2 of the output's scale (8 mantissa bits through two matmuls).
    "split": the chunk row's window cut into sub-rows over the same table,
    as the call cuts a window too wide for it (1,024 such tokens)."""
    nh, hd, ps, maxp, pages = 20, 128, 16, 6, 32
    if use_kernel == "split":
        import importlib
        rpa = importlib.import_module("hetu_tpu.ops.ragged_paged_attention")
        # the cell's own: 1,024 tokens x 32 rows in 32 sub-windows; the
        # accepted cells' chunks (256 tokens of 16, 8 or 1 rows) uncut
        assert rpa.window_split(1024, nh, 1, jnp.bfloat16) == 32
        assert [rpa.window_split(256, h, kv, jnp.bfloat16)
                for h, kv in ((32, 2), (64, 8), (12, 12))] == [1, 1, 1]
        monkeypatch.setattr(rpa, "MAX_WINDOW_ROWS", 128)
        monkeypatch.setattr(rpa, "SPLIT_WINDOW_ROWS", 128)
        assert rpa.window_split(24, nh, 1, jnp.bfloat16) == 8
        assert rpa.window_split(1, nh, 1, jnp.bfloat16) == 1
    rng = np.random.default_rng(3)
    kp = jnp.asarray(rng.standard_normal((pages, 1, ps, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((pages, 1, ps, hd)), jnp.bfloat16)
    ctx = np.asarray([70, 33, 1, 90])
    q_lens = np.asarray([1, 1, 1, 24])
    table = rng.permutation(np.arange(1, pages))[: 4 * maxp].reshape(4, maxp)
    for (name, width, rows) in (("decode", 1, slice(0, 3)),
                                ("chunk", 24, slice(3, 4))):
        n = len(ctx[rows])
        q = jnp.asarray(rng.standard_normal((n * width, nh, hd)),
                        jnp.bfloat16)
        kw = dict(q_lens=jnp.asarray(q_lens[rows]),
                  cu_q=jnp.arange(n + 1) * width,
                  page_tables=jnp.asarray(table[rows], jnp.int32),
                  ctx_lens=jnp.asarray(ctx[rows]), max_q=width)
        if use_kernel:
            got = ragged_paged_attention_pallas(
                q, kp, vp, interpret=True,
                name=f"mqa_{name}_{use_kernel}", **kw)
        else:
            got = ragged_paged_attention_reference(q, kp, vp, **kw)
        for i in range(n):
            c, ql = int(ctx[rows][i]), int(q_lens[rows][i])
            k = kp[table[rows][i]].reshape(-1, hd)[:c].astype(F32)
            v = vp[table[rows][i]].reshape(-1, hd)[:c].astype(F32)
            qi = q[i * width: i * width + ql].astype(F32)
            s = jnp.einsum("qhd,kd->hqk", qi, k) / np.sqrt(hd)
            seen = np.arange(c)[None, :] <= (c - ql + np.arange(ql))[:, None]
            pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            want = jnp.einsum("hqk,kd->qhd", pr, v)
            close(got[i * width: i * width + ql].astype(F32), want, 2e-2)


# -- the translation ----------------------------------------------------------

def test_jamba_config_reads_the_published_keys():
    """Layers 7 and 21 and no other are attention; every published layer is
    its mixer and then a dense MLP; the head is tied; the parameter count
    from shapes alone (nothing is allocated) is the published 3.03 B."""
    pub = published(tiny=False)
    cfg = hy.jamba_config(pub)
    assert len(cfg.layer_pattern) == 56
    assert cfg.layer_pattern[1::2] == ("mlp",) * 28
    mixers = cfg.layer_pattern[0::2]
    assert [i for i, m in enumerate(mixers) if m == "attention"] == [7, 21]
    assert set(mixers) == {"attention", "mamba1"}
    assert cfg.state_mixer == "mamba1"
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (20, 1, 128)
    assert (cfg.mamba1_inner, cfg.mamba_state_dim, cfg.mamba1_dt_rank) == \
        (5120, 16, 160)
    assert cfg.position == "none" and cfg.tie_embeddings
    shapes = hy.param_shapes(cfg)
    assert "lm_head.weight" not in shapes
    assert shapes["h0.mamba.A_log"] == (16, 5120)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 3_029_337_472
    per_layer = sum(int(np.prod(s)) for k, s in shapes.items()
                    if k.startswith(("h0.", "h1.")))
    assert per_layer == 104_161_472          # a Mamba-1 layer and its MLP
    assert pub["reduced"] == []


@pytest.mark.parametrize("key,value", [("num_experts", 16),
                                       ("sliding_window", 4096),
                                       ("mamba_proj_bias", True)])
def test_jamba_config_refuses_what_it_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        hy.jamba_config({**published(), key: value})


def test_one_pattern_holds_one_kind_of_recurrent_mixer():
    from hetu_tpu.models.gpt import GPTConfig
    with pytest.raises(ValueError, match="one kind of recurrent mixer"):
        GPTConfig(num_layers=2, layer_pattern=("mamba1", "mamba2"))


def test_init_follows_the_published_mamba1_initialiser():
    pub = published()
    cfg = hy.jamba_config(pub, dtype="float32")
    state = hy.init_state(cfg, 3)
    a = np.asarray(state["h0.mamba.A_log"])
    assert np.allclose(np.exp(a), np.arange(1, 17)[:, None])
    dt = np.asarray(jax.nn.softplus(state["h0.mamba.dt_proj.bias"]))
    assert 0.001 <= dt.min() and dt.max() <= 0.1001
    assert (np.asarray(state["h0.mamba.D"]) == 1).all()
    assert state["h0.mamba.A_log"].dtype == jnp.float32
    assert "lm_head.weight" not in state
