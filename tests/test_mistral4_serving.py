"""A latent-attention / gated-expert stack (``model_type: mistral4``: the
``(L, E) x depth`` pattern) through the serving engine, against the plain
float32 reference (``benchmark/reference_mistral4.py``: NOT absorbed, no
cache), at tiny widths on the CPU with seeded random weights.

Tolerances, each with its reason:

* ``GAP_F32`` 1e-4 — float32 system against the float32 reference, in
  logit units of the reference (a served greedy token's logit below the
  reference's best, teacher-forced).  The two differ by reassociation only
  (absorbed against decompressed attention, paged against whole-sequence,
  the grouped experts against a per-expert loop): ~1e-6 at these widths; a
  wrong mask, rotation, scale or share reads 0.1-1.
* ``TENSOR_F32`` 2e-5 (relative to the tensor's largest entry) — the same
  pair compared tensor against tensor.
* ``GAP_BF16`` 0.02 — the bf16 system against the float32 reference: bf16
  keeps 8 mantissa bits; at these widths (hidden 64, 2 x 2 layers, weights
  of std 0.05) the served tokens read 0 to 0.004 below the reference's
  best over 6 requests; the float8 reading of the same sequences (every
  weight matrix and every mixer's input and output rounded to e4m3) reads
  0.05-0.3, which has to fail it.
"""
from __future__ import annotations

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
import reference_mistral4 as ref  # noqa: E402

from hetu_tpu.models import hybrid as hy  # noqa: E402
from hetu_tpu.ops.moe_grouped import (ffn_tile,  # noqa: E402
                                     grouped_experts)
from hetu_tpu.serving import Engine  # noqa: E402
from hetu_tpu.serving.decode import build_unified_step_fn  # noqa: E402

GAP_F32 = 1e-4
TENSOR_F32 = 2e-5
GAP_BF16 = 0.02
VOCAB = 256
ORIG = 16                 # original_max_position_embeddings of the tiny model


def published(**kw) -> dict:
    """A tiny ``mistral4`` config under the published keys: nope / rope / v
    widths that differ (8 / 8 / 16), q rank 24, latent 32; 8 routed experts
    of which 4 are held from offset 2, top-3, one shared."""
    d = dict(model_type="mistral4", hidden_size=64, num_attention_heads=4,
             head_dim=16, num_hidden_layers=2, vocab_size=VOCAB,
             max_position_embeddings=4096, hidden_act="silu",
             rms_norm_eps=1e-6, tie_word_embeddings=False, kv_lora_rank=32,
             q_lora_rank=24, qk_nope_head_dim=8, qk_rope_head_dim=8,
             qk_head_dim=16, v_head_dim=16, rope_interleave=True,
             rope_parameters=dict(
                 beta_fast=32, beta_slow=1, factor=128,
                 llama_4_scaling_beta=0.1, mscale=1, mscale_all_dim=1,
                 original_max_position_embeddings=ORIG, rope_theta=10000,
                 rope_type="yarn", type="yarn"),
             n_routed_experts=4, moe_router_outputs=8, expert_offset=2,
             n_shared_experts=1, norm_topk_prob=True, num_experts_per_tok=3,
             moe_intermediate_size=48, routed_scaling_factor=1, n_group=1,
             topk_group=1, first_k_dense_replace=0, dtype="float32")
    d.update(kw)
    return d


def build(seed: int = 3, std: float = 0.2, **kw):
    pub = published(**kw)
    cfg = hy.mistral4_config(pub, init_std=std)
    return pub, cfg, hy.init_state(cfg, seed)


def engine(state, cfg, **kw):
    kw = {"num_pages": 48, "page_size": 8, "max_batch": 3, "chunk_size": 16,
          "max_model_len": 96, "prefix_cache": True, "debug": True,
          "use_kernel": False, **kw}
    return Engine(state, cfg, **kw)


def prompts(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).tolist() for n in lens]


def gaps(pub, state, prompt, out):
    return ref.greedy_logit_gaps(state, prompt + list(out), len(prompt),
                                 ref.spec_from_config(pub), pad_to=96,
                                 max_new=12)


def _sub(state, prefix):
    return {k[len(prefix):]: v for k, v in state.items()
            if k.startswith(prefix)}


# -- (a) engine against reference: chunks, then decode through the pages -----

@pytest.mark.parametrize("dtype,use_kernel,tol", [
    ("float32", False, GAP_F32), ("float32", True, GAP_F32),
    ("bfloat16", True, GAP_BF16)])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(
        dtype, use_kernel, tol):
    """Logits, not tokens: every served token's logit lies within ``tol``
    of the reference's best.  Prompts of 21-47 tokens are prefilled in
    chunks of 16 and decoded through the latent pages at positions on both
    sides of ``original_max_position_embeddings`` (16) and of the q
    scale's second step (32)."""
    pub, cfg, state = build(dtype=dtype, std=0.05 if dtype == "bfloat16"
                            else 0.2)
    eng = engine(state, cfg, use_kernel=use_kernel, prefix_cache=False)
    ps = prompts([47, 21, 33])
    hs = [eng.add_request(p, 12) for p in ps]
    eng.run()
    assert eng.compile_count == 1 and eng.state_store is None
    for p, h in zip(ps, hs):
        assert len(h.out_tokens) == 12
        assert max(gaps(pub, state, p, h.out_tokens)) <= tol
    if dtype == "bfloat16":
        spec = ref.spec_from_config(pub)
        low = max(max(ref.lowp_choice_gaps(
            state, p + list(h.out_tokens), len(p), spec, 96, 12))
            for p, h in zip(ps, hs))
        assert low > tol, low     # the float8 reading fails the tolerance


# -- (b) the share test -------------------------------------------------------

def test_the_four_shares_and_the_shared_expert_once_give_the_uncut_layer():
    """Expert parallelism without the exchange: each of 4 shares routes
    over all 8 experts and computes its own 2; their routed parts, with
    the shared expert counted once, add up to the uncut reference's layer."""
    pub, cfg, state = build(n_routed_experts=8, expert_offset=0)
    u = jax.random.normal(jax.random.PRNGKey(0), (9, 64), jnp.float32)
    live = jnp.ones((9,), bool)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(u, _sub(state, "h1.moe."), ref.spec_from_config(pub))
        total, loads = 0.0, []
        for share in range(4):
            part = dataclasses.replace(cfg, experts_held=2,
                                       expert_offset=2 * share)
            params = {k: v for k, v in state.items()
                      if share == 0 or ".shared." not in k}
            for n in ("w1", "w2", "w3"):
                params[f"h1.moe.experts.{n}"] = \
                    state[f"h1.moe.experts.{n}"][2 * share: 2 * share + 2]
            out, load = hy.latent_moe(part, params, 1, u, live)
            total = total + out
            loads.append(int(load.sum()))
    assert np.abs(np.asarray(total - whole)).max() \
        <= TENSOR_F32 * float(jnp.abs(whole).max())
    assert sum(loads) == 9 * 3          # every assignment on exactly one share


# -- (c) absorbed against non-absorbed attention ------------------------------

def test_absorbed_attention_equals_the_decompressed_one():
    """The program's algebra (``W_kvb``'s k-half folded into q, its v-half
    out of the 32-wide latent output, one shared rotary key, q and v head
    widths that differ once absorbed: 8 + 8 against 16) against the
    reference's per-head keys and values, on one whole sequence."""
    pub, cfg, state = build()
    t = 40
    u = jax.random.normal(jax.random.PRNGKey(1), (t, 64), jnp.float32)
    spec = ref.spec_from_config(pub)
    pos = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(u, _sub(state, "h0.attn."), spec)
        q, c_kv, k_r = hy.mla_in(cfg, state, 0, u)
        cos, sin, qs = hy.mla_rotary_tables(cfg, t)
        q = q * qs[:, None, None]
        q_cat = hy.mla_absorb_q(cfg, state, 0, q, hy.mla_rotate(
            cfg, q[..., cfg.nope_dim:], cos, sin))
        k = jnp.concatenate([c_kv, hy.mla_rotate(cfg, k_r, cos, sin)], -1)
        assert q_cat.shape == (t, 4, 32 + 8) and k.shape == (t, 32 + 8)
        s = jnp.einsum("qhc,kc->hqk", q_cat, k) * cfg.mla_softmax_scale
        s = jnp.where(pos[None, None, :] <= pos[None, :, None], s, -jnp.inf)
        o_lat = jnp.einsum("hqk,kc->qhc", jax.nn.softmax(s, -1), c_kv)
        got = hy.mla_absorb_out(cfg, state, 0, o_lat, jnp.float32) \
            @ state["h0.attn.out.weight"].T
    assert got.shape == want.shape
    assert np.abs(np.asarray(got - want)).max() \
        <= TENSOR_F32 * float(jnp.abs(want).max())


# -- (d) YaRN tables and the q scale against the formula ----------------------

@pytest.mark.parametrize("pos", [0, 5, ORIG - 1, ORIG, 3 * ORIG + 1, 4000])
def test_yarn_interleaved_tables_and_q_scale_follow_the_formula(pos):
    """Written out by hand for the tiny model (rope 8, theta 10000, factor
    128, original 16, beta 32 / 1): pair i of the INTERLEAVED stream is
    (x[2i], x[2i + 1]); the program rotates [evens | odds] by halves."""
    pub, cfg, _ = build()
    d, base, factor = 8, 10000.0, 128.0
    turn = lambda n: d * math.log(ORIG / (n * 2 * math.pi)) / \
        (2 * math.log(base))                                 # noqa: E731
    low, high = max(math.floor(turn(32)), 0), min(math.ceil(turn(1)), d - 1)
    inv = []
    for i in range(d // 2):
        f = base ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append(f / factor * ramp + f * (1 - ramp))
    cos, sin, qs = hy.mla_rotary_tables(cfg, 4096)
    assert float(qs[pos]) == pytest.approx(
        1 + 0.1 * math.log(1 + pos // ORIG), rel=1e-6)
    x = np.random.RandomState(pos).randn(1, 2, d).astype(np.float32)
    got = np.asarray(hy.mla_rotate(cfg, jnp.asarray(x), cos[pos][None],
                                   sin[pos][None]))
    for i in range(d // 2):
        a, b, ang = x[0, :, 2 * i], x[0, :, 2 * i + 1], pos * inv[i]
        np.testing.assert_allclose(
            got[0, :, i], a * math.cos(ang) - b * math.sin(ang), atol=2e-5)
        np.testing.assert_allclose(
            got[0, :, d // 2 + i], b * math.cos(ang) + a * math.sin(ang),
            atol=2e-5)
    # and the reference's in-place pairs give the same products
    spec = ref.spec_from_config(pub)
    mine = np.asarray(ref.rotate_pairs(jnp.asarray(x), [pos], spec))
    np.testing.assert_allclose(mine[0, :, 0::2], got[0, :, :d // 2],
                               atol=2e-5)
    np.testing.assert_allclose(mine[0, :, 1::2], got[0, :, d // 2:],
                               atol=2e-5)
    assert cfg.mla_softmax_scale == pytest.approx(
        16 ** -0.5 * (0.1 * math.log(128) + 1) ** 2)


# -- (e) the gated, tiled kernel (interpreted) against a per-expert loop ------

_E, _L, _F, _T, _K = 5, 128, 320, 80, 2
_GATED_CASES = {
    # width 320 in tiles of 128: the last tile hangs over by 64
    "width_not_a_multiple_of_the_tile": dict(tile=128),
    # one tile: the whole-expert case, gated
    "whole_expert": dict(tile=None),
    # expert 3 chosen by nobody; expert 1 by every token: 80 rows, two
    # row blocks of 64 under each tile
    "empty_expert_and_one_over_a_row_block": dict(tile=128, skew=True),
    "tile_256_of_320": dict(tile=256, skew=True),
}


@pytest.mark.parametrize("case", list(_GATED_CASES))
def test_gated_tiled_kernel_equals_a_per_expert_loop(case):
    spec = _GATED_CASES[case]
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(_T, _L).astype(np.float32))
    w1, w3 = (jnp.asarray(rng.randn(_E, _L, _F).astype(np.float32) * 0.1)
              for _ in range(2))
    w2 = jnp.asarray(rng.randn(_E, _F, _L).astype(np.float32) * 0.1)
    idx = rng.randint(0, _E + 2, (_T, _K))          # some fall outside
    if spec.get("skew"):
        idx = np.where((idx == 3) | (idx == 1), 4, idx)
        idx[:, 0] = 1
    wts = jnp.asarray(rng.rand(_T, _K).astype(np.float32))
    live = jnp.asarray(rng.rand(_T) < 0.9) if not spec.get("skew") \
        else jnp.ones((_T,), bool)
    out, load = grouped_experts(x, jnp.asarray(idx, jnp.int32), wts, live,
                                w1, w2, w3, activation="silu",
                                tile=spec["tile"], interpret=True)
    want = np.zeros((_T, _L), np.float32)
    counts = np.zeros(_E, np.int64)
    with jax.default_matmul_precision("highest"):
        for e in range(_E):
            y = np.asarray((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
            for j in range(_K):
                on = (idx[:, j] == e) & np.asarray(live)
                want += np.where(on[:, None], np.asarray(wts)[:, j:j + 1] * y,
                                 0.0)
                counts[e] += on.sum()
    assert np.array_equal(np.asarray(load), counts)
    if spec.get("skew"):
        assert counts[3] == 0 and counts[1] == _T > 64
    assert np.abs(np.asarray(out) - want).max() \
        <= 5 * TENSOR_F32 * np.abs(want).max()


def test_the_shape_decides_the_tile():
    # the hybrid's un-gated expert whole, as before; this one in 4 tiles
    assert ffn_tile(1024, 2688, 2, 2) == 2688
    assert ffn_tile(4096, 2048, 3, 2) == 512
    assert ffn_tile(128, 320, 3, 4) == 320


# -- (f) a prefix-cache hit over latent pages ---------------------------------

def test_a_prefix_cache_hit_over_latent_pages_gives_the_cold_runs_logits():
    """The same prompt served cold and again behind a cached 40-token
    document: the step's logits at the last prompt token agree (the cached
    latent pages are what the cold run wrote), the tokens are the same,
    and the reference agrees with both."""
    pub, cfg, state = build()
    doc = prompts([40], seed=5)[0]
    a, b = (doc + tail for tail in prompts([7, 5], seed=6))
    cold = engine(state, cfg, prefix_cache=False)
    h_cold = cold.add_request(b, 10)
    cold.run()
    eng = engine(state, cfg)
    eng.add_request(a, 4)
    eng.run()
    h_hit = eng.add_request(b, 10)
    eng.run()
    assert eng.metrics_summary()["prefix_cache_tokens_saved"] == 40
    assert list(h_hit.out_tokens) == list(h_cold.out_tokens)
    assert max(gaps(pub, state, b, h_hit.out_tokens)) <= GAP_F32
    assert eng.metrics_summary()["latent_pages_attended"] >= \
        eng.metrics_summary()["latent_pages_attended_distinct"] > 0


def test_rows_on_one_document_share_its_pages_in_the_counters():
    pub, cfg, state = build()
    doc = prompts([40], seed=8)[0]
    eng = engine(state, cfg)
    eng.add_request(doc + [1, 2, 3], 2)
    eng.run()
    eng.reset_metrics()
    for tail in prompts([4, 6, 5], seed=9):
        eng.add_request(doc + tail, 8)
    eng.run()
    m = eng.metrics_summary()
    # three rows attend the document's five cached pages each step
    assert m["latent_pages_attended_distinct"] < m["latent_pages_attended"]
    assert m["prefix_cache_tokens_saved"] == 3 * 40


def test_the_grid_steps_counter_follows_the_kernels_own_rule():
    """``latent_grid_steps``: per step and layer, each live row's pages
    over the group its region's latent call walks a grid step, rounded
    up — the group from the rule the kernel wrapper reads, at the
    engine's shapes (decode rows and the chunk row take their own); the
    ``unified_step`` span carries the step's count."""
    from hetu_tpu.obs.tracer import SpanTracer
    from hetu_tpu.ops.ragged_paged_attention import (
        latent_pages_per_grid_step)
    pub, cfg, state = build()
    eng = engine(state, cfg, tracer=SpanTracer(), prefix_cache=False)
    sch = eng.scheduler

    def group(width):                # the kernel wrapper's own call
        return latent_pages_per_grid_step(
            width, cfg.num_heads, sum(cfg.latent_page_dims),
            eng.max_pages_per_seq,
            (eng.pool.k_pages[0], eng.pool.v_pages[0]))

    assert group(1) > 1              # else the count is the pages'
    want, account = [], eng.account

    def spy(rows, *step):
        want.append(sum(
            -(-eng.pool.pages_for(req.pos + q)
              // group(1 if row < sch.max_batch else sch.chunk))
            for req, q, row in rows))
        return account(rows, *step)

    eng.account = spy
    for n, out in ((47, 6), (21, 9), (70, 3)):
        eng.add_request(prompts([n], seed=n)[0], out)
    eng.run()
    steps = [e for e in eng.tracer.events() if e.name == "unified_step"]
    assert len(steps) == len(want) > 9
    assert [e.attrs["latent_grid_steps"] for e in steps] == want
    m = eng.metrics_summary()
    assert m["latent_grid_steps"] == sum(want) < m["latent_pages_attended"]


# -- (g) the translation, its refusals, and what stays refused ----------------

@pytest.mark.parametrize("change,word", [
    (dict(n_group=2), "group-limited"), (dict(topk_group=2), "group-limited"),
    (dict(first_k_dense_replace=1), "dense layers")])
def test_the_translation_refuses_what_it_would_have_to_guess(change, word):
    with pytest.raises(ValueError, match=word):
        hy.mistral4_config(published(**change))


def test_the_translation_keeps_the_published_widths():
    pub = published()
    cfg = hy.mistral4_config(pub)
    assert cfg.layer_pattern == ("mla", "moe") * 2 and cfg.num_layers == 4
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.kv_latent_dim,
            cfg.mla_q_rank) == (8, 8, 16, 32, 24)
    assert (cfg.num_experts, cfg.held_experts, cfg.expert_offset,
            cfg.moe_top_k) == (8, 4, 2, 3)
    assert cfg.moe_gated and cfg.moe_norm_topk and cfg.moe_router == "softmax"
    assert cfg.paged_layers == (0, 2) and not cfg.layers_of("mamba2")
    shapes = hy.param_shapes(cfg)
    assert shapes["h0.attn.k_up.weight"] == (4, 8, 32)
    assert shapes["h0.attn.v_up.weight"] == (4, 16, 32)
    assert shapes["h1.moe.experts.w3"] == (4, 64, 48)
    assert "h1.moe.router.bias" not in shapes


def test_refusals_follow_the_pattern_not_the_stack():
    pub, cfg, state = build()
    assert engine(state, cfg).prefix_cache is not None     # no M layer
    from hetu_tpu.models.gpt import GPTConfig
    with pytest.raises(ValueError, match="one page pool holds one layout"):
        dataclasses.replace(cfg, layer_pattern=("mla", "attention", "mla",
                                                "moe"))
    with pytest.raises(ValueError, match="mla mixer"):
        GPTConfig(mla_q_rank=8)
    with pytest.raises(ValueError, match="speculative verify rows"):
        build_unified_step_fn(cfg, 2, 8, 1, 4, 8, spec_k=2)
    # a pattern with a state-space layer still refuses the prefix cache
    from tests.test_hybrid_serving import build as build_hybrid
    _, hcfg, hstate = build_hybrid("*EM")
    with pytest.raises(ValueError, match="prefix_cache=True is not built"):
        Engine(hstate, hcfg, num_pages=16, page_size=8, prefix_cache=True)
