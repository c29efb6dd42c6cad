"""A model that generates by diffusion over blocks (``model_type:
sdar_moe``) through the serving engine — ``Scheduler``, ``PagedKVPool``,
the pattern builder's block region — against the plain reference
(``benchmark/reference_sdar.py``), at a small size on the CPU with seeded
weights: the block-wise mask of the ragged call, the engine's block loop
under the three unmask rules, the counts, and what is refused."""
from __future__ import annotations

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import reference_sdar as ref  # noqa: E402

from hetu_tpu.models import hybrid as hy  # noqa: E402
from hetu_tpu.serving import DenoiseRule, Engine  # noqa: E402
from hetu_tpu.serving.spec import SpecConfig  # noqa: E402

rpa = importlib.import_module("hetu_tpu.ops.ragged_paged_attention")

B, MASK = 4, 95
PUB = dict(
    model_type="sdar_moe", vocab_size=96, hidden_size=32,
    num_attention_heads=8, num_key_value_heads=1, head_dim=8,
    rope_theta=1e6, max_position_embeddings=512, hidden_act="silu",
    rms_norm_eps=1e-6, tie_word_embeddings=False, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=16, num_hidden_layers=2,
    norm_topk_prob=True, assumed={"block_length": B, "mask_token_id": MASK},
    dtype="float32")
PAGE, CHUNK = 8, 16
STATIC2 = DenoiseRule(steps=2, rule="low_confidence_static")
CONF_TOL = 2e-5          # float32 against float32: the served confidences


def build(seed=0, **changes):
    pub = {**PUB, **changes}
    cfg = hy.sdar_moe_config(pub, init_std=0.3)
    return cfg, hy.init_state(cfg, seed), ref.spec_from_config(pub)


@pytest.fixture(scope="module")
def model():
    return build()


def engine(cfg, state, rule=STATIC2, **kw):
    kw = {"num_pages": 64, "page_size": PAGE, "max_batch": 4,
          "chunk_size": CHUNK, "prefill_rows": 1, "prefix_cache": False,
          "use_kernel": False, "debug": True, **kw}
    return Engine(state, cfg, denoise=rule, **kw)


def prompts(lengths, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 90, n).tolist() for n in lengths]


def assert_served_as_generated(h, state, spec, rule, eos=None, log=True):
    """The request against the reference's block loop: the emitted tokens;
    and, pass by pass, the state going in, the unmasked set, its tokens and
    the served confidences of the masked positions (= the served logits at
    the choice, normalised: within ``CONF_TOL`` of ``denoise_logits``')."""
    out, want = ref.generate(state, h.prompt, h.max_new_tokens, spec,
                             steps=rule.steps, rule=rule.rule, tau=rule.tau,
                             eos=eos)
    assert h.out_tokens == out
    if not log:
        return want
    assert len(h.denoise_log) == len(want)
    for got, exp in zip(h.denoise_log, want):
        assert got[:4] == exp[:4]
        np.testing.assert_allclose(got[4], exp[4], atol=CONF_TOL)
        # the unmasked set is the rule's on the SERVED confidences
        masked = [j for j in range(B) if got[1][j] == MASK]
        if masked:
            k = len(exp[2]) if rule.rule != "low_confidence_dynamic" else \
                None
            if k is not None:
                assert list(got[2]) == ref.unmask_set(
                    masked, list(got[4]), k, rule.rule, rule.tau)
    return want


# -- the mask -----------------------------------------------------------------

def _ragged_case(seed=0, heads=8, kv=1, hd=16, page=8):
    """A chunk region (one row of 16 queries behind 8 cached tokens) and a
    block region (3 rows of 4 queries at block-aligned contexts)."""
    rng = np.random.RandomState(seed)
    pages = 12
    kp = jnp.asarray(rng.randn(pages, kv, page, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(pages, kv, page, hd), jnp.float32)
    chunk = dict(q=jnp.asarray(rng.randn(16, heads, hd), jnp.float32),
                 q_lens=jnp.asarray([16]), cu_q=jnp.asarray([0, 16]),
                 page_tables=jnp.asarray([[1, 2, 3, 0]]),
                 ctx_lens=jnp.asarray([24]), max_q=16)
    block = dict(q=jnp.asarray(rng.randn(12, heads, hd), jnp.float32),
                 q_lens=jnp.asarray([4, 0, 4]), cu_q=jnp.asarray([0, 4, 8, 12]),
                 page_tables=jnp.asarray([[4, 5, 6, 0], [0, 0, 0, 0],
                                          [7, 8, 9, 10]]),
                 ctx_lens=jnp.asarray([20, 0, 28]), max_q=4)
    return kp, vp, {"chunk": chunk, "block": block}


def _dense_block_attention(q, kp, vp, table, ctx, qlen, block):
    """One row, plainly: every key up to the end of the query's block."""
    hd = q.shape[-1]
    k = np.concatenate([np.asarray(kp[p]) for p in table], 1)   # [kv, n, hd]
    v = np.concatenate([np.asarray(vp[p]) for p in table], 1)
    out = []
    for j in range(qlen):
        p = ctx - qlen + j
        n = min(ctx, (p // block + 1) * block)
        s = np.einsum("hd,nd->hn", np.asarray(q[j]), k[0, :n]) * hd ** -0.5
        pr = np.exp(s - s.max(-1, keepdims=True))
        out.append((pr / pr.sum(-1, keepdims=True)) @ v[0, :n])
    return np.stack(out)


@pytest.mark.parametrize("region", ["chunk", "block"])
@pytest.mark.parametrize("op", ["reference", "pallas"])
def test_block_mask_of_the_ragged_call(region, op):
    """``mask_block=B``: the Pallas call (interpreted) and the reference op
    against a plain per-row softmax over the keys up to the end of each
    query's block, GQA 8 : 1, over the chunk and the block region."""
    kp, vp, cases = _ragged_case()
    case = cases[region]
    fn = rpa.ragged_paged_attention_reference if op == "reference" else \
        rpa.ragged_paged_attention_pallas
    kw = {"interpret": True} if op == "pallas" else {}
    got = np.asarray(fn(case["q"], kp, vp, case["q_lens"], case["cu_q"],
                        case["page_tables"], case["ctx_lens"],
                        max_q=case["max_q"], mask_block=B, **kw))
    for i, qlen in enumerate(np.asarray(case["q_lens"])):
        if not qlen:
            continue
        lo = int(case["cu_q"][i])
        want = _dense_block_attention(
            case["q"][lo:lo + qlen], kp, vp,
            np.asarray(case["page_tables"][i]), int(case["ctx_lens"][i]),
            int(qlen), B)
        np.testing.assert_allclose(got[lo:lo + qlen], want, atol=2e-5)
    causal = np.asarray(fn(case["q"], kp, vp, case["q_lens"], case["cu_q"],
                           case["page_tables"], case["ctx_lens"],
                           max_q=case["max_q"], **kw))
    assert np.abs(causal - got).max() > 1e-3     # another mask


@pytest.mark.parametrize("region", ["chunk", "block"])
def test_mask_block_one_is_the_call_as_it_was(region):
    """``mask_block=1`` is the causal line itself: the same jaxpr as the
    call without the argument, the same bits out."""
    kp, vp, cases = _ragged_case(seed=1)
    case = cases[region]
    args = (case["q"], kp, vp, case["q_lens"], case["cu_q"],
            case["page_tables"], case["ctx_lens"])

    def call(**kw):
        return lambda *a: rpa.ragged_paged_attention_pallas.__wrapped__(
            *a, max_q=case["max_q"], interpret=True, **kw)
    assert str(jax.make_jaxpr(call())(*args)) == \
        str(jax.make_jaxpr(call(mask_block=1))(*args))
    np.testing.assert_array_equal(np.asarray(call()(*args)),
                                  np.asarray(call(mask_block=1)(*args)))
    cols, qpos = jnp.arange(9)[None], jnp.arange(3)[:, None]
    np.testing.assert_array_equal(rpa.block_mask(cols, qpos, 9, 1),
                                  cols <= qpos)


def test_a_token_moves_no_served_block_before_it(model):
    """Through the engine: two prompts that differ in block 2 alone open
    their first generated block behind IDENTICAL blocks 0-1, and the
    reference agrees on both."""
    cfg, state, spec = model
    a = prompts([12])[0]
    b = list(a)
    b[9] = (a[9] + 5) % 90
    eng = engine(cfg, state)
    ha, hb = eng.add_request(a, 8), eng.add_request(b, 8)
    eng.run()
    assert ha.out_tokens != hb.out_tokens
    assert_served_as_generated(ha, state, spec, STATIC2)
    assert_served_as_generated(hb, state, spec, STATIC2)


# -- the engine against the block loop ---------------------------------------

@pytest.mark.parametrize("length", [8, 9, 10, 11])
def test_prompt_tails_open_the_first_block(model, length):
    """``L mod B`` in {0, 1, 2, 3}: the prompt's whole blocks are prefilled
    under the block mask, what is left opens the first block unmasked."""
    cfg, state, spec = model
    eng = engine(cfg, state)
    h = eng.add_request(prompts([length], seed=length)[0], 9)
    eng.run()
    log = assert_served_as_generated(h, state, spec, STATIC2)
    assert log[0][1].count(MASK) == B - length % B
    assert eng.compile_count == 1 and eng.pool.free_pages == \
        eng.pool.num_usable


@pytest.mark.parametrize("kernel", [False, True])
def test_prompts_across_chunk_boundaries_and_a_short_one(model, kernel):
    """Prompts of 3 (no whole block: no prefill at all), 37 and 50 tokens
    (three and four chunks of 16) in one batch; ``max_new_tokens`` not a
    multiple of the block.  ``kernel``: the interpreted Pallas path."""
    cfg, state, spec = model
    eng = engine(cfg, state, use_kernel=kernel)
    hs = [eng.add_request(p, n) for p, n in zip(
        prompts([3, 37, 50, 16]), (5, 10, 7, 13))]
    eng.run()
    for h in hs:
        assert_served_as_generated(h, state, spec, STATIC2)
        assert len(h.out_tokens) == h.max_new_tokens
    assert eng.compile_count == 1


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rule", ["low_confidence_static", "sequential"])
def test_steps_and_static_rules(model, steps, rule):
    cfg, state, spec = model
    dr = DenoiseRule(steps=steps, rule=rule)
    eng = engine(cfg, state, rule=dr)
    hs = [eng.add_request(p, 9) for p in prompts([9, 22, 8])]
    eng.run()
    counts = ref.schedule(B, steps)
    for h in hs:
        assert_served_as_generated(h, state, spec, dr)
        whole = [e for e in h.denoise_log if e[1].count(MASK) == B]
        assert all(len(e[2]) == counts[0] for e in whole)


def test_the_dynamic_rule_crosses_tau(model):
    """Logits built so that some confidences pass ``tau`` and some do not:
    a pass then unmasks more than its schedule's count, a block takes fewer
    passes, and the engine follows the reference through all of it."""
    cfg, state, spec = model
    sharp = dict(state)
    sharp["lm_head.weight"] = state["lm_head.weight"] * 6.0
    dr = DenoiseRule(steps=4, rule="low_confidence_dynamic", tau=0.7)
    eng = engine(cfg, sharp, rule=dr)
    hs = [eng.add_request(p, 12) for p in prompts([9, 22, 8, 13])]
    eng.run()
    picked, above = [], []
    for h in hs:
        assert_served_as_generated(h, sharp, spec, dr)
        for at, x, got, toks, conf in h.denoise_log:
            if MASK in x:
                picked.append(len(got))
                above.append(sum(c > dr.tau for c in conf))
                masked = [j for j in range(B) if x[j] == MASK]
                # the schedule's one by rank, and every one above tau
                assert list(got) == ref.unmask_set(
                    masked, list(conf), 1, dr.rule, dr.tau)
    assert max(picked) > 1 and min(picked) == 1      # both kinds occurred
    assert any(a == 0 for a in above) and any(a >= 2 for a in above)


def test_an_end_of_sequence_inside_a_block(model):
    cfg, state, spec = model
    prompt = prompts([9], seed=3)[0]
    free = engine(cfg, state)
    full = free.add_request(prompt, 12)
    free.run()
    eos = full.out_tokens[5]            # inside the second generated block
    eng = engine(cfg, state)
    h = eng.add_request(prompt, 12, eos_token_id=eos)
    eng.run()
    cut = full.out_tokens[:full.out_tokens.index(eos) + 1]
    assert h.out_tokens == cut and len(cut) < 12
    assert_served_as_generated(h, state, spec, STATIC2, eos=eos)
    assert eng.pool.free_pages == eng.pool.num_usable


def test_a_preemption_in_an_open_block(model):
    """A pool too small for the batch: the earlier request, a short prompt
    answered at length, asks for a page while the later one sits in an open
    block with a pass done; the later one is evicted, keeps its committed
    tokens, re-prefills them under the block mask and makes the block's
    passes again — and both still emit what the reference does."""
    cfg, state, spec = model
    eng = engine(cfg, state, num_pages=8, max_batch=2)
    hs = [eng.add_request(p, n) for p, n in zip(
        prompts([6, 29], seed=4), (40, 18))]
    eng.run()
    assert eng.counters["preemptions"].value > 0
    lost = 0
    for h in hs:
        want = assert_served_as_generated(h, state, spec, STATIC2, log=False)
        commits = [e[0] for e in h.denoise_log if MASK not in e[1]]
        assert commits == [e[0] for e in want if MASK not in e[1]]
        # the passes of a block that was open at the eviction are logged,
        # lost with the block, and made again
        lost += len(h.denoise_log) - len(want)
    assert lost > 0 and max(h.n_preemptions for h in hs) >= 1
    # an evicted request's passes are in the count, its commits are once
    assert eng.counters["block_commit_passes"].value == \
        eng.counters["blocks_committed"].value
    assert eng.pool.free_pages == eng.pool.num_usable


# -- the fused row: a commit rides the next block's first pass ----------------

def _row_passes_recounted(hs):
    """The benchmark driver's recount of the block forwards, from the
    requests' own lengths (``serve_open_loop_block.expected_row_passes``:
    a committed block's passes by the schedule "+ 1 commit")."""
    from drivers.serve_open_loop_block import expected_row_passes
    return expected_row_passes(hs, hs, B, ref.schedule(B, STATIC2.steps))


def _eos_in_the_second_block(cfg, state):
    free = engine(cfg, state)
    full = free.add_request(prompts([8], seed=4)[0], 12)
    free.run()
    return full.out_tokens[5]


# case -> (prompt lengths, max_new_tokens, engine arguments, commits fused,
# preemptions); every request arrives at once
FUSED_CASES = {
    # three blocks behind a prompt of whole blocks: fused, fused, plain
    "several_blocks": ([8], [12], {}, 2, 0),
    # L mod 4 = 2: the first block opens with two prompt tokens
    "prompt_tail": ([10], [10], {}, 2, 0),
    # one block: its commit is the request's last, a plain one
    "last_block_plain": ([8], [4], {}, 0, 0),
    # an end-of-sequence id in the second block: that commit is plain
    "eos_in_a_block": ([8], [12], {}, 1, 0),
    # four pages for two requests of two pages each: at its second commit
    # the longer one finds no page for the block behind it (the shorter one
    # ends in that step and holds its two), commits plainly and takes the
    # page a step later, from what the other freed
    "no_page_for_the_next_block": (
        [8, 8], [8, 12], dict(num_pages=5, prefill_rows=2), 2, 0),
    # the same squeeze with the earlier request the longer: it falls back
    # at its first commit while the later one rides a fused row, then asks
    # for its page and the later one is evicted with a first pass done
    "preempted_behind_a_fused_row": (
        [12, 8], [8, 12], dict(num_pages=5, prefill_rows=2), 2, 1),
    # a fused row, a plain denoise row and a prompt chunk in one step
    "mixed_step": ([8, 9, 37], [12, 9, 6], {}, 2 + 2 + 1, 0),
}


@pytest.mark.parametrize("case", FUSED_CASES)
def test_a_commit_rides_the_next_blocks_first_pass(model, case):
    """Served tokens and every pass of ``denoise_log`` (place, state going
    in, picked set, tokens, confidences) equal the reference's, where a
    commit and the next block's first denoise pass are ONE row of 2B
    positions; the counters count block forwards, so the driver's recount
    holds with a fused row counted twice; and how often the mechanism
    engages is what the case says."""
    cfg, state, spec = model
    lengths, new, kw, fused, preemptions = FUSED_CASES[case]
    eos = _eos_in_the_second_block(cfg, state) \
        if case == "eos_in_a_block" else None
    eng = engine(cfg, state, **kw)
    hs = [eng.add_request(p, n, eos_token_id=eos)
          for p, n in zip(prompts(lengths, seed=4), new)]
    eng.run()
    c = {k: v.value for k, v in eng.counters.items()}
    lost = 0
    for h in hs:
        want = assert_served_as_generated(h, state, spec, STATIC2, eos=eos,
                                          log=not h.n_preemptions)
        assert [e[:4] for e in h.denoise_log if MASK not in e[1]] == \
            [e[:4] for e in want if MASK not in e[1]]
        lost += len(h.denoise_log) - len(want)
    assert c["preemptions"] == preemptions
    assert c["block_row_passes"] == _row_passes_recounted(hs) + lost
    assert c["block_commit_passes"] == c["blocks_committed"]
    assert c["block_commits_fused"] == fused
    assert eng.compile_count == 1
    assert eng.pool.free_pages == eng.pool.num_usable
    steps = [t["rows"] for t in eng.tap if t["kind"] == "unified"]
    vbase = eng.scheduler.max_batch + eng.scheduler.prefill_rows
    if len(hs) == 1 and not lengths[0] % B and eos is None:
        # n blocks under the two-pass rule: 2n + 1 generating steps (3n
        # with a commit pass of its own a block)
        generating = [r for r in steps if r[0][0] >= vbase]
        assert len(generating) == 2 * (new[0] // B) + 1
    if case == "eos_in_a_block":
        assert len(hs[0].out_tokens) == 6
    if case == "no_page_for_the_next_block":
        # the second commit of the longer request went in B wide although
        # the request went on
        assert [q for r in steps for row, pos, q in r
                if row == vbase + 1 and pos == 12] == [B] * 2
    if case == "preempted_behind_a_fused_row":
        # exactly the fused row's first pass of the next block was lost
        assert lost == 1 and hs[1].n_preemptions == 1
        late = hs[1].denoise_log
        i = next(i for i, e in enumerate(late) if e[0] == 12)
        assert late[i - 1][0] == 8 and MASK not in late[i - 1][1]
        assert late[i][1] == (MASK,) * B == late[i + 1][1]
    if case == "mixed_step":
        kinds = [{"chunk" if row < vbase else q for row, _, q in r}
                 for r in steps]
        assert {"chunk", B, 2 * B} in kinds


def test_drawn_sampling_replays_and_ignores_the_batch(model):
    """Temperature > 0 goes through the repo's one per-row sampler, keyed by
    (seed, position): the same request alone and in a batch, twice."""
    cfg, state, _ = model
    p = prompts([10, 21, 9])

    def serve(batch):
        eng = engine(cfg, state)
        hs = [eng.add_request(q, 10, temperature=0.8, top_k=20, seed=7 + i)
              for i, q in enumerate(batch)]
        eng.run()
        return [h.out_tokens for h in hs]
    together = serve(p)
    assert serve(p) == together
    assert serve(p[:1])[0] == together[0]
    greedy = engine(cfg, state)
    g = greedy.add_request(p[0], 10)
    greedy.run()
    assert g.out_tokens != together[0]
    assert all(0 <= t < PUB["vocab_size"] and t != MASK
               for out in together for t in out)


# -- the counts ---------------------------------------------------------------

def test_counts_of_passes_and_provisional_kv(model):
    cfg, state, spec = model
    from hetu_tpu import obs
    tracer = obs.SpanTracer(capacity=1 << 14)
    eng = engine(cfg, state, tracer=tracer)
    lengths, new = [8, 9, 10, 11, 37], [8, 7, 9, 12, 10]
    hs = [eng.add_request(p, n) for p, n in zip(prompts(lengths), new)]
    eng.run()
    c = {k: v.value for k, v in eng.counters.items()}
    counts = ref.schedule(B, STATIC2.steps)

    def passes(masks):
        done = t = 0
        while done < masks:
            done, t = done + counts[t], t + 1
        return t
    blocks = expected = denoise = 0
    for length, h in zip(lengths, hs):
        first = B - length % B
        n = len(h.out_tokens)
        rest = -(-(n - min(n, first)) // B)
        blocks += 1 + rest
        denoise += passes(first) + rest * passes(B)
    expected = denoise + blocks
    assert c["block_row_passes"] == expected
    assert c["block_commit_passes"] == c["blocks_committed"] == blocks
    assert c["kv_tokens_provisional"] == B * denoise
    assert c["block_positions"] == B * expected
    assert c["block_tokens_unmasked"] == sum(
        B - l % B for l in lengths) + B * (blocks - len(lengths))
    assert c["block_positions_masked"] > c["block_tokens_unmasked"]
    # one transfer each way a step, tokens counted as they are emitted
    assert c["h2d_copies"] == c["d2h_fetches"] == c["step_calls"]
    assert c["tokens_generated"] == sum(new)
    assert c["prefill_tokens"] == sum(lengths)
    assert c["kv_tokens_written"] == sum(l // B * B for l in lengths) + \
        B * expected
    assert c["decode_steps"] == 0
    steps = [e for e in tracer.events() if e.name == "unified_step"]
    assert len(steps) == c["step_calls"]
    for k in ("block_rows", "block_commit_rows", "block_fused_rows",
              "block_unmasked", "block_masked", "attn_pairs", "kv_pages_distinct", "moe_local",
              "moe_experts_hit", "moe_blocks", "moe_load_peak"):
        assert all(k in e.attrs for e in steps), k
    assert sum(e.attrs["block_rows"] for e in steps) == expected
    assert sum(e.attrs["block_commit_rows"] for e in steps) == blocks
    mixes = [e.attrs for e in tracer.events() if e.name == "engine_step"
             and "block_slots" in e.attrs]
    # a fused row is one slot and two forwards: every commit but a
    # request's last rides with the next block's first pass
    fused = blocks - len(hs)
    assert c["block_commits_fused"] == fused == sum(
        e.attrs["block_fused_rows"] for e in steps)
    assert sum(a["block_slots"] for a in mixes) + fused == expected
    assert sum(a["tokens"] for a in mixes) == c["kv_tokens_written"]
    assert all(a["decode_slots"] == 0 == a["verify_slots"] for a in mixes)


def test_attn_pairs_are_counted_from_the_mask():
    from hetu_tpu.serving.step_account import _block_pairs
    import types
    rows = types.SimpleNamespace(pos=[0, 8, 16], ctx=[16, 12, 20])
    # 16 queries in 4 blocks: 4 x (4 + 8 + 12 + 16); a block at 8: 4 x 12;
    # a block at 16: 4 x 20
    assert _block_pairs(rows, B) == 4 * (4 + 8 + 12 + 16) + 48 + 80


# -- what is not built is refused, by name ------------------------------------

def _mamba_pub():
    return dict(layer_pattern=("mamba2", "moe"), num_layers=2,
                mamba_num_heads=4, mamba_head_dim=8, mamba_state_dim=8)


@pytest.mark.parametrize("what,match", [
    ("spec", "speculative decoding is not built with block-wise"),
    ("mesh", "a mesh is not built with block-wise"),
    ("prefix_page", "page_size % diffusion_block == 0"),
    ("chunk", "multiples of the block length"),
    ("page", "multiples of the block length"),
    ("mamba2", "recurrent \\(mamba2\\) state"),
    ("window", "window layers"),
    ("mtp", "no next token to draft"),
    ("steps", "must lie in 1..4"),
    ("rule", "unknown unmask rule"),
    ("mask_in_prompt", "the mask id 95 in a prompt"),
    ("adopt", "adoption is not built with block-wise"),
    ("plain_block", "describe the attention mixer of a layer_pattern"),
    ("denoise_elsewhere", "describes block-wise generation"),
    ("unstated", "assumed.block_length"),
])
def test_refusals(model, what, match):
    cfg, state, _ = model
    import dataclasses
    from hetu_tpu.models.gpt import GPTConfig
    from hetu_tpu.serving.decode import build_unified_step_fn
    with pytest.raises(ValueError, match=match):
        if what == "spec":
            engine(cfg, state, spec=SpecConfig(self_draft=1))
        elif what == "mesh":
            engine(cfg, state, mesh=object())
        elif what == "prefix_page":
            engine(cfg, state, prefix_cache=True, page_size=6)
        elif what == "chunk":
            build_unified_step_fn(cfg, 4, 18, 1, 8, PAGE)
        elif what == "page":
            build_unified_step_fn(cfg, 4, CHUNK, 1, 8, 6)
        elif what == "mamba2":
            build_unified_step_fn(dataclasses.replace(
                cfg, **_mamba_pub()), 4, CHUNK, 1, 8, PAGE)
        elif what == "window":
            build_unified_step_fn(dataclasses.replace(
                cfg, attn_window=8, attn_window_layers=(0,)), 4, CHUNK, 1,
                8, PAGE)
        elif what == "mtp":
            build_unified_step_fn(dataclasses.replace(
                cfg, mtp_pattern=("attention", "moe")), 4, CHUNK, 1, 8, PAGE)
        elif what == "steps":
            engine(cfg, state, rule=DenoiseRule(steps=5))
        elif what == "rule":
            engine(cfg, state, rule=DenoiseRule(rule="random"))
        elif what == "mask_in_prompt":
            engine(cfg, state).add_request([1, MASK, 3], 4)
        elif what == "adopt":
            engine(cfg, state).adopt_request([1, 2, 3], [4], 8)
        elif what == "plain_block":
            GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                      num_heads=4, diffusion_block=4, mask_token_id=95)
        elif what == "denoise_elsewhere":
            plain = dataclasses.replace(cfg, diffusion_block=0,
                                        mask_token_id=None)
            Engine(state, plain, num_pages=16, page_size=PAGE,
                   prefix_cache=False, use_kernel=False, denoise=STATIC2)
        elif what == "unstated":
            hy.sdar_moe_config({**PUB, "assumed": {}})


def test_the_prefix_cache_is_sound_where_a_page_holds_whole_blocks(model):
    """``page_size % B == 0``: a cached page's K/V depends on the tokens up
    to its own end; a second request over the same prompt resumes behind
    the cached pages and emits the same tokens."""
    cfg, state, spec = model
    eng = engine(cfg, state, prefix_cache=True)
    prompt = prompts([29])[0]
    first = eng.add_request(prompt, 9)
    eng.run()
    again = eng.add_request(prompt, 9)
    eng.run()
    assert again.cached_tokens >= 2 * PAGE
    assert again.out_tokens == first.out_tokens
    assert_served_as_generated(again, state, spec, STATIC2, log=False)
