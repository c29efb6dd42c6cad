"""The plain reference of the block-diffusion stack
(``benchmark/reference_sdar.py``) against its own equations, at a small
size on the CPU with seeded weights: the block-wise mask, ``denoise_logits``
as ``forward`` over a concatenation, the batched form the cell's check
calls, the schedule and the three unmask rules, and the block loop."""
from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import reference_sdar as ref  # noqa: E402

from hetu_tpu.models import hybrid as hy  # noqa: E402

B, MASK = 4, 95
PUB = dict(
    model_type="sdar_moe", vocab_size=96, hidden_size=32,
    num_attention_heads=8, num_key_value_heads=1, head_dim=8,
    rope_theta=1e6, max_position_embeddings=512, hidden_act="silu",
    rms_norm_eps=1e-6, tie_word_embeddings=False, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=16, num_hidden_layers=2,
    norm_topk_prob=True, assumed={"block_length": B, "mask_token_id": MASK},
    dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = hy.sdar_moe_config(PUB, init_std=0.3)
    return hy.init_state(cfg, 5), ref.spec_from_config(PUB)


def ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, 90, n).tolist()


@pytest.mark.parametrize("block", [0, 1, 3])
def test_a_token_moves_its_own_block_and_nothing_before_it(model, block):
    """Changing a token of block ``n`` moves no logit of a block before
    ``n`` and moves every position of block ``n`` (both ways inside it)
    and of the blocks behind it."""
    params, spec = model
    seq = ids(16)
    at = block * B + 2
    other = list(seq)
    other[at] = (seq[at] + 7) % 90
    a, b = ref.forward(params, seq, spec), ref.forward(params, other, spec)
    moved = np.abs(np.asarray(a) - np.asarray(b)).max(-1)
    assert (moved[:block * B] == 0).all()
    assert (moved[block * B:] > 1e-6).all()


def test_denoise_logits_is_forward_over_the_concatenation(model):
    params, spec = model
    committed, x = ids(12), [7, MASK, 9, MASK]
    lg = ref.denoise_logits(params, committed, x, spec)
    whole = ref.forward(params, committed + x, spec)
    assert lg.shape == (B, PUB["vocab_size"])
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(whole[-B:]))
    # a masked position reads as the mask id's embedding: another id there
    # is another state
    y = ref.denoise_logits(params, committed, [7, 3, 9, MASK], spec)
    assert np.abs(np.asarray(y) - np.asarray(lg)).max() > 1e-6


@pytest.mark.parametrize("pad", [0, 8])
def test_the_batched_form_gives_what_one_pass_at_a_time_gives(model, pad):
    """``denoise_logits_many``: one pass of the committed sequence, whose
    keys every block state reads up to its own block — also with the
    sequence padded to a compiled shape and a committed version of the
    pass's own block behind it."""
    params, spec = model
    seq = ids(24, seed=2)
    passes = [(0, [MASK] * B), (8, [seq[8], MASK, MASK, seq[11]]),
              (20, [MASK, 5, MASK, MASK]), (8, [MASK] * B)]
    many = ref.denoise_logits_many(params, seq + [0] * pad, passes, spec,
                                   n_committed=len(seq))
    for i, (at, x) in enumerate(passes):
        one = ref.denoise_logits(params, seq[:at], x, spec)
        np.testing.assert_allclose(np.asarray(many[i * B:(i + 1) * B]),
                                   np.asarray(one), atol=2e-5)


def test_compile_ahead_keeps_the_calls_the_check_makes(model):
    params, spec = model
    ref._AHEAD.clear()
    kept = ref.compile_ahead(params, spec, pad_to=32, passes=3)
    assert kept == len(ref._AHEAD) >= 5
    before = dict(ref._AHEAD)
    seq = ids(20)
    ref.served_passes(params, seq, [(8, tuple([MASK] * B), (0,), (3,),
                                     (0.1,) * B)], spec, 32, 3)
    assert ref._AHEAD == before          # nothing new: the shapes were met
    ref._AHEAD.clear()


@pytest.mark.parametrize("steps,counts", [(1, [4]), (2, [2, 2]),
                                          (3, [2, 1, 1]), (4, [1, 1, 1, 1])])
def test_the_schedule_is_the_familys(steps, counts):
    assert ref.schedule(B, steps) == counts and sum(counts) == B


@pytest.mark.parametrize("rule,k,tau,want", [
    ("low_confidence_static", 2, 0.9, [1, 3]),
    ("low_confidence_static", 9, 0.9, [0, 1, 3]),        # the masks left
    ("low_confidence_dynamic", 1, 0.45, [1, 3]),         # 0.5 crosses tau
    ("low_confidence_dynamic", 1, 0.9, [1]),
    ("low_confidence_dynamic", 1, 0.05, [0, 1, 3]),
    ("sequential", 2, 0.9, [0, 1]),
    ("sequential", 1, 0.0, [0]),
])
def test_the_unmask_rules(rule, k, tau, want):
    masked, conf = [0, 1, 3], [0.1, 0.6, 0.5]
    assert ref.unmask_set(masked, conf, k, rule, tau) == want


def test_ties_go_to_the_lower_position():
    assert ref.unmask_set([0, 1, 2, 3], [0.3, 0.5, 0.5, 0.3], 1,
                          "low_confidence_static", 2.0) == [1]
    assert ref.unmask_set([0, 1, 2, 3], [0.3] * 4, 2,
                          "low_confidence_static", 2.0) == [0, 1]
    with pytest.raises(ValueError, match="unknown rule"):
        ref.unmask_set([0], [0.1], 1, "random", 0.9)


@pytest.mark.parametrize("length", [8, 9, 10, 11, 3])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_generate_walks_whole_blocks(model, length, steps):
    """A block of ``m`` masks takes the schedule's passes for ``m`` under
    the static rule; the first block opens with the prompt's ``L mod B``
    trailing tokens; every logged pass unmasks what the rule gives."""
    params, spec = model
    prompt, new = ids(length, seed=length), 10
    out, log = ref.generate(params, prompt, new, spec, steps=steps,
                            rule="low_confidence_static")
    assert len(out) == new
    counts = ref.schedule(B, steps)
    whole = length // B * B
    commits = [e for e in log if MASK not in e[1]]
    assert [e[0] for e in commits] == list(range(whole, whole + B * len(
        commits), B))
    seq = prompt[:whole] + [t for e in commits for t in e[1]]
    assert seq[:length] == prompt and seq[length:length + new] == out
    first = log[0]
    assert first[0] == whole and list(first[1][:length - whole]) == \
        prompt[whole:] and first[1].count(MASK) == B - (length - whole)
    at, t = None, 0
    for e in log:
        if MASK not in e[1]:
            continue
        t = t + 1 if e[0] == at else 0
        at = e[0]
        assert len(e[2]) == min(counts[t], e[1].count(MASK))
        assert len(e[4]) == e[1].count(MASK)


def test_generate_ends_behind_an_end_of_sequence_token(model):
    params, spec = model
    prompt = ids(9, seed=3)
    out, _ = ref.generate(params, prompt, 12, spec, steps=2,
                          rule="low_confidence_static")
    eos = out[5]                    # inside the second generated block
    cut, log = ref.generate(params, prompt, 12, spec, steps=2,
                            rule="low_confidence_static", eos=eos)
    assert cut == out[:out.index(eos) + 1]
    assert sum(MASK not in e[1] for e in log) < 4


def test_float8_is_another_reading(model):
    params, spec = model
    seq = ids(12)
    a = ref.forward(params, seq, spec)
    b = ref.forward(params, seq, spec, lowp="float8")
    c = ref.forward(params, seq, spec, lowp="bfloat16")
    assert np.abs(np.asarray(a - b)).max() > 10 * np.abs(
        np.asarray(a - c)).max() > 0


def test_the_file_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference_sdar.py")) as f:
        src = f.read()
    assert "import hetu_tpu" not in src and "from hetu_tpu" not in src
    assert jax.default_backend() == "cpu"
