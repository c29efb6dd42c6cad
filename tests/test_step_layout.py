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
