"""chip_smoke.py — does hetu-tpu still start on the chip?

Drives the two steps every benchmark cell will time, once, through the
entry points a user calls, at GPT-2 124M's published widths, in ONE
process (a second process could not have the chip):

  train   examples/train_gpt.py's own ``main`` — define-and-run graph,
          GPTLMHeadModel, AdamOptimizer.minimize, the native Dataloader,
          ``g.run`` — six bf16 steps at batch 32 x 1024 with the fused
          LM-head loss; the compiled step must hold the Mosaic calls of
          flash forward and backward.
  parity  flash (fwd + grads), ragged paged and latent ragged attention
          against their float32 references on the chip.
  serve   serving.Engine on seeded random weights: 64-token pages over a
          stated share of HBM, eight requests of 32-900 prompt tokens,
          two sharing a 512-token prefix; one executable, holding the
          ragged paged kernel; greedy tokens checked against
          models.generate()'s own forward.

It measures nothing.  Seconds it prints are smoke observations, not a
benchmark.  Without a TPU it exits non-zero and prints no result; no
phase is wrapped in ``try``, so any failure is a non-zero exit.  The last
line of a passing run is one JSON object, ``{"ok": true, "device":
{"platform", "kind", "count"}}`` and nothing more; the line before it is
the summary, which ends with ``"claim": null``.

Run it twice in one call to the chip tool to see the compile cache work:
the cache sits where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "examples"))

# max |kernel - reference| / max(1, max |reference|), reference in float32
# at "highest" matmul precision.  bf16 carries 8 mantissa bits (2^-8 =
# 3.9e-3 per rounding); a kernel rounds its inputs, its probabilities and
# its output, and the backward compounds two such passes.  A wrong mask,
# page or head is an error of order 1.
TOL_BF16_FWD = 2e-2
TOL_BF16_GRAD = 4e-2
# the engine's greedy token must score within this many logit units of
# the best token under models.generate()'s own forward, teacher-forced on
# the engine's output, in float32 logits.  Random-weight logits spread
# ~0.5 across the vocabulary and the winner leads the runner-up by ~0.1
# on average, so paths that differ in bf16 rounding may swap near-ties
# (which this allows) but never pick an ordinary token (~2 below the top).
LOGIT_TOL = 5e-2
# share of the chip's HBM (memory_stats()["bytes_limit"]) the KV pages take
# as stored.  At head_dim 64 the serving step needs twice that again as
# scratch: XLA stores a [P, 12, 64, 64] array with the page axis in the
# lanes and re-lays every page array out row-major (lanes padded 64 ->
# 128) for the Mosaic call, all 24 copies live at once (CHANGES.md, PR 21).
KV_HBM_SHARE = 0.25


@dataclasses.dataclass(frozen=True)
class Sizes:
    """GPT-2 124M at its published widths (``FULL``); tier-1 drives the
    same phases at toy widths on the CPU with the kernels interpreted."""
    vocab: int = 50304
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    seq: int = 1024
    batch: int = 32
    steps: int = 6
    page: int = 64
    max_batch: int = 16
    chunk: int = 256
    new_tokens: int = 32
    prompt_lens: tuple = (32, 96, 200, 333, 552, 600, 777, 900)
    shared_prefix: int = 512      # prompts 4 and 5 start with the same 512
    latent: tuple = (16, 512, 64)  # heads, d_c, rope

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


FULL = Sizes()


def _device_line(phase: str, what: str, compile_s: float, run_s: float,
                 **more) -> None:
    import jax
    d = jax.devices()[0]
    rec = {"phase": phase, "platform": d.platform,
           "device_kind": d.device_kind, "devices": len(jax.devices()),
           "ran": what, "compile_s": round(compile_s, 2),
           "run_s": round(run_s, 3), **more}
    print("chip_smoke " + json.dumps(rec), flush=True)


def _kernel_calls(hlo_text: str, name: str) -> int:
    """Mosaic custom calls in a compiled executable's HLO text whose
    instruction name carries the Pallas kernel's ``name``."""
    return sum(1 for line in hlo_text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and name in line.split(" = ")[0])


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all()
    return float(np.abs(got - ref).max() / max(1.0, np.abs(ref).max()))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def phase_train(sz: Sizes, on_chip: bool) -> None:
    import train_gpt
    from hetu_tpu.csrc.build import load_dataloader_core
    # asked for, not hoped for: a g++ failure raises here with its stderr
    load_dataloader_core(required=True)
    run = train_gpt.main([
        "--vocab-size", str(sz.vocab), "--hidden", str(sz.hidden),
        "--layers", str(sz.layers), "--heads", str(sz.heads),
        "--seq-len", str(sz.seq), "--global-batch", str(sz.batch),
        "--steps", str(sz.steps), "--log-every", "1", "--bf16"])
    cfg = run.model.config
    assert (cfg.dtype, cfg.position, cfg.activation, cfg.norm,
            cfg.fused_lm_ce) == ("bfloat16", "learned", "gelu",
                                 "layernorm", True), cfg
    assert run.loader._lib is not None, "python loader ran, not the native"
    losses = run.losses
    assert len(losses) == sz.steps and np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    calls = {}
    if on_chip:
        hlo = run.graph.analysis_handles()[-1].compiled_text()
        calls = {k: _kernel_calls(hlo, k) for k in ("flash_fwd",
                                                    "flash_bwd")}
        assert min(calls.values()) >= sz.layers, \
            f"train step lacks flash Mosaic calls: {calls}"
    steady = float(np.median(run.step_seconds[1:]))
    _device_line("train", f"examples/train_gpt.py {sz.layers}L "
                 f"h{sz.hidden} b{sz.batch}x{sz.seq} bf16 fused-CE, "
                 f"{sz.steps} steps", run.step_seconds[0] - steady, steady,
                 losses=[round(x, 4) for x in losses], mosaic_calls=calls,
                 loader="native")


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def _timed(fn, *args):
    """(result, first-call seconds, second-call seconds) of a jitted fn."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _ragged_batch(rng, sz: Sizes, num_pages: int):
    """``max_batch`` decode rows (q_len 1) + one ``chunk``-token prefill
    row, ragged context lengths, non-contiguous pages; padding slots of
    the page table point at the trash page 0."""
    s = sz.max_batch + 1
    maxp = sz.seq // sz.page
    q_lens = np.array([1] * sz.max_batch + [sz.chunk], np.int32)
    ctx = rng.randint(1, sz.seq, size=s).astype(np.int32)
    ctx[-1] = rng.randint(sz.chunk, sz.seq)        # chunk ends mid-prompt
    ctx[0] = sz.seq                                # a full-length history
    cu = np.zeros(s + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    need = -(-ctx // sz.page)
    assert need.sum() < num_pages
    perm = rng.permutation(np.arange(1, num_pages))
    pt = np.zeros((s, maxp), np.int32)
    k = 0
    for i in range(s):
        pt[i, :need[i]] = perm[k:k + need[i]]
        k += need[i]
    mask = np.ones(int(cu[-1]), bool)              # every token is real
    return q_lens, cu, pt, ctx, mask


def phase_parity(sz: Sizes, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops.attention import sdpa_reference
    from hetu_tpu.ops.pallas.flash_attention import flash_attention
    from hetu_tpu.ops.quantization import quantize_rows
    from hetu_tpu.ops.ragged_paged_attention import (
        latent_ragged_paged_attention_pallas,
        latent_ragged_paged_attention_reference,
        ragged_paged_attention_pallas, ragged_paged_attention_reference)

    rng = np.random.RandomState(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    errs, compile_s, run_s = {}, 0.0, 0.0

    def ref32(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return jax.jit(run)

    # -- flash, forward and grads, causal, at the model's head geometry
    shape = (2, sz.seq, sz.heads, sz.head_dim)
    q, k, v, w = (jnp.asarray(rng.randn(*shape), bf16) for _ in range(4))

    def fwd_and_grads(attn):
        def f(q, k, v):
            def loss(q, k, v):
                o = attn(q, k, v)
                return jnp.sum(o.astype(f32) * w.astype(f32)), o
            (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
            return (o, *g)
        return f

    got, c, r = _timed(jax.jit(fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True))), q, k, v)
    compile_s, run_s = compile_s + c - r, run_s + r
    want = ref32(fwd_and_grads(
        lambda q, k, v: sdpa_reference(q, k, v, causal=True)))(
            *(x.astype(f32) for x in (q, k, v)))
    for name, a, b in zip(("flash_out", "flash_dq", "flash_dk", "flash_dv"),
                          got, want):
        errs[name] = _rel_err(a, b)
        assert errs[name] < (TOL_BF16_FWD if name == "flash_out"
                             else TOL_BF16_GRAD), (name, errs[name])

    # -- ragged paged: decode rows + one chunk, every KV head of the model
    num_pages = 2 * (sz.max_batch + 1) * (sz.seq // sz.page)
    q_lens, cu, pt, ctx, mask = _ragged_batch(rng, sz, num_pages)
    desc = tuple(jnp.asarray(a) for a in (q_lens, cu, pt, ctx))
    t = int(cu[-1])
    pshape = (num_pages, sz.heads, sz.page, sz.head_dim)
    q = jnp.asarray(rng.randn(t, sz.heads, sz.head_dim), bf16)
    kp, vp = (jnp.asarray(rng.randn(*pshape), bf16) for _ in range(2))
    got, c, r = _timed(jax.jit(lambda q, kp, vp: ragged_paged_attention_pallas(
        q, kp, vp, *desc, max_q=sz.chunk)), q, kp, vp)
    compile_s, run_s = compile_s + c - r, run_s + r
    want = ref32(lambda q, kp, vp: ragged_paged_attention_reference(
        q, kp, vp, *desc, max_q=sz.chunk))(
            *(x.astype(f32) for x in (q, kp, vp)))
    errs["ragged"] = _rel_err(np.asarray(got, np.float32)[mask],
                              np.asarray(want)[mask])
    assert errs["ragged"] < TOL_BF16_FWD, errs

    # -- latent ragged (MLA): bf16 latent + rope pages, then int8 / nf4
    nh, d_c, d_r = sz.latent
    scale = (sz.head_dim + d_r) ** -0.5
    lat = rng.randn(num_pages, 1, sz.page, d_c).astype(np.float32)
    variants = [("latent", jnp.asarray(lat, bf16), None, None, d_r)]
    for quant in ("int8", "nf4"):
        codes, absmax = quantize_rows(jnp.asarray(lat), quant)
        variants.append((f"latent_{quant}", codes, absmax, quant, 0))
    for name, cp, sp, quant, dr in variants:
        q = jnp.asarray(rng.randn(t, nh, d_c + dr), f32)
        rp = jnp.asarray(rng.randn(num_pages, 1, sz.page, dr), bf16) \
            if dr else None
        kw = dict(max_q=sz.chunk, softmax_scale=scale, scale_pages=sp,
                  quant=quant, latent_dim=d_c)
        got, c, r = _timed(jax.jit(
            lambda q, cp=cp, rp=rp, kw=kw:
            latent_ragged_paged_attention_pallas(q, cp, rp, *desc, **kw)), q)
        compile_s, run_s = compile_s + c - r, run_s + r
        want = ref32(
            lambda q, cp=cp, rp=rp, kw=kw:
            latent_ragged_paged_attention_reference(q, cp, rp, *desc,
                                                    **kw))(q)
        errs[name] = _rel_err(np.asarray(got)[mask], np.asarray(want)[mask])
        assert errs[name] < TOL_BF16_FWD, errs

    _device_line("parity", f"flash fwd+grads {shape}; ragged paged "
                 f"{sz.max_batch}x1 + 1x{sz.chunk} rows, {sz.heads} KV "
                 f"heads x {sz.head_dim}, {sz.page}-token pages; latent "
                 f"ragged {nh} heads d_c {d_c} + rope {d_r} (bf16, int8, "
                 f"nf4 pages)", compile_s, run_s,
                 rel_err={k: float(f"{e:.2e}") for k, e in errs.items()},
                 tol={"bf16_fwd": TOL_BF16_FWD, "bf16_grad": TOL_BF16_GRAD})


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _teacher_forced_gaps(state, cfg, seqs, prompt_lens, n_new):
    """For each sequence (prompt + the engine's tokens), how far each
    engine token's float32 logit lies below the best token's under
    ``models.generate``'s forward (``decode_step`` over dense caches, no
    paging, no Pallas kernel) fed the same tokens: ``[requests, n_new]``.
    Sequences are right-padded to one length — causal attention keeps
    padding out of every position that is read."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.generate import _Params, decode_step
    width = max(len(s) for s in seqs) - 1
    ids = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s) - 1] = s[:-1]
    pos = np.asarray([[pl - 1 + j for j in range(n_new)]
                      for pl in prompt_lens], np.int32)
    picked = np.asarray([s[pl:pl + n_new] for s, pl in
                         zip(seqs, prompt_lens)], np.int32)
    cdt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    @jax.jit
    def gaps(params, ids, pos, picked):
        p = _Params.__new__(_Params)
        p.s, p.cfg = params, cfg
        shape = (ids.shape[0], width, cfg.kv_heads, cfg.head_dim)
        caches = [(jnp.zeros(shape, cdt), jnp.zeros(shape, cdt))
                  for _ in range(cfg.num_layers)]
        _, _, hidden = decode_step(cfg, p, ids, caches, 0, None, None,
                                   return_hidden=True)
        h = jnp.take_along_axis(hidden, pos[:, :, None], axis=1)
        head = p("lm_head.weight")
        head = head if head is not None else p("wte.weight")
        with jax.default_matmul_precision("highest"):
            logits = h.astype(jnp.float32) @ head.T.astype(jnp.float32)
        best = logits.max(-1)
        mine = jnp.take_along_axis(logits, picked[:, :, None], -1)[..., 0]
        return best - mine

    return np.asarray(gaps(_Params(state, cfg).s, ids, pos, picked))


def phase_serve(sz: Sizes, on_chip: bool) -> None:
    import jax
    import hetu_tpu as ht
    from hetu_tpu import models
    from hetu_tpu.graph.graph import get_executable
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.serving import Engine

    cfg = GPTConfig(vocab_size=sz.vocab, hidden_size=sz.hidden,
                    num_layers=sz.layers, num_heads=sz.heads,
                    max_seq_len=sz.seq, sp=False, dtype="bfloat16",
                    position="learned", activation="gelu",
                    norm="layernorm")
    ht.set_seed(0)
    with ht.graph("eager", create_new=True):
        state = {k: np.asarray(v) for k, v in
                 GPTLMHeadModel(cfg).state_dict().items()}

    dev = jax.devices()[0]
    kv_page_bytes = 2 * sz.layers * sz.heads * sz.page * sz.head_dim * 2
    if on_chip:
        limit = dev.memory_stats()["bytes_limit"]
        num_pages = int(KV_HBM_SHARE * limit / kv_page_bytes)
    else:
        limit, num_pages = 0, 4 * len(sz.prompt_lens) * sz.seq // sz.page
    before = dev.memory_stats()["bytes_in_use"] if on_chip else 0
    # use_kernel: Engine() picks the kernel from the platform; off the
    # chip the tier-1 run asks for it so the same code runs interpreted
    eng = Engine(state, cfg, num_pages=num_pages, page_size=sz.page,
                 max_batch=sz.max_batch, chunk_size=sz.chunk,
                 prefix_cache=True, name="chip_smoke",
                 use_kernel=None if on_chip else True)
    assert eng.use_kernel
    pool_bytes = (dev.memory_stats()["bytes_in_use"] - before) \
        if on_chip else 0

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, sz.vocab, n).tolist() for n in sz.prompt_lens]
    a, b = [i for i, n in enumerate(sz.prompt_lens)
            if n > sz.shared_prefix][:2]
    prompts[b][:sz.shared_prefix] = prompts[a][:sz.shared_prefix]
    # the sharer arrives once the rest has finished: only a FINISHED
    # request's pages enter the prefix cache
    reqs = {i: eng.add_request(p, sz.new_tokens)
            for i, p in enumerate(prompts) if i != b}
    t0 = time.perf_counter()
    eng.step()                                    # compiles THE executable
    first = time.perf_counter() - t0
    eng.run()
    reqs[b] = eng.add_request(prompts[b], sz.new_tokens)
    out = eng.run()
    wall = time.perf_counter() - t0
    steps = eng.executable_calls
    outs = [out[reqs[i].req_id] for i in range(len(prompts))]
    assert all(len(o) == sz.new_tokens for o in outs), \
        [len(o) for o in outs]
    m = eng.metrics_summary()
    assert m["compile_count"] == 1, m["compile_count"]
    assert m["prefix_cache_hits"] >= 1 and \
        m["prefix_cache_tokens_saved"] >= sz.shared_prefix, m
    assert m["host_logit_fetches"] == 0
    calls = 0
    if on_chip:
        calls = _kernel_calls(
            get_executable("chip_smoke/unified").compiled_text(),
            "ragged_paged_attention")
        assert calls >= sz.layers, f"serving step lacks the kernel: {calls}"

    seqs = [p + o for p, o in zip(prompts, outs)]
    gaps = _teacher_forced_gaps(state, cfg, seqs, sz.prompt_lens,
                                sz.new_tokens)
    assert gaps.shape == (len(prompts), sz.new_tokens)
    assert float(gaps.max()) <= LOGIT_TOL, \
        f"engine token below generate()'s best by {gaps.max():.4f} logits"
    # models.generate() itself, free-running, on the shortest prompt (one
    # compile per prompt length): a mismatch is allowed only at a near-tie,
    # which the teacher-forced check above has already bounded
    want = np.asarray(models.generate(
        state, cfg, np.asarray([prompts[0]], np.int32), sz.new_tokens,
        temperature=0.0))[0, len(prompts[0]):].tolist()
    agree = next((j for j, (x, y) in enumerate(zip(outs[0], want))
                  if x != y), sz.new_tokens)
    steady = (wall - first) / max(steps - 1, 1)
    _device_line("serve", f"serving.Engine {sz.layers}L h{sz.hidden} bf16, "
                 f"{num_pages} x {sz.page}-token pages, max_batch "
                 f"{sz.max_batch}, chunk {sz.chunk}, prefix cache on; "
                 f"{len(prompts)} requests x {sz.new_tokens} new tokens",
                 first - steady, wall - first, steps=steps,
                 kv_pages_nominal_gb=round(num_pages * kv_page_bytes / 1e9,
                                           2),
                 kv_pages_hbm_gb=round(pool_bytes / 1e9, 2),
                 hbm_limit_gb=round(limit / 1e9, 2),
                 kv_hbm_share=round(pool_bytes / limit, 3) if limit else None,
                 compile_count=m["compile_count"], mosaic_calls=calls,
                 prefix_tokens_saved=int(m["prefix_cache_tokens_saved"]),
                 max_logit_gap=round(float(gaps.max()), 4),
                 logit_tol=LOGIT_TOL,
                 generate_agrees_for=f"{agree}/{sz.new_tokens} tokens")
    eng.unregister_analysis()


# ---------------------------------------------------------------------------

def result_line(devices) -> str:
    """The last line of a passing run: one JSON object with exactly the
    keys ``ok`` and ``device`` (``platform``, ``kind``, ``count``), the
    device as JAX reports it.  The driver refuses any other shape."""
    d = devices[0]
    return json.dumps({"ok": True,
                       "device": {"platform": str(d.platform),
                                  "kind": str(d.device_kind),
                                  "count": len(devices)}})


def main() -> None:
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{d.platform!r} ({d.device_kind!r}, {len(jax.devices())} "
                 f"device(s)). Nothing was run.")
    from hetu_tpu.graph.graph import clear_executables
    from hetu_tpu.planner.profile_hardware import chip_for
    from hetu_tpu.utils.compile_cache import (cache_entries,
                                              enable_compile_cache)
    chip = chip_for(d.platform, d.device_kind)   # unknown kind: an error
    cache = enable_compile_cache()
    print(f"chip_smoke: {d.device_kind} ({chip.name}), "
          f"{len(jax.devices())} device(s); compile cache {cache} holds "
          f"{cache_entries(cache)} entries", flush=True)
    phase_train(FULL, on_chip=True)
    clear_executables()      # the graph's plans pin 1.6 GB of train state
    gc.collect()
    phase_parity(FULL, on_chip=True)
    phase_serve(FULL, on_chip=True)
    print(f"chip_smoke: compile cache {cache} holds "
          f"{cache_entries(cache)} entries", flush=True)
    # the summary says what was established and that nothing is claimed;
    # the LAST line is the driver's contract and holds exactly these keys
    print("chip_smoke " + json.dumps({
        "phase": "summary", "phases_passed": ["train", "parity", "serve"],
        "claim": None}), flush=True)
    print(result_line(jax.devices()), flush=True)


if __name__ == "__main__":
    main()
