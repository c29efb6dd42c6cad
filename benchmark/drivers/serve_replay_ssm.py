"""Driver ``serve_replay_ssm``: ``serve_replay`` for a Mamba-1 / attention
configuration with dense feed-forwards (published ``jamba`` keys), held
WHOLE on one chip.  The replay, the measured loop, the timestamps and the
latency statistics are ``serve_replay``'s and ``serve_common``'s; what
differs is how the model is made (``hetu_tpu.models.hybrid.jamba_config``:
one translation from the published keys, the weights drawn on the device
from ``--seed``), how the engine is sized (K/V pages for ``max_batch``
sequences of ``max_model_len``; the recurrent state has one slot a sequence
by construction) and which plain reference decides ``correct``
(``reference_jamba``: float32, the recurrence token by token).  Traffic
parameter ``lowp_reading`` (``--set lowp_reading=true``) also logs the
reference's own float8 reading of the limits, for PERF.md."""
from __future__ import annotations

import gc
import time

import jax
import reference_jamba as reference
import traffic as traffic_lib
from serve_common import latency_stats, measure, serve_facts, warm_up


def build(ctx):
    # a program without this model fails here, before anything is made
    from hetu_tpu.models.hybrid import init_state, jamba_config
    from hetu_tpu.serving import Engine
    c, s, a = ctx.config, ctx.config["serve"], ctx.config["assumed"]
    cfg = jamba_config(c, init_std=float(a["initializer_range"]))
    t = time.monotonic()
    state = init_state(cfg, ctx.seed, time_step=(
        a["time_step_min"], a["time_step_max"], a["time_step_floor"]))
    next(iter(state.values())).block_until_ready()
    num_pages = int(s.get("num_pages") or
                    s["max_batch"] * -(-s["max_model_len"] // s["page_size"])
                    + 1)
    eng = Engine(state, cfg, num_pages=num_pages, page_size=s["page_size"],
                 max_batch=s["max_batch"], max_model_len=s["max_model_len"],
                 chunk_size=s["chunk_size"], prefill_rows=s["prefill_rows"],
                 prefix_cache=bool(s["prefix_cache"]), name="bench",
                 use_kernel=True if ctx.rehearse else None)
    if not eng.use_kernel:
        raise RuntimeError("the engine did not pick the ragged kernel")
    st = eng.state_store
    ctx.log(f"weights + engine in {time.monotonic() - t:.1f} s; weights "
            f"{sum(v.nbytes for v in state.values()) / 1e9:.2f} GB; K/V pool "
            f"{num_pages} pages = {num_pages * eng.pool.page_bytes / 1e9:.2f}"
            f" GB; state store {st.num_slots} slots = "
            f"{st.num_slots * st.slot_bytes / 1e9:.2f} GB")
    return state, cfg, eng


def check_tokens(ctx, state, requests, m: dict, picks) -> dict:
    """The served tokens of the picked requests, teacher-forced through
    the plain reference: at most ``reference.GAP_SHARE_TOL`` of them more
    than ``reference.LOGIT_GAP_TOL`` logits below the reference's best
    token (why a share: the reference's own header).  After the window."""
    spec = reference.spec_from_config(ctx.config)
    pad_to = int(ctx.traffic["max_total"])
    max_new = max(r.max_new_tokens for r in requests)
    gaps, low = [], []
    t = time.monotonic()
    for i in picks:
        out = list(m["handles"][i].out_tokens)
        if not out:
            continue
        seq = requests[i].prompt + out
        gaps += reference.greedy_logit_gaps(
            state, seq, len(requests[i].prompt), spec, pad_to, max_new)
        if ctx.traffic.get("lowp_reading"):
            low += reference.lowp_choice_gaps(
                state, seq, len(requests[i].prompt), spec, pad_to, max_new)
    tol = reference.LOGIT_GAP_TOL
    share = lambda g: sum(v > tol for v in g) / len(g)      # noqa: E731
    beyond = share(gaps) if gaps else 1.0
    lowp = share(low) if low else None
    ctx.log(f"reference check: {len(gaps)} tokens of {len(picks)} requests, "
            f"{100 * beyond:.2f} % beyond {tol} logits (rule <= "
            f"{100 * reference.GAP_SHARE_TOL:.0f} %), worst gap "
            f"{max(gaps, default=0.0):.4f}, mean "
            f"{sum(gaps) / max(len(gaps), 1):.4f}, in "
            f"{time.monotonic() - t:.1f} s" +
            (f"; float8 reading {100 * lowp:.2f} % beyond, worst "
             f"{max(low):.4f}" if low else ""))
    return {"checked_tokens": len(gaps), "beyond_share": beyond,
            "worst_logit_gap": max(gaps, default=0.0),
            "lowp_beyond_share": lowp,
            "ok": bool(gaps) and beyond <= reference.GAP_SHARE_TOL}


def run(ctx) -> dict:
    mix = ctx.traffic
    state, cfg, eng = build(ctx)
    requests, _ = traffic_lib.serve_requests(mix, ctx.seed, ctx.seconds,
                                             cfg.vocab_size)
    # warm-up compiles the one executable on FIXED prompts (the same ids in
    # every run: set-up does not follow the seed): two chunks, and a short
    chunk = ctx.config["serve"]["chunk_size"]
    warm_up(ctx, eng, [[(7 * j + 3) % cfg.vocab_size for j in range(n)]
                       for n in (chunk + chunk // 2, 8)])
    # the replay's prompts are ~3 M ids as Python lists, twice over (the
    # generator's, and the engine's copy at queueing): the start-up heap is
    # frozen and the window holds no full collection, as a server that
    # takes its requests off the wire has none (serve_replay_mla)
    gc.collect()
    gc.freeze()
    young, middle, old = gc.get_threshold()
    gc.set_threshold(young, middle, 1 << 30)
    m = measure(ctx, eng, requests, stop_when_idle=True)
    gc.set_threshold(young, middle, old)
    lat = latency_stats(requests, m, due_share=1.0)
    done = [i for i, h in enumerate(m["handles"]) if h.done]
    # the two first done (they met a filling batch) and the two last
    picks = (done[:2] + done[-2:]) if len(done) >= 4 else done
    queue_left = len(eng.queue)
    in_use = eng.state_store.in_use
    # the pool, the store AND the weights leave the device before the
    # reference comes (the engine itself stays registered with the analysis
    # plane): the reference's 33k-token float32 activations take their
    # room — beside 6 GB of weights they would not fit under the serving's
    # own peak — and it reads the weights from the host, a sublayer at a
    # time.  The peak stays the serving's
    eng.pool.set_pages((), ())
    eng.state_store.set_arrays((), ())
    eng.params = None
    del eng
    weights = jax.device_get(state)
    for v in state.values():
        v.delete()
    del state
    gc.collect()
    chk = check_tokens(ctx, weights, requests, m, picks)
    drained = queue_left == 0              # the replay was too short
    counters = m["counters"]
    notes = {**lat, "steps": m["steps"], "elapsed_s": m["elapsed"],
             "requests": len(requests), "queue_left": queue_left,
             "done": len(done), "state_slots_in_use": in_use,
             "compiled_in_window": m["compiled_in_window"], **chk,
             **{k: counters.get(k) for k in (
                 "prefill_tokens", "preemptions", "ssm_slots_walked",
                 "ssm_chunk_tokens_walked", "ssm_chunk_tokens_padded")}}
    return {
        "correct": chk["ok"] and m["compiled_in_window"] == 0
        and not drained,
        "attempted": lat["first_tokens"], "failed": 0,
        "end_to_end": {"serve_tokens_per_s": lat["out_tokens"] / m["elapsed"]},
        "notes": notes,
        "facts": serve_facts(m, lat),
    }
