"""Driver ``serve_open_loop_gdn``: ``serve_open_loop`` for a gated-delta /
attention configuration with dense feed-forwards (published ``olmo_hybrid``
keys), one of two pipeline stages on one chip.  The measured loop, the
timestamps and the latency statistics are ``serve_common``'s; what differs
is how the model is made (``hetu_tpu.models.hybrid.olmo_hybrid_config``: one
translation from the published keys, the weights drawn on the device from
``--seed``), how the engine is sized (``serve.num_pages`` K/V pages for the
stage's full-attention layers; the recurrent state has one slot a sequence
by construction) and which plain reference decides ``correct``
(``reference_olmo_hybrid``: float32, the recurrence token by token).

* the reference's calls are compiled on a thread BESIDE the warm-up
  (``reference.compile_ahead``: shapes alone, no device work) and the
  thread is joined before the requests are queued; if it fails the check
  compiles them itself, later.
* after the window the K/V pool and the state store leave the device
  before the reference comes: its float32 weights (a sublayer's at a time,
  each call waited for) and activations take their room, and the peak
  stays the serving's.

Traffic parameter ``lowp_reading`` (``--set lowp_reading=true``) also logs
the reference's own float8 reading of the limits, for PERF.md."""
from __future__ import annotations

import gc
import threading
import time

import reference_olmo_hybrid as reference
import traffic as traffic_lib
from drivers.serve_open_loop import _queue_wait_p90
from serve_common import latency_stats, measure, serve_facts, warm_up


def build(ctx):
    # a program without this model fails here, before anything is made
    from hetu_tpu.models.hybrid import init_state, olmo_hybrid_config
    from hetu_tpu.serving import Engine
    c, s, a = ctx.config, ctx.config["serve"], ctx.config["assumed"]
    cfg = olmo_hybrid_config(c, init_std=float(a["initializer_range"]))
    t = time.monotonic()
    state = init_state(cfg, ctx.seed, time_step=(
        a["time_step_min"], a["time_step_max"], a["time_step_floor"]))
    next(iter(state.values())).block_until_ready()
    eng = Engine(state, cfg, num_pages=int(s["num_pages"]),
                 page_size=s["page_size"], max_batch=s["max_batch"],
                 max_model_len=s["max_model_len"],
                 chunk_size=s["chunk_size"], prefill_rows=s["prefill_rows"],
                 prefix_cache=bool(s["prefix_cache"]), name="bench",
                 use_kernel=True if ctx.rehearse else None)
    if not eng.use_kernel:
        raise RuntimeError("the engine did not pick the ragged kernel")
    st = eng.state_store
    ctx.log(f"weights + engine in {time.monotonic() - t:.1f} s; weights "
            f"{sum(v.nbytes for v in state.values()) / 1e9:.2f} GB; K/V pool "
            f"{s['num_pages']} pages = "
            f"{s['num_pages'] * eng.pool.page_bytes / 1e9:.2f} GB; state "
            f"store {st.num_slots} slots = "
            f"{st.num_slots * st.slot_bytes / 1e9:.2f} GB")
    return state, cfg, eng


def compile_reference(ctx, state, sizes: dict):
    t = time.monotonic()
    try:
        kept = reference.compile_ahead(
            state, reference.spec_from_config(ctx.config), **sizes)
        if ctx.traffic.get("lowp_reading"):
            kept = reference.compile_ahead(
                state, reference.spec_from_config(ctx.config), lowp=True,
                **sizes)
        ctx.log(f"reference: {kept} calls compiled beside the warm-up in "
                f"{time.monotonic() - t:.1f} s")
    except Exception as e:       # the check then compiles what it calls
        ctx.log(f"reference: compiling ahead failed ({e!r})")


def check_tokens(ctx, state, requests, m: dict, picks, sizes: dict) -> dict:
    """The served tokens of the picked requests, teacher-forced through
    the plain reference: at most ``reference.GAP_SHARE_TOL`` of them more
    than ``reference.LOGIT_GAP_TOL`` logits below the reference's best
    token (why a share: the reference's own header).  After the window."""
    spec = reference.spec_from_config(ctx.config)
    gaps, low = [], []
    t = time.monotonic()
    for i in picks:
        out = list(m["handles"][i].out_tokens)
        if not out:
            continue
        seq, n = requests[i].prompt + out, len(requests[i].prompt)
        gaps += reference.greedy_logit_gaps(state, seq, n, spec, **sizes)
        if ctx.traffic.get("lowp_reading"):
            low += reference.lowp_choice_gaps(state, seq, n, spec, **sizes)
    tol = reference.LOGIT_GAP_TOL
    share = lambda g: sum(v > tol for v in g) / len(g)      # noqa: E731
    beyond = share(gaps) if gaps else 1.0
    lowp = share(low) if low else None
    ctx.log(f"reference check: {len(gaps)} tokens of {len(picks)} requests, "
            f"{100 * beyond:.2f} % beyond {tol} logits (rule <= "
            f"{100 * reference.GAP_SHARE_TOL:g} %), worst gap "
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
    checked = mix["checked"]
    sizes = {"pad_to": int(checked["pad_to"]),
             "max_new": int(checked["max_new"])}
    ahead = threading.Thread(
        target=compile_reference, args=(ctx, state, sizes), daemon=True)
    ahead.start()
    # warm-up compiles the one executable on FIXED prompts (the same ids in
    # every run: set-up does not follow the seed): two chunks, and a short
    chunk = ctx.config["serve"]["chunk_size"]
    warm_up(ctx, eng, [[(7 * j + 3) % cfg.vocab_size for j in range(n)]
                       for n in (chunk + chunk // 2, 8)])
    t = time.monotonic()
    ahead.join()
    ctx.log(f"reference: waited {time.monotonic() - t:.1f} s more for its "
            f"compiles")
    m = measure(ctx, eng, requests)
    lat = latency_stats(requests, m)
    done = [i for i, h in enumerate(m["handles"]) if h.done]
    picks = [done[(k * len(done)) // 4] for k in range(4)] if done else []
    counters = m["counters"]
    running_at_end = len(eng.running)
    pages_in_use = eng.pool.num_usable - eng.pool.free_pages
    slots_in_use = eng.state_store.in_use
    facts = serve_facts(m, lat,
                        queue_wait_p90_ms=_queue_wait_p90(requests, m))
    eng.pool.set_pages((), ())
    eng.state_store.set_arrays((), ())
    del eng
    gc.collect()
    chk = check_tokens(ctx, state, requests, m, picks, sizes)
    notes = {**lat, "steps": m["steps"], "elapsed_s": m["elapsed"],
             "requests": len(requests), "done": len(done),
             "compiled_in_window": m["compiled_in_window"],
             "waiting_at_end": sum(1 for r, h in zip(requests, m["handles"])
                                   if not m["stamps"][h.req_id]
                                   and r.due_s <= m["elapsed"]),
             "running_at_end": running_at_end,
             "pages_in_use_at_end": pages_in_use,
             "state_slots_in_use_at_end": slots_in_use, **chk,
             "out_tokens_per_s": lat["out_tokens"] / m["elapsed"],
             **{k: counters.get(k) for k in (
                 "prefill_tokens", "preemptions", "ssm_slots_walked",
                 "ssm_slots_store", "ssm_chunk_tokens_walked",
                 "ssm_chunk_tokens_padded")}}
    return {
        "correct": chk["ok"] and m["compiled_in_window"] == 0,
        "attempted": lat["judged"], "failed": lat["missed"],
        "end_to_end": {"tbt_p95_ms": lat["tbt_p95_ms"]},
        "notes": notes,
        "facts": facts,
    }
