"""Driver ``serve_open_loop_hybrid``: ``serve_open_loop`` for a hybrid
state-space / attention / latent-expert configuration (published
``nemotron_h`` keys).  The measured loop, the timestamps and the latency
statistics are ``serve_common``'s; what differs is how the model is made
(``hetu_tpu.models.hybrid``: one translation from the published keys, the
weights drawn on the device from ``--seed``), how the engine is sized (K/V
pages for ``max_batch`` sequences of ``max_model_len``; the recurrent
state has one slot per sequence by construction) and which plain
reference decides ``correct`` (``reference_hybrid``).  Traffic parameter
``lowp_reading`` (``--set lowp_reading=true``) also logs the reference's
own float8 reading of the tolerance, for PERF.md."""
from __future__ import annotations

import time

import reference_hybrid as reference
import traffic as traffic_lib
from drivers.serve_open_loop import _queue_wait_p90
from serve_common import latency_stats, measure, serve_facts, warm_up


def build(ctx):
    from hetu_tpu.models.hybrid import hybrid_config, init_state
    from hetu_tpu.serving import Engine
    c, s = ctx.config, ctx.config["serve"]
    cfg = hybrid_config(c, init_std=float(c["assumed"]["initializer_range"]))
    t = time.monotonic()
    state = init_state(cfg, ctx.seed, time_step=(
        c["time_step_min"], c["time_step_max"], c["time_step_floor"]))
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
    ctx.log(f"weights + engine in {time.monotonic() - t:.1f} s; K/V pool "
            f"{num_pages} pages = {num_pages * eng.pool.page_bytes / 1e9:.2f}"
            f" GB; state store {eng.state_store.num_slots} slots = "
            f"{eng.state_store.num_slots * eng.state_store.slot_bytes / 1e9:.2f} GB")
    return state, cfg, eng


def check_tokens(ctx, state, requests, m: dict, picks) -> dict:
    """Every served token of the picked requests within
    ``reference.LOGIT_GAP_TOL`` logits of the plain reference's best
    token, teacher-forced on the served sequence.  After the window."""
    spec = reference.spec_from_config(ctx.config)
    pad_to = int(ctx.traffic["max_total"])
    max_new = max(r.max_new_tokens for r in requests)
    worst, checked, lowp = 0.0, 0, None
    t = time.monotonic()
    for i in picks:
        out = list(m["handles"][i].out_tokens)
        if not out:
            continue
        seq = requests[i].prompt + out
        gaps = reference.greedy_logit_gaps(
            state, seq, len(requests[i].prompt), spec, pad_to, max_new)
        worst = max(worst, max(gaps))
        checked += len(gaps)
        if ctx.traffic.get("lowp_reading"):
            low = max(reference.lowp_choice_gaps(
                state, seq, len(requests[i].prompt), spec, pad_to, max_new))
            lowp = low if lowp is None else max(lowp, low)
    ctx.log(f"reference check: {checked} tokens of {len(picks)} requests, "
            f"worst logit gap {worst:.4f} (rule <= "
            f"{reference.LOGIT_GAP_TOL}) in {time.monotonic() - t:.1f} s" +
            (f"; float8 reading {lowp:.4f}" if lowp is not None else ""))
    return {"checked_tokens": checked, "worst_logit_gap": worst,
            "lowp_logit_gap": lowp,
            "ok": checked > 0 and worst <= reference.LOGIT_GAP_TOL}


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
    m = measure(ctx, eng, requests)
    lat = latency_stats(requests, m)
    done = [i for i, h in enumerate(m["handles"]) if h.done]
    picks = [done[(k * len(done)) // 4] for k in range(4)] if done else []
    chk = check_tokens(ctx, state, requests, m, picks)
    notes = {**lat, "steps": m["steps"],
             "elapsed_s": m["elapsed"], "requests": len(requests),
             "compiled_in_window": m["compiled_in_window"],
             "waiting_at_end": sum(1 for r, h in zip(requests, m["handles"])
                                   if not m["stamps"][h.req_id]
                                   and r.due_s <= m["elapsed"]),
             "running_at_end": len(eng.running), **chk,
             "out_tokens_per_s": lat["out_tokens"] / m["elapsed"],
             "preemptions": m["counters"].get("preemptions"),
             "moe_assignments_local":
                 m["counters"].get("moe_assignments_local"),
             "moe_assignments_total":
                 m["counters"].get("moe_assignments_total")}
    return {
        "correct": chk["ok"] and m["compiled_in_window"] == 0,
        "attempted": lat["judged"], "failed": lat["missed"],
        "end_to_end": {"tbt_p95_ms": lat["tbt_p95_ms"]},
        "notes": notes,
        "facts": serve_facts(
            m, lat, queue_wait_p90_ms=_queue_wait_p90(requests, m)),
    }
