"""Driver ``serve_open_loop``: requests arrive on a schedule fixed by the
traffic mix (independent users), below the knee.  The tails are the
end-to-end metrics: TTFT from each request's due time and the gap between
consecutive tokens.  (PR 24 keeps only the TBT tail as an end-to-end
metric: at this system's capacity a window holds ~60 requests, and a 90th
percentile over them repeats no better than 3-6 %; the TTFT tail is
recorded as a per-layer value.)"""
from __future__ import annotations

import stats
import traffic as traffic_lib
from serve_common import (build_engine, check_tokens, latency_stats,
                          make_weights, measure, serve_facts, warm_up)


def run(ctx) -> dict:
    mix = ctx.traffic
    state = make_weights(ctx.config, ctx.seed)
    eng, cfg = build_engine(ctx, state)
    requests, _ = traffic_lib.serve_requests(mix, ctx.seed, ctx.seconds,
                                             cfg.vocab_size)
    # warm-up compiles the one executable: a two-chunk prompt and a short one
    warm = traffic_lib._rng(ctx.seed, 9)
    chunk = ctx.config["serve"]["chunk_size"]
    warm_up(ctx, eng, [warm.randint(0, cfg.vocab_size, n).tolist()
                       for n in (chunk + chunk // 2, 8)])
    m = measure(ctx, eng, requests)
    lat = latency_stats(requests, m)
    done = [i for i, h in enumerate(m["handles"]) if h.done]
    picks = [done[(k * len(done)) // 4] for k in range(4)] if done else []
    chk = check_tokens(ctx, state, cfg, requests, m, picks)
    notes = {**lat, "steps": m["steps"],
             "elapsed_s": m["elapsed"], "requests": len(requests),
             "compiled_in_window": m["compiled_in_window"],
             "waiting_at_end": sum(1 for r, h in zip(requests, m["handles"])
                                   if not m["stamps"][h.req_id]
                                   and r.due_s <= m["elapsed"]),
             "running_at_end": len(eng.running), **chk,
             "out_tokens_per_s": lat["out_tokens"] / m["elapsed"],
             "preemptions": m["counters"].get("preemptions")}
    queue_wait = _queue_wait_p90(requests, m)
    return {
        "correct": chk["ok"] and m["compiled_in_window"] == 0,
        "attempted": lat["judged"], "failed": lat["missed"],
        "end_to_end": {"tbt_p95_ms": lat["tbt_p95_ms"]},
        "notes": notes,
        "facts": serve_facts(m, lat, queue_wait_p90_ms=queue_wait),
    }


def _queue_wait_p90(requests, m):
    """Admit time minus due time, 90th percentile, from the program's own
    ``admit`` instants where the run was traced (else nothing)."""
    due = {h.req_id: m["t0"] + r.due_s
           for r, h in zip(requests, m["handles"])}
    waits = [e.ts - due[e.attrs["req"]] for e in m["host_spans"]
             if e.name == "admit" and e.attrs.get("req") in due]
    return stats.percentile(waits, 90) * 1e3 if waits else None
