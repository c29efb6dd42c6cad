"""Driver ``serve_replay``: a fixed replay from the seed, everything due
at t = 0 and long enough that the queue is never empty in the window —
offline / above-the-knee serving.  The end-to-end metric is the output
tokens completed per second over the whole window; tails are recorded
as per-layer values and never judged (a saturated queue makes them swing).

Where the mix shares prefixes, set-up serves each document once, so the
window starts with the prefix cache a long-running deployment has."""
from __future__ import annotations

import traffic as traffic_lib
from serve_common import (build_engine, check_tokens, latency_stats,
                          make_weights, measure, serve_facts, warm_up)


def run(ctx) -> dict:
    mix = ctx.traffic
    state = make_weights(ctx.config, ctx.seed)
    eng, cfg = build_engine(ctx, state)
    requests, docs = traffic_lib.serve_requests(mix, ctx.seed, ctx.seconds,
                                                cfg.vocab_size)
    warm = traffic_lib._rng(ctx.seed, 9)
    # each document + 8 own tokens: compiles the executable and leaves the
    # documents' full pages in the prefix cache
    warm_up(ctx, eng, [d + warm.randint(0, cfg.vocab_size, 8).tolist()
                       for d in docs] or
            [warm.randint(0, cfg.vocab_size, 40).tolist()])
    m = measure(ctx, eng, requests, stop_when_idle=True)
    lat = latency_stats(requests, m, due_share=1.0)
    started = [i for i, h in enumerate(m["handles"]) if h.done]
    # two requests whose document was in the cache and the two first done
    picks = (started[:2] + started[-2:]) if len(started) >= 4 else started
    chk = check_tokens(ctx, state, cfg, requests, m, picks)
    drained = len(eng.queue) == 0          # the replay was too short
    tokens_per_s = lat["out_tokens"] / m["elapsed"]
    notes = {**lat, "steps": m["steps"], "elapsed_s": m["elapsed"],
             "requests": len(requests), "queue_left": len(eng.queue),
             "compiled_in_window": m["compiled_in_window"], **chk,
             "prefix_tokens_saved":
                 m["counters"].get("prefix_cache_tokens_saved"),
             "prefill_tokens": m["counters"].get("prefill_tokens"),
             "preemptions": m["counters"].get("preemptions")}
    return {
        "correct": chk["ok"] and m["compiled_in_window"] == 0
        and not drained,
        "attempted": lat["first_tokens"], "failed": 0,
        "end_to_end": {"serve_tokens_per_s": tokens_per_s},
        "notes": notes,
        "facts": serve_facts(m, lat),
    }
