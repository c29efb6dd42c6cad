"""Driver ``serve_replay_mla``: ``serve_replay`` for a latent-attention /
gated-expert configuration (published ``mistral4`` keys).  The replay, the
measured loop, the timestamps and the latency statistics are
``serve_replay``'s and ``serve_common``'s; what differs is how the model is
made (``hetu_tpu.models.hybrid``: one translation from the published keys,
the weights drawn on the device from ``--seed``), how the engine is sized
(the latent page pool takes ``serve.pool_gb`` of ``c_kv | k_r`` data, 640 B
a token a layer: the cell's working set and what a deployment keeps for
colder documents; the rotary stream's zero lanes come on top) and which plain reference
decides ``correct`` (``reference_mistral4``: float32, not absorbed).
Traffic parameter ``lowp_reading`` (``--set lowp_reading=true``) also logs
the reference's own float8 reading of the tolerance, for PERF.md."""
from __future__ import annotations

import gc
import time

import reference_mistral4 as reference
import traffic as traffic_lib
from serve_common import latency_stats, measure, serve_facts, warm_up


def build(ctx):
    # a program without this model fails here, before anything is made
    from hetu_tpu.models.hybrid import init_state, mistral4_config
    from hetu_tpu.serving import Engine
    c, s = ctx.config, ctx.config["serve"]
    cfg = mistral4_config(c, init_std=float(c["assumed"]["initializer_range"]))
    t = time.monotonic()
    state = init_state(cfg, ctx.seed)
    next(iter(state.values())).block_until_ready()
    page_bytes = c["num_hidden_layers"] * s["page_size"] * 2 * (
        c["kv_lora_rank"] + c["qk_rope_head_dim"])
    num_pages = int(s.get("num_pages") or s["pool_gb"] * 1e9 / page_bytes)
    eng = Engine(state, cfg, num_pages=num_pages, page_size=s["page_size"],
                 max_batch=s["max_batch"], max_model_len=s["max_model_len"],
                 chunk_size=s["chunk_size"], prefill_rows=s["prefill_rows"],
                 prefix_cache=bool(s["prefix_cache"]), name="bench",
                 use_kernel=True if ctx.rehearse else None)
    if not eng.use_kernel:
        raise RuntimeError("the engine did not pick the ragged kernel")
    ctx.log(f"weights + engine in {time.monotonic() - t:.1f} s; latent pool "
            f"{num_pages} pages x {s['page_size']} tokens = "
            f"{num_pages * eng.pool.page_bytes / 1e9:.2f} GB")
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
            f"{max(gaps, default=0.0):.4f}, in {time.monotonic() - t:.1f} s" +
            (f"; float8 reading {100 * lowp:.2f} % beyond, worst "
             f"{max(low):.4f}" if low else ""))
    return {"checked_tokens": len(gaps), "beyond_share": beyond,
            "worst_logit_gap": max(gaps, default=0.0),
            "lowp_beyond_share": lowp,
            "ok": bool(gaps) and beyond <= reference.GAP_SHARE_TOL}


def run(ctx) -> dict:
    mix = ctx.traffic
    state, cfg, eng = build(ctx)
    requests, docs = traffic_lib.serve_requests(mix, ctx.seed, ctx.seconds,
                                                cfg.vocab_size)
    warm = traffic_lib._rng(ctx.seed, 9)
    # each document + 8 own tokens: compiles the executable and leaves the
    # documents' full pages in the prefix cache
    warm_up(ctx, eng, [d + warm.randint(0, cfg.vocab_size, 8).tolist()
                       for d in docs])
    # the replay holds its 1,200 prompts of ~16.7k ids twice over as Python
    # lists (the generator's, and the engine's copy at queueing): a full
    # collection walks 40 M list slots, 0.1-0.4 s = two to six steps, and
    # whether a window holds one or three of them was most of the cell's
    # spread.  A server freezes its start-up heap and takes requests off
    # the wire: the start-up heap is frozen and the window holds no full
    # collection (the young generations go on as they were)
    gc.collect()
    gc.freeze()
    young, middle, old = gc.get_threshold()
    gc.set_threshold(young, middle, 1 << 30)
    m = measure(ctx, eng, requests, stop_when_idle=True)
    gc.set_threshold(young, middle, old)
    lat = latency_stats(requests, m, due_share=1.0)
    done = [i for i, h in enumerate(m["handles"]) if h.done]
    # two requests whose document was in the cache and the two first done
    picks = (done[:2] + done[-2:]) if len(done) >= 4 else done
    queue_left = len(eng.queue)
    # the pool goes before the reference comes (the engine itself stays
    # registered with the analysis plane): the reference's 17k-token
    # float32 activations take its room, and the peak stays the serving's
    eng.pool.set_pages((), ())
    del eng
    gc.collect()
    chk = check_tokens(ctx, state, requests, m, picks)
    drained = queue_left == 0              # the replay was too short
    counters = m["counters"]
    notes = {**lat, "steps": m["steps"], "elapsed_s": m["elapsed"],
             "requests": len(requests), "queue_left": queue_left,
             "compiled_in_window": m["compiled_in_window"], **chk,
             **{k: counters.get(k) for k in (
                 "prefix_cache_tokens_saved", "prefill_tokens", "preemptions",
                 "moe_assignments_local", "moe_assignments_total",
                 "latent_pages_attended", "latent_pages_attended_distinct")}}
    return {
        "correct": chk["ok"] and m["compiled_in_window"] == 0
        and not drained,
        "attempted": lat["first_tokens"], "failed": 0,
        "end_to_end": {"serve_tokens_per_s": lat["out_tokens"] / m["elapsed"]},
        "notes": notes,
        "facts": serve_facts(m, lat),
    }
