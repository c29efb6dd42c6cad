"""What the two serving drivers share: weights from the seed on the
device, the engine built through ``hetu_tpu.serving.Engine`` as a user
builds it, the measured loop (``add_request(arrival_time=, stream_cb=)``,
``step()``), the benchmark's own timestamps, and the check of served
tokens against the plain reference.
"""
from __future__ import annotations

import math
import time

import numpy as np

import reference
import stats


def make_weights(config: dict, seed: int):
    """Every tensor of the model under the program's own tensor names, in
    the dtype it is served in, made on the device in ONE jitted call from
    the seed: normal(0, initializer_range), the two output projections of
    a block scaled by 1/sqrt(2 * n_layer) (GPT-2), norms at (1, 0), biases
    0 — what ``GPTLMHeadModel`` itself draws, without the host."""
    import jax
    import jax.numpy as jnp
    h, layers, ffn = config["n_embd"], config["n_layer"], config["n_inner"]
    std = float(config["initializer_range"])
    dt = jnp.bfloat16 if config["dtype"] == "bfloat16" else jnp.float32
    mats = {"wte.weight": ((config["vocab_size"], h), std),
            "wpe": ((config["n_positions"], h), std)}
    vecs = {"ln_f.weight": (h, 1.0), "ln_f.bias": (h, 0.0)}
    for i in range(layers):
        p = f"h{i}."
        mats[p + "attn.qkv.weight"] = ((3 * h, h), std)
        mats[p + "attn.out.weight"] = ((h, h), std / math.sqrt(2 * layers))
        mats[p + "mlp.up.weight"] = ((ffn, h), std)
        mats[p + "mlp.down.weight"] = ((h, ffn), std / math.sqrt(2 * layers))
        for name, n, v in (("ln_1.weight", h, 1.0), ("ln_1.bias", h, 0.0),
                           ("ln_2.weight", h, 1.0), ("ln_2.bias", h, 0.0),
                           ("attn.qkv.bias", 3 * h, 0.0),
                           ("attn.out.bias", h, 0.0),
                           ("mlp.up.bias", ffn, 0.0),
                           ("mlp.down.bias", h, 0.0)):
            vecs[p + name] = (n, v)

    @jax.jit
    def build(key):
        out = {}
        for k, (name, (shape, s)) in zip(
                jax.random.split(key, len(mats)), sorted(mats.items())):
            out[name] = (s * jax.random.normal(k, shape, jnp.float32)).astype(dt)
        for name, (n, v) in vecs.items():
            out[name] = jnp.full((n,), v, dt)
        return out

    return build(jax.random.PRNGKey(seed % (2 ** 31)))


def build_engine(ctx, state):
    import jax
    from hetu_tpu.serving import Engine
    from drivers_util import gpt_config
    c, s = ctx.config, ctx.config["serve"]
    cfg = gpt_config(c)
    page_bytes = 2 * c["n_layer"] * c["n_embd"] * s["page_size"] * 2
    if ctx.rehearse:
        num_pages = int(s["num_pages"])
    else:
        limit = jax.devices()[0].memory_stats()["bytes_limit"]
        num_pages = int(s["kv_hbm_share"] * limit / page_bytes)
    ctx.log(f"KV pool: {num_pages} pages x {s['page_size']} tokens = "
            f"{num_pages * page_bytes / 1e9:.2f} GB")
    eng = Engine(state, cfg, num_pages=num_pages, page_size=s["page_size"],
                 max_batch=s["max_batch"], chunk_size=s["chunk_size"],
                 prefill_rows=s["prefill_rows"],
                 prefix_cache=bool(s["prefix_cache"]), name="bench",
                 use_kernel=True if ctx.rehearse else None)
    if not eng.use_kernel:
        raise RuntimeError("the engine did not pick the ragged kernel")
    return eng, cfg


def warm_up(ctx, eng, prompts, new_tokens: int = 4):
    """Serve ``prompts`` to the end: compiles THE executable (fixed shapes,
    one compile covers decode rows and the prefill chunk) and leaves the
    finished prompts' pages in the prefix cache."""
    t = time.monotonic()
    for p in prompts:
        eng.add_request(p, new_tokens)
    eng.run()
    ctx.log(f"warm-up: {len(prompts)} requests in "
            f"{time.monotonic() - t:.1f} s, compile_count {eng.compile_count}")


def measure(ctx, eng, requests, stop_when_idle: bool = False) -> dict:
    """The window.  Every request is queued up front with its due time as
    ``arrival_time`` (the queue is arrival-gated, so the generator cannot
    run late); the loop steps while anything is due or running and sleeps
    to the next arrival when idle.  Timestamps are the benchmark's own,
    on ``time.monotonic`` (the engine's clock)."""
    tracer = None
    if ctx.trace:
        from hetu_tpu import obs
        tracer = obs.SpanTracer(capacity=1 << 20)
        eng.set_tracer(tracer)
    eng.reset_metrics()
    compiles_before = eng.compile_count
    stamps = {}                                   # req_id -> [token times]

    def on_token(req, tok):
        stamps[req.req_id].append(time.monotonic())

    step_contexts = []
    t0 = ctx.begin_window()
    handles = []
    for r in requests:
        h = eng.add_request(r.prompt, r.max_new_tokens,
                            arrival_time=t0 + r.due_s, stream_cb=on_token)
        stamps[h.req_id] = []
        handles.append(h)
    steps = 0
    trace_t0 = None
    while ctx.in_window():
        now = time.monotonic()
        nxt = eng.queue.next_arrival()
        if not eng.running and (nxt is None or nxt > now):
            if nxt is None and stop_when_idle:
                break
            with ctx.span("idle_wait"):
                time.sleep(max(0.0, min(nxt if nxt is not None
                                        else ctx.t_end, ctx.t_end) - now))
            continue
        if ctx._tracing and trace_t0 is None:
            trace_t0 = now
        with ctx.span("engine.step"):
            eng.step()
        steps += 1
        if ctx._tracing and eng.tap:
            last = eng.tap[-1]
            if last.get("kind") == "unified":
                ctx_t = sum(c for _, _, _, c in last["reads"])
                q_t = sum(q for _, _, q, _ in last["reads"])
                pairs = sum(q * c - q * (q - 1) // 2
                            for _, _, q, c in last["reads"])
                step_contexts.append((now, ctx_t, q_t, pairs))
    elapsed = ctx.end_window()
    t1 = t0 + elapsed
    if tracer is not None:
        eng.set_tracer(None)
    counters = eng.metrics_summary()
    return {"t0": t0, "t1": t1, "elapsed": elapsed, "steps": steps,
            "handles": handles, "stamps": stamps, "counters": counters,
            "compiled_in_window": eng.compile_count - compiles_before,
            "host_spans": tracer.events() if tracer else [],
            "step_contexts": step_contexts,
            "trace_host_window": (trace_t0 or t1, t1)}


def latency_stats(requests, m: dict, due_share: float = 0.9) -> dict:
    """TTFT from each request's DUE time, over the requests due in the
    first ``due_share`` of the window (a later one may rightly still be
    waiting when the window closes); one of those with no first token by
    the window's end is a miss.  TBT over every gap between consecutive
    tokens of one request."""
    t0, elapsed = m["t0"], m["elapsed"]
    ttft, missed, judged, gaps, out_tokens = [], 0, 0, [], 0
    for r, h in zip(requests, m["handles"]):
        ts = m["stamps"][h.req_id]
        out_tokens += len(ts)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        if r.due_s <= due_share * elapsed:
            judged += 1
            if ts:
                ttft.append(ts[0] - (t0 + r.due_s))
            else:
                missed += 1
    out = {"judged": judged, "missed": missed, "out_tokens": out_tokens,
           "finished": sum(1 for h in m["handles"] if h.done),
           "first_tokens": len(ttft), "gaps": len(gaps)}
    # a miss sits beyond any percentile: it is counted as the window
    out["ttft_p90_ms"] = stats.percentile(
        ttft + [elapsed] * missed, 90) * 1e3 if judged else None
    out["ttft_p50_ms"] = stats.median(ttft) * 1e3 if ttft else None
    out["tbt_p95_ms"] = stats.percentile(gaps, 95) * 1e3 if gaps else None
    out["tbt_p50_ms"] = stats.median(gaps) * 1e3 if gaps else None
    return out


def serve_facts(m: dict, lat: dict, **values) -> dict:
    """What the readers get from a serving window."""
    return {"values": {"steps": m["steps"], "window_s": m["elapsed"],
                       "host_window": (m["t0"], m["t1"]),
                       "trace_host_window": m["trace_host_window"],
                       "step_contexts": m["step_contexts"],
                       "out_tokens_per_s": lat["out_tokens"] / m["elapsed"],
                       "ttft_p90_ms": lat["ttft_p90_ms"],
                       "tbt_p95_ms": lat["tbt_p95_ms"], **values},
            "host_spans": m["host_spans"], "counters": m["counters"]}


def check_tokens(ctx, state, cfg, requests, m: dict, picks) -> dict:
    """Every served token of the picked requests within
    ``reference.LOGIT_GAP_TOL`` logits of the plain reference's best
    token, teacher-forced on the served sequence.  After the window."""
    c = ctx.config
    max_new = max(r.max_new_tokens for r in requests)
    worst, checked = 0.0, 0
    t = time.monotonic()
    for i in picks:
        out = list(m["handles"][i].out_tokens)
        if not out:
            continue
        gaps = reference.greedy_logit_gaps(
            state, requests[i].prompt + out, len(requests[i].prompt),
            c["n_layer"], c["n_head"], pad_to=c["n_positions"],
            max_new=max_new, eps=c["layer_norm_epsilon"])
        worst = max(worst, max(gaps))
        checked += len(gaps)
    ctx.log(f"reference check: {checked} tokens of {len(picks)} requests, "
            f"worst logit gap {worst:.4f} (rule <= "
            f"{reference.LOGIT_GAP_TOL}) in {time.monotonic() - t:.1f} s")
    return {"checked_tokens": checked, "worst_logit_gap": worst,
            "ok": checked > 0 and worst <= reference.LOGIT_GAP_TOL}
