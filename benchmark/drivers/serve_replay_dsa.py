"""Driver ``serve_replay_dsa``: ``serve_replay_mla``'s replay for an indexed
/ window latent-attention configuration (published ``dots3_note`` keys).
The latency statistics, the facts the readers get and the warm-up are
``serve_common``'s; what differs from ``serve_replay_mla``:

* the model (``hetu_tpu.models.hybrid.dots3_config``: one translation from
  the published keys, the weights drawn on the device from ``--seed``) and
  the engine's sizing: ``serve.pool_gb`` of full-layer data (latent + index
  key of a token of every full layer; the rotary stream's zero lanes come
  on top) and ``serve.window_pages`` page ids of the window layers' space;
* the window's loop is this file's own, because ``serve_common.measure``
  queues the replay INSIDE the window: 600 prompts of 33k ids are some 5 s
  of list copying with no step, which the latent cell pays (PERF.md
  section 7).  A server takes requests off the wire while it steps; here
  the replay is queued BEFORE ``begin_window`` and the window holds steps
  alone.  The timestamps and the definition of ``serve_tokens_per_s``
  (tokens streamed inside the window over its seconds) are ``measure``'s;
* the collector is frozen as ``serve_replay_mla`` freezes it;
* the engine starts a step's device-to-host copy when it enqueues the step
  (``Engine(early_fetch=True)``): the blocking fetch cost this cell 1.6 ms
  of host hand-offs a step, the part of the step that differs most from one
  process to the next (PERF.md section 6, PR 39);
* the plain reference that decides ``correct`` is ``reference_dots3``, and
  beside the logit check runs the selection check
  (``index_select_overlap``: the program's indexer arithmetic on the
  reference's own layer input against the reference's float32 selection).

* the reference's calls are compiled on a thread BESIDE the warm-up
  (``reference.compile_ahead``: shapes alone, no device work) and the
  thread is joined before the replay is queued: compiling them takes
  70-100 s on this host, running them 20 s, and a run has 360 s.  The
  check is the same call for call; if the thread fails it compiles them
  itself, later.

Traffic parameter ``lowp_reading`` (``--set lowp_reading=true``) also logs
the reference's own float8 readings of both tolerances, for PERF.md."""
from __future__ import annotations

import gc
import threading
import time

import jax
import numpy as np

import reference_dots3 as reference
import traffic as traffic_lib
from serve_common import latency_stats, serve_facts, warm_up


def build(ctx):
    # a program without this model fails here, before anything is made
    from hetu_tpu.models.hybrid import dots3_config, init_state
    from hetu_tpu.serving import Engine
    c, s = ctx.config, ctx.config["serve"]
    cfg = dots3_config(c, init_std=float(c["assumed"]["initializer_range"]))
    t = time.monotonic()
    state = init_state(cfg, ctx.seed)
    next(iter(state.values())).block_until_ready()
    full = sum(t == "full_attention" for t in c["layer_types"])
    page_bytes = full * s["page_size"] * 2 * (
        c["kv_lora_rank"] + c["qk_rope_head_dim"] + c["index_head_dim"])
    num_pages = int(s.get("num_pages") or s["pool_gb"] * 1e9 / page_bytes)
    eng = Engine(state, cfg, num_pages=num_pages, page_size=s["page_size"],
                 max_batch=s["max_batch"], max_model_len=s["max_model_len"],
                 chunk_size=s["chunk_size"], prefill_rows=s["prefill_rows"],
                 prefix_cache=bool(s["prefix_cache"]), name="bench",
                 window_pages=s["window_pages"], early_fetch=True,
                 use_kernel=True if ctx.rehearse else None)
    if not eng.use_kernel:
        raise RuntimeError("the engine did not pick the device path")
    pool = eng.pool
    ctx.log(f"weights + engine in {time.monotonic() - t:.1f} s; full-layer "
            f"pool {num_pages} pages x {s['page_size']} tokens = "
            f"{num_pages * pool.page_bytes / 1e9:.2f} GB; window space "
            f"{pool.window.num_pages} pages = "
            f"{pool.window.num_pages * pool.window_page_bytes / 1e9:.2f} GB "
            f"({eng.window_table_pages} a row)")
    return state, cfg, eng


def measure(ctx, eng, requests) -> dict:
    """The window: ``serve_common.measure``'s timestamps and result, with
    the replay queued before it opens (every request is due at once) and
    the loop stepping until the window closes or nothing is left."""
    tracer = None
    if ctx.trace:
        from hetu_tpu import obs
        tracer = obs.SpanTracer(capacity=1 << 20)
        eng.set_tracer(tracer)
    stamps = {}                                   # req_id -> [token times]

    def on_token(req, tok):
        stamps[req.req_id].append(time.monotonic())

    handles = []
    due = time.monotonic()
    for r in requests:
        h = eng.add_request(r.prompt, r.max_new_tokens, arrival_time=due,
                            stream_cb=on_token)
        stamps[h.req_id] = []
        handles.append(h)
    gc.collect()
    gc.freeze()
    young, middle, old = gc.get_threshold()
    gc.set_threshold(young, middle, 1 << 30)
    eng.reset_metrics()
    compiles_before = eng.compile_count
    step_contexts = []
    t0 = ctx.begin_window()
    steps, trace_t0 = 0, None
    while ctx.in_window() and eng.has_work:
        now = time.monotonic()
        if ctx._tracing and trace_t0 is None:
            trace_t0 = now
        with ctx.span("engine.step"):
            eng.step()
        steps += 1
        if ctx._tracing and eng.tap and eng.tap[-1].get("kind") == "unified":
            reads = eng.tap[-1]["reads"]
            step_contexts.append((
                now, sum(c for _, _, _, c in reads),
                sum(q for _, _, q, _ in reads),
                sum(q * c - q * (q - 1) // 2 for _, _, q, c in reads)))
    elapsed = ctx.end_window()
    gc.set_threshold(young, middle, old)
    if tracer is not None:
        eng.set_tracer(None)
    return {"t0": t0, "t1": t0 + elapsed, "elapsed": elapsed, "steps": steps,
            "handles": handles, "stamps": stamps,
            "counters": eng.metrics_summary(),
            "compiled_in_window": eng.compile_count - compiles_before,
            "host_spans": tracer.events() if tracer else [],
            "step_contexts": step_contexts,
            "trace_host_window": (trace_t0 or t0 + elapsed, t0 + elapsed)}


def pick(requests, done) -> list:
    """Four finished requests on ONE document, the one most of the
    finished requests read: its first and last done and two between.  The
    reference passes the document once (``document_state``: most of its
    time) and then each request's own positions."""
    by_doc = {}
    for i in done:
        by_doc.setdefault(requests[i].document, []).append(i)
    if not by_doc:
        return []
    mine = by_doc[min(by_doc, key=lambda d: (-len(by_doc[d]), d))]
    return list(dict.fromkeys(mine[(len(mine) - 1) * j // 3]
                              for j in range(4)))


def reference_sizes(ctx, requests) -> dict:
    """The shapes of the reference's evaluation, one for every request:
    the sequence padded to ``max_total``, the positions read to the
    longest output, and the tail behind the shared document."""
    pad_to = int(ctx.traffic["max_total"])
    doc_len = int(ctx.traffic["shared_prefix"]["tokens"])
    return {"doc_len": doc_len, "pad_to": pad_to, "tail": pad_to - doc_len,
            "max_new": max(r.max_new_tokens for r in requests)}


def compile_reference(ctx, state, sizes: dict):
    t = time.monotonic()
    try:
        kept = reference.compile_ahead(
            state, reference.spec_from_config(ctx.config), **sizes)
        ctx.log(f"reference: {kept} calls compiled beside the warm-up in "
                f"{time.monotonic() - t:.1f} s")
    except Exception as e:       # the check then compiles what it calls
        ctx.log(f"reference: compiling ahead failed ({e!r})")


def check_tokens(ctx, state, cfg, requests, m: dict, picks) -> dict:
    """The served tokens of the picked requests, teacher-forced through
    the plain reference: at most ``reference.GAP_SHARE_TOL`` of them more
    than ``reference.LOGIT_GAP_TOL`` logits below the reference's best
    token; and, at every full layer, the program's indexer arithmetic on
    the reference's layer input selects, for the generated positions, at
    least ``reference.SELECT_OVERLAP_TOL`` of the positions the reference's
    float32 indexer selects.  After the window."""
    from hetu_tpu.models import hybrid as hy
    spec = reference.spec_from_config(ctx.config)
    items = reference._freeze(spec)
    # every generated position lies behind the shared document: the
    # reference computes what those positions depend on and no more
    sizes = reference_sizes(ctx, requests)
    pad_to, tail, max_new = sizes["pad_to"], sizes["tail"], sizes["max_new"]
    lowp = bool(ctx.traffic.get("lowp_reading"))
    gaps, low, overlaps, low_overlaps = [], [], [], []
    t = time.monotonic()
    known = known_low = None
    doc_of = None
    for i in picks:                  # grouped by document (``pick``)
        out = list(m["handles"][i].out_tokens)
        if not out:
            continue
        if requests[i].document != doc_of:
            # the document's own pass, once for the requests on it
            doc_of = requests[i].document
            doc = requests[i].prompt[:pad_to - tail]
            known = reference.document_state(state, doc, spec)
            jax.block_until_ready(known)
            ctx.log(f"reference: document {doc_of} passed in "
                    f"{time.monotonic() - t:.1f} s")
            known_low = reference.document_state(
                state, doc, spec, lowp="float8") if lowp else None
        n_prompt = len(requests[i].prompt)
        seq = requests[i].prompt + out
        # the generated positions, padded to one length for every request
        # (one compiled shape); the padding repeats the last and is cut
        positions = np.minimum(np.arange(max_new), len(out) - 1) + \
            n_prompt - 1

        def probe(layer, u, sel, first, n=len(out)):
            theirs = np.asarray(sel[positions - first])[:n]
            mine = np.asarray(hy.index_positions(cfg, state, layer, u,
                                                 positions))[:n]
            overlaps.append(reference.select_overlap(mine, theirs, pad_to))
            if lowp:
                p = {k[len(f"h{layer}."):]: v for k, v in state.items()
                     if k.startswith(f"h{layer}.")}
                rounded, _ = reference.select_positions(
                    u, p, spec_items=items, lowp="float8", first=first)
                low_overlaps.append(reference.select_overlap(
                    np.asarray(rounded[positions - first])[:n], theirs,
                    pad_to))

        gaps += reference.greedy_logit_gaps(
            state, seq, n_prompt, spec, pad_to, max_new, probe=probe,
            tail=tail, known=known)
        if lowp:
            low += reference.lowp_choice_gaps(
                state, seq, n_prompt, spec, pad_to, max_new, tail=tail,
                known=known, known_lowp=known_low)
    del known, known_low
    tol = reference.LOGIT_GAP_TOL
    share = lambda g: sum(v > tol for v in g) / len(g)      # noqa: E731
    beyond = share(gaps) if gaps else 1.0
    overlap = min(overlaps, default=0.0)
    ctx.log(f"reference check: {len(gaps)} tokens of {len(picks)} requests, "
            f"{100 * beyond:.2f} % beyond {tol} logits (rule <= "
            f"{100 * reference.GAP_SHARE_TOL:.0f} %), worst gap "
            f"{max(gaps, default=0.0):.4f}; index_select_overlap least "
            f"{overlap:.4f} mean {np.mean(overlaps or [0.0]):.4f} (rule >= "
            f"{reference.SELECT_OVERLAP_TOL}), in "
            f"{time.monotonic() - t:.1f} s" +
            (f"; float8 reading {100 * share(low):.2f} % beyond, worst "
             f"{max(low):.4f}, overlap least {min(low_overlaps):.4f} mean "
             f"{np.mean(low_overlaps):.4f}" if low else ""))
    return {"checked_tokens": len(gaps), "beyond_share": beyond,
            "worst_logit_gap": max(gaps, default=0.0),
            "index_select_overlap": overlap,
            "lowp_beyond_share": share(low) if low else None,
            "lowp_select_overlap": min(low_overlaps) if low_overlaps
            else None,
            "ok": bool(gaps) and beyond <= reference.GAP_SHARE_TOL
            and overlap >= reference.SELECT_OVERLAP_TOL}


def run(ctx) -> dict:
    mix = ctx.traffic
    state, cfg, eng = build(ctx)
    requests, docs = traffic_lib.serve_requests(mix, ctx.seed, ctx.seconds,
                                                cfg.vocab_size)
    sizes = reference_sizes(ctx, requests)
    ahead = threading.Thread(
        target=compile_reference, args=(ctx, state, sizes), daemon=True)
    ahead.start()
    warm = traffic_lib._rng(ctx.seed, 9)
    # each document + 8 own tokens: compiles the executable and leaves the
    # documents' full pages, and the window layers' tail at each
    # document's end, in the prefix cache
    warm_up(ctx, eng, [d + warm.randint(0, cfg.vocab_size, 8).tolist()
                       for d in docs])
    t = time.monotonic()
    ahead.join()
    ctx.log(f"reference: waited {time.monotonic() - t:.1f} s more for its "
            f"compiles")
    m = measure(ctx, eng, requests)
    lat = latency_stats(requests, m, due_share=1.0)
    picks = pick(requests, [i for i, h in enumerate(m["handles"])
                            if h.done])
    queue_left = len(eng.queue)
    window_space = eng.pool.window
    notes_pool = {"window_pages_in_use": window_space.in_use,
                  "window_pages": window_space.num_pages - 1,
                  "full_pages_in_use":
                  eng.pool.num_usable - eng.pool.free_pages}
    # the pools go before the reference comes (the engine itself stays
    # registered with the analysis plane): the reference's 33k-token
    # float32 activations take their room, and the peak stays the serving's
    eng.pool.set_pages((), ())
    del eng
    gc.collect()
    chk = check_tokens(ctx, state, cfg, requests, m, picks)
    drained = queue_left == 0              # the replay was too short
    counters = m["counters"]
    notes = {**lat, "steps": m["steps"], "elapsed_s": m["elapsed"],
             "requests": len(requests), "queue_left": queue_left,
             "compiled_in_window": m["compiled_in_window"], **chk,
             **notes_pool,
             **{k: counters.get(k) for k in (
                 "prefix_cache_tokens_saved", "prefill_tokens", "preemptions",
                 "moe_assignments_local", "moe_assignments_total",
                 "index_pairs_scored", "index_positions_selected",
                 "window_pages_held", "full_pages_held", "host_before_s",
                 "call_s", "host_after_s", "between_steps_s",
                 "slow_step_s")}}
    return {
        "correct": chk["ok"] and m["compiled_in_window"] == 0
        and not drained,
        "attempted": lat["first_tokens"], "failed": 0,
        "end_to_end": {"serve_tokens_per_s": lat["out_tokens"] / m["elapsed"]},
        "notes": notes,
        "facts": serve_facts(m, lat),
    }
