"""Driver ``serve_open_loop_block``: ``serve_open_loop`` for a configuration
that GENERATES BY DIFFUSION OVER BLOCKS (published ``sdar_moe`` keys).  The
measured loop, the timestamps and the latency statistics are
``serve_common``'s; what differs:

* the model (``hetu_tpu.models.hybrid.sdar_moe_config``: one translation
  from the published keys and the two the file states under ``assumed``,
  the weights drawn on the device from ``--seed``) and the engine's sizing:
  ``serve.pool_gb`` of K/V (a token of every layer), ``serve.denoise`` as
  the engine's ``DenoiseRule`` (steps, rule);
* the traffic: ids below ``vocab_below`` (the mask id is never in a prompt),
  output lengths rounded up to a multiple of ``output_multiple`` (the block
  length: generation of fixed length, whole blocks);
* a row's tokens are stamped at their block's commit, so ``tbt_p95_ms`` reads
  the gap between a row's blocks (three gaps in four are ~0);
* the collector is held off the window (full collections only: the
  start-up heap is frozen, as the replay drivers do) and the plain
  reference, ``reference_sdar``, is compiled on a thread BESIDE the warm-up
  (``compile_ahead``: shapes alone) and run after the window, the engine's
  pool freed first.

``correct``, of what the timed path produced, on four finished requests
whose ``Request.denoise_log`` the engine kept (a pass: block, state going
in, positions unmasked, their tokens, the served confidences of the masked
positions):

(i)   a sample of each request's denoise passes (the first and the last
      block's, one of every mask pattern that occurs — a block that opens
      with prompt tokens among them — then evenly over the rest, up to
      ``checked.passes``; fewer than ``checked.min_passes`` fails) through
      ``reference_sdar.denoise_logits_many`` at the published widths: the
      share of unmasked positions where the reference's logit of the served
      token lies more than ``LOGIT_GAP_TOL`` under the reference's best
      there is at most ``GAP_SHARE_TOL``;
(ii)  the served confidences of those passes' masked positions: the share
      further than ``CONF_LOG_TOL`` from the reference's in log space is at
      most ``CONF_SHARE_TOL``;
(iii) the unmasked set of EVERY logged pass of the four equals, exactly,
      the rule's on the SERVED confidences (``reference_sdar.unmask_set``:
      integer logic, the selection itself);
(iv)  the emitted tokens equal the committed blocks, in position order;
(v)   the counter ``block_row_passes`` equals the passes the window's
      blocks take by the configured rule, recounted here from the requests'
      own lengths (a committed block of ``m`` masks: the schedule's passes
      for ``m``, + 1 commit; an open one: the passes it has had), so that no
      later change wins here by dropping a pass; ``block_commit_passes ==
      blocks_committed``;
(vi)  no compile in the window.

Traffic parameter ``control`` (``--set control=<fault>``) plants a fault
that the run has to report as ``correct: false`` (PERF.md records one run
of each): ``float8`` judges the reference's own float8 choices and
confidences in the served ones' place (the limits' second reading),
``skipped_pass`` serves with one denoise pass a block where the
configuration says two."""
from __future__ import annotations

import gc
import threading
import time

import reference_sdar as reference
import traffic as traffic_lib
from drivers.serve_open_loop import _queue_wait_p90
from serve_common import latency_stats, measure, serve_facts, warm_up

CONTROLS = (None, "float8", "skipped_pass")


def build(ctx):
    # a program without this model fails here, before anything is made
    from hetu_tpu.models.hybrid import init_state, sdar_moe_config
    from hetu_tpu.serving import Engine
    from hetu_tpu.serving.request import DenoiseRule
    c, s = ctx.config, ctx.config["serve"]
    cfg = sdar_moe_config(c, init_std=float(
        c["assumed"]["initializer_range"]))
    t = time.monotonic()
    state = init_state(cfg, ctx.seed)
    next(iter(state.values())).block_until_ready()
    page_bytes = c["num_hidden_layers"] * s["page_size"] * 2 * 2 * (
        c["num_key_value_heads"] * c["head_dim"])
    num_pages = int(s.get("num_pages") or s["pool_gb"] * 1e9 / page_bytes)
    rule = dict(s["denoise"])
    if ctx.traffic.get("control") == "skipped_pass":
        rule["steps"] = 1
    eng = Engine(state, cfg, num_pages=num_pages, page_size=s["page_size"],
                 max_batch=s["max_batch"], max_model_len=s["max_model_len"],
                 chunk_size=s["chunk_size"], prefill_rows=s["prefill_rows"],
                 prefix_cache=bool(s["prefix_cache"]), name="bench",
                 denoise=DenoiseRule(**rule),
                 use_kernel=True if ctx.rehearse else None)
    if not eng.use_kernel:
        raise RuntimeError("the engine did not pick the ragged kernel")
    ctx.log(f"weights + engine in {time.monotonic() - t:.1f} s; K/V pool "
            f"{num_pages} pages x {s['page_size']} tokens = "
            f"{num_pages * eng.pool.page_bytes / 1e9:.2f} GB; block "
            f"{cfg.diffusion_block}, rule {rule}")
    return state, cfg, eng


def compile_reference(ctx, state, sizes: dict):
    t = time.monotonic()
    try:
        kept = reference.compile_ahead(
            state, reference.spec_from_config(ctx.config), **sizes)
        ctx.log(f"reference: {kept} calls compiled beside the warm-up in "
                f"{time.monotonic() - t:.1f} s")
    except Exception as e:       # the check then compiles what it calls
        ctx.log(f"reference: compiling ahead failed ({e!r})")


def passes_of(masks: int, counts) -> int:
    """Denoise passes a block of ``masks`` masked positions takes under the
    static schedule ``counts``."""
    done = t = 0
    while done < masks:
        done += counts[t]
        t += 1
    return t


def expected_row_passes(requests, handles, block: int, counts) -> int:
    """(v) of the header: the block rows the window computed, from each
    request's own lengths."""
    total = 0
    for r, h in zip(requests, handles):
        first = block - len(r.prompt) % block
        n = len(h.out_tokens)
        if n:
            blocks = 1 + -(-(n - min(n, first)) // block)
            total += passes_of(first, counts) + 1 + (blocks - 1) * (
                passes_of(block, counts) + 1)
        total += h.block_pass if h.block is not None else 0
    return total


def sample_passes(log, mask: int, want: int):
    """Up to ``want`` of a request's denoise passes (header, (i))."""
    denoise = [e for e in log if mask in e[1]]
    if len(denoise) <= want:
        return denoise
    first, last = denoise[0][0], denoise[-1][0]
    chosen, seen = [], set()
    for i, e in enumerate(denoise):
        pattern = tuple(t == mask for t in e[1])
        if e[0] in (first, last) or pattern not in seen:
            chosen.append(i)
        seen.add(pattern)
    rest = [i for i in range(len(denoise)) if i not in set(chosen)]
    room = max(0, want - len(chosen))
    chosen += [rest[(k * len(rest)) // room] for k in range(room)]
    return [denoise[i] for i in sorted(set(chosen))[:want]]


def rule_holds(log, spec: dict, rule: dict) -> bool:
    """(iii): every logged denoise pass unmasked what the rule gives on the
    served confidences.  A pass's index in its block is counted from the
    log: 0 where the state going in is not what the pass before left."""
    b, mask = spec["block"], spec["mask_id"]
    counts = reference.schedule(b, rule["steps"])
    left, t = None, 0
    for at, x, picked, toks, conf in log:
        masked = [j for j in range(b) if x[j] == mask]
        if not masked:
            left = None
            continue
        t = t + 1 if left == (at, x) else 0
        if list(picked) != reference.unmask_set(
                masked, list(conf), counts[t], rule["rule"],
                rule.get("tau", 0.9)):
            return False
        after = list(x)
        for j, tok in zip(picked, toks):
            after[j] = tok
        left = (at, tuple(after))
    return True


def check_served(ctx, state, requests, m: dict, picks, sizes: dict) -> dict:
    """(i)-(iv) of the header on the picked requests.  After the window."""
    spec = reference.spec_from_config(ctx.config)
    b, mask = spec["block"], spec["mask_id"]
    control = ctx.traffic.get("control")
    lowp = "float8" if control == "float8" else None
    rule = ctx.config["serve"]["denoise"]
    want, least = sizes["passes"], int(ctx.traffic["checked"]["min_passes"])
    gaps, confs, served_gaps, served_confs = [], [], [], []
    rules_ok = tokens_ok = enough = True
    checked_passes = logged = 0
    t = time.monotonic()
    for i in picks:
        h, prompt = m["handles"][i], requests[i].prompt
        log = h.denoise_log
        logged += len(log)
        whole = len(prompt) // b * b
        commits = [e for e in log if mask not in e[1]]
        ids = prompt[:whole] + [tok for e in commits for tok in e[1]]
        # (iv) what was emitted is what was committed, in position order
        tokens_ok &= [e[0] for e in commits] == list(
            range(whole, whole + b * len(commits), b)) and \
            ids[len(prompt):len(prompt) + h.max_new_tokens] == \
            list(h.out_tokens)
        rules_ok &= rule_holds(log, spec, rule)
        passes = sample_passes(log, mask, want)
        enough &= len(passes) >= least
        checked_passes += len(passes)
        g, c = reference.served_passes(state, ids, passes, spec,
                                       sizes["pad_to"], want)
        if lowp:        # what is judged is the reference's float8 reading
            served_gaps, served_confs = served_gaps + g, served_confs + c
            g, c = reference.served_passes(state, ids, passes, spec,
                                           sizes["pad_to"], want, lowp=lowp)
        gaps, confs = gaps + g, confs + c
    share = lambda v, tol: sum(x > tol for x in v) / len(v) if v else 1.0  # noqa: E731
    beyond = share(gaps, reference.LOGIT_GAP_TOL)
    conf_beyond = share(confs, reference.CONF_LOG_TOL)
    ctx.log(f"reference check{f' (CONTROL {control})' if control else ''}: "
            f"{checked_passes} passes of {len(picks)} requests ({logged} "
            f"logged): {len(gaps)} unmasked positions, "
            f"{100 * beyond:.2f} % beyond {reference.LOGIT_GAP_TOL} logits "
            f"(rule <= {100 * reference.GAP_SHARE_TOL:.0f} %), worst "
            f"{max(gaps, default=0.0):.4f}; {len(confs)} confidences, "
            f"{100 * conf_beyond:.2f} % beyond {reference.CONF_LOG_TOL} in "
            f"log space (rule <= {100 * reference.CONF_SHARE_TOL:.1f} %), "
            f"worst {max(confs, default=0.0):.4f}; unmasked sets "
            f"{'agree' if rules_ok else 'DISAGREE'} with the rule on the "
            f"served confidences; emitted tokens "
            f"{'are' if tokens_ok else 'ARE NOT'} the committed blocks; in "
            f"{time.monotonic() - t:.1f} s" +
            (f"; what was served: "
             f"{100 * share(served_gaps, reference.LOGIT_GAP_TOL):.2f} % of "
             f"the positions beyond, "
             f"{100 * share(served_confs, reference.CONF_LOG_TOL):.2f} % of "
             f"the confidences" if lowp else ""))
    # how the two distances are spread: what the limits were set from
    spread = {name: {str(tol): round(share(v, tol), 4)
                     for tol in (0.05, 0.1, 0.15, 0.2, 0.25, 0.3, 0.5, 1.0)}
              for name, v in (("logit_gap_beyond", gaps),
                              ("conf_log_gap_beyond", confs))}
    return {"checked_passes": checked_passes, "checked_positions": len(gaps),
            **spread,
            "checked_confidences": len(confs), "beyond_share": beyond,
            "conf_beyond_share": conf_beyond,
            "worst_logit_gap": max(gaps, default=0.0),
            "worst_conf_log_gap": max(confs, default=0.0),
            "unmask_sets_ok": rules_ok, "emitted_ok": tokens_ok,
            "enough_passes": enough, "control": control,
            "ok": bool(gaps) and bool(confs) and rules_ok and tokens_ok
            and enough and beyond <= reference.GAP_SHARE_TOL
            and conf_beyond <= reference.CONF_SHARE_TOL}


def run(ctx) -> dict:
    mix = ctx.traffic
    if mix.get("control") not in CONTROLS:
        raise ValueError(f"control is one of {CONTROLS[1:]}")
    state, cfg, eng = build(ctx)
    mult = int(mix["output_multiple"])
    requests, _ = traffic_lib.serve_requests(mix, ctx.seed, ctx.seconds,
                                             int(mix["vocab_below"]))
    requests = [traffic_lib.ServeRequest(
        r.due_s, r.prompt, -(-r.max_new_tokens // mult) * mult)
        for r in requests]
    sizes = {"pad_to": int(mix["checked"]["pad_to"]),
             "passes": int(mix["checked"]["passes"])}
    ahead = threading.Thread(
        target=compile_reference, args=(ctx, state, sizes), daemon=True)
    ahead.start()
    # warm-up compiles the one executable on FIXED prompts (the same ids in
    # every run: set-up does not follow the seed): two chunks, and a short
    chunk = ctx.config["serve"]["chunk_size"]
    warm_up(ctx, eng, [[(7 * j + 3) % int(mix["vocab_below"])
                        for j in range(n)]
                       for n in (chunk + chunk // 2, 8)], new_tokens=mult)
    t = time.monotonic()
    ahead.join()
    ctx.log(f"reference: waited {time.monotonic() - t:.1f} s more for its "
            f"compiles")
    # the start-up heap frozen, no full collection in the window (the
    # young generations go on as they were): serve_replay_mla says why
    gc.collect()
    gc.freeze()
    young, middle, old = gc.get_threshold()
    gc.set_threshold(young, middle, 1 << 30)
    m = measure(ctx, eng, requests)
    gc.set_threshold(young, middle, old)
    lat = latency_stats(requests, m)
    done = [i for i, h in enumerate(m["handles"]) if h.done]
    picks = [done[(k * len(done)) // 4] for k in range(4)] if done else []
    counters = m["counters"]
    running_at_end = len(eng.running)
    rule = ctx.config["serve"]["denoise"]
    expected = expected_row_passes(
        requests, m["handles"], cfg.diffusion_block,
        reference.schedule(cfg.diffusion_block, rule["steps"]))
    passes_ok = counters.get("block_commit_passes") == \
        counters.get("blocks_committed") and (
            counters.get("block_row_passes") == expected
            if not counters.get("preemptions")
            else counters.get("block_row_passes", 0) >= expected)
    facts = serve_facts(m, lat,
                        queue_wait_p90_ms=_queue_wait_p90(requests, m))
    # the pool goes before the reference comes (the engine itself stays
    # registered with the analysis plane): the reference's float32
    # activations take its room, and the peak stays the serving's
    eng.pool.set_pages((), ())
    del eng
    gc.collect()
    chk = check_served(ctx, state, requests, m, picks, sizes)
    notes = {**lat, "steps": m["steps"], "elapsed_s": m["elapsed"],
             "requests": len(requests),
             "compiled_in_window": m["compiled_in_window"],
             "waiting_at_end": sum(1 for r, h in zip(requests, m["handles"])
                                   if not m["stamps"][h.req_id]
                                   and r.due_s <= m["elapsed"]),
             "running_at_end": running_at_end, **chk,
             "expected_block_row_passes": expected,
             "block_row_passes_ok": passes_ok,
             "out_tokens_per_s": lat["out_tokens"] / m["elapsed"],
             **{k: counters.get(k) for k in (
                 "preemptions", "block_row_passes", "block_commit_passes",
                 "blocks_committed", "block_tokens_unmasked",
                 "block_positions", "block_positions_masked",
                 "kv_tokens_provisional", "kv_tokens_written",
                 "moe_assignments_local", "moe_assignments_total",
                 "host_before_s", "call_s", "host_after_s",
                 "between_steps_s", "slow_step_s")}}
    return {
        "correct": chk["ok"] and passes_ok and m["compiled_in_window"] == 0,
        "attempted": lat["judged"], "failed": lat["missed"],
        "end_to_end": {"tbt_p95_ms": lat["tbt_p95_ms"]},
        "notes": notes,
        "facts": facts,
    }
