"""Train a tiny GPT, checkpoint it (in the background), restore, and
decode with the KV-cache generation engine.

The inference half of the reference's GPT recipe (its examples stop at
training; this closes the loop a switching user expects).  Self-checking:
trains on a periodic token stream and asserts the generated continuation
reproduces the period.

Run (CPU sim):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/generate_gpt.py
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import hetu_tpu as ht  # noqa: E402
from hetu_tpu import models, optim  # noqa: E402
from hetu_tpu.models import GPTConfig, GPTLMHeadModel  # noqa: E402
from hetu_tpu.utils.checkpoint import (load_checkpoint,  # noqa: E402
                                       save_checkpoint)


def serve_demo(state, cfg, args):
    """Continuous-batching serving demo: N prompts with staggered
    wall-clock arrivals through hetu_tpu.serving.Engine; prints
    per-request TTFT/latency and aggregate tokens/s."""
    import time

    from hetu_tpu import obs
    from hetu_tpu.serving import Engine

    rng = np.random.RandomState(0)
    period = np.array([3, 7, 1, 12], np.int32)
    # --trace-out: record the full per-request trace plane and dump a
    # Perfetto-loadable chrome trace after the run (DESIGN.md §15)
    tracer = obs.SpanTracer() if args.trace_out else None
    # --spec-draft-layers N: speculative decoding behind a truncated
    # N-layer self-draft proposing --spec-k tokens per step
    # (DESIGN.md §20) — the temp-0 self-check below still holds
    # bit-for-bit, only the tokens-per-step cadence changes
    spec = None
    if args.spec_draft_layers > 0:
        from hetu_tpu.models import draft_state_from
        from hetu_tpu.serving import SpecConfig
        dstate, dcfg = draft_state_from(state, cfg,
                                        args.spec_draft_layers)
        spec = SpecConfig(dstate, dcfg, k=args.spec_k)
    eng = Engine(state, cfg, num_pages=64, page_size=8, max_batch=8,
                 prefix_cache=not args.no_prefix_cache, tracer=tracer,
                 spec=spec)
    n = args.serve_requests
    t0 = time.monotonic()
    reqs = []
    for i in range(n):
        plen = int(rng.choice([4, 6, 8]))
        phase = int(rng.randint(4))
        prompt = [int(period[(phase + j) % 4]) for j in range(plen)]
        reqs.append(eng.add_request(
            prompt, max_new_tokens=int(rng.randint(6, 14)),
            temperature=args.temperature, top_p=args.top_p, seed=i,
            arrival_time=time.monotonic() + i * args.serve_stagger))
    eng.run()
    wall = time.monotonic() - t0
    total_new = 0
    for r in reqs:
        ttft = r.first_token_time - r.submit_time
        lat = r.finish_time - r.submit_time
        total_new += r.n_generated
        print(f"req {r.req_id}: prompt {r.prompt_len:2d} tok, "
              f"+{r.n_generated:2d} new, ttft {ttft * 1e3:7.1f} ms, "
              f"latency {lat * 1e3:7.1f} ms, "
              f"preemptions {r.n_preemptions}")
        if args.temperature == 0.0:
            # the engine contract: continuous batching reproduces a solo
            # dense-cache generate() run bit-for-bit at temperature 0
            want = np.asarray(models.generate(
                state, cfg, np.asarray([r.prompt], np.int32),
                r.n_generated))[0, r.prompt_len:].tolist()
            assert r.out_tokens == want, (r.req_id, r.out_tokens, want)
    m = eng.metrics_summary()
    print(f"served {n} requests / {total_new} tokens in {wall:.2f}s "
          f"({total_new / wall:.1f} tok/s aggregate)")
    print(f"engine: {int(m['executable_calls'])} unified-step calls, "
          f"{int(m['preemptions'])} preemptions, "
          f"{int(m['compile_count'])} compiled executable(s), "
          f"{int(m['host_logit_fetches'])} host logit fetches, "
          f"ttft p90 {m['ttft']['p90'] * 1e3:.1f} ms")
    if spec is not None:
        print(f"speculative decoding: draft {args.spec_draft_layers} "
              f"of {cfg.num_layers} layers, k={args.spec_k}; "
              f"{int(m['spec_proposed'])} proposed / "
              f"{int(m['spec_accepted'])} accepted "
              f"(rate {m['spec_accept_rate']:.2f}), "
              f"{int(m['spec_bonus_tokens'])} bonus tokens, "
              f"{m['accepted_per_step']:.2f} accepted tokens/step")
    if not args.no_prefix_cache:
        print(f"prefix cache: hit rate "
              f"{m['prefix_cache_hit_rate']:.2f} "
              f"({int(m['prefix_cache_hits'])} hits / "
              f"{int(m['prefix_cache_misses'])} misses), "
              f"{int(m['prefix_cache_tokens_saved'])} prefill tokens "
              f"saved, {int(m['prefix_cache_evictions'])} evictions, "
              f"{int(m['prefix_cache_pages'])} pages cached")
    if args.temperature == 0.0:
        print("self-check OK: every served request matches its solo "
              "generate() run bit-for-bit")
    if tracer is not None:
        events = tracer.events()
        obs.write_chrome_trace(events, args.trace_out)
        print(f"\nper-request serving timelines (from the trace):")
        print(obs.timeline_summary(events))
        print("\npredicted-vs-observed reconciliation:")
        print(obs.reconcile(events).summary())
        print(f"\nwrote {len(events)} trace events to {args.trace_out} — "
              f"open it at https://ui.perfetto.dev (one track per "
              f"request)")


def cluster_demo(state, cfg, args):
    """Serving-cluster demo (``--replicas N``): staggered shared-prefix
    requests through ``serving.cluster.EngineCluster`` — prefix-aware
    routing over N replicas (disaggregated prefill/decode with
    ``--disaggregate``), per-replica hit rates, and ONE merged Perfetto
    trace with per-replica tracks plus the router's decision track."""
    import time

    from hetu_tpu import obs
    from hetu_tpu.serving import EngineCluster

    rng = np.random.RandomState(0)
    period = np.array([3, 7, 1, 12], np.int32)
    tracer = obs.SpanTracer() if args.trace_out else None
    mode = "disaggregated" if args.disaggregate else "replicated"
    cl = EngineCluster(state, cfg, num_replicas=args.replicas,
                       mode=mode, num_prefill=1, name="demo_cluster",
                       num_pages=64, page_size=8, max_batch=8,
                       prefix_cache=not args.no_prefix_cache,
                       tracer=tracer, ttl=30.0)
    n = args.serve_requests
    t0 = time.monotonic()
    header = [int(period[j % 4]) for j in range(8)]   # shared prefix
    # wave 1: one request carries the shared header into a replica's
    # prefix cache (and pays the compile)
    reqs = [cl.add_request(header + [int(period[0]), int(period[1])],
                           max_new_tokens=8,
                           temperature=args.temperature,
                           top_p=args.top_p, seed=0)]
    cl.run()
    # wave 2: staggered same-header arrivals — the router sends them
    # to the cache-holding replica (watch the `route` reasons)
    for i in range(1, n):
        tail = [int(period[(i + j) % 4]) for j in range(2)]
        reqs.append(cl.add_request(
            header + tail, max_new_tokens=int(rng.randint(6, 14)),
            temperature=args.temperature, top_p=args.top_p, seed=i,
            arrival_time=time.monotonic() + i * args.serve_stagger))
    cl.run()
    wall = time.monotonic() - t0
    total_new = sum(len(r.out_tokens) for r in reqs)
    for r in reqs:
        print(f"req {r.req_id}: prompt {len(r.prompt):2d} tok, "
              f"+{len(r.out_tokens):2d} new on replica {r.replica}"
              f" ({r.n_reroutes} reroutes)")
        if args.temperature == 0.0:
            want = np.asarray(models.generate(
                state, cfg, np.asarray([r.prompt], np.int32),
                len(r.out_tokens)))[0, len(r.prompt):].tolist()
            assert r.out_tokens == want, (r.req_id, r.out_tokens, want)
    ms = cl.metrics_summary()
    print(f"cluster served {n} requests / {total_new} tokens in "
          f"{wall:.2f}s over {ms['alive_replicas']} replicas "
          f"({mode}); fleet hit rate "
          f"{ms['prefix_cache_hit_rate']:.2f}, "
          f"{int(ms['prefix_cache_tokens_saved'])} prefill tokens "
          f"saved, {int(ms['cluster_handoffs'])} KV handoffs "
          f"({int(ms['handoff_payload_bytes'])} B priced at "
          f"{ms['handoff_predicted_s'] * 1e6:.1f} us on the wire)")
    for rid, facts in sorted(ms["per_replica"].items()):
        print(f"  {rid} [{facts['role']}]: hit rate "
              f"{facts['prefix_cache_hit_rate']:.2f}, "
              f"{facts['cached_pages']} cached pages")
    if args.temperature == 0.0:
        print("self-check OK: every routed request matches its solo "
              "generate() run bit-for-bit")
    if tracer is not None:
        events = tracer.events()
        obs.write_chrome_trace(events, args.trace_out)
        routes = [e for e in events if e.name == "route"]
        print(f"\nrouter decisions: "
              + ", ".join(f"req {e.attrs['req']}->r{e.attrs['replica']}"
                          f" ({e.attrs['reason']})" for e in routes))
        print(f"wrote {len(events)} trace events to {args.trace_out} — "
              f"one merged Perfetto timeline: r<i>/... tracks per "
              f"replica beside the router track")
    cl.close()


def slo_demo(state, cfg, args):
    """SLO traffic-plane demo (``--slo-demo``, DESIGN.md §22): mixed
    priority classes with a mid-trace burst through a 2-replica cluster
    managed by the autoscaler, with the host-RAM KV tier staging cold
    prefix pages — prints per-class latency tails against their
    targets, the scale events, and the host tier's accounting."""
    import time

    from hetu_tpu.serving import EngineCluster
    from hetu_tpu.serving.slo import (Autoscaler, DEFAULT_TARGETS,
                                      SLO_CLASSES)

    period = np.array([3, 7, 1, 12], np.int32)
    auto = Autoscaler(min_replicas=1, max_replicas=2, backlog_high=3,
                      backlog_low=0, hysteresis_steps=2,
                      cooldown_steps=8)
    cl = EngineCluster(state, cfg, num_replicas=2, name="slo_demo",
                       num_pages=64, page_size=8, max_batch=8,
                       coordinator=False, max_queue_depth=2,
                       autoscaler=auto,
                       host_tier=not args.no_prefix_cache,
                       prefix_cache=not args.no_prefix_cache)
    header = [int(period[j % 4]) for j in range(8)]
    # warm/compile in class batch (best-effort — no target to distort)
    cl.add_request(header + [3, 7], 2, slo_class="batch")
    cl.run()
    if not args.no_prefix_cache:
        # the cold sweep: warm header pages fall to host staging, the
        # same-header wave below pulls them back through the priced
        # transport instead of re-prefilling
        for r in cl.replicas:
            r.engine.prefix_cache.evict(64)
    t0 = time.monotonic()
    reqs = []
    for i in range(12):
        tail = [int(period[(i + j) % 4]) for j in range(2)]
        # sparse trough (the controller drains a replica), then a
        # dense interactive-heavy burst (it readmits it)
        dt = i * 0.04 if i < 4 else 0.16 + (i - 4) * 0.001
        c = SLO_CLASSES[(i + 2) % 3] if i < 4 \
            else ("interactive" if i % 2 else "standard")
        reqs.append(cl.add_request(header + tail, max_new_tokens=8,
                                   temperature=args.temperature,
                                   slo_class=c,
                                   arrival_time=t0 + dt))
    cl.run()
    ms = cl.metrics_summary()
    print("slo traffic plane:")
    for c in SLO_CLASSES:
        rs = [r for r in reqs if r.slo_class == c and r.token_times]
        if not rs:
            continue
        worst = max(r.token_times[0] - r.submit_time for r in rs)
        tgt = DEFAULT_TARGETS[c]["ttft_s"]
        bound = (f"(target {tgt * 1e3:.0f} ms)" if tgt
                 else "(best effort)")
        print(f"  {c:>11}: {len(rs):2d} reqs, worst ttft "
              f"{worst * 1e3:7.1f} ms {bound}")
    print(f"  scale events: {int(ms['scale_ups'])} up / "
          f"{int(ms['scale_downs'])} down; class inversions: "
          f"{int(ms['class_inversions'])}")
    print(f"  host tier: {int(ms['host_evictions'])} pages staged, "
          f"{int(ms['host_hits'])} refetched, "
          f"{int(ms['host_refetch_bytes'])} B back over the wire")
    if args.temperature == 0.0:
        for r in reqs:
            want = np.asarray(models.generate(
                state, cfg, np.asarray([r.prompt], np.int32),
                len(r.out_tokens)))[0, len(r.prompt):].tolist()
            assert r.out_tokens == want, (r.req_id, r.out_tokens, want)
        print("  self-check OK: scaling + host-tier round-trips kept "
              "every output bit-for-bit")
    cl.close()


def main():
    from hetu_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--ckpt", type=str, default="")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass (on-device; 0 disables)")
    ap.add_argument("--serve", action="store_true",
                    help="after training, push staggered requests "
                         "through the continuous-batching engine")
    ap.add_argument("--serve-requests", type=int, default=6)
    ap.add_argument("--serve-stagger", type=float, default=0.05,
                    help="arrival spacing in seconds")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable copy-on-write prefix caching "
                         "(DESIGN.md §13; on by default)")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="with --serve: speculative decoding with a "
                         "truncated N-layer self-draft (DESIGN.md "
                         "§20; 0 disables); prints the acceptance "
                         "rate, temp-0 output stays bit-for-bit")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per verify burst")
    ap.add_argument("--kv-latent-dim", type=int, default=0,
                    help="convert the restored checkpoint to MLA "
                         "compressed latent KV (DESIGN.md §21) before "
                         "decoding/serving; pages shrink to one "
                         "[latent_dim] stream per token.  Exact when "
                         ">= the joint kv rank (<= hidden size); "
                         "0 disables")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --serve: route the requests across N "
                         "engine replicas (serving.cluster, DESIGN.md "
                         "§17) and print per-replica hit rates")
    ap.add_argument("--disaggregate", action="store_true",
                    help="with --replicas N>=2: dedicated prefill/"
                         "decode replicas with priced KV-page handoff")
    ap.add_argument("--slo-demo", action="store_true",
                    help="mixed-class traffic through the autoscaled "
                         "2-replica cluster with the host-RAM KV tier "
                         "(DESIGN.md §22): per-class latency tails, "
                         "scale events, host-tier hit accounting")
    ap.add_argument("--trace-out", type=str, default="",
                    help="with --serve: trace the demo and write a "
                         "Perfetto-loadable chrome trace JSON here, "
                         "printing the per-request timeline summary")
    args = ap.parse_args()
    ckpt = args.ckpt or os.path.join(tempfile.mkdtemp(), "gpt")

    cfg = GPTConfig(vocab_size=16, hidden_size=args.hidden, num_layers=2,
                    num_heads=4, max_seq_len=32, sp=False, dropout=0.0,
                    position="learned", activation="gelu")
    period = np.array([3, 7, 1, 12], np.int32)
    data = np.tile(period, (8, 8))                       # [8, 32]

    ht.set_seed(0)
    with ht.graph("define_and_run", create_new=True) as g:
        ids = ht.placeholder("int32", (8, 32), name="ids")
        lbl = ht.placeholder("int32", (8, 32), name="lbl")
        model = GPTLMHeadModel(cfg)
        loss = model(ids, lbl)
        opt = optim.AdamOptimizer(lr=3e-3)
        train_op = opt.minimize(loss)
        feed = {ids: data, lbl: np.roll(data, -1, 1)}
        first = last = None
        for step in range(args.steps):
            out = g.run(loss, [loss, train_op], feed)
            v = float(np.asarray(out[0]))
            first = v if first is None else first
            last = v
        print(f"trained {args.steps} steps: loss {first:.3f} -> {last:.3f}")
        # background save: file IO overlaps the remaining work
        handle = save_checkpoint(model, opt, ckpt, step=args.steps,
                                 background=True)
        handle.wait(timeout=300)

    # fresh process-style restore: new graph, zeroed params, load, decode
    with ht.graph("define_and_run", create_new=True):
        model2 = GPTLMHeadModel(cfg)
        ids2 = ht.placeholder("int32", (1, 8), name="warm")
        model2.logits(ids2)  # materialize params
        # a demo checkpoint written moments ago has no generation
        # manifest to verify against — a deliberate raw load says so
        # (the unverified-restore rule forbids silent ones)
        ts = load_checkpoint(model2, None, ckpt, verify_exempt=True)
        print(f"restored checkpoint at step {ts['step']}")
        state = {k: np.asarray(v) for k, v in model2.state_dict().items()}

    if args.kv_latent_dim > 0:
        # weight-absorbed MLA conversion (DESIGN.md §21): everything
        # below — solo decode, the serving demo, the cluster demo —
        # runs on compressed latent KV pages from here on
        from hetu_tpu.models.gpt import mla_state_from
        full = 2 * cfg.kv_heads * cfg.head_dim
        state, cfg = mla_state_from(state, cfg,
                                    kv_latent_dim=args.kv_latent_dim)
        print(f"MLA conversion: {full} -> {args.kv_latent_dim} KV "
              f"floats per token per layer "
              f"({full / args.kv_latent_dim:.1f}x smaller pages)")

    prompt = np.array([[3, 7, 1, 12, 3, 7]], np.int32)
    out = np.asarray(models.generate(state, cfg, prompt, 10,
                                     temperature=args.temperature))
    print("prompt      :", prompt[0].tolist())
    print("continuation:", out[0, prompt.shape[1]:].tolist())
    if args.temperature == 0.0:
        want = [period[(2 + i) % 4] for i in range(10)]
        assert out[0, prompt.shape[1]:].tolist() == want, "pattern lost"
        print("self-check OK: greedy decode reproduces the trained period")

    if args.serve:
        if args.replicas > 1:
            cluster_demo(state, cfg, args)
        else:
            serve_demo(state, cfg, args)
    if args.slo_demo:
        slo_demo(state, cfg, args)


if __name__ == "__main__":
    main()
