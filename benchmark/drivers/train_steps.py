"""Driver ``train_steps``: pre-training steps through the program's normal
entry points — ``ht.graph("define_and_run")``, ``GPTLMHeadModel``,
``AdamOptimizer(...).minimize``, the native ``Dataloader``, ``g.run`` —
the calls ``examples/train_gpt.py`` makes (its ``main`` takes a step count
and fixes its data seed, so its thirty lines are re-stated here).

Set-up: build, draw the initial weights, take the plain reference's loss
on one sequence at those weights, then run the warm-up steps (the first
feeds that one sequence in every row, so its loss is the system's loss on
it).  Window: whole steps until ``--seconds`` is up, each timed to the
loss on the host.  tokens/s = all tokens of all steps over all the time.
"""
from __future__ import annotations

import time

import numpy as np


def run(ctx) -> dict:
    import jax
    import hetu_tpu as ht
    import reference
    import traffic as traffic_lib
    from jax.sharding import PartitionSpec as P
    from hetu_tpu import optim
    from hetu_tpu.csrc.build import load_dataloader_core
    from hetu_tpu.data import Dataloader, GPTSeqDataset
    from drivers_util import gpt_config

    c, mix, lay = ctx.config, ctx.traffic, ctx.config["train_layout"]
    batch, seq = int(mix["global_batch"]), int(mix["seq_len"])
    dp, tp = int(lay["dp"]), int(lay["tp"])
    cfg = gpt_config(c)
    mesh = ht.create_mesh({"dp": dp, "tp": tp}, jax.devices()[:dp * tp]) \
        if dp * tp > 1 else None
    # one device holds the whole [B*S, V] logits, so there the LM head and
    # the loss run fused in chunks; on a mesh the vocab-parallel loss
    # shards them (as examples/train_gpt.py decides it)
    cfg.fused_lm_ce = mesh is None
    micro = int(mix["micro_batch"])
    num_micro = batch // (micro * dp)
    if num_micro * micro * dp != batch:
        raise ValueError(f"global batch {batch} is not micro-batch {micro} "
                         f"x dp {dp} x a whole number")

    load_dataloader_core(required=True)      # the native loader or an error
    tokens = traffic_lib.token_stream(mix, ctx.seed, cfg.vocab_size)
    loader = Dataloader(GPTSeqDataset(tokens, seq_len=seq), batch_size=batch,
                        shuffle=True, seed=ctx.seed % (2 ** 31))
    if loader._lib is None:
        raise RuntimeError("the python loader ran, not the native core")

    def batches():
        while True:
            for b in loader:
                yield b[:, :seq], b[:, seq:]
    feed = batches()

    ht.set_seed(ctx.seed % (2 ** 31))
    pspec = P("dp", None) if mesh else None
    with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
        ids = ht.parallel_placeholder("int32", (batch, seq), pspec=pspec,
                                      name="input_ids")
        labels = ht.parallel_placeholder("int32", (batch, seq), pspec=pspec,
                                         name="labels")
        from hetu_tpu.models import GPTLMHeadModel
        model = GPTLMHeadModel(cfg)
        loss = model(ids, labels)
        train_op = optim.AdamOptimizer(
            lr=float(mix["lr"]), zero=int(lay["zero"]),
            grad_comm=lay["grad_comm"],
            flat_state=bool(lay["flat_state"])).minimize(loss)

        def step(x, y):
            out = g.run(loss, [loss, train_op], {ids: x, labels: y},
                        num_micro_batches=num_micro)
            # dispatch is asynchronous: the step has taken its time only
            # once the loss is on the host
            return out[0]

        # -- correctness, outside the window: the reference's loss on one
        # sequence at the initial weights vs the system's on the same
        x0, y0 = next(feed)
        x1 = np.repeat(x0[:1], batch, 0)
        y1 = np.repeat(y0[:1], batch, 0)
        t = time.monotonic()
        params = {name: g.get_tensor_value(p)
                  for name, p in model.named_parameters()}
        ref_loss = reference.loss(params, x1[0], y1[0], cfg.num_layers,
                                  cfg.num_heads, c["layer_norm_epsilon"])
        del params
        ctx.log(f"reference loss {ref_loss:.5f} in {time.monotonic() - t:.1f} s")
        t = time.monotonic()
        first = step(x1, y1)
        returned_bf16 = str(getattr(first, "dtype", "")) == "bfloat16"
        sys_loss = float(np.asarray(first, np.float32))
        ctx.log(f"first step (compiles) {time.monotonic() - t:.1f} s, "
                f"loss {sys_loss:.5f}")
        tol = reference.LOSS_TOL + (reference.BF16_LOSS_STEP
                                    if returned_bf16 else 0.0)
        # warm-up: on a mesh the second step compiles a second executable
        for _ in range(int(mix["warmup_steps"]) - 1):
            t = time.monotonic()
            float(np.asarray(step(*next(feed)), np.float32))
            ctx.log(f"warm-up step {time.monotonic() - t:.2f} s")
        plans_before = len(g._plan_pool)

        tracer = None
        if ctx.trace:
            from hetu_tpu import obs
            tracer = obs.SpanTracer()
            obs.install_tracer(tracer)
        losses, step_s = [], []
        steps_before_trace = None
        ctx.begin_window()
        while ctx.in_window():
            if ctx.trace_started_at is not None and steps_before_trace is None:
                steps_before_trace = len(losses)
            t = time.monotonic()
            with ctx.span("next_loader"):
                x, y = next(feed)
            with ctx.span("g.run"):
                out = step(x, y)
            with ctx.span("fetch_loss"):
                losses.append(float(np.asarray(out, np.float32)))
            step_s.append(time.monotonic() - t)
        elapsed = ctx.end_window()
        if tracer is not None:
            from hetu_tpu import obs
            obs.install_tracer(None)
        compiled_in_window = len(g._plan_pool) - plans_before

    n = len(losses)
    q = max(1, n // 4)
    finite = bool(np.isfinite(losses).all())
    falls = float(np.mean(losses[-q:])) < float(np.mean(losses[:q]))
    agrees = abs(sys_loss - ref_loss) <= tol
    mesh_ok = mesh is None or len({d.id for d in mesh.devices.flat}) == dp * tp
    tokens_per_s = n * batch * seq / elapsed
    # starting the profiler stalls the loop for seconds (9 s on four chips,
    # PR 24): the traced run's own rate, for mfu, is taken before it
    rate_for_mfu = tokens_per_s
    if steps_before_trace:
        rate_for_mfu = steps_before_trace * batch * seq / (
            ctx.trace_started_at - ctx.t0)
    notes = {"steps": n, "elapsed_s": elapsed, "loss_first": losses[0],
             "loss_last": losses[-1], "system_loss": sys_loss,
             "reference_loss": ref_loss, "loss_tol": tol,
             "loss_returned_bf16": returned_bf16,
             "compiled_in_window": compiled_in_window,
             "median_step_s": float(np.median(step_s)),
             "checks": {"finite": finite, "falls": falls, "agrees": agrees,
                        "mesh": mesh_ok,
                        "no_compile": compiled_in_window == 0}}
    return {
        "correct": finite and falls and agrees and mesh_ok
        and compiled_in_window == 0,
        "attempted": n, "failed": 0 if finite else int(
            np.sum(~np.isfinite(losses))),
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "notes": notes,
        "facts": {"values": {"tokens_per_s": rate_for_mfu, "steps": n,
                             "window_s": elapsed, "seq_len": seq,
                             "micro_batch": micro, "num_micro": num_micro,
                             "global_batch": batch},
                  "host_spans": tracer.events() if tracer else [],
                  "counters": {}},
    }
