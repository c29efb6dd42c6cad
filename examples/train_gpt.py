"""GPT/LLaMA pre-training entry point.

Counterpart of the reference's canonical LLM pretrain script
(``examples/gpt/train_hetu.py``): argparse surface for model/parallel
config, ds_parallel_config JSON or (dp, tp, pp) flags, micro-batched
training with grad accumulation, AMP, checkpoint save/resume, and the
native prefetching dataloader.

Run (8 simulated devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/train_gpt.py --dp 2 --tp 4 --steps 20 --hidden 128 \
      --layers 2 --seq-len 64

On a TPU host ONE process drives every chip it needs (a second process
cannot have them): ``python examples/train_gpt.py --dp 2 --tp 2 --bf16``
on four chips, no flags for one.  Kernels are chosen by platform, and
the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="GPT/LLaMA pretraining")
    # model (reference train_hetu.py:479-588 surface)
    p.add_argument("--model", choices=["gpt", "llama"], default="gpt")
    p.add_argument("--vocab-size", type=int, default=50304)
    p.add_argument("--hidden", type=int, default=768)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--seq-len", type=int, default=1024)
    # parallel layout
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1, help="pipeline stages")
    p.add_argument("--sp", action="store_true", help="sequence parallel")
    p.add_argument("--grad-comm", choices=["fp32", "bf16", "int8"],
                   default=None,
                   help="explicit coalesced gradient sync transport "
                        "(None keeps the implicit GSPMD per-tensor sync)")
    p.add_argument("--flat-state", action="store_true",
                   help="flat dp-sharded optimizer state + reduce-"
                        "scatter-only sync (needs --grad-comm and "
                        "--zero 1/2/3; half the gradient wire bytes)")
    p.add_argument("--zero", type=int, default=0, choices=[0, 1, 2, 3],
                   help="ZeRO level for optimizer state/grad/param "
                        "sharding; 3 with --flat-state shards params AT "
                        "REST (1/dp fp32 masters only, just-in-time "
                        "bucket all-gather each step)")
    p.add_argument("--ds-config", type=str, default=None,
                   help="ds_parallel_config JSON path (overrides dp/tp/pp)")
    p.add_argument("--auto-parallel", action="store_true",
                   help="let the Galvatron-style planner pick "
                        "(dp, tp, pp, zero, micro-batch) for the visible "
                        "devices (overrides dp/tp/pp/zero flags)")
    p.add_argument("--calibrate", action="store_true",
                   help="with --auto-parallel: profile the live backend "
                        "(matmul/HBM/collectives) to calibrate the "
                        "planner's cost model first")
    # training
    p.add_argument("--global-batch", type=int, default=16)
    p.add_argument("--micro-batch", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--data", type=str, default=None,
                   help="token .npy file; synthetic data if omitted")
    p.add_argument("--save", type=str, default=None,
                   help="checkpoint dir (saved at the end)")
    p.add_argument("--load", type=str, default=None)
    p.add_argument("--log-every", type=int, default=5)
    p.add_argument("--trace-out", type=str, default=None,
                   help="trace the run (per-step feed/executable/commit "
                        "phase spans) and write a Perfetto-loadable "
                        "chrome trace JSON here")
    return p.parse_args(argv)


def main(argv=None):
    """Train; returns what ran (graph, model, loader, per-step losses
    and wall seconds) so a caller — ``chip_smoke.py`` — can check it."""
    args = parse_args(argv)
    import jax
    import hetu_tpu as ht
    from jax.sharding import PartitionSpec as P
    from hetu_tpu import optim
    from hetu_tpu.data import Dataloader, GPTSeqDataset
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel, llama_config
    from hetu_tpu.utils import get_logger
    from hetu_tpu.utils.compile_cache import enable_compile_cache

    log = get_logger("train_gpt")
    enable_compile_cache()
    n_dev = len(jax.devices())
    dp, tp, pp, zero = args.dp, args.tp, args.pp, args.zero
    mk = llama_config if args.model == "llama" else GPTConfig
    cfg = mk(vocab_size=args.vocab_size, hidden_size=args.hidden,
             num_layers=args.layers, num_heads=args.heads,
             max_seq_len=args.seq_len, sp=args.sp,
             dtype="bfloat16" if args.bf16 else "float32")
    if args.auto_parallel:
        # closed planner loop (reference Galvatron
        # hybrid_parallel_config.py:13): search (pp, dp, tp, zero,
        # recompute, micro-batch) for THIS model on THESE devices
        from hetu_tpu.planner import (plan_for_gpt, plan_summary,
                                      profile_and_calibrate)
        cal = profile_and_calibrate(reps=3) if args.calibrate else None
        plan = plan_for_gpt(cfg, global_batch=args.global_batch,
                            seq=args.seq_len, n_chips=n_dev,
                            calibration=cal)
        summ = plan_summary(plan)
        dp, tp, pp = summ["dp"], summ["tp"], summ["pp"]
        zero = summ["zero"]
        if args.micro_batch is None and plan.micro_batch:
            args.micro_batch = plan.micro_batch
        log.info("auto-parallel plan: %s", json.dumps(summ))
    if args.ds_config:
        with open(args.ds_config) as f:
            cfg_json = json.load(f)
        ncfg = len(cfg_json["devices"])
        assert ncfg <= n_dev, f"config wants {ncfg} devices, have {n_dev}"
        from hetu_tpu.utils.ds_config import parse_layout
        dp, tp, pp, cfg_zero = parse_layout(cfg_json)
        zero = max(zero, int(cfg_zero))  # config may carry level 0-3
    assert dp * tp * pp <= n_dev, \
        f"dp*tp*pp={dp * tp * pp} > devices={n_dev}"

    if pp > 1:
        mesh = ht.create_mesh({"pp": pp, "dp": dp, "tp": tp},
                              jax.devices()[:dp * tp * pp])
    elif dp * tp > 1:
        mesh = ht.create_mesh({"dp": dp, "tp": tp},
                              jax.devices()[:dp * tp])
    else:
        mesh = None
    # one device holds the whole [B*S, V] logits (3.3 GB in bf16 at the
    # default widths and batch 32 — with its backward it does not fit a
    # 16 GB chip), so there the LM head and the loss run fused in chunks;
    # on a mesh the vocab-parallel loss shards the logits over dp x tp
    cfg.fused_lm_ce = mesh is None
    micro = args.micro_batch or max(1, args.global_batch // dp)
    num_micro = max(1, args.global_batch // (micro * dp))

    # data: token stream -> fixed windows through the native loader
    if args.data:
        tokens = np.load(args.data)
    else:
        # seeded synthetic text: Zipf-distributed ids, the unigram
        # statistics of real text, so the first few steps already lower
        # the loss — uniform ids would leave nothing to learn
        rng = np.random.RandomState(0)
        tokens = (rng.zipf(1.2, args.global_batch * args.seq_len * 64)
                  - 1) % args.vocab_size
    ds = GPTSeqDataset(tokens, seq_len=args.seq_len)
    loader = Dataloader(ds, batch_size=args.global_batch, shuffle=True)
    print("loader:", "native C++ prefetch core" if loader._lib is not None
          else "python (the native core could not be built)")

    batch_shape = (args.global_batch, args.seq_len)
    with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
        ids = ht.parallel_placeholder(
            "int32", batch_shape, pspec=P("dp", None) if mesh else None,
            name="input_ids")
        labels = ht.parallel_placeholder(
            "int32", batch_shape, pspec=P("dp", None) if mesh else None,
            name="labels")
        if pp > 1:
            from hetu_tpu.models.gpt_pipeline import GPTPipelineModel
            model = GPTPipelineModel(cfg, num_stages=pp)
            loss = model(ids, labels, num_micro_batches=num_micro)
        else:
            model = GPTLMHeadModel(cfg)
            loss = model(ids, labels)
        train_op = optim.AdamOptimizer(
            lr=args.lr, zero=zero, grad_comm=args.grad_comm,
            flat_state=args.flat_state).minimize(loss)
        if args.load:
            from hetu_tpu.utils.checkpoint import load_model
            load_model(model, args.load)
            log.info("resumed from %s", args.load)

        tracer = None
        if args.trace_out:
            from hetu_tpu import obs
            tracer = obs.SpanTracer()
            obs.install_tracer(tracer)   # graph.run phases pick it up
        step = 0
        losses, step_seconds = [], []
        while step < args.steps:
            for batch in loader:
                if step >= args.steps:
                    break
                if isinstance(batch, tuple):   # python loader: (x, y)
                    x, y = batch
                else:                          # native loader: one matrix
                    x, y = batch[:, :args.seq_len], batch[:, args.seq_len:]
                t0 = time.perf_counter()
                # pp>1: micro-batching happens inside pipeline_spmd
                out = g.run(loss, [loss, train_op], {ids: x, labels: y},
                            num_micro_batches=1 if pp > 1 else num_micro)
                # dispatch is asynchronous: the step has taken its time
                # only once the loss is on the host
                losses.append(float(np.asarray(out[0])))
                step_seconds.append(time.perf_counter() - t0)
                step += 1
                if step % args.log_every == 0 or step == args.steps:
                    # the first two steps compile (on a mesh, twice)
                    mean = float(np.mean(step_seconds[2:])) \
                        if step > 2 else 0.0
                    tput = (args.global_batch * args.seq_len
                            / mean) if mean else 0.0
                    print(f"step {step:5d} | loss {losses[-1]:.4f} | "
                          f"{mean * 1e3:.1f} ms/step | {tput_fmt(tput)}")
        if tracer is not None:
            from hetu_tpu import obs
            obs.install_tracer(None)
            obs.write_chrome_trace(tracer.events(), args.trace_out)
            print(obs.reconcile(tracer.events()).summary())
            print(f"wrote {len(tracer.events())} trace events to "
                  f"{args.trace_out} (open at https://ui.perfetto.dev)")
        if args.save:
            from hetu_tpu.utils.checkpoint import save_model
            d = os.path.dirname(os.path.abspath(args.save))
            os.makedirs(d, exist_ok=True)
            save_model(model, args.save)
            log.info("saved to %s", args.save)
    return types.SimpleNamespace(args=args, graph=g, model=model,
                                 loader=loader, losses=losses,
                                 step_seconds=step_seconds)


def tput_fmt(tokens_per_s: float) -> str:
    if tokens_per_s >= 1e6:
        return f"{tokens_per_s / 1e6:.2f}M tok/s"
    return f"{tokens_per_s / 1e3:.1f}k tok/s"


if __name__ == "__main__":
    main()
