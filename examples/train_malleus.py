"""Malleus end-to-end: elastic training with straggler injection,
profiling, re-solving, and live strategy hot-switch.

Counterpart of the reference's Malleus workflow
(``examples/malleus/pretrain_gpt.py`` + ``test_straggler_workload.py`` +
``test_accuracy.py``): train a GPT under an initial dp x tp layout,
inject a synthetic straggler workload mid-run, profile per-device step
ratios, re-solve the hetero layout with the StrategyModel (optionally
calibrated from live measurements via planner.profile_hardware), and
hot-switch parameters + optimizer states to the new layout without
losing training state.

Self-checking accuracy gate (the reference's ``test_accuracy``): the
loss stream must be continuous across the switch — the first loss after
the switch may not regress by more than a small epsilon vs the last loss
before it, and the final loss must be below the initial one.

Run (8 simulated devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/train_malleus.py --steps 12
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def parse_args():
    p = argparse.ArgumentParser(description="Malleus elastic pretraining")
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--switch-at", type=int, default=6)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--calibrate", action="store_true",
                   help="measure comm/compute constants first "
                        "(profile_hardware) instead of defaults")
    p.add_argument("--straggle", type=float, default=3.0,
                   help="slowdown ratio injected on device 0")
    return p.parse_args()


def main():
    args = parse_args()
    if args.steps <= args.switch_at + 2:
        raise SystemExit(
            f"--steps ({args.steps}) must exceed --switch-at + 2 "
            f"({args.switch_at + 2}): the run needs profile steps and at "
            "least one post-switch step for the accuracy gate")
    import jax
    import hetu_tpu as ht
    from jax.sharding import PartitionSpec as P
    from hetu_tpu import optim
    from hetu_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from hetu_tpu.elastic import Straggler, StragglerWorkload, StrategyModel
    from hetu_tpu.elastic.trainer import Trainer
    from hetu_tpu.models import GPTLMHeadModel, llama_config

    n_dev = min(8, len(jax.devices()))
    devices = jax.devices()[:n_dev]
    mesh = ht.create_mesh({"dp": n_dev // 2, "tp": 2}, devices)

    if args.calibrate:
        from hetu_tpu.planner import profile_and_calibrate
        cal = profile_and_calibrate(
            mesh=mesh, axis="tp", matmul_sizes=(256, 512),
            hbm_bytes=1 << 22, coll_sizes=(1 << 12, 1 << 15), reps=3)
        solver = StrategyModel.from_calibration(
            cal, num_devices=n_dev, num_layers=args.layers,
            batch=args.global_batch, seq=args.seq_len,
            hidden=args.hidden, ffn=4 * args.hidden)
        print(f"calibrated: layer_comm_cost={solver.layer_comm_cost:.4f} "
              f"pipeline_p2p_cost={solver.pipeline_p2p_cost:.4f}")
    else:
        solver = StrategyModel(num_devices=n_dev, num_layers=args.layers)

    cfg = llama_config(vocab_size=args.vocab_size, hidden_size=args.hidden,
                       num_layers=args.layers, num_heads=args.heads,
                       max_seq_len=args.seq_len, sp=False)
    rng = np.random.RandomState(0)
    with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
        ids = ht.parallel_placeholder(
            "int32", (args.global_batch, args.seq_len),
            pspec=P("dp", None), name="ids")
        lbl = ht.parallel_placeholder(
            "int32", (args.global_batch, args.seq_len),
            pspec=P("dp", None), name="lbl")
        model = GPTLMHeadModel(cfg)
        loss = model(ids, lbl)
        opt = optim.AdamOptimizer(lr=args.lr)
        train_op = opt.minimize(loss)

        # two fixed batches cycled (memorizable corpus -> the loss can
        # actually fall, which the accuracy gate below requires)
        batches = []
        for b in range(2):
            I = np.random.RandomState(b).randint(
                0, args.vocab_size,
                (args.global_batch, args.seq_len)).astype(np.int32)
            batches.append({ids: I, lbl: np.roll(I, -1, 1)})

        def data_provider(step):
            return batches[step % len(batches)]

        straggler = Straggler(n_dev)
        trainer = Trainer(g, loss, train_op, opt, data_provider, solver,
                          straggler=straggler, switch_threshold=0.02)

        # phase 1: homogeneous layout
        pre = trainer.train_steps(args.switch_at)
        print("pre-switch losses:", [round(x, 4) for x in pre])

        # inject a straggler (reference test_straggler_workload.py) and
        # retune from the *measured* profile
        ratios = [args.straggle] + [1.0] * (n_dev - 1)
        straggler.inject(StragglerWorkload(ratios))
        trainer.profile(steps=2)
        measured = straggler.read_profile()
        print("measured straggler ratios:", [round(r, 2) for r in measured])
        switched = trainer.retune(measured)
        print("retune -> switched:", switched,
              "| strategy:", trainer.current_strategy.describe()
              if trainer.current_strategy else None)

        # phase 2: continue training on the (possibly new) layout
        post = trainer.train_steps(args.steps - args.switch_at - 2)
        print("post-switch losses:", [round(x, 4) for x in post])

    # -- accuracy gates (reference examples/malleus/test_accuracy.py)
    all_losses = pre + post
    assert all(np.isfinite(all_losses)), all_losses
    # continuity: first post-switch loss must not regress vs the last
    # pre-switch loss by more than 10% of its magnitude
    assert post[0] <= pre[-1] + 0.1 * abs(pre[-1]), (pre[-1], post[0])
    assert all_losses[-1] < all_losses[0], all_losses
    hist = trainer.history
    print(f"malleus e2e OK: {all_losses[0]:.4f} -> {all_losses[-1]:.4f} | "
          f"switches recorded: {len(hist)}")


if __name__ == "__main__":
    main()
