"""Hydraulis end-to-end: variable-length LLM pretraining with
dispatch -> bucket packing -> packed (varlen) CP training.

Counterpart of the reference's Hydraulis workflow
(``examples/hydraulis/train_hetu.py`` + ``strategy/dynamic_pulp.py`` +
``data_utils/bucket.py``): a lognormal variable-length corpus is sorted
per global batch, dispatched across a strategy pool (MILP/greedy
makespan balancing), FFD-packed into per-strategy buckets, and trained
packed — segment ids give exact varlen masking through flash/ring
attention (the reference's cu_seqlens path), with CP (ring attention)
active when the mesh has a cp axis.

Self-checking: trains, prints losses, and verifies (a) every sequence is
dispatched exactly once, (b) packing stays within each strategy's
max_seqlen, (c) the packed loss stream is finite and trends down.

Run (8 simulated devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/train_hydraulis.py --steps 8
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def parse_args():
    p = argparse.ArgumentParser(description="Hydraulis varlen pretraining")
    p.add_argument("--vocab-size", type=int, default=512)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--max-seqlen", type=int, default=256)
    p.add_argument("--global-batch", type=int, default=16)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--cp", type=int, default=2)
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def make_corpus(rng, n_docs, vocab, max_len):
    """Lognormal doc lengths (the reference's CommonCrawl-style skew)."""
    lens = np.clip(np.exp(rng.normal(4.2, 0.8, n_docs)).astype(int) + 8,
                   16, max_len)
    return [rng.randint(0, vocab, L).astype(np.int32) for L in lens]


def main():
    args = parse_args()
    import jax
    import hetu_tpu as ht
    from hetu_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from jax.sharding import PartitionSpec as P
    from hetu_tpu import optim
    from hetu_tpu.data.bucket import (Bucket, get_sorted_batch_and_len)
    from hetu_tpu.models import GPTLMHeadModel, llama_config
    from hetu_tpu.planner import (ChipSpec, ClusterSpec, DispatchStrategy,
                                  dynamic_dispatch)

    rng = np.random.RandomState(args.seed)
    n_dev = args.dp * args.cp * args.tp
    assert n_dev <= len(jax.devices()), \
        f"need {n_dev} devices, have {len(jax.devices())}"
    mesh = ht.create_mesh({"dp": args.dp, "cp": args.cp, "tp": args.tp},
                          jax.devices()[:n_dev])

    # -- strategy pool: a long-sequence tier and a short-sequence tier
    # (reference generate_strategy.py; coefficients here are the analytic
    # tp-scaled quadratic — profile_hardware can refit them)
    pool = [
        DispatchStrategy(tp=args.tp, pp=1, cp=args.cp, a=1e-9, b=1e-6,
                         c=1e-4, max_seqlen=args.max_seqlen),
        DispatchStrategy(tp=args.tp, pp=1, cp=1, a=4e-9, b=4e-6,
                         c=1e-4, max_seqlen=args.max_seqlen // 2),
    ]

    corpus = make_corpus(rng, args.global_batch * args.steps * 2,
                         args.vocab_size, args.max_seqlen)

    cfg = llama_config(vocab_size=args.vocab_size, hidden_size=args.hidden,
                       num_layers=args.layers, num_heads=args.heads,
                       max_seq_len=args.max_seqlen, sp=False,
                       cp_axis="cp")
    pad_id = 0

    with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
        # one placeholder shape per strategy tier (graph shape-buckets
        # re-use compiled plans across iterations)
        rows = args.global_batch  # fixed packed-row budget per tier
        feeds = {}
        for j, st in enumerate(pool):
            feeds[j] = (
                ht.parallel_placeholder("int32", (rows, st.max_seqlen),
                                        pspec=P("dp", None),
                                        name=f"ids{j}"),
                ht.parallel_placeholder("int32", (rows, st.max_seqlen),
                                        pspec=P("dp", None),
                                        name=f"lbl{j}"),
                ht.parallel_placeholder("int32", (rows, st.max_seqlen),
                                        pspec=P("dp", None),
                                        name=f"seg{j}"),
            )
        model = GPTLMHeadModel(cfg)
        losses_ops = {}
        opt = optim.AdamOptimizer(lr=args.lr)
        for j, (ids, lbl, seg) in feeds.items():
            loss = model(ids, lbl, segment_ids=seg)
            losses_ops[j] = (loss, opt.minimize(loss))

        step_losses = []
        for step in range(args.steps):
            batch_docs = [corpus[(step * args.global_batch + i)
                                 % len(corpus)]
                          for i in range(args.global_batch)]
            maxlen = max(len(d) for d in batch_docs)
            global_batch = np.full((len(batch_docs), maxlen), pad_id,
                                   np.int32)
            for i, d in enumerate(batch_docs):
                global_batch[i, :len(d)] = d
            sorted_batch, sorted_lens = get_sorted_batch_and_len(
                global_batch, pad_id)

            # dispatch sequences across the pool (makespan balancing)
            groups = dynamic_dispatch(pool, sorted_lens, use_ilp=False)
            assert sum(len(gr) for gr in groups) == len(sorted_lens), \
                "dispatch must cover every sequence exactly once"

            iter_losses = []
            for j, idxs in enumerate(groups):
                if not len(idxs):
                    continue
                st = pool[j]
                # FFD-pack this tier's sequences (alignment = 2*cp so
                # the SYM/ring split divides evenly)
                in_b = Bucket(pad_id, st.max_seqlen,
                              alignment=max(16, 2 * args.cp))
                lb_b = Bucket(pad_id, st.max_seqlen,
                              alignment=max(16, 2 * args.cp))
                for i in idxs:
                    n = int(sorted_lens[i])
                    seq = sorted_batch[i, :n]
                    in_b.add_data(seq[:-1], n - 1)
                    lb_b.add_data(seq[1:], n - 1)
                in_b.pack_data()
                lb_b.pack_data()
                packed = in_b.packed_batch
                labels = lb_b.packed_batch
                assert packed.shape[1] <= st.max_seqlen, \
                    f"packed width {packed.shape[1]} > {st.max_seqlen}"
                # segment ids from packed cu_seqlens; -1 on padding —
                # cu offsets are alignment-padded, so mark only each
                # doc's VALID span (alignment-gap positions stay -1 and
                # their labels -100: no training on padding)
                segs = np.full(packed.shape, -1, np.int32)
                for r, (cu, lens) in enumerate(zip(
                        in_b.packed_cu_seqlens_list,
                        in_b.packed_valid_lens_list)):
                    for d0 in range(len(lens)):
                        segs[r, cu[d0]:cu[d0] + lens[d0]] = d0
                lbls = np.where(segs >= 0, labels, -100).astype(np.int32)
                # fixed feed shape: pad rows + width to the tier budget
                IDS = np.full((rows, st.max_seqlen), pad_id, np.int32)
                LBL = np.full((rows, st.max_seqlen), -100, np.int32)
                SEG = np.full((rows, st.max_seqlen), -1, np.int32)
                r, w = packed.shape
                assert r <= rows, f"packed rows {r} > budget {rows}"
                IDS[:r, :w] = packed
                LBL[:r, :w] = lbls
                SEG[:r, :w] = segs
                ids_t, lbl_t, seg_t = feeds[j]
                loss, op = losses_ops[j]
                out = g.run(loss, [loss, op],
                            {ids_t: IDS, lbl_t: LBL, seg_t: SEG})
                iter_losses.append(float(np.asarray(out[0])))
            step_loss = float(np.mean(iter_losses))
            step_losses.append(step_loss)
            sizes = [len(gr) for gr in groups]
            print(f"step {step:3d} | loss {step_loss:.4f} | "
                  f"dispatch {sizes} | packed tiers "
                  f"{[pool[j].max_seqlen for j in range(len(pool))]}")

    assert all(np.isfinite(step_losses)), step_losses
    assert step_losses[-1] < step_losses[0], \
        f"loss did not decrease: {step_losses}"
    print(f"hydraulis e2e OK: {step_losses[0]:.4f} -> {step_losses[-1]:.4f}")


if __name__ == "__main__":
    main()
