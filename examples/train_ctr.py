"""CTR training entry point (WDL / DeepFM / DCN).

Counterpart of the reference's CTR recipes (``v1/examples/ctr/run_hetu.py``
over Criteo/Adult): synthetic Criteo-like data by default, pluggable
embedding backend — dense, HET-style cached (``--cached-embedding``), or
any compression method (``--compress hash|robe|tt|...``).

Run: JAX_PLATFORMS=cpu python examples/train_ctr.py --model deepfm \
         --steps 50 --cached-embedding
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

COMPRESSORS = {
    "hash": ("HashEmbedding", dict(table_size=1 << 14)),
    "compo": ("CompositionalEmbedding", dict(num_buckets=1 << 10)),
    "robe": ("ROBEEmbedding", dict(robe_size=1 << 16)),
    "dpq": ("DPQEmbedding", dict(num_codebooks=4, codebook_size=64)),
    "tt": ("TensorTrainEmbedding", dict(ranks=16)),
    "lowrank": ("LowRankEmbedding", dict(rank=8)),
    "quant": ("QuantizedEmbedding", dict(bits=8)),
}


def parse_args():
    p = argparse.ArgumentParser(description="CTR training")
    p.add_argument("--model", choices=["wdl", "deepfm", "dcn"],
                   default="wdl")
    p.add_argument("--vocab-size", type=int, default=100000)
    p.add_argument("--fields", type=int, default=26)
    p.add_argument("--dense", type=int, default=13)
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--cached-embedding", action="store_true",
                   help="HET-style device cache over a host master table")
    p.add_argument("--cache-size", type=int, default=1 << 14)
    p.add_argument("--compress", choices=sorted(COMPRESSORS), default=None)
    return p.parse_args()


def main():
    args = parse_args()
    import hetu_tpu as ht
    from hetu_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from hetu_tpu import optim
    from hetu_tpu.models.ctr import DCN, DeepFM, WDL, ctr_loss

    rng = np.random.RandomState(0)
    n_samples = args.batch * 64
    ids_all = rng.randint(0, args.vocab_size,
                          (n_samples, args.fields)).astype(np.int32)
    dense_all = rng.randn(n_samples, args.dense).astype(np.float32)
    w = rng.randn(args.dense)
    labels_all = (dense_all @ w + 0.1 * rng.randn(n_samples) > 0) \
        .astype(np.float32)

    cls = {"wdl": WDL, "deepfm": DeepFM, "dcn": DCN}[args.model]
    with ht.graph("define_and_run", create_new=True) as g:
        emb = None
        if args.cached_embedding:
            from hetu_tpu.embedding import CachedEmbedding
            emb = CachedEmbedding(args.vocab_size, args.dim,
                                  cache_size=args.cache_size, policy="lfu")
        elif args.compress:
            import hetu_tpu.embedding as E
            cls_name, kw = COMPRESSORS[args.compress]
            emb = getattr(E, cls_name)(args.vocab_size, args.dim, **kw)
        sp = ht.placeholder("int32", (args.batch, args.fields), name="sp")
        dn = ht.placeholder("float32", (args.batch, args.dense), name="dn")
        lb = ht.placeholder("float32", (args.batch,), name="lb")
        model = cls(args.fields, args.vocab_size, embedding_dim=args.dim,
                    num_dense=args.dense, embedding=emb)
        loss = ctr_loss(model(sp, dn), lb)
        opt = optim.AdamOptimizer(lr=args.lr)
        train_op = opt.minimize(loss)
        if args.cached_embedding:
            emb.attach_optimizer(opt)
        for step in range(args.steps):
            s = (step * args.batch) % (n_samples - args.batch)
            ids = ids_all[s:s + args.batch]
            feed_ids = emb.prepare_batch(ids) if args.cached_embedding \
                else ids
            out = g.run(loss, [loss, train_op],
                        {sp: feed_ids, dn: dense_all[s:s + args.batch],
                         lb: labels_all[s:s + args.batch]})
            if (step + 1) % 10 == 0:
                print(f"step {step + 1:4d} | loss "
                      f"{float(np.asarray(out[0])):.4f}")
        if args.cached_embedding:
            emb.flush()
            print("cache:", emb.hit_info)


if __name__ == "__main__":
    main()
