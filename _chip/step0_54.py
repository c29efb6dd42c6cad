"""Step 0 of PR 54: ``flash_fwd`` and ``flash_bwd_fused`` alone at the train
cells' call shape (q | k | v of 12 heads x 128 on the lanes of one bf16
[4, 2048, 4608], causal): ms a call (host clock round CALLS calls, the
device kept busy) and the compile seconds of each variant.

    python _chip/step0_54.py <checkout root> <label>

Any checkout times its kernels as they are, and its forward at blocks of
512.  The tree the diagonal WALK was timed in is this one under
``git apply _chip/walk_54.patch`` (a diagonal block in ``_DIAG_SPLIT``
sub-blocks of q rows, each against the kv columns it can see): there the
script also times every sub-block width, and the walk forced to ONE
sub-block, which is the whole tile masked in a branch of its own — what the
final tree does.  The session also cut the walk the other way (sub-blocks
of kv columns, each for the rows at or below it) and ran the blocks below
the diagonal in chunks, through knobs that were not kept: PERF.md, PR 54,
has every row."""
import json
import os
import sys
import time

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_compilation_cache", False)
from hetu_tpu.ops.pallas import flash_attention as fa

assert fa.__file__.startswith(root), fa.__file__
B, S, H, D = 4, 2048, 12, 128
SCALE = 1.0 / np.sqrt(D)
CALLS = 200
walks = hasattr(fa, "_DIAG_SPLIT")     # under _chip/walk_54.patch alone

rng = np.random.RandomState(54)
qkv = jnp.asarray(rng.randn(B, S, 3 * H * D), jnp.bfloat16)
do = jnp.asarray(rng.randn(B, S, H * D), jnp.bfloat16)


def fwd(x):
    return fa._qkv_fwd(x, None, H, SCALE, True)


def bwd(x, g, out, lse):
    return fa._bwd_call(fa._Layout(B, H, D), x, x, x, (0, H, 2 * H), g, out,
                        lse, S, S, SCALE, True, None, 0)


def timed(fn, *args):
    t0 = time.perf_counter()
    # (a new function each time: jit's trace cache is keyed by identity,
    # and a variant is module state read while tracing)
    run = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    res = jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            res = run(*args)
        jax.block_until_ready(res)
        best = min(best, (time.perf_counter() - t0) / CALLS * 1e3)
    return res, round(best, 4), round(compile_s, 2)


def variant(block_fwd=None, split=1, width=None):
    """``split`` 1 is the parent's whole tile (``_causal_mask``'s
    ``lax.cond``); ``width``: the walk forced to one sub-block of that
    many rows, the whole tile under ``_diag_mask`` in a branch."""
    os.environ.pop("HETU_TPU_FLASH_BLOCK_FWD", None)
    if block_fwd:
        os.environ["HETU_TPU_FLASH_BLOCK_FWD"] = str(block_fwd)
    if walks:
        fa._DIAG_SPLIT = split
        fa._diag_width = (lambda *a: width) if width else diag_width


def diff(got, want):
    return max(float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max())
               for a, b in zip(got, want))


diag_width = getattr(fa, "_diag_width", None)
rows = []
variant()
(out0, lse0), ms, cs = timed(fwd, qkv)
rows.append(("fwd", "blocks 1024, whole", ms, cs, 0.0))
grads0, ms, cs = timed(bwd, qkv, do, out0, lse0)
rows.append(("bwd", "blocks 512, whole", ms, cs, 0.0))
variant(block_fwd=512)
(out, _), ms, cs = timed(fwd, qkv)
rows.append(("fwd", "blocks 512, whole", ms, cs, diff([out], [out0])))
for block, split in ((1024, 2), (1024, 4), (1024, 8), (512, 2), (512, 4),
                     (1024, 0)) if walks else ():
    variant(block_fwd=block, split=split or 1, width=0 if split else block)
    (out, lse), ms, cs = timed(fwd, qkv)
    name = f"walk w={block // split}" if split else "whole, no lax.cond"
    rows.append(("fwd", f"blocks {block}, {name}", ms, cs,
                 diff([out], [out0])))
for split in (2, 4, 0) if walks else ():
    variant(split=split or 1, width=0 if split else 512)
    grads, ms, cs = timed(bwd, qkv, do, out0, lse0)
    name = f"walk w={512 // split}" if split else "whole, no lax.cond"
    rows.append(("bwd", f"blocks 512, {name}", ms, cs, diff(grads, grads0)))

dev = jax.devices()[0]
print(f"step0_54 {sys.argv[2]} on {dev.platform} {dev.device_kind}; "
      f"{CALLS} calls x 3, best; max |diff| against the whole-tile result")
for kernel, name, ms, cs, err in rows:
    print(f"  {kernel}  {name:36s} {ms:8.4f} ms/call  compile {cs:6.2f} s"
          f"  diff {err:.3g}")
os.makedirs("chiprun_out", exist_ok=True)
with open(f"chiprun_out/step0_54_{sys.argv[2]}.json", "w") as f:
    json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
