"""What can be known about the chip path without a chip (ISSUE 21).

- the Pallas kernels LOWER for platform ``tpu`` at the widths the smoke
  runs (``jax.export``), and — where this installation's libtpu can
  describe a v5e without owning one — Mosaic COMPILES them;
- ``chip_smoke.py``'s phases run at toy widths on the CPU with the
  kernels interpreted, and the script itself refuses to run off-chip;
- the compile-cache helper, the kernel dispatchers (no fallback), the
  engine's platform rule, the hash-keyed native build, and the flash
  kernel's placement under a dp x tp mesh.
"""
import functools
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import hetu_tpu as ht  # noqa: E402
from hetu_tpu import ops  # noqa: E402

rpa = importlib.import_module("hetu_tpu.ops.ragged_paged_attention")
kvw = importlib.import_module("hetu_tpu.ops.paged_kv_write")
fa = importlib.import_module("hetu_tpu.ops.pallas.flash_attention")
mg = importlib.import_module("hetu_tpu.ops.moe_grouped")
ssd = importlib.import_module("hetu_tpu.ops.ssd")
ix = importlib.import_module("hetu_tpu.ops.index_score")
sscan = importlib.import_module("hetu_tpu.ops.selective_scan")
gdelta = importlib.import_module("hetu_tpu.ops.gated_delta")
selatt = importlib.import_module("hetu_tpu.ops.selected_attention")

I32, BF16, F32 = jnp.int32, jnp.bfloat16, jnp.float32
# the serving step's ragged batch at GPT-2 124M widths: 16 decode rows +
# one 256-token chunk, 64-token pages, 1024-token contexts
T, S, MAXP, CHUNK, PAGE, PAGES = 272, 17, 16, 256, 64, 128


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _desc():
    return (_sds((S,), I32), _sds((S + 1,), I32), _sds((S, MAXP), I32),
            _sds((S,), I32))


def _kernel_cases():
    """name -> (fn, abstract args): every kernel at the smoke's widths."""
    def flash_grads(q, k, v):
        return jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True).astype(F32).sum(), argnums=(0, 1, 2))(
                q, k, v)

    def ragged(q, kp, vp, *d, max_q=CHUNK, mask_block=1):
        return rpa.ragged_paged_attention_pallas(
            q, kp, vp, *d, max_q=max_q, interpret=False,
            mask_block=mask_block)

    def region(rows, max_q, heads=12, kv_heads=12, maxp=32, mask_block=1):
        # one region of the serving step at the benchmark's widths
        # (Cerebras-GPT-590M: 12 kv heads x 128, 64-token pages, 32
        # pages a row; the hybrid configuration: 32 query heads on 2 kv
        # heads, 64 decode slots; the window / full K/V stack: 64 query
        # heads on 8 kv heads, 48 rows, 272 pages a row)
        t = rows * max_q
        return (functools.partial(ragged, max_q=max_q,
                                  mask_block=mask_block), (
            _sds((t, heads, 128), BF16),
            *(_sds((PAGES, kv_heads, PAGE, 128), BF16),) * 2,
            _sds((rows,), I32), _sds((rows + 1,), I32),
            _sds((rows, maxp), I32), _sds((rows,), I32)))

    def latent(quant):
        def run(q, cp, side, *d):
            return rpa.latent_ragged_paged_attention_pallas(
                q, cp, None if quant else side, *d, max_q=CHUNK,
                softmax_scale=0.1, scale_pages=side if quant else None,
                quant=quant, latent_dim=512, interpret=False)
        return run

    def kv_write(*widths):
        # a layer's KV write at the serving step's token axis (32 decode
        # slots + one 256-token chunk): (heads, row width, dtype) a pool
        t = 32 + CHUNK

        def run(tp, to, ql, cu, *arrays):
            pools, news = arrays[:len(widths)], arrays[len(widths):]
            tile = kvw.write_tile(pools)
            plan = kvw.kv_write_plan(
                tp, to, ql, cu, regions=((0, 32, 1), (32, 1, CHUNK)),
                page_size=PAGE, tile=tile)
            return kvw.paged_kv_write(pools, news, plan, tile=tile,
                                      interpret=False)
        return (run, (
            _sds((t,), I32), _sds((t,), I32), _sds((33,), I32),
            _sds((34,), I32),
            *(_sds((PAGES, h, PAGE, w), dt) for h, w, dt in widths),
            *(_sds((t, h, w), dt) for h, w, dt in widths)))

    def grouped_experts(x, idx, w, live, w1, w2):
        # an expert layer of the hybrid configuration's serving step: 64
        # decode slots + one 256-token chunk, top-22, 128 experts held
        # from offset 128, latent 1024, experts 2688 wide
        return mg.grouped_experts(x, idx, w, live, w1, w2,
                                  expert_offset=128, interpret=False)

    def grouped_gated(x, idx, w, live, w1, w2, w3):
        # an expert layer of the latent-attention configuration's step: 32
        # decode slots + one 256-token chunk, top-4, 32 gated experts of
        # 4096 x 2048 held from offset 0: walked in four tiles of 512
        return mg.grouped_experts(x, idx, w, live, w1, w2, w3,
                                  activation="silu", interpret=False)

    def latent_region(rows, max_q):
        # one region of that step's latent attention: 32 heads on a 256 |
        # 64 cache (the rotary stream padded to the 128 lanes), 272 pages
        # a row (17,408 tokens)
        def run(q, cp, rp, *d):
            return rpa.latent_ragged_paged_attention_pallas(
                q, cp, rp, *d, max_q=max_q, softmax_scale=0.19,
                latent_dim=256, interpret=False)
        return (run, (
            _sds((rows * max_q, 32, 384), F32),
            _sds((PAGES, 1, PAGE, 256), BF16), _sds((PAGES, 1, PAGE, 128), BF16),
            _sds((rows,), I32), _sds((rows + 1,), I32),
            _sds((rows, 272), I32), _sds((rows,), I32)))

    def decode_slots(x, dt, a, b, c, d, store, slots, n, fresh):
        # a mamba2 layer's one-token recurrence over the hybrid
        # configuration's state store: 64 slots of 128 heads x 64 x 128
        # float32, a whole slot (4 MB) a grid step
        return ssd.ssd_decode_slots(x, dt, a, b, c, d, store, slots, n,
                                    fresh, interpret=False)

    def scan_chunk(x, dt, a, b, c, d, store, slot, n, fresh):
        # a mamba1 layer's selective scan over a 1,024-token chunk at the
        # whole-model configuration's widths: 5,120 channels x 16 states,
        # the row's state in its slot of a 16-slot store; 64 tokens of x,
        # dt and y in VMEM at a time, B and C as scalars out of SMEM
        return sscan.selective_scan_chunk(x, dt, a, b, c, d, store, slot, n,
                                          fresh, interpret=False)

    def scan_slots(x, dt, a, b, c, d, store, slots, n, fresh):
        # ... and its one-token walk over the live slots
        return sscan.selective_scan_slots(x, dt, a, b, c, d, store, slots,
                                          n, fresh, interpret=False)

    def delta_chunk(q, k, v, a, b, store, slot, n, fresh):
        # a gdn layer's chunk form over a 256-token chunk at the two-stage
        # configuration's widths: 30 heads of 96 x 192, two side by side
        # on the state's 384 lanes, the row's state in its slot of a
        # 48-slot store; a triangular solve and five float32 matmuls a
        # block of 64 tokens
        return gdelta.gated_delta_chunk(q, k, v, a, b, store, slot, n,
                                        fresh, interpret=False)

    def delta_slots(q, k, v, a, b, store, slots, n, fresh):
        # ... and its one-token walk over the live slots: a whole 2.2 MB
        # slot a grid step, sums over sublanes against lane-broadcast
        # columns
        return gdelta.gated_delta_slots(q, k, v, a, b, store, slots, n,
                                        fresh, interpret=False)

    def delta_args(t, *tail):
        return (_sds((t, 30, 96), F32), _sds((t, 30, 96), F32),
                _sds((t, 30, 192), F32), _sds((t, 30), F32),
                _sds((t, 30), F32), _sds((48, 15, 96, 384), F32), *tail)

    def scan_args(t, *tail):
        return (_sds((t, 5120), F32), _sds((t, 5120), F32),
                _sds((16, 5120), F32), _sds((t, 16), F32), _sds((t, 16), F32),
                _sds((5120,), F32), _sds((16, 16, 40, 128), F32), *tail)

    def index_score(rows, shared):
        # a full layer's indexer over the indexed / window latent
        # configuration's step: 64 index heads x 128, 528 slots of 64 a
        # row; a 256-token chunk scores ONE context 64 queries x 16 slots a
        # grid step (two float32 tiles of 4096 x 1024), 32 decode rows a
        # context each, 32 slots a step
        def run(iq, iw, keys, table, ctx):
            return ix.index_score_pages_pallas(iq, iw, keys, table, ctx,
                                               interpret=False)
        return (run, (
            _sds((rows, 64, 128), BF16), _sds((rows, 64), F32),
            _sds((PAGES, 1, PAGE, 128), BF16),
            _sds((528,) if shared else (rows, 528), I32),
            _sds(() if shared else (rows,), I32)))

    def selected(q_cat, sel, valid):
        # a block of 32 queries of a full layer behind its gather: 128
        # heads over 2,048 rows of 640 bf16 lanes a query, a query a grid
        # step (its rows double-buffered: 5.2 MB of VMEM)
        return selatt.selected_attention_pallas(
            q_cat, sel, valid, d_c=512, scale=192 ** -0.5, interpret=False)

    def flash_qkv_grad(x):
        return jax.grad(lambda x: fa.flash_attention_qkv(
            x, 12, causal=True).astype(F32).sum())(x)

    qkv = lambda s, b=1, d=64: (_sds((b, s, 12, d), BF16),) * 3  # noqa: E731
    pages = (_sds((PAGES, 12, PAGE, 64), BF16),) * 2
    return {
        # 1024: fused single-kernel backward; 8192: split dq / dkv
        "flash_fused": (flash_grads, qkv(1024)),
        "flash_split": (flash_grads, qkv(8192)),
        # head_dim 128 read out of [b, s, h*d] as it is, at the train
        # cell's shape (Cerebras-GPT-590M, micro-batch 4): three arrays,
        # the split backward, and q | k | v on the fused projection's lanes
        "flash_native": (flash_grads, qkv(2048, 4, 128)),
        "flash_native_split": (flash_grads, qkv(4096, 1, 128)),
        "flash_qkv": (flash_qkv_grad, (_sds((4, 2048, 3 * 12 * 128), BF16),)),
        "ragged_12kv_x64": (ragged, (_sds((T, 12, 64), BF16), *pages,
                                     *_desc())),
        "ragged_decode_region": region(32, 1),
        "ragged_chunk_region": region(1, CHUNK),
        "ragged_decode_gqa16": region(64, 1, heads=32, kv_heads=2),
        "ragged_chunk_gqa16": region(1, CHUNK, heads=32, kv_heads=2),
        # a full layer of the window / full K/V stack: 16 blocks of K and
        # of V a grid step in decode and verify, each a slot's 8 kv heads;
        # 4 under a chunk, each 4 of them
        "ragged_decode_gqa8_x272": region(48, 1, 64, 8, 272),
        "ragged_verify_gqa8_x272": region(48, 2, 64, 8, 272),
        "ragged_chunk_gqa8_x272": region(1, CHUNK, 64, 8, 272),
        "latent_512_64": (latent(None), (
            _sds((T, 16, 576), F32), _sds((PAGES, 1, PAGE, 512), BF16),
            _sds((PAGES, 1, PAGE, 64), BF16), *_desc())),
        "latent_int8": (latent("int8"), (
            _sds((T, 16, 512), F32), _sds((PAGES, 1, PAGE, 512), jnp.int8),
            _sds((PAGES, 1, PAGE, 1), F32), *_desc())),
        "latent_nf4": (latent("nf4"), (
            _sds((T, 16, 512), F32), _sds((PAGES, 1, PAGE, 256), jnp.uint8),
            _sds((PAGES, 1, PAGE, 1), F32), *_desc())),
        # the benchmark's pool (16-row bf16 tiles), and the two whose
        # rows do not fill the lanes (written a page at a time)
        "kv_write_12kv_x128": kv_write((12, 128, BF16), (12, 128, BF16)),
        "kv_write_latent_512_64": kv_write((1, 512, BF16), (1, 64, BF16)),
        "kv_write_int8_sidecar": kv_write((1, 512, jnp.int8), (1, 1, F32)),
        "kv_write_latent_256_128": kv_write((1, 256, BF16), (1, 128, BF16)),
        "latent_256_128_decode_region": latent_region(32, 1),
        "latent_256_128_chunk_region": latent_region(1, CHUNK),
        "moe_grouped_experts": (grouped_experts, (
            _sds((320, 1024), BF16), _sds((320, 22), I32),
            _sds((320, 22), F32), _sds((320,), jnp.bool_),
            _sds((128, 1024, 2688), BF16), _sds((128, 2688, 1024), BF16))),
        "ssd_decode_slots": (decode_slots, (
            _sds((64, 128, 64), F32), _sds((64, 128), F32), _sds((128,), F32),
            _sds((64, 8, 128), F32), _sds((64, 8, 128), F32),
            _sds((128,), F32), _sds((64, 128, 64, 128), F32),
            _sds((64,), I32), _sds((1,), I32), _sds((64,), jnp.bool_))),
        "selective_scan_chunk": (scan_chunk, scan_args(
            1024, _sds((), I32), _sds((), I32), _sds((), jnp.bool_))),
        "selective_scan_slots": (scan_slots, scan_args(
            16, _sds((16,), I32), _sds((1,), I32), _sds((16,), jnp.bool_))),
        "gated_delta_chunk": (delta_chunk, delta_args(
            256, _sds((), I32), _sds((), I32), _sds((), jnp.bool_))),
        "gated_delta_slots": (delta_slots, delta_args(
            48, _sds((48,), I32), _sds((1,), I32), _sds((48,), jnp.bool_))),
        # 20 query heads on ONE kv head at a 1,024-token chunk: the window
        # is cut in two (ragged_paged_attention.window_split)
        # the block-wise model's block region: 64 slots two blocks of 4
        # wide (a fused row fills one), 32 query heads on 4 kv heads, 41
        # pages a row, under the block-wise mask
        "ragged_block_gqa8_x41": region(64, 8, 32, 4, 41, mask_block=4),
        "ragged_chunk_mqa20_x528": region(1, 1024, 20, 1, 528),
        "ragged_decode_mqa20_x528": region(16, 1, 20, 1, 528),
        "index_score_chunk": index_score(CHUNK, True),
        "index_score_decode": index_score(32, False),
        "selected_attention": (selected, (
            _sds((32, 128, 640), F32), _sds((32, 2048, 640), BF16),
            _sds((32, 2048), jnp.bool_))),
        "moe_grouped_gated_tiled": (grouped_gated, (
            _sds((288, 4096), BF16), _sds((288, 4), I32),
            _sds((288, 4), F32), _sds((288,), jnp.bool_),
            _sds((32, 4096, 2048), BF16), _sds((32, 2048, 4096), BF16),
            _sds((32, 4096, 2048), BF16))),
    }


# the Mosaic compile is seconds per kernel and tier-1 has none to spare:
# it takes the three that broke there — the k/v block, scoped VMEM at a
# 256-token chunk of d_c 512, the 4-bit unpack; head-major flash compiled
# as it was — and the serving step's one-token window, whose 16-row bf16
# tile is the layout most likely to be refused; of the KV write, the
# benchmark's pool and the one written in whole one-lane pages; of flash,
# the train cell's call: three lane blocks of the fused [b, s, 3*h*d]; the
# grouped experts: two whole expert matrices double-buffered (33 MB of VMEM),
# and the gated ones in tiles (three matrices of 4096 x 512, 25 MB); the
# latent chunk region at 32 heads: q and output blocks over the whole
# padded token axis in float32 (25 + 17 MB, twice); the live-slot walk:
# a 4 MB state block in and out, double-buffered (16 MB of VMEM), a
# [heads, head_dim] vector spread along the state's lanes and a sum over
# them back; the K/V call with 16 blocks of K and of V a grid step, a
# slot's 8 kv heads each (verify: 15 MB of VMEM), and with 4 of 4 heads
# beside a 256-token chunk's score tiles (35 MB, four heads unrolled);
# the indexer's scoring call: a chunk's two float32 tiles of 4096 x 1024
# (32 MB) and a head-major sum over whole tiles, a decode row's 64-row
# operand summed over sublanes into a one-row output block; the selective
# scan: blocks of B and C scalars in SMEM (whole 1,024-word tiles), 16
# state registers a channel group over a token loop; the K/V call of 20
# query heads on one kv head, whose 1,024-token window runs out of VMEM
# uncut; the block region's call at two blocks a slot under the block mask;
# the gated delta rule: float32 matmuls of 64 x 96 and 96 x 384 operands
# (neither a multiple of 128) and a 2.2 MB state block in and out; the
# attention over a dsa layer's selected rows: a query's 2.6 MB of rows
# double-buffered beside its float32 score tile of 128 x 2048, a product
# with the rows transposed and one with their first 512 lanes
AOT_CASES = ("ragged_12kv_x64", "ragged_decode_region",
             "ragged_chunk_region", "ragged_decode_gqa16",
             "ragged_verify_gqa8_x272", "ragged_chunk_gqa8_x272",
             "latent_512_64",
             "latent_nf4", "kv_write_12kv_x128", "kv_write_int8_sidecar",
             "flash_qkv", "moe_grouped_experts", "moe_grouped_gated_tiled",
             "latent_256_128_chunk_region", "ssd_decode_slots",
             "index_score_chunk", "index_score_decode",
             "selective_scan_chunk", "selective_scan_slots",
             "ragged_chunk_mqa20_x528", "ragged_block_gqa8_x41",
             "gated_delta_chunk", "gated_delta_slots", "selected_attention")


@pytest.fixture
def kernels_not_interpreted(monkeypatch):
    # flash reads the platform itself; the paged kernels take interpret=
    monkeypatch.setattr(fa, "on_tpu", lambda: True)


@pytest.mark.parametrize("name", list(_kernel_cases()))
def test_kernels_lower_for_tpu(name, kernels_not_interpreted):
    """Pallas -> Mosaic lowering needs no chip.  A k/v block of 1 on a
    kv_heads axis in second-to-last position (the seed's layout) is
    refused right here for any model with more than one KV head."""
    fn, args = _kernel_cases()[name]
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exp.mlir_module()


@pytest.mark.parametrize("rows,shared", [(CHUNK, True), (32, False)])
def test_the_indexed_layer_lowers_with_no_score_tile_and_no_key_copy(
        rows, shared, monkeypatch):
    """``hy.indexed_attention`` lowered for the TPU at the indexed / window
    latent configuration's shapes (128 heads over a 512 | 64 latent padded
    to 640, 64 index heads x 128, top 2,048 of 528 slots x 64): the scores
    come out of ONE Mosaic call, and the module's widest float32 array
    over the 33,792 positions is the scores themselves, a row a query: no
    ``[32, 64, 33792]`` tile, and no gathered ``[33792, 128]`` copy of a
    context's index keys (the XLA arithmetic has both: the control).  The
    attention over the selected rows is the module's SECOND Mosaic call,
    and the ``[32, 128, 2048]`` float32 tile of its scores is gone with it
    (the control has it)."""
    import re
    from hetu_tpu.models import hybrid as hy
    monkeypatch.setattr(ix, "on_tpu", lambda: True)
    monkeypatch.setattr(selatt, "on_tpu", lambda: True)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "dots3-ep8.json")) as f:
        geo = hy.dots3_config(json.load(f)).geometry("dsa")
    args = (_sds((rows, 64, 128), BF16), _sds((rows, 64), F32),
            _sds((rows, geo.heads, 640), F32), _sds((rows,), I32),
            _sds((528,) if shared else (rows, 528), I32),
            _sds((PAGES, 1, PAGE, 640), BF16),
            _sds((PAGES, 1, PAGE, 128), BF16))

    def lowered(use_kernel):
        def run(iq, iw, qc, qpos, table, cp, xp):
            return hy.indexed_attention(geo, iq, iw, qc, qpos, table,
                                        (cp, xp), use_kernel=use_kernel)
        return jax.export.export(jax.jit(run), platforms=["tpu"])(
            *args).mlir_module()

    def widest(text):
        # most float32 rows of 33,792 positions in one array
        return max(int(np.prod([int(d) for d in m.split("x")]))
                   for m in re.findall(r"tensor<([\dx]+)x33792xf32>", text))

    copy = re.compile(r"tensor<(\d+x)?33792x128xbf16>")
    tile = "tensor<32x128x2048xf32>"
    text = lowered(True)
    assert text.count("tpu_custom_call") == 2
    assert widest(text) == rows and not copy.search(text)
    assert tile not in text
    xla = lowered(False)
    assert widest(xla) == 32 * 64 and copy.search(xla)
    assert tile in xla and "tpu_custom_call" not in xla


_AOT_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r}); sys.path.insert(0, {tests!r})
import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
except Exception as e:
    print("NO_TOPOLOGY", type(e).__name__, e); sys.exit(0)
import test_chip_bringup as t
t.fa.on_tpu = lambda: True
sh = SingleDeviceSharding(dev)
for name in t.AOT_CASES:
    fn, args = t._kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            for a in args]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, name
    print("COMPILED", name, dev.device_kind)
"""


def test_kernels_compile_for_v5e_without_a_chip():
    """"Lowers" is necessary, not sufficient: the Mosaic compile is where
    scoped VMEM and unsupported vector ops show.  libtpu can compile for
    a described v5e topology with no chip attached; where it cannot, the
    test skips and the chip run is the only judge."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _AOT_SCRIPT.format(
            repo=REPO, tests=os.path.join(REPO, "tests"))],
        env=env, capture_output=True, text=True, timeout=300)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip("libtpu cannot describe a v5e here: " +
                    proc.stdout.strip()[-200:])
    assert proc.returncode == 0, proc.stderr[-3000:]
    compiled = [l.split()[1] for l in proc.stdout.splitlines()
                if l.startswith("COMPILED")]
    assert compiled == list(AOT_CASES)


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

TOY = chip_smoke.Sizes(vocab=256, hidden=64, layers=2, heads=4, seq=128,
                       batch=4, steps=6, page=8, max_batch=4, chunk=16,
                       new_tokens=3, prompt_lens=(5, 11, 29, 27),
                       shared_prefix=24, latent=(4, 32, 8))


@pytest.mark.parametrize("phase", ["train", "parity", "serve"])
def test_smoke_phase_runs_at_toy_widths(phase, capsys, monkeypatch,
                                        tmp_path):
    # train_gpt.main places the compile cache; with the variable set it
    # touches nothing, so this session keeps the (absent) cache it has
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    getattr(chip_smoke, f"phase_{phase}")(TOY, on_chip=False)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("chip_smoke ")][-1]
    assert f'"phase": "{phase}"' in line and '"platform": "cpu"' in line
    assert '"compile_s"' in line and '"run_s"' in line


def test_smoke_result_line_holds_exactly_the_contract_keys():
    # the driver refuses a last line with any other key (it refused
    # one that also carried "claim")
    rec = json.loads(chip_smoke.result_line(jax.devices()))
    assert rec == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert type(rec["device"]["count"]) is int


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_entry_point_refuses_to_run_without_a_tpu(script):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "TPU" in proc.stderr
    assert "{" not in proc.stdout          # no result, no metric


# ---------------------------------------------------------------------------
# compile cache, dispatch, engine rule, native build
# ---------------------------------------------------------------------------

def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    from hetu_tpu.utils import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert cc.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    (tmp_path / "jit_f-0123-cache").write_bytes(b"x")
    (tmp_path / "jit_f-0123-atime").write_bytes(b"x")
    assert cc.cache_entries(str(tmp_path)) == 1
    assert cc.cache_entries(str(tmp_path / "absent")) == 0


class _KernelBroke(Exception):
    pass


def _broken(*a, **kw):
    raise _KernelBroke("trace-time kernel error")


def test_dispatchers_propagate_kernel_errors(monkeypatch):
    q = jnp.zeros((1, 8, 2, 8))
    monkeypatch.setattr(fa, "flash_attention", _broken)
    with pytest.raises(_KernelBroke):
        ops.sdpa(q, q, q, use_flash=True)
    # a bias is the one thing that routes a flash request to the reference
    ops.sdpa(q, q, q, use_flash=True, bias=jnp.zeros((1, 2, 8, 8)))

    pages = jnp.zeros((3, 2, 4, 8))
    d = (jnp.ones((1,), I32), jnp.asarray([0, 1], I32),
         jnp.ones((1, 1), I32), jnp.ones((1,), I32))
    monkeypatch.setattr(rpa, "ragged_paged_attention_pallas", _broken)
    with pytest.raises(_KernelBroke):
        rpa.ragged_paged_attention(jnp.zeros((1, 2, 8)), pages, pages, *d,
                                   max_q=1, use_kernel=True)
    monkeypatch.setattr(rpa, "latent_ragged_paged_attention_pallas",
                        _broken)
    with pytest.raises(_KernelBroke):
        rpa.latent_ragged_paged_attention(
            jnp.zeros((1, 2, 8)), jnp.zeros((3, 1, 4, 8)), None, *d,
            max_q=1, softmax_scale=1.0, use_kernel=True)


def test_engine_picks_the_kernel_from_the_platform(monkeypatch):
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.serving import Engine
    engine_mod = importlib.import_module("hetu_tpu.serving.engine")
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=32, sp=False)
    with ht.graph("eager", create_new=True):
        state = {k: np.asarray(v) for k, v in
                 GPTLMHeadModel(cfg).state_dict().items()}
    kw = dict(num_pages=4, page_size=8, max_batch=2, chunk_size=8)
    assert Engine(state, cfg, **kw).use_kernel is False       # cpu
    monkeypatch.setattr(engine_mod, "on_tpu", lambda: True)
    assert Engine(state, cfg, **kw).use_kernel is True
    assert Engine(state, cfg, use_kernel=False, **kw).use_kernel is False


def test_native_build_is_keyed_on_its_sources(monkeypatch, tmp_path):
    build = importlib.import_module("hetu_tpu.csrc.build")
    monkeypatch.setattr(build, "_BUILD_DIR", str(tmp_path))
    monkeypatch.setattr(build, "_CACHE", {})
    # a stale binary copied along with the tree, newer than the sources
    stale = tmp_path / "libhetu_dataloader.so"
    stale.write_bytes(b"not the library these sources build")
    lib = build.load_dataloader_core(required=True)
    assert lib.hetu_loader_create is not None
    built = [n for n in os.listdir(tmp_path) if n != stale.name]
    assert len(built) == 1 and built[0].startswith("libhetu_dataloader-")
    assert os.path.basename(lib._name) == built[0]

    bad = tmp_path / "bad.cc"
    bad.write_text("this is not C++\n")
    assert build.load_native("bad", [str(bad)]) is None        # preferred
    with pytest.raises(build.NativeBuildError, match="error"):  # asked for
        build.load_native("bad", [str(bad)], required=True)


@pytest.mark.parametrize("shape", [{"dp": 4, "tp": 1}, {"dp": 2, "tp": 2}],
                         ids=["dp4", "dp2tp2"])
def test_fused_qkv_flash_is_placed_by_shard_map_under_a_mesh(devices8, shape):
    """``attention_qkv`` under a mesh: whole fused rows per batch shard
    where tp is 1 (the four-chip train cell); where tp cuts the fused
    axis across q | k | v, sliced after all and the heads sharded."""
    from hetu_tpu.nn.parallel import sharded
    mesh = ht.create_mesh(shape, devices8[:4])
    spec = P("dp", None, "tp")
    x = np.random.RandomState(0).randn(4, 64, 3 * 2 * 128).astype(np.float32)
    outs = {}
    for flash in (False, True):
        with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
            ph = ht.parallel_placeholder("float32", x.shape, pspec=spec,
                                         name="qkv")
            o = ops.attention_qkv(sharded(ph, spec), 2, causal=True,
                                  use_flash=flash)
            loss = ops.reduce_sum(ops.mul(o, o))
            (gx,) = g.make_gradients(loss, [ph])
            outs[flash] = [np.asarray(r) for r in g.run(
                loss, [o, gx], {ph: x})]
            jaxpr = str(g.analysis_handles()[-1].jaxpr)
            assert ("shard_map" in jaxpr) == flash
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_flash_is_placed_by_shard_map_under_a_mesh(devices8):
    """A Mosaic call cannot be partitioned by GSPMD (on a chip the bare
    call fails to lower under dp x tp): with the kernel chosen, attention
    runs per shard of the sharding q was annotated with."""
    from hetu_tpu.nn.parallel import sharded
    mesh = ht.create_mesh({"dp": 2, "tp": 2}, devices8[:4])
    spec = P("dp", None, "tp", None)
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(4, 64, 4, 16).astype(np.float32) for _ in range(3))
    outs = {}
    for flash in (False, True):
        with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
            ph = [ht.parallel_placeholder("float32", q.shape, pspec=spec,
                                          name=n) for n in "qkv"]
            o = ops.attention(*(sharded(t, spec) for t in ph), causal=True,
                              use_flash=flash)
            loss = ops.reduce_sum(ops.mul(o, o))
            (gq,) = g.make_gradients(loss, [ph[0]])
            outs[flash] = [np.asarray(r) for r in g.run(
                loss, [o, gq], dict(zip(ph, (q, k, v))))]
            jaxpr = str(g.analysis_handles()[-1].jaxpr)
            assert ("shard_map" in jaxpr) == flash
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
