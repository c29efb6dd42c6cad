"""Pallas flash-attention kernel tests (interpret mode on CPU).

Oracle: the jnp reference SDPA (itself validated against torch in
test_ops.py::TestAttention).  Covers fwd/bwd, causal/full, packed
segment-ids (varlen), LSE output, GQA-shaped inputs, odd block sizes —
in both layouts the kernels read: head_dim 128 out of [b, s, h*d] as it
is ("native"), head_dim 64 transposed to [b*h, s, d] ("head_major").
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import obs
from hetu_tpu.ops.attention import sdpa_reference
from hetu_tpu.ops.pallas import flash_attention as fa
from hetu_tpu.ops.pallas.flash_attention import (flash_attention,
                                                flash_attention_qkv,
                                                flash_attention_with_lse)

# head_dim -> the layout the kernels take it in
LAYOUTS = {128: "native", 64: "head_major"}
head_dims = pytest.mark.parametrize("d", list(LAYOUTS))


def _mk(b=2, s=128, h=2, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, d), dtype)
                 for _ in range(3))


class TestFlashForward:
    @head_dims
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal, d):
        q, k, v = _mk(d=d)
        out = flash_attention(q, k, v, causal=causal)
        ref = sdpa_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_odd_seq_blocks(self):
        # seq 96 -> block sizes fall back to smaller powers of two
        q, k, v = _mk(s=96)
        out = flash_attention(q, k, v, causal=True)
        ref = sdpa_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    @head_dims
    def test_segment_ids_packing(self, d):
        q, k, v = _mk(d=d)
        b, s = q.shape[0], q.shape[1]
        segs = jnp.asarray(np.repeat(np.arange(4), s // 4)[None].repeat(b, 0))
        out = flash_attention(q, k, v, causal=True, segment_ids=segs)
        ref = sdpa_reference(q, k, v, causal=True, segment_ids=segs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    @head_dims
    def test_fully_masked_rows_empty_contract(self, d):
        """Rows that see no valid kv position (ring varlen padding, -1 seg
        ids everywhere) must emit out=0, lse=-inf — the contract
        ring_attention's _merge/backward guards rely on — in BOTH the
        single-kv-block fast path and the multi-block accumulate path."""
        for s in (128, 384):  # 128 -> single-kv fast path; 384 -> 3 blocks
            # of 128 through the accumulate/_finalize path
            q, k, v = _mk(s=s, d=d)
            b = q.shape[0]
            # first half of each batch row is a real doc, second half pad
            seg = np.zeros((b, s), np.int32)
            seg[:, s // 2:] = -1
            # pad ids differ between q and kv so pad rows match NOTHING
            # (with shared ids, pad attends pad; use distinct sentinel)
            segs = jnp.asarray(seg)
            kv_seg = jnp.asarray(np.where(seg < 0, -2, seg))
            out, lse = flash_attention_with_lse(
                q, k, v, causal=False, segment_ids=(segs, kv_seg))
            out = np.asarray(out)
            lse = np.asarray(lse)
            assert np.all(out[:, s // 2:] == 0.0), f"s={s}"
            assert np.all(np.isneginf(lse[:, :, s // 2:])), f"s={s}"
            # valid rows still match the reference on valid kv
            ref = sdpa_reference(q[:, : s // 2], k[:, : s // 2],
                                 v[:, : s // 2], causal=False)
            np.testing.assert_allclose(out[:, : s // 2], np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)

    @head_dims
    def test_lse(self, d):
        q, k, v = _mk(d=d)
        out, lse = flash_attention_with_lse(q, k, v, causal=True)
        assert lse.shape == (2, 2, 128)
        # oracle LSE from dense logits
        d = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(1.0 * d)
        qi = jnp.arange(128)[:, None]
        ki = jnp.arange(128)[None, :]
        logits = jnp.where(ki <= qi, logits, -jnp.inf)
        ref_lse = jax.nn.logsumexp(logits, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-4, atol=1e-4)


def _sq(x):
    return jnp.sum(x.astype(jnp.float32) ** 2)


def _assert_grads(got, want, tol=1e-3):
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol, err_msg=f"d{name}")


class TestBothLayouts:
    """Forward and jax.grad in the layout each head_dim takes, at sizes
    tier-1 can afford (the slow classes below keep the larger ones)."""

    @head_dims
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("packed", [False, True])
    def test_grads_match_reference(self, d, causal, packed):
        q, k, v = _mk(b=1, s=256, d=d)      # fused backward, 2 x 2 blocks
        segs = jnp.asarray(np.repeat(np.arange(2), 128)[None]) \
            if packed else None
        obs.reset_counts()
        got = jax.grad(lambda *a: _sq(flash_attention(
            *a, causal=causal, segment_ids=segs)), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: _sq(sdpa_reference(
            *a, causal=causal, segment_ids=segs)), argnums=(0, 1, 2))(q, k, v)
        _assert_grads(got, want)
        assert [dict(key)["layout"] for key in obs.counts("flash_calls")] \
            == [LAYOUTS[d]]

    @head_dims
    def test_split_backward(self, d, monkeypatch):
        monkeypatch.setattr(fa, "_FUSED_DKV_VMEM_BYTES", 0)  # force split
        q, k, v = _mk(b=1, s=256, d=d)
        segs = jnp.asarray(np.repeat(np.arange(2), 128)[None])
        got = jax.grad(lambda *a: _sq(flash_attention(
            *a, causal=True, segment_ids=segs)), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: _sq(sdpa_reference(
            *a, causal=True, segment_ids=segs)), argnums=(0, 1, 2))(q, k, v)
        _assert_grads(got, want)

    @head_dims
    @pytest.mark.parametrize("split", [False, True])
    def test_causal_offset_as_ring_attention_passes_it(self, d, split,
                                                       monkeypatch):
        """The SYM tail half: q rows sh.. against the whole kv, the
        diagonal shifted by sh (``parallel/ring_attention.py``)."""
        if split:
            monkeypatch.setattr(fa, "_FUSED_DKV_VMEM_BYTES", 0)
        q, k, v = _mk(b=1, s=256, d=d)
        sh = 128
        scale = 1.0 / np.sqrt(d)
        qt = q[:, sh:]
        out, lse = fa._flash_fwd(qt, k, v, scale, True, None,
                                 causal_offset=sh)
        # the reference's causal mask is ki <= qi + (sk - sq): that shift
        ref, vjp = jax.vjp(lambda *a: sdpa_reference(*a, causal=True),
                           qt, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        do = jnp.asarray(np.random.RandomState(1).randn(*out.shape),
                         jnp.float32)
        got = fa._flash_bwd(scale, True, None, (qt, k, v, out, lse), do,
                            causal_offset=sh)
        _assert_grads(got, vjp(do))

    @head_dims
    @pytest.mark.parametrize("packed", [False, True])
    def test_fused_qkv_matches_reference(self, d, packed):
        """q | k | v on one array's last axis: block index maps at
        head_dim 128, slices and the head-major path at 64."""
        q, k, v = _mk(b=1, s=256, d=d)
        b, s, h, _ = q.shape
        segs = jnp.asarray(np.repeat(np.arange(2), 128)[None]) \
            if packed else None
        qkv = jnp.concatenate([x.reshape(b, s, h * d) for x in (q, k, v)],
                              axis=-1)

        def ref(x):
            return sdpa_reference(*(t.reshape(b, s, h, d) for t in
                                    jnp.split(x, 3, axis=-1)),
                                  segment_ids=segs).reshape(b, s, h * d)

        obs.reset_counts()
        np.testing.assert_allclose(
            np.asarray(flash_attention_qkv(qkv, h, segment_ids=segs)),
            np.asarray(ref(qkv)), rtol=1e-4, atol=1e-4)
        got = jax.grad(lambda x: _sq(flash_attention_qkv(
            x, h, segment_ids=segs)))(qkv)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(jax.grad(lambda x: _sq(ref(x)))(qkv)),
            rtol=1e-3, atol=1e-3)
        assert [dict(key)["layout"] for key in obs.counts("flash_calls")] \
            == [LAYOUTS[d]]

    def test_native_layout_transposes_nothing(self):
        """At head_dim 128 no operand and no result changes layout round
        the kernels, forward or backward; at 64 they do."""
        def transposes(d):
            q, k, v = _mk(b=1, s=128, d=d)
            jaxpr = jax.make_jaxpr(jax.grad(
                lambda *a: _sq(flash_attention(*a)), argnums=(0, 1, 2)))(
                    q, k, v)
            return sum(e.primitive.name == "transpose"
                       for e in jaxpr.jaxpr.eqns)

        obs.reset_counts()
        assert transposes(128) == 0
        assert obs.counts("flash_calls") == {(("layout", "native"),): 2}
        assert transposes(64) >= 12

    def test_fused_qkv_cuts_no_slice(self):
        qkv = jnp.zeros((1, 128, 3 * 2 * 128), jnp.float32)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda x: _sq(flash_attention_qkv(x, 2))))(qkv)
        # (the log-sum-exp's narrow [b*h, s, 8] is sliced; nothing as
        # wide as a head is)
        moved = [e.primitive.name for e in jaxpr.jaxpr.eqns
                 if e.primitive.name in ("slice", "split", "transpose",
                                         "concatenate")
                 and any(v.aval.shape[-1] >= 128 for v in e.invars)]
        assert moved == []


def _dense(q, k, v, offset, q_ids, kv_ids):
    """(out, lse) of causal attention with the diagonal shifted right by
    ``offset`` and (q, kv) segment ids, through the plain reference: the
    segment mask goes in as its ``bias``."""
    bias = jnp.where(q_ids[:, None, :, None] == kv_ids[:, None, None, :],
                     0.0, -jnp.inf)
    out = sdpa_reference(q, k, v, causal=True, bias=bias)
    assert k.shape[1] - q.shape[1] == offset    # the reference's own shift
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    qi = jnp.arange(q.shape[1])[:, None] + offset
    logits = jnp.where(jnp.arange(k.shape[1])[None, :] <= qi, logits + bias,
                       -jnp.inf)
    return out, jax.nn.logsumexp(logits, axis=-1)


class TestBlockGrid:
    """Grids of 2 x 2 and 2 x 3 blocks of 512: blocks below the diagonal,
    blocks it crosses (shifted or not) and blocks past it, in all four
    kernels — the file's other cases run one or two blocks a sequence."""

    @head_dims
    @pytest.mark.parametrize("packed", [False, True])
    @pytest.mark.parametrize("split", [False, True])
    @pytest.mark.parametrize("offset", [0, 512])
    def test_matches_reference(self, d, packed, split, offset, monkeypatch):
        monkeypatch.setenv("HETU_TPU_FLASH_BLOCK_FWD", "512")
        if split:
            monkeypatch.setattr(fa, "_FUSED_DKV_VMEM_BYTES", 0)
        sk = 1024 + offset
        q, k, v = _mk(b=1, s=sk, h=2, d=d)
        q = q[:, offset:]
        # documents that end inside blocks, one of them past the offset
        ends = (300, 724, 1100, sk) if packed else (sk,)
        kv_ids = jnp.asarray(np.searchsorted(ends, np.arange(sk),
                                             side="right")[None], jnp.int32)
        segs = (kv_ids[:, offset:], kv_ids) if packed else None
        scale = 1.0 / np.sqrt(d)

        out, lse = fa._flash_fwd(q, k, v, scale, True, segs,
                                 causal_offset=offset)
        do = jnp.asarray(np.random.RandomState(1).randn(*out.shape),
                         jnp.float32)
        got = fa._flash_bwd(scale, True, segs, (q, k, v, out, lse), do,
                            causal_offset=offset)

        (ref, ref_lse), vjp = jax.vjp(
            lambda *a: _dense(*a, offset, kv_ids[:, offset:], kv_ids),
            q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-4, atol=1e-4)
        _assert_grads(got, vjp((do, jnp.zeros_like(ref_lse))))


    @pytest.mark.parametrize("split", [False, True])
    def test_no_cond_carries_a_score_tile(self, split, monkeypatch):
        """Which blocks are masked is a branch round a kernel's whole
        compute body (``pl.when``: a ``cond`` over refs), never a ``cond``
        that takes the [bq, bk] scores in and hands them back: that one
        cost the forward 45 % of its time on a v5e (module docstring)."""
        monkeypatch.setenv("HETU_TPU_FLASH_BLOCK_FWD", "512")
        if split:
            monkeypatch.setattr(fa, "_FUSED_DKV_VMEM_BYTES", 0)
        q, k, v = _mk(b=1, s=1024, h=1, d=128)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: _sq(flash_attention(*a)), argnums=(0, 1, 2)))(q, k, v)

        def conds(jp):
            for e in jp.eqns:
                if e.primitive.name == "cond":
                    yield e
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from conds(sub)

        found = list(conds(jaxpr.jaxpr))
        # forward: init, below, crossed, finalize; backward: six, or 4 + 4
        assert len(found) == (12 if split else 10)
        for e in found:
            tiles = [v.aval for v in (*e.invars, *e.outvars)
                     if isinstance(v.aval, jax.core.ShapedArray)
                     and v.aval.ndim == 2 and v.aval.size >= 512 * 512]
            assert tiles == [], tiles


@pytest.mark.slow
class TestFlashBackward:
    def test_grads_match_reference(self):
        q, k, v = _mk()

        def loss_fa(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(sdpa_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3,
                                       err_msg=f"d{name}")

    def test_grads_with_segments(self):
        q, k, v = _mk(s=64)
        segs = jnp.asarray(np.repeat(np.arange(2), 32)[None].repeat(2, 0))

        def loss_fa(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, segment_ids=segs) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(
                sdpa_reference(q, k, v, causal=True, segment_ids=segs) ** 2)

        g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3,
                                       err_msg=f"d{name}")


@pytest.mark.slow
class TestSplitBackwardPath:
    """The long-sequence fallback (split dq / dkv kernels) must stay
    correct even though short tests route to the fused kernel."""

    def test_split_path_matches_reference(self, monkeypatch):
        from hetu_tpu.ops.pallas import flash_attention as fa
        monkeypatch.setattr(fa, "_FUSED_DKV_VMEM_BYTES", 0)  # force split
        q, k, v = _mk()
        segs = jnp.asarray(
            np.repeat(np.arange(2), 64)[None].repeat(2, 0))

        def loss_fa(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, segment_ids=segs) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(sdpa_reference(
                q, k, v, causal=True, segment_ids=segs) ** 2)

        g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3,
                                       err_msg=f"d{name}")

    def test_fused_and_split_agree(self, monkeypatch):
        from hetu_tpu.ops.pallas import flash_attention as fa
        q, k, v = _mk(s=256)

        def grads(q, k, v):
            return jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True) ** 2),
                argnums=(0, 1, 2))(q, k, v)

        g_fused = grads(q, k, v)
        monkeypatch.setattr(fa, "_FUSED_DKV_VMEM_BYTES", 0)
        g_split = grads(q, k, v)
        for name, a, b in zip("qkv", g_fused, g_split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")


class TestReviewRegressions:
    def test_segment_ids_under_jit(self):
        """segment_ids must be a traced arg (works inside jit/graph step)."""
        q, k, v = _mk(s=64)
        segs = jnp.asarray(np.repeat(np.arange(2), 32)[None].repeat(2, 0))
        f = jax.jit(lambda q, k, v, s: flash_attention(
            q, k, v, causal=True, segment_ids=s))
        out = f(q, k, v, segs)
        ref = sdpa_reference(q, k, v, causal=True, segment_ids=segs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        # and grads under jit
        g = jax.jit(jax.grad(lambda q, k, v, s: jnp.sum(
            flash_attention(q, k, v, segment_ids=s) ** 2)))(q, k, v, segs)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_irregular_seq_len(self):
        """Sequences with no power-of-two block fall back to one full block."""
        q, k, v = _mk(s=72)
        out = flash_attention(q, k, v, causal=True)
        ref = sdpa_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_bfloat16(self):
        q, k, v = _mk(s=128, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True)
        ref = sdpa_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2)
