"""Attention kernels: jnp reference path + Pallas flash dispatch.

Reference: ``hetu/graph/ops/Attention.cc`` (wrapping vendored flash-attn2
CUDA, varlen via cu_seqlens at ``impl/kernel/FlashAttention.cu:48-56``).
On TPU the flash kernel is Pallas (``hetu_tpu/ops/pallas/flash_attention.py``);
on CPU/simulation we use the jnp path (XLA fuses it adequately for tests).

Layout convention follows the reference: [batch, seq, num_heads, head_dim],
and the flash kernels read it in place: with ``head_dim % 128 == 0`` a
head's block is a lane block of the [b, s, h*d] view, so nothing is
transposed round the call (a narrower head still goes head-major; the
shape decides, ``pallas/flash_attention.py``).  :func:`sdpa_qkv` is
self-attention straight off a fused projection's [b, s, 3*h*d]: the same
kernels with three block index maps on the one array.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import on_tpu


def sdpa_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True,
                   softmax_scale: Optional[float] = None,
                   bias: Optional[jax.Array] = None,
                   segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Plain scaled-dot-product attention, numerically standard.

    ``segment_ids`` ([batch, seq] int) implements packed/varlen attention —
    tokens attend only within their segment, the TPU-native equivalent of
    the reference's cu_seqlens varlen path (ops/Attention.h:286,371).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    # [b, h, sq, sk]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    mask = None
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        mask = (ki <= qi + (sk - sq))
    if segment_ids is not None:
        seg_mask = (segment_ids[:, :, None] == segment_ids[:, None, :])
        seg_mask = seg_mask[:, None, :, :]
        mask = seg_mask if mask is None else (mask[None, None] & seg_mask)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def sdpa(q, k, v, causal: bool = True, softmax_scale: Optional[float] = None,
         bias: Optional[jax.Array] = None,
         segment_ids: Optional[jax.Array] = None,
         use_flash: Optional[bool] = None) -> jax.Array:
    """Dispatching attention entry point: the Pallas flash kernel on
    platform ``tpu``, the jnp reference elsewhere and whenever a
    ``bias`` is given (flash takes none).  A kernel error propagates."""
    if use_flash is None:
        use_flash = on_tpu()
    if use_flash and bias is None:
        from .pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale,
                               segment_ids=segment_ids)
    return sdpa_reference(q, k, v, causal=causal,
                          softmax_scale=softmax_scale, bias=bias,
                          segment_ids=segment_ids)


def sdpa_qkv(qkv, num_heads: int, causal: bool = True,
             softmax_scale: Optional[float] = None,
             segment_ids: Optional[jax.Array] = None,
             use_flash: Optional[bool] = None) -> jax.Array:
    """Self-attention on a fused projection: ``qkv`` [b, s, 3*h*d] (q | k
    | v on the last axis, ``num_heads`` heads each) -> [b, s, h*d].
    Dispatches as :func:`sdpa` does."""
    if use_flash is None:
        use_flash = on_tpu()
    if use_flash:
        from .pallas.flash_attention import flash_attention_qkv
        return flash_attention_qkv(qkv, num_heads, causal=causal,
                                   softmax_scale=softmax_scale,
                                   segment_ids=segment_ids)
    b, s, w = qkv.shape
    q, k, v = (x.reshape(b, s, num_heads, -1)
               for x in jnp.split(qkv, 3, axis=-1))
    return sdpa_reference(q, k, v, causal=causal,
                          softmax_scale=softmax_scale,
                          segment_ids=segment_ids).reshape(b, s, w // 3)
