"""Autoregressive generation with a static KV cache.

Inference companion to the training stack: takes a trained
:class:`~hetu_tpu.models.gpt.GPTLMHeadModel`'s ``state_dict()`` and
decodes with XLA-friendly machinery — a preallocated ``[b, max_len]``
KV cache updated by ``lax.dynamic_update_slice`` and a ``lax.scan``
token loop, so the whole decode compiles to ONE program with static
shapes (no per-token retracing, no growing sequence).

The reference is a training system (its examples stop at loss curves);
this module covers the inference half a switching user expects.  Single
program = single device or GSPMD-sharded under an outer ``jit`` with
sharded weights — the weight layouts are exactly the training layouts
(W [out, in], ``y = x @ W.T``; see nn/parallel.py).

Supported configs: learned or rotary positions, layernorm/rmsnorm,
gelu/swiglu/silu/relu MLPs, GQA (kv_heads < num_heads), tied or untied
lm_head.  Dropout is ignored (inference).  MoE blocks decode via a
dense per-token top-k expert mix (no capacity buckets — every token
reaches its chosen experts).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .gpt import GPTConfig


def norm_eps(cfg: GPTConfig) -> float:
    """``cfg.norm_eps``, or the norm's own default."""
    if cfg.norm_eps is not None:
        return float(cfg.norm_eps)
    return 1e-6 if cfg.norm == "rmsnorm" else 1e-5


def _norm_apply(cfg: GPTConfig, w, b, x):
    xf = x.astype(jnp.float32)
    eps = norm_eps(cfg)
    if cfg.norm == "rmsnorm":
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (xf * w).astype(x.dtype)
    m = jnp.mean(xf, -1, keepdims=True)
    v = jnp.var(xf, -1, keepdims=True)
    out = (xf - m) * lax.rsqrt(v + eps) * w + (b if b is not None else 0.0)
    return out.astype(x.dtype)


def _act(cfg: GPTConfig, h):
    if cfg.activation == "swiglu":
        x1, x2 = jnp.split(h, 2, axis=-1)  # silu(x1) * x2, as ops.swiglu
        return jax.nn.silu(x1) * x2
    if cfg.activation == "gelu":
        return jax.nn.gelu(h)
    if cfg.activation == "silu":
        return jax.nn.silu(h)
    return jax.nn.relu(h)


def _rotary_tables(cfg: GPTConfig, max_len: int):
    # MLA rotates only the decoupled rope slice (width cfg.rope_dim);
    # full-head rotates the whole head
    d = cfg.rope_dim if cfg.is_mla else cfg.head_dim
    inv = 1.0 / (10000.0 ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.outer(np.arange(max_len, dtype=np.float32), inv)
    emb = np.concatenate([ang, ang], axis=-1)
    return jnp.asarray(np.cos(emb)), jnp.asarray(np.sin(emb))  # [L, d]


def _rope(x, cos, sin):
    # x: [b, s, h, d]; cos/sin: [s, d] (already position-gathered)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return x * c + rot * s


class _Params:
    """state_dict view normalizing the two naming conventions: module
    paths (``transformer.h.0.attn.qkv.weight``, Module.state_dict) and
    tensor names (``h0.attn.qkv.weight``, checkpoint files)."""

    @staticmethod
    def _norm(key: str) -> str:
        if key.startswith("transformer."):
            key = key[len("transformer."):]
        if key.startswith("h."):                    # h.0.attn -> h0.attn
            rest = key[2:]
            idx, _, tail = rest.partition(".")
            key = f"h{idx}.{tail}"
        return key

    def __init__(self, state: Dict[str, Any], cfg: GPTConfig):
        self.s = {self._norm(k): jnp.asarray(v) for k, v in state.items()}
        self.cfg = cfg

    def __call__(self, name: str):
        return self.s.get(name)

    def layer(self, i: int, part: str):
        return self.s.get(f"h{i}.{part}")


def _attn_step(cfg: GPTConfig, p: _Params, i: int, x, k_cache, v_cache,
               pos, cos, sin):
    """One attention pass for s_new tokens starting at position ``pos``
    against caches holding everything before them.  Returns
    (out [b, s_new, H], new caches)."""
    b, s_new, _ = x.shape
    c = cfg
    hd, nh, nkv = c.head_dim, c.num_heads, c.kv_heads
    qkv = x @ p.layer(i, "attn.qkv.weight").T
    qb = p.layer(i, "attn.qkv.bias")
    if qb is not None:
        qkv = qkv + qb
    q_size, kv_size = nh * hd, nkv * hd
    q = qkv[..., :q_size].reshape(b, s_new, nh, hd)
    k = qkv[..., q_size:q_size + kv_size].reshape(b, s_new, nkv, hd)
    v = qkv[..., q_size + kv_size:].reshape(b, s_new, nkv, hd)
    if c.position == "rotary":
        idx = pos + jnp.arange(s_new)
        q = _rope(q, cos[idx], sin[idx])
        k = _rope(k, cos[idx], sin[idx])
    k_cache = lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                       (0, pos, 0, 0))
    v_cache = lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                       (0, pos, 0, 0))
    L = k_cache.shape[1]
    kk = jnp.repeat(k_cache, nh // nkv, axis=2) if nkv != nh else k_cache
    vv = jnp.repeat(v_cache, nh // nkv, axis=2) if nkv != nh else v_cache
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) / math.sqrt(hd)
    kpos = jnp.arange(L)[None, None, None, :]
    qpos = (pos + jnp.arange(s_new))[None, None, :, None]
    scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs,
                      vv.astype(jnp.float32)).astype(x.dtype)
    attn = attn.reshape(b, s_new, nh * hd)
    out = attn @ p.layer(i, "attn.out.weight").T
    ob = p.layer(i, "attn.out.bias")
    if ob is not None:
        out = out + ob
    return out, k_cache, v_cache


def _mla_attn_step(cfg: GPTConfig, p: _Params, i: int, x, c_cache, r_cache,
                   pos, cos, sin):
    """MLA twin of :func:`_attn_step` over LATENT caches: ``c_cache``
    [b, max_len, 1, d_c] holds the shared compressed KV stream,
    ``r_cache`` [b, max_len, 1, d_r] the decoupled rotated key (width 0
    for learned positions).  Weight absorption (FlashMLA-ETAP): scores
    are ``(q_nope @ k_up) . c`` per query head and the attention output
    stays latent until one ``v_up`` einsum per QUERY token — no cached
    token is ever decompressed.  The serving unified step mirrors these
    contractions exactly; that alignment is the temp-0 bitwise
    contract."""
    b, s_new, _ = x.shape
    c = cfg
    hd, nh = c.head_dim, c.num_heads
    d_c, d_r = c.kv_latent_dim, c.rope_dim
    q = x @ p.layer(i, "attn.q.weight").T
    qb = p.layer(i, "attn.q.bias")
    if qb is not None:
        q = q + qb
    q = q.reshape(b, s_new, nh, hd + d_r)
    kv = x @ p.layer(i, "attn.kv_a.weight").T
    kvb = p.layer(i, "attn.kv_a.bias")
    if kvb is not None:
        kv = kv + kvb
    c_kv = kv[..., :d_c]                                  # [b, s, d_c]
    k_up = p.layer(i, "attn.k_up.weight")                 # [nh, hd, d_c]
    v_up = p.layer(i, "attn.v_up.weight")
    q_abs = jnp.einsum("bshd,hdc->bshc", q[..., :hd].astype(jnp.float32),
                       k_up.astype(jnp.float32))
    c_cache = lax.dynamic_update_slice(
        c_cache, c_kv[:, :, None, :].astype(c_cache.dtype), (0, pos, 0, 0))
    if d_r:
        idx = pos + jnp.arange(s_new)
        q_rope = _rope(q[..., hd:], cos[idx], sin[idx])
        k_rope = _rope(kv[..., d_c:][:, :, None, :], cos[idx], sin[idx])
        r_cache = lax.dynamic_update_slice(
            r_cache, k_rope.astype(r_cache.dtype), (0, pos, 0, 0))
        q_cat = jnp.concatenate([q_abs, q_rope.astype(jnp.float32)], -1)
        k_cat = jnp.concatenate([c_cache, r_cache], -1)[:, :, 0]
    else:
        q_cat, k_cat = q_abs, c_cache[:, :, 0]            # [b, L, d_c]
    L = c_cache.shape[1]
    scores = jnp.einsum("bshc,bkc->bhsk", q_cat,
                        k_cat.astype(jnp.float32)) / math.sqrt(hd + d_r)
    kpos = jnp.arange(L)[None, None, None, :]
    qpos = (pos + jnp.arange(s_new))[None, None, :, None]
    scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhsk,bkc->bshc", probs,
                       c_cache[:, :, 0].astype(jnp.float32))
    attn = jnp.einsum("bshc,hdc->bshd", o_lat,
                      v_up.astype(jnp.float32)).astype(x.dtype)
    attn = attn.reshape(b, s_new, nh * hd)
    out = attn @ p.layer(i, "attn.out.weight").T
    ob = p.layer(i, "attn.out.bias")
    if ob is not None:
        out = out + ob
    return out, c_cache, r_cache


def _moe_params(p: _Params, i: int):
    def moe_p(part):
        # module-path keys say "mlp.moe.*" (MoEMLP wraps the layer);
        # tensor-name keys say "moe.*" (parallel_parameter names)
        v = p.layer(i, f"mlp.moe.{part}")
        return v if v is not None else p.layer(i, f"moe.{part}")
    return (moe_p("gate.wg"), moe_p("experts.w1"), moe_p("experts.b1"),
            moe_p("experts.w2"), moe_p("experts.b2"))


def _moe_route(cfg: GPTConfig, wg, x):
    """Top-k routing shared by the dense and dispatched paths — identical
    gate arithmetic so the two can never route differently.  dtype
    fidelity with training (nn/moe.py): gate LOGITS in model dtype (a
    full-f32 matmul could break near-ties), softmax in fp32."""
    gates = jax.nn.softmax(
        (x @ wg.T.astype(x.dtype)).astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(gates, cfg.moe_top_k)           # [b, s, k]
    return gates, topv, topi


def _moe_act(cfg: GPTConfig):
    return {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
            "silu": jax.nn.silu}[
        "silu" if cfg.activation == "swiglu" else cfg.activation]


def _moe_mlp(cfg: GPTConfig, p: _Params, i: int, x):
    """Dense per-token top-k expert mix for decode (no capacity buckets:
    every token reaches its chosen experts — exact vs. training when
    training ran uncongested).  All E experts run batched: one einsum on
    the MXU beats gather/scatter at decode (s_new=1).  The prefill pass
    (s_new > 1) routes through :func:`_moe_mlp_dispatched` instead, whose
    FLOPs scale with k/E rather than running every expert on every token
    (reference moe_layer.py:45 dispatches via layout_transform+AllToAll)."""
    wg, w1, b1, w2, b2 = _moe_params(p, i)
    if x.shape[1] > 1:
        return _moe_mlp_dispatched(cfg, x, wg, w1, b1, w2, b2)
    gates, topv, topi = _moe_route(cfg, wg, x)
    weights = jnp.zeros_like(gates)
    for j in range(cfg.moe_top_k):
        weights = weights + topv[..., j:j + 1] * jax.nn.one_hot(
            topi[..., j], gates.shape[-1], dtype=gates.dtype)
    act = _moe_act(cfg)
    h = act(jnp.einsum("bsd,edf->bsef", x, w1) + b1[:, 0])
    y = jnp.einsum("bsef,efd->bsed", h, w2) + b2[:, 0]
    return jnp.einsum("bse,bsed->bsd", weights,
                      y.astype(jnp.float32)).astype(x.dtype)


def _moe_block_size(n_assign: int, num_experts: int) -> int:
    """Back-compat alias of ops.moe_dispatch.pick_block_size (the FLOPs
    bound test reads it here)."""
    from ..ops.moe_dispatch import pick_block_size
    return pick_block_size(n_assign, num_experts)


def _moe_mlp_dispatched(cfg: GPTConfig, x, wg, w1, b1, w2, b2):
    """Capacity-FREE dispatched MoE for prefill (blocked group-GEMM,
    ops/moe_dispatch.py): FLOPs ~k/E of the dense all-experts path with
    NO dropped tokens — exact equivalence, asserted in tests.  The
    reference reaches the same dataflow with layout_transform + AllToAll
    ops (v1 moe_layer.py:45) but drops over-capacity tokens."""
    from ..ops.moe_dispatch import blocked_group_gemm
    b, s, d = x.shape
    gates, topv, topi = _moe_route(cfg, wg, x)
    out = blocked_group_gemm(
        x.reshape(b * s, d), topi.reshape(b * s, -1),
        topv.reshape(b * s, -1), w1, b1, w2, b2, _moe_act(cfg))
    return out.reshape(b, s, d).astype(x.dtype)

def _lm_head(p: _Params, x):
    """LM-head projection for already-normed hidden states ``x`` [b, H]
    -> fp32 logits [b, V].  Split out of :func:`_forward` so the serving
    engine can project at the last TRUE token of a padded prefill."""
    head = p("lm_head.weight")
    w = head if head is not None else p("wte.weight")
    return x.astype(jnp.float32) @ w.T.astype(jnp.float32)


def _forward(cfg: GPTConfig, p: _Params, ids, caches, pos, cos, sin,
             return_hidden: bool = False):
    """Stack forward for ``ids`` [b, s_new] at absolute position ``pos``;
    returns (logits of the LAST position [b, V], new caches), plus the
    final-norm hidden states [b, s_new, H] when ``return_hidden`` (the
    serving prefill projects logits at the last true token of a padded
    prompt instead of the last padded position)."""
    c = cfg
    x = p("wte.weight")[ids].astype(jnp.bfloat16 if c.dtype == "bfloat16"
                                    else jnp.float32)
    if c.position == "learned":
        idx = pos + jnp.arange(ids.shape[1])
        x = x + p("wpe")[idx].astype(x.dtype)
    new_caches = []
    for i in range(c.num_layers):
        k_cache, v_cache = caches[i]
        h = _norm_apply(c, p.layer(i, "ln_1.weight"),
                        p.layer(i, "ln_1.bias"), x)
        step = _mla_attn_step if c.is_mla else _attn_step
        a, k_cache, v_cache = step(c, p, i, h, k_cache, v_cache,
                                   pos, cos, sin)
        x = x + a
        h = _norm_apply(c, p.layer(i, "ln_2.weight"),
                        p.layer(i, "ln_2.bias"), x)
        if c.is_moe_layer(i):
            h = _moe_mlp(c, p, i, h)
        else:
            h = _act(c, h @ p.layer(i, "mlp.up.weight").T +
                     (p.layer(i, "mlp.up.bias") if p.layer(i, "mlp.up.bias")
                      is not None else 0.0))
            h = h @ p.layer(i, "mlp.down.weight").T
            db = p.layer(i, "mlp.down.bias")
            if db is not None:
                h = h + db
        x = x + h
        new_caches.append((k_cache, v_cache))
    x = _norm_apply(c, p("ln_f.weight"), p("ln_f.bias"), x)
    logits = _lm_head(p, x[:, -1])                 # [b, V]
    if return_hidden:
        return logits, new_caches, x
    return logits, new_caches


def decode_step(cfg: GPTConfig, p: _Params, tokens, caches, pos, cos, sin,
                return_hidden: bool = False):
    """Single decode step against dense ``[b, max_len, kvh, hd]`` caches:
    ``tokens`` [b, s_new] at absolute position ``pos`` -> (last-position
    logits [b, V], updated caches).

    The one entry point both inference paths share: ``generate()``'s
    ``lax.scan`` calls it with s_new=1, and the serving engine's prefill
    executable (``hetu_tpu/serving/decode.py``) calls it over the whole
    padded prompt (``return_hidden=True``, to re-project logits at the
    last TRUE token) before scattering the dense caches into KV pages.
    """
    return _forward(cfg, p, tokens, caches, pos, cos, sin, return_hidden)


def generate(state: Dict[str, Any], cfg: GPTConfig, prompt_ids,
             max_new_tokens: int, temperature: float = 0.0,
             top_k: int = 0, seed: int = 0) -> jax.Array:
    """Decode ``max_new_tokens`` tokens after ``prompt_ids`` [b, s0].

    ``temperature == 0`` -> greedy; otherwise softmax sampling, with
    optional ``top_k`` truncation.  Returns [b, s0 + max_new_tokens].
    The token loop is a single ``lax.scan`` (one compile, static shapes).
    """
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    if max_new_tokens == 0:
        return prompt_ids
    p = _Params(state, cfg)
    b, s0 = prompt_ids.shape
    max_len = s0 + max_new_tokens
    if cfg.position == "learned" and max_len > cfg.max_seq_len:
        raise ValueError(f"max_len {max_len} exceeds learned-position "
                         f"table {cfg.max_seq_len}")
    key = (_dataclasses.astuple(cfg), b, s0, int(max_new_tokens),
           float(temperature), int(top_k))
    fn = _DECODE_CACHE.get(key)
    if fn is None:
        fn = _build_decode_fn(cfg, b, s0, int(max_new_tokens),
                              float(temperature), int(top_k))
        if len(_DECODE_CACHE) >= 16:
            _DECODE_CACHE.pop(next(iter(_DECODE_CACHE)))
        _DECODE_CACHE[key] = fn
    return fn(p.s, prompt_ids, jax.random.PRNGKey(seed))


# the decode program is cached by (config, shapes, sampling params) —
# params/prompt/rng flow as ARGUMENTS, so repeated generate() calls hit
# the same compiled program instead of retracing per call
_DECODE_CACHE: Dict[Any, Any] = {}
import dataclasses as _dataclasses  # noqa: E402


def _build_decode_fn(cfg: GPTConfig, b: int, s0: int, max_new_tokens: int,
                     temperature: float, top_k: int):
    max_len = s0 + max_new_tokens
    cdt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    cos, sin = (_rotary_tables(cfg, max_len) if cfg.position == "rotary"
                else (None, None))

    def pick(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits / temperature
        if top_k > 0:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        return jax.random.categorical(key, lg).astype(jnp.int32)

    @jax.jit
    def run(params, prompt_ids, key0):
        p = _Params.__new__(_Params)
        p.s, p.cfg = params, cfg
        if cfg.is_mla:
            # one shared latent stream + (optional) decoupled rope key —
            # mirrors the paged pool's latent k/v page shapes
            shapes = ((b, max_len, 1, cfg.kv_latent_dim),
                      (b, max_len, 1, cfg.rope_dim))
        else:
            shapes = ((b, max_len, cfg.kv_heads, cfg.head_dim),) * 2
        caches = [(jnp.zeros(shapes[0], cdt), jnp.zeros(shapes[1], cdt))
                  for _ in range(cfg.num_layers)]
        logits, cs = decode_step(cfg, p, prompt_ids, caches, 0, cos, sin)
        key, sub = jax.random.split(key0)
        tok = pick(logits, sub)

        def step(carry, _):
            cs, tok, pos, key = carry
            logits, cs = decode_step(cfg, p, tok[:, None], cs, pos, cos, sin)
            key, sub = jax.random.split(key)
            nxt = pick(logits, sub)
            return (cs, nxt, pos + 1, key), tok

        (_, last, _, _), toks = lax.scan(
            step, (cs, tok, jnp.int32(s0), key), None,
            length=max_new_tokens - 1) if max_new_tokens > 1 else \
            ((None, tok, None, None), jnp.zeros((0, b), jnp.int32))
        seq = jnp.concatenate([toks, last[None]], axis=0)  # [T, b]
        return jnp.concatenate([prompt_ids, seq.T], axis=1)

    return run
