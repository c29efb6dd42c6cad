"""Plain reference for the latent-attention / gated-expert stack
(``model_type: mistral4``): the forward pass in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — no kernel, no cache, no
batching, one sequence at a time, and the attention NOT absorbed: every
token's latent is decompressed into per-head keys and values, which is the
published description and not the program's algebra.  It imports nothing
of the program under test.  ``hetu_tpu/models/mistral4_reference.py`` is a
byte-for-byte copy (a test holds them equal): the CPU tests use that one,
the benchmark cell this one.

Every published layer is ``h = x + MLA(RMSNorm(x))``, ``y = h + MoE(RMSNorm
(h))``; then a final RMSNorm and an untied head.  The equations, with every
departure from the published description:

* MLA: ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` -> heads x (nope | rope);
  ``[c_kv | k_r] = W_kva u``; ``c_kv = RMSNorm(c_kv)``; ``k_nope = W_uk
  c_kv``, ``v = W_uv c_kv`` per head (the two halves of ``W_kvb``, stored
  apart as ``k_up`` / ``v_up``); rotary on ``q_r`` and on the ONE shared
  ``k_r``, interleaved pairs ``(2i, 2i + 1)`` rotated in place, YaRN
  frequencies; ``score = s (q_nope . k_nope + q_r . k_r)``, causal softmax,
  ``o = sum p v``, out ``W_o o``.  ASSUMED (the config carries neither):
  ``s = (nope + rope) ** -0.5 * m * m``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1`` (the ``deepseek_v3`` module's convention);
  ``llama_4_scaling_beta`` multiplies q by ``1 + beta * ln(1 + floor(pos /
  original_max_position_embeddings))`` (the Llama-4 convention).  Attention
  runs in blocks of query rows (``Q_BLOCK``) so that a 17k-token sequence's
  scores fit beside the served weights; the arithmetic is the full
  softmax's.
* MoE: ``p = softmax(W_r u)`` over ALL routed experts in float32 (ASSUMED:
  softmax, the publisher's own convention; the key set names no scoring
  function); top-k by ``lax.top_k``; the chosen values renormalised to sum
  1 (``norm_topk_prob``) x ``routed_scaling_factor``; expert ``W_down
  (silu(W_gate u) * W_up u)`` over the experts HELD here (``experts_held``
  from ``expert_offset``: what the absent experts would add is left out, as
  in the program); plus one shared expert of the same form, unweighted
  (``shared=False`` leaves it out, for the share test).
* The vocabulary is the slice the weights hold; the vision tower is left
  out.

Weights come in as the program's own tensors (names in
``hetu_tpu/models/hybrid.py``; a projection ``W`` is ``[out, in]`` used as
``x @ W.T``; expert stacks are ``w1`` (gate), ``w3`` (up) ``[E, in, out]``,
``w2`` (down) ``[E, out, in]``), in whatever dtype they are served in, and
are upcast one layer (one expert) at a time.

Tolerances, and why (the cell's ``correct``; the CPU tests state their
own).  The system computes in bf16; this file in float32.  A served greedy
token is BEYOND when it scores more than ``LOGIT_GAP_TOL`` (0.3) logit
units below the reference's best token, teacher-forced on the served
sequence; the run is correct when at most ``GAP_SHARE_TOL`` (15 %) of the
checked tokens are beyond.  Why a share and not the worst token: with
seeded random weights this stack is ill-conditioned in ANY 8-bit-mantissa
arithmetic — top-4 of a 128-wide softmax router has margins of ~0.1 logit,
a token whose fourth expert flips gets a different expert at weight ~0.25,
and the five routers after it amplify that — so this file ITSELF, rounded
to bf16 (``lowp_choice_gaps(..., lowp="bfloat16")``: no kernel, no cache),
puts 2-3 of 64 tokens beyond with gaps up to 1.3, and the system 3 of 64
with gaps up to 4.7 (my chip runs, PR 35).  The worst token therefore
separates nothing (float8's is 3.5-5.7); the share does.  First reading,
the system in bf16 on the chip, share beyond over the cell's seeds, and
second reading, this file rounded to float8 (e4m3, the nearest precision
below bf16: every weight matrix and every mixer's input and output, scaled
per tensor), which has to fail: both in ``PERF.md`` section 4 (PR 35).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LOGIT_GAP_TOL = 0.3
GAP_SHARE_TOL = 0.15
Q_BLOCK = 128

F32 = jnp.float32


def spec_from_config(config: dict) -> dict:
    """The sizes this file needs, from the published ``config.json`` keys
    (and ``n_routed_experts`` / ``expert_offset`` / the depth as cut)."""
    rp = config["rope_parameters"]
    factor = float(rp["factor"])
    m = 0.1 * float(rp.get("mscale_all_dim", 0)) * math.log(factor) + 1.0 \
        if factor > 1 else 1.0
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return {
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "nope": nope, "rope": rope, "v": config["v_head_dim"],
        "latent": config["kv_lora_rank"],
        "scale": (nope + rope) ** -0.5 * m * m,
        "theta": float(rp["rope_theta"]), "factor": factor,
        "orig": int(rp["original_max_position_embeddings"]),
        "beta_fast": float(rp["beta_fast"]),
        "beta_slow": float(rp["beta_slow"]),
        "q_beta": float(rp.get("llama_4_scaling_beta", 0.0)),
        "routed": config.get("moe_router_outputs",
                             config["n_routed_experts"]),
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "held": config["n_routed_experts"],
        "offset": config.get("expert_offset", 0),
        "eps": float(config["rms_norm_eps"]),
    }


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(p: dict) -> dict:
    return {k: v.astype(F32) for k, v in p.items()}


def yarn_inv_freq(spec: dict):
    """The rotary stream's ``rope / 2`` frequencies: a pair that turns
    more than ``beta_fast`` times in the original positions keeps its
    frequency, one that turns fewer than ``beta_slow`` times is slowed by
    ``factor``, linearly between (by pair index).  Constants of the model:
    made on the host in float64 (a device's float32 ``pow`` is some 1e-6
    off, which 17,000 positions turn into 0.05 rad)."""
    d, base, orig = spec["rope"], spec["theta"], spec["orig"]
    inv = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def pair_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(pair_of(spec["beta_fast"])), 0)
    high = min(math.ceil(pair_of(spec["beta_slow"])), d - 1)
    if high == low:
        high = low + 0.001
    slowed = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return inv / spec["factor"] * slowed + inv * (1.0 - slowed)


def rotate_pairs(x, pos, spec: dict):
    """``x [T, ..., rope]`` at positions ``pos [T]`` (host integers): the
    pair ``(x[2i], x[2i + 1])`` turned by ``pos * freq_i``, in place."""
    ang = np.asarray(pos, np.float64)[:, None] * yarn_inv_freq(spec)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = (jnp.asarray(f(ang), F32) for f in (np.cos, np.sin))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return out.reshape(x.shape)


def q_scale(pos, spec: dict):
    """The per-position factor on q, ``[T]`` (host integers in)."""
    return jnp.asarray(1.0 + spec["q_beta"] * np.log1p(
        np.asarray(pos) // spec["orig"]), F32)


# -- the two mixers -----------------------------------------------------------

def mla(u, p: dict, spec: dict):
    """``u`` [T, hidden] (already normed) -> [T, hidden]; not absorbed."""
    nh, n, r, v, d_c = (spec["heads"], spec["nope"], spec["rope"],
                        spec["v"], spec["latent"])
    t = u.shape[0]
    pos = np.arange(t)
    c_q = _rms(u @ p["q_a.weight"].T, p["q_a_norm.weight"], spec["eps"]) \
        if "q_a.weight" in p else u
    q = (c_q @ p["q_b.weight"].T).reshape(t, nh, n + r)
    q = q * q_scale(pos, spec)[:, None, None]
    kv = u @ p["kv_a.weight"].T
    c_kv = _rms(kv[:, :d_c], p["kv_a_norm.weight"], spec["eps"])
    k_r = rotate_pairs(kv[:, d_c:], pos, spec)                  # [T, r]
    k_nope = jnp.einsum("tc,hdc->thd", c_kv, p["k_up.weight"])  # [T, nh, n]
    val = jnp.einsum("tc,hdc->thd", c_kv, p["v_up.weight"])     # [T, nh, v]
    q_r = rotate_pairs(q[..., n:], pos, spec)
    blk = min(Q_BLOCK, t)
    pad = -t % blk

    kpos = jnp.arange(t)

    def rows(args):
        qn, qr, qpos = args                        # [blk, nh, n], .., [blk]
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope) +
             jnp.einsum("qhd,kd->hqk", qr, k_r)) * spec["scale"]
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), val)

    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)  # noqa: E731
                            ).reshape((-1, blk) + a.shape[1:])
    o = lax.map(rows, (cut(q[..., :n]), cut(q_r), cut(kpos)))
    o = o.reshape(-1, nh * v)[:t]
    return o @ p["out.weight"].T


def route(u, p: dict, spec: dict):
    """Combine weights ``[T, routed]`` over ALL routed experts (zero where
    an expert was not chosen)."""
    s = jax.nn.softmax(u @ p["router.weight"].T, -1)
    w, idx = lax.top_k(s, spec["top_k"])
    if spec["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(w * spec["route_scale"])


def moe(u, p: dict, spec: dict, shared: bool = True, routed: bool = True):
    """``p`` keeps its expert stacks in the served dtype: they are upcast
    one expert at a time inside the scan."""
    small = _f32({k: v for k, v in p.items() if not k.startswith("experts.")})
    out = jnp.zeros_like(u)
    if routed:
        w = route(u, small, spec)
        w = lax.dynamic_slice_in_dim(w, spec["offset"], spec["held"], 1)

        def one(acc, inp):
            w1, w3, w2, w_e = inp              # [H, F], [H, F], [F, H], [T]
            hid = jax.nn.silu(u @ w1.astype(F32)) * (u @ w3.astype(F32))
            return acc + w_e[:, None] * (hid @ w2.astype(F32)), None

        r, _ = lax.scan(one, jnp.zeros_like(u),
                        (p["experts.w1"], p["experts.w3"], p["experts.w2"],
                         w.T))
        out = out + r
    if shared:
        hid = jax.nn.silu(u @ small["shared.gate.weight"].T) * \
            (u @ small["shared.up.weight"].T)
        out = out + hid @ small["shared.down.weight"].T
    return out


# -- the stack ----------------------------------------------------------------

def _fp8(v):
    """Through float8 (e4m3: 3 mantissa bits) and back, scaled per tensor
    so that its largest entry sits at the format's largest (448)."""
    s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 448.0
    return (v / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _bf16(v):
    return v.astype(jnp.bfloat16).astype(F32)


_ROUND = {None: lambda v: v, "float8": _fp8, "bfloat16": _bf16}


@functools.partial(jax.jit, static_argnames=("kind", "spec_items", "lowp"))
def _layer(x, p, kind: str, spec_items, lowp=None):
    spec = dict(spec_items)
    # lowp: what a deployment in that precision rounds — every weight
    # matrix, and the mixer's input and output
    rnd = _ROUND[lowp]
    if lowp:
        p = {k: rnd(v.astype(F32)).astype(v.dtype) if v.ndim >= 2 else v
             for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        u = rnd(_rms(x, p["norm.weight"].astype(F32), spec["eps"]))
        if kind == "mla":
            return x + rnd(mla(u, _f32(_sub(p, "attn.")), spec))
        return x + rnd(moe(u, _sub(p, "moe."), spec))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, w, head, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rms(x, w.astype(F32), eps) @ head.astype(F32).T


def _sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _freeze(spec: dict):
    return tuple(sorted(spec.items()))


def logits(params: dict, ids, spec: dict, positions=None, lowp=None):
    """float32 logits ``[len(positions) or T, vocab]`` of ONE sequence
    ``ids [T]``: one jitted call a half-layer (tensors ``h{2l}.`` the
    attention, ``h{2l + 1}.`` the experts), so only one's float32 weights
    are live at a time."""
    x = params["wte.weight"][jnp.asarray(ids, jnp.int32)].astype(F32)
    for i in range(2 * spec["layers"]):
        x = _layer(x, _sub(params, f"h{i}."), kind=("mla", "moe")[i % 2],
                   spec_items=_freeze(spec), lowp=lowp)
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    return _head(x, params["ln_f.weight"], params["lm_head.weight"],
                 eps=spec["eps"])


def _padded(seq, prompt_len: int, pad_to: int, max_new: int):
    n_new = len(seq) - prompt_len
    ids = list(seq[:-1]) + [0] * (pad_to - (len(seq) - 1))
    pos = [prompt_len - 1 + j for j in range(n_new)]
    return n_new, ids, pos + [pos[-1]] * (max_new - n_new)


def lowp_choice_gaps(params: dict, seq, prompt_len: int, spec: dict,
                     pad_to: int, max_new: int, lowp: str = "float8"):
    """The second reading of the tolerance: at each generated position of
    ``seq``, the token the forward pass rounded to ``lowp`` (``float8``,
    or ``bfloat16``: what the served precision alone does to this file)
    would pick, scored against this file's float32 logits."""
    n_new, ids, pos = _padded(seq, prompt_len, pad_to, max_new)
    lg = logits(params, ids, spec, positions=pos)[:n_new]
    low = logits(params, ids, spec, positions=pos, lowp=lowp)[:n_new]
    mine = jnp.take_along_axis(lg, low.argmax(-1)[:, None], -1)[:, 0]
    return [float(g) for g in (lg.max(-1) - mine)]


def greedy_logit_gaps(params: dict, seq, prompt_len: int, spec: dict,
                      pad_to: int, max_new: int):
    """How far each generated token's logit lies below the reference's
    best token, teacher-forced on the system's own output: ``seq`` is
    prompt + generated tokens, right-padded to ``pad_to`` (every mixer is
    causal, so padding reaches no position read) and the positions read
    padded to ``max_new``, so every request shares one compiled shape.
    Returns one gap per generated token."""
    n_new, ids, pos = _padded(seq, prompt_len, pad_to, max_new)
    lg = logits(params, ids, spec, positions=pos)[:n_new]
    picked = jnp.asarray(seq[prompt_len:], jnp.int32)
    mine = jnp.take_along_axis(lg, picked[:, None], -1)[:, 0]
    return [float(g) for g in (lg.max(-1) - mine)]
