"""Hybrid stacks: one mixer per layer — Mamba-2 / attention / latent
attention (MLA) / MoE.

A ``GPTConfig`` with a ``layer_pattern`` runs ``x = x + mixer_l(RMSNorm_l
(x))`` for every layer, a final norm and an untied head.  This module is
the one place that knows the stack's PARAMETER NAMES and the mixers'
arithmetic; the serving step (``serving/decode.py``) lays them over the
ragged token axis and owns the state (K/V pages for the attention layers,
state slots for the mamba2 layers).  The plain float32 reference is
``models/hybrid_reference.py``.

Tensors (a projection ``W`` is ``[out, in]``, used as ``x @ W.T``)::

    wte.weight [V, H]   lm_head.weight [V, H]   ln_f.weight [H]
    h{i}.norm.weight [H]                                  every layer
    h{i}.mamba.in_proj.weight  [2*inner + 2*G*N + heads, H]   z | xBC | dt
    h{i}.mamba.conv.weight [K, inner + 2*G*N]   .conv.bias [inner + 2*G*N]
    h{i}.mamba.dt_bias / .A_log / .D [heads]    (float32)
    h{i}.mamba.norm.weight [inner]    h{i}.mamba.out_proj.weight [H, inner]
    h{i}.attn.qkv.weight [(nh + 2*kv)*hd, H]    h{i}.attn.out.weight [H, nh*hd]
    mla (``L``; nope / rope / v widths n / r / v, latent d_c, q rank R):
    h{i}.attn.q_a.weight [R, H]   .q_a_norm.weight [R]
    h{i}.attn.q_b.weight [nh*(n + r), R]    a head's rows: nope | rope
    h{i}.attn.kv_a.weight [d_c + r, H]      .kv_a_norm.weight [d_c]
    h{i}.attn.k_up.weight [nh, n, d_c]   .v_up.weight [nh, v, d_c]
                          (the two halves of W_kvb, as decode absorbs them)
    h{i}.attn.out.weight [H, nh*v]
    h{i}.moe.router.weight [E_all, H]   .router.bias [E_all]  (float32)
    h{i}.moe.latent_down.weight [L, H]  h{i}.moe.latent_up.weight [H, L]
    h{i}.moe.experts.w1 [E_held, L, F]  h{i}.moe.experts.w2 [E_held, F, L]
    h{i}.moe.shared.up.weight [Fs, H]   h{i}.moe.shared.down.weight [H, Fs]
    gated experts (``cfg.moe_gated``): w1 is the gate, and beside it
    h{i}.moe.experts.w3 [E_held, L, F]  h{i}.moe.shared.gate.weight [Fs, H]

Without ``moe_latent_dim`` the experts work on the hidden itself (``L =
H``, no latent projections).  Without a router bias (a softmax router)
``router.bias`` is absent.

What the serving engine refuses depends on the pattern, not on the
stack being a pattern: the prefix cache and speculation for a pattern
with an ``M`` layer (a page prefix carries no recurrent state);
speculation and page quantisation for every pattern; ``L`` beside ``*``
(one pool, one layout).  ``mistral4_config`` is the translation for the
``(L, E) x depth`` stacks of ``model_type: mistral4``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.phases import phase
from ..ops.moe_grouped import ACTIVATIONS, grouped_experts
from ..ops.ssd import causal_conv, ssd_chunk_scan, ssd_decode_step
from .generate import norm_eps
from .gpt import GPTConfig

F32 = jnp.float32
MIXER_OF = {"M": "mamba2", "*": "attention", "E": "moe", "L": "mla"}
# float32 whatever the model's dtype: the recurrence's own parameters and
# the router (its scores decide a top-k)
_F32_PARAMS = ("mamba.dt_bias", "mamba.A_log", "mamba.D",
               "moe.router.weight", "moe.router.bias")


def hybrid_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of a
    ``nemotron_h``-type model (as cut: ``n_routed_experts`` = experts held
    here, ``moe_router_outputs`` = the router's width) to ``GPTConfig``."""
    pattern = tuple(MIXER_OF[ch] for ch in pub["hybrid_override_pattern"])
    if len(pattern) != pub["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    if pub["hidden_size"] != pub["num_attention_heads"] * pub["head_dim"]:
        raise ValueError("head_dim other than hidden / heads is not built")
    if pub.get("n_group", 1) != 1 or pub.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not built")
    kw = dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=len(pattern), num_heads=pub["num_attention_heads"],
        num_kv_heads=pub["num_key_value_heads"],
        max_seq_len=pub["max_position_embeddings"],
        activation=pub["mlp_hidden_act"], norm="rmsnorm", position="none",
        norm_eps=float(pub["layer_norm_epsilon"]),
        tie_embeddings=bool(pub["tie_word_embeddings"]), sp=False,
        dtype=pub.get("dtype", "bfloat16"), layer_pattern=pattern,
        mamba_num_heads=pub["mamba_num_heads"],
        mamba_head_dim=pub["mamba_head_dim"],
        mamba_n_groups=pub["n_groups"],
        mamba_state_dim=pub["ssm_state_size"],
        mamba_conv_kernel=pub["conv_kernel"],
        mamba_chunk_size=pub["chunk_size"],
        num_experts=pub.get("moe_router_outputs", pub["n_routed_experts"]),
        experts_held=pub["n_routed_experts"],
        expert_offset=pub.get("expert_offset", 0),
        moe_top_k=pub["num_experts_per_tok"], moe_router="sigmoid_bias",
        moe_router_scale=float(pub["routed_scaling_factor"]),
        moe_ffn_size=pub["moe_intermediate_size"],
        moe_latent_dim=pub.get("moe_latent_size"),
        moe_shared_ffn_size=pub["moe_shared_expert_intermediate_size"]
        * pub["n_shared_experts"])
    kw.update(overrides)
    return GPTConfig(**kw)


def mistral4_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of a
    ``mistral4``-type model (as cut: ``n_routed_experts`` = experts held
    here, ``moe_router_outputs`` = the router's width) to ``GPTConfig``:
    every published layer is an ``L`` layer then an ``E`` layer of the
    pattern.  What the keys do not carry is the caller's to state
    (``pub["assumed"]``): the softmax router is this translation's, the
    softmax scale follows the ``deepseek_v3`` convention ``(n + r) ** -0.5
    * m * m`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``."""
    if pub.get("n_group", 1) != 1 or pub.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    if pub.get("first_k_dense_replace", 0):
        raise ValueError("leading dense layers (first_k_dense_replace > 0) "
                         "are not built: every layer is an expert layer")
    rp = pub["rope_parameters"]
    if rp.get("rope_type", rp.get("type")) != "yarn":
        raise ValueError("the rotary stream is built with YaRN tables")
    nope, rope = pub["qk_nope_head_dim"], pub["qk_rope_head_dim"]
    factor = float(rp["factor"])

    def ms(m):              # the family's get_mscale
        return 0.1 * float(m) * math.log(factor) + 1.0 if factor > 1 else 1.0

    mscale, mall = rp.get("mscale"), rp.get("mscale_all_dim")
    m_all = ms(mall) if mall else 1.0
    table = ms(mscale) / ms(mall) if mscale and mall else ms(1)
    beta = float(rp.get("llama_4_scaling_beta", 0.0))
    orig = int(rp["original_max_position_embeddings"])
    layers = pub["num_hidden_layers"]
    kw = dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=2 * layers, num_heads=pub["num_attention_heads"],
        max_seq_len=pub["max_position_embeddings"],
        activation=pub["hidden_act"], norm="rmsnorm", position="rotary",
        norm_eps=float(pub["rms_norm_eps"]),
        tie_embeddings=bool(pub["tie_word_embeddings"]), sp=False,
        dtype=pub.get("dtype", "bfloat16"),
        layer_pattern=("mla", "moe") * layers,
        kv_latent_dim=pub["kv_lora_rank"], kv_rope_dim=rope,
        mla_q_rank=pub["q_lora_rank"], mla_nope_dim=nope,
        mla_v_dim=pub["v_head_dim"],
        attn_scale=(nope + rope) ** -0.5 * m_all * m_all,
        rope_theta=float(rp["rope_theta"]),
        rope_interleave=bool(pub.get("rope_interleave", True)),
        rope_yarn=(factor, orig, float(rp["beta_fast"]),
                   float(rp["beta_slow"]), table),
        q_pos_scale=(beta, orig) if beta else None,
        num_experts=pub.get("moe_router_outputs", pub["n_routed_experts"]),
        experts_held=pub["n_routed_experts"],
        expert_offset=pub.get("expert_offset", 0),
        moe_top_k=pub["num_experts_per_tok"], moe_router="softmax",
        moe_norm_topk=bool(pub["norm_topk_prob"]), moe_gated=True,
        moe_router_scale=float(pub["routed_scaling_factor"]),
        moe_ffn_size=pub["moe_intermediate_size"],
        moe_shared_ffn_size=pub["moe_intermediate_size"]
        * pub["n_shared_experts"])
    kw.update(overrides)
    return GPTConfig(**kw)


def mla_rotary_tables(cfg: GPTConfig, max_len: int):
    """``(cos, sin [max_len, r], q_scale [max_len])`` of the rotary
    stream, float32: YaRN frequencies where ``cfg.rope_yarn`` (each
    frequency between its own and its ``1 / factor``, by how many turns
    it makes in the original positions), times the tables' factor; the
    halves laid ``[angles | angles]`` for the half-split rotation (an
    interleaved stream is brought into that order first: ``mla_rotate``).
    ``q_scale`` is the per-position factor on q (1 without
    ``cfg.q_pos_scale``)."""
    d = cfg.rope_dim
    freqs = cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv, table = 1.0 / freqs, 1.0
    if cfg.rope_yarn:
        factor, orig, fast, slow, table = cfg.rope_yarn

        def turn_dim(turns):
            return d * math.log(orig / (turns * 2 * math.pi)) / \
                (2 * math.log(cfg.rope_theta))

        low = max(math.floor(turn_dim(fast)), 0)
        high = min(math.ceil(turn_dim(slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) /
                       ((high - low) or 0.001), 0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
    pos = np.arange(max_len, dtype=np.float64)
    ang = np.outer(pos, inv)
    emb = np.concatenate([ang, ang], -1)
    scale = np.ones(max_len)
    if cfg.q_pos_scale:
        beta, period = cfg.q_pos_scale
        scale = 1 + beta * np.log1p(np.floor(pos / period))
    as32 = lambda a: jnp.asarray(a.astype(np.float32))  # noqa: E731
    return as32(np.cos(emb) * table), as32(np.sin(emb) * table), as32(scale)


def param_shapes(cfg: GPTConfig) -> Dict[str, Tuple[int, ...]]:
    """Every tensor of a hybrid stack under its name."""
    c = cfg
    hd = c.hidden_size
    out = {"wte.weight": (c.vocab_size, hd),
           "lm_head.weight": (c.vocab_size, hd), "ln_f.weight": (hd,)}
    inner, cd, mh = c.mamba_inner, c.mamba_conv_dim, c.mamba_num_heads
    lat = c.moe_latent_dim or hd
    for i, mixer in enumerate(c.layer_pattern):
        p = f"h{i}."
        out[p + "norm.weight"] = (hd,)
        if mixer == "mamba2":
            out[p + "mamba.in_proj.weight"] = (inner + cd + mh, hd)
            out[p + "mamba.conv.weight"] = (c.mamba_conv_kernel, cd)
            out[p + "mamba.conv.bias"] = (cd,)
            for n in ("dt_bias", "A_log", "D"):
                out[p + "mamba." + n] = (mh,)
            out[p + "mamba.norm.weight"] = (inner,)
            out[p + "mamba.out_proj.weight"] = (hd, inner)
        elif mixer == "attention":
            q, kv = c.num_heads * c.head_dim, c.kv_heads * c.head_dim
            out[p + "attn.qkv.weight"] = (q + 2 * kv, hd)
            out[p + "attn.out.weight"] = (hd, q)
        elif mixer == "mla":
            nh, d_c, qk = c.num_heads, c.kv_latent_dim, c.nope_dim + c.rope_dim
            if c.mla_q_rank:
                out[p + "attn.q_a.weight"] = (c.mla_q_rank, hd)
                out[p + "attn.q_a_norm.weight"] = (c.mla_q_rank,)
            out[p + "attn.q_b.weight"] = (nh * qk, c.mla_q_rank or hd)
            out[p + "attn.kv_a.weight"] = (d_c + c.rope_dim, hd)
            out[p + "attn.kv_a_norm.weight"] = (d_c,)
            out[p + "attn.k_up.weight"] = (nh, c.nope_dim, d_c)
            out[p + "attn.v_up.weight"] = (nh, c.v_dim, d_c)
            out[p + "attn.out.weight"] = (hd, nh * c.v_dim)
        else:
            out[p + "moe.router.weight"] = (c.num_experts, hd)
            if c.moe_router == "sigmoid_bias":
                out[p + "moe.router.bias"] = (c.num_experts,)
            if c.moe_latent_dim:
                out[p + "moe.latent_down.weight"] = (lat, hd)
                out[p + "moe.latent_up.weight"] = (hd, lat)
            out[p + "moe.experts.w1"] = (c.held_experts, lat, c.moe_ffn_size)
            out[p + "moe.experts.w2"] = (c.held_experts, c.moe_ffn_size, lat)
            if c.moe_gated:
                out[p + "moe.experts.w3"] = out[p + "moe.experts.w1"]
            if c.moe_shared_ffn_size:
                if c.moe_gated:
                    out[p + "moe.shared.gate.weight"] = (
                        c.moe_shared_ffn_size, hd)
                out[p + "moe.shared.up.weight"] = (c.moe_shared_ffn_size, hd)
                out[p + "moe.shared.down.weight"] = (hd,
                                                     c.moe_shared_ffn_size)
    return out


def param_dtype(cfg: GPTConfig, name: str):
    if name.endswith(_F32_PARAMS):
        return F32
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else F32


def init_state(cfg: GPTConfig, seed: int, time_step=(0.001, 0.1, 1e-4),
               router_bias_std: float = 0.0) -> Dict[str, jax.Array]:
    """Seeded random weights, made on the device, one jitted call per
    layer so that no more than one layer's temporaries are live: matrices
    normal(0, init_std), the projections back into the residual stream
    (``out_proj``, ``attn.out``, ``latent_up``, ``shared.down``) scaled by
    ``1 / sqrt(num_layers)`` (the published ``rescale_prenorm_residual``:
    one residual branch a layer); norms 1, conv bias 0; ``dt`` log-uniform
    in ``[time_step_min, time_step_max]`` floored at ``time_step_floor``
    and stored as ``dt_bias = dt + log(-expm1(-dt))`` (inverse softplus),
    ``A`` uniform 1..16 as ``A_log``, ``D`` 1 — the published
    initialiser.  The hardware bit generator (``rbg``) keeps the
    temporaries at the tensors' own size."""
    shapes = param_shapes(cfg)
    groups: Dict[str, Dict[str, Tuple[int, ...]]] = {}   # layer -> tails
    for name, shape in shapes.items():
        head, _, tail = name.partition(".")
        if not (head[0] == "h" and head[1:].isdigit()):
            head, tail = "", name
        groups.setdefault(head, {})[tail] = shape
    std, down = cfg.init_std, cfg.init_std / math.sqrt(cfg.num_layers)
    t_min, t_max, t_floor = time_step

    def draw(key, tail, shape):
        dt = param_dtype(cfg, tail)
        if tail.endswith("norm.weight") or tail in ("ln_f.weight", "mamba.D"):
            return jnp.ones(shape, dt)
        if tail == "mamba.conv.bias":
            return jnp.zeros(shape, dt)
        if tail == "moe.router.bias":
            return router_bias_std * jax.random.normal(key, shape, dt)
        if tail == "mamba.A_log":
            return jnp.log(jax.random.uniform(key, shape, dt, 1.0, 16.0))
        if tail == "mamba.dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, dt, math.log(t_min), math.log(t_max)))
            step = jnp.maximum(step, t_floor)
            return step + jnp.log(-jnp.expm1(-step))
        s = down if tail in ("mamba.out_proj.weight", "attn.out.weight",
                             "moe.latent_up.weight",
                             "moe.shared.down.weight") else std
        if tail == "mamba.conv.weight":             # fan-in K
            s = 1.0 / math.sqrt(shape[0])
        return s * jax.random.normal(key, shape, dt)

    build = {}                      # one compile per distinct layer shape
    root = jax.random.key(int(seed) % (2 ** 31), impl="rbg")
    state = {}
    for key, (head, tails) in zip(jax.random.split(root, len(groups)),
                                  sorted(groups.items())):
        sig = tuple(sorted(tails.items()))
        if sig not in build:
            build[sig] = jax.jit(lambda k, sig=sig: {
                t: draw(kk, t, shape) for kk, (t, shape)
                in zip(jax.random.split(k, len(sig)), sig)})
        for tail, v in build[sig](key).items():
            state[f"{head}.{tail}" if head else tail] = v
    return state


# -- the mixers over plain arrays ---------------------------------------------

class MambaWeights:
    """One mamba2 layer's tensors, looked up once."""

    def __init__(self, params: dict, i: int):
        g = lambda n: params[f"h{i}.mamba.{n}"]  # noqa: E731
        self.in_proj, self.out_proj = g("in_proj.weight"), g("out_proj.weight")
        self.conv_w, self.conv_b = g("conv.weight"), g("conv.bias")
        self.dt_bias, self.a_log, self.d = g("dt_bias"), g("A_log"), g("D")
        self.norm = g("norm.weight")


def _split_zxd(cfg: GPTConfig, zxd):
    inner, cd = cfg.mamba_inner, cfg.mamba_conv_dim
    return zxd[..., :inner], zxd[..., inner:inner + cd], zxd[..., inner + cd:]


def _ssm_inputs(cfg: GPTConfig, w: MambaWeights, conv_out, dt_raw):
    """silu on the conv's output, the split into x / B / C, softplus."""
    c = cfg
    n = conv_out.shape[0]
    gn = c.mamba_n_groups * c.mamba_state_dim
    xbc = jax.nn.silu(conv_out)
    x = xbc[:, :c.mamba_inner].reshape(n, c.mamba_num_heads, c.mamba_head_dim)
    b = xbc[:, c.mamba_inner:c.mamba_inner + gn].reshape(
        n, c.mamba_n_groups, c.mamba_state_dim)
    cc = xbc[:, c.mamba_inner + gn:].reshape(
        n, c.mamba_n_groups, c.mamba_state_dim)
    dt = jax.nn.softplus(dt_raw.astype(F32) + w.dt_bias.astype(F32))
    return x, b, cc, dt, -jnp.exp(w.a_log.astype(F32))


def mamba_rows(cfg: GPTConfig, w: MambaWeights, zxd, conv_state, ssm_state,
               live, fresh):
    """One token for each state slot: ``zxd`` [S, in_proj width] in SLOT
    order, ``conv_state`` [S, K-1, conv_dim], ``ssm_state`` [S, H, P, N]
    float32 — the whole store, updated where ``live`` and left as it is
    elsewhere; a ``fresh`` slot (its sequence's first token) starts from
    zeros.  Returns ``(y [S, inner] float32 before the gate, new conv
    state, new ssm state)``."""
    _, xbc, dt_raw = _split_zxd(cfg, zxd)
    with phase("ssm_conv"):
        tail = jnp.where(fresh[:, None, None], 0, conv_state)
        full = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], 1)
        conv = jnp.einsum("skc,kc->sc", full.astype(F32),
                          w.conv_w.astype(F32)) + w.conv_b.astype(F32)
        new_conv = jnp.where(live[:, None, None], full[:, 1:], conv_state)
    with phase("ssm_scan"):
        x, b, c, dt, a = _ssm_inputs(cfg, w, conv, dt_raw)
        s0 = jnp.where(fresh[:, None, None, None], 0.0, ssm_state)
        y, new = ssd_decode_step(x, dt, a, b, c, w.d, s0)
        new_ssm = jnp.where(live[:, None, None, None], new, ssm_state)
    return y.reshape(y.shape[0], cfg.mamba_inner), new_conv, new_ssm


def mamba_chunk(cfg: GPTConfig, w: MambaWeights, zxd, tail, state, length,
                fresh):
    """A run of ``length`` (<= C) consecutive tokens of ONE sequence:
    ``zxd`` [C, in_proj width], ``tail`` [K-1, conv_dim] and ``state``
    [H, P, N] float32 as the sequence left them (ignored when ``fresh``).
    Returns ``(y [C, inner] float32 before the gate, new tail, new
    state)``."""
    _, xbc, dt_raw = _split_zxd(cfg, zxd)
    n = zxd.shape[0]
    with phase("ssm_conv"):
        tail0 = jnp.where(fresh, 0, tail)
        conv, new_tail = causal_conv(xbc, w.conv_w, w.conv_b, tail0, length)
    with phase("ssm_scan"):
        x, b, c, dt, a = _ssm_inputs(cfg, w, conv, dt_raw)
        q = min(cfg.mamba_chunk_size, n)
        pad = -n % q
        if pad:
            x, b, c, dt = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                           for v in (x, b, c, dt))
        y, new_state = ssd_chunk_scan(
            x, dt, a, b, c, w.d, jnp.where(fresh, 0.0, state), q, length)
    return y[:n].reshape(n, cfg.mamba_inner), new_tail, new_state


def mamba_gate_norm(cfg: GPTConfig, w: MambaWeights, y, z, dtype):
    """``GroupRMSNorm_G(y * silu(z)) * w``: the gate comes before the
    norm, and the norm runs over each of the G groups of ``inner / G``."""
    n, g = y.shape[0], cfg.mamba_n_groups
    v = (y * jax.nn.silu(z.astype(F32))).reshape(n, g, -1)
    v = v * lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + norm_eps(cfg))
    return (v.reshape(n, -1) * w.norm.astype(F32)).astype(dtype)


def _rms(cfg: GPTConfig, v, w):
    f = v.astype(F32)
    f = f * lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + norm_eps(cfg))
    return (f * w.astype(F32)).astype(v.dtype)


def mla_in(cfg: GPTConfig, params: dict, i: int, u):
    """An mla layer's way in, token by token on ``u`` [n, H] (normed):
    the low-rank q behind its norm, the normed latent and the raw rotary
    key.  Returns ``(q [n, nh, nope + rope], c_kv [n, d_c], k_r [n, r])``."""
    g = lambda n: params.get(f"h{i}.attn.{n}")  # noqa: E731
    c_q = u
    if g("q_a.weight") is not None:
        c_q = _rms(cfg, u @ g("q_a.weight").T, g("q_a_norm.weight"))
    q = (c_q @ g("q_b.weight").T).reshape(
        u.shape[0], cfg.num_heads, cfg.nope_dim + cfg.rope_dim)
    kv = u @ g("kv_a.weight").T
    d_c = cfg.kv_latent_dim
    return q, _rms(cfg, kv[:, :d_c], g("kv_a_norm.weight")), kv[:, d_c:]


def mla_rotate(cfg: GPTConfig, x, cos_t, sin_t):
    """The rotary stream of ``x [T, ..., r]`` at per-token tables ``cos_t,
    sin_t [T, r]`` (``mla_rotary_tables`` gathered by position).  An
    interleaved stream (pairs ``(2i, 2i + 1)``) is brought into ``[evens
    | odds]`` and rotated by halves; it stays in that order — q and the
    cached key take the same road, and only their product is read."""
    if cfg.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    f = x.astype(F32)
    return (f * cos_t.reshape(shape) +
            rot.astype(F32) * sin_t.reshape(shape)).astype(x.dtype)


def mla_absorb_q(cfg: GPTConfig, params: dict, i: int, q, q_rot):
    """``W_kvb``'s k-half folded into q: ``q [T, nh, nope + r]`` with its
    rotary part already rotated as ``q_rot [T, nh, r]`` -> float32 ``[T,
    nh, d_c + r]``, a query against the cached ``c_kv | k_r`` itself."""
    k_up = params[f"h{i}.attn.k_up.weight"]
    q_abs = jnp.einsum("thd,hdc->thc", q[..., :cfg.nope_dim].astype(F32),
                       k_up.astype(F32))
    return jnp.concatenate([q_abs, q_rot.astype(F32)], -1)


def mla_absorb_out(cfg: GPTConfig, params: dict, i: int, o_lat, dtype):
    """``W_kvb``'s v-half out of the latent output ``o_lat [T, nh, d_c]``
    -> ``[T, nh * v]``: one up-projection a QUERY token, cached tokens
    are never decompressed."""
    v_up = params[f"h{i}.attn.v_up.weight"]
    o = jnp.einsum("thc,hdc->thd", o_lat.astype(F32), v_up.astype(F32))
    return o.reshape(o.shape[0], -1).astype(dtype)


def moe_route(cfg: GPTConfig, w_router, bias, u):
    """Scores over ALL routed experts in float32 (the matmul too: a bf16
    pass would reorder near-ties).  ``sigmoid_bias``: top-k of sigmoid
    score + bias, the chosen scores renormalised and scaled; ``softmax``:
    top-k of the softmax, its values the weights (the plain block's
    rule; ``moe_norm_topk`` renormalises them to sum 1).  Returns ``(idx
    [T, k] int32, weights [T, k] float32)``."""
    logits = jnp.dot(u.astype(F32), w_router.astype(F32).T,
                     precision=lax.Precision.HIGHEST)
    if cfg.moe_router == "softmax":
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.moe_top_k)
        if cfg.moe_norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return idx, w * cfg.moe_router_scale
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + bias.astype(F32), cfg.moe_top_k)
    chosen = jnp.take_along_axis(s, idx, -1)
    w = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * cfg.moe_router_scale
    return idx, w


def moe_route_down(cfg: GPTConfig, params: dict, i: int, u):
    """An expert layer's way in, token by token on ``u`` [n, H] (normed):
    the router's choice and the latent the routed experts work on.
    Returns ``(idx [n, k] int32, weights [n, k] float32, lat [n, L])``."""
    g = lambda n: params.get(f"h{i}.moe.{n}")  # noqa: E731
    with phase("moe_router"):
        idx, w = moe_route(cfg, g("router.weight"), g("router.bias"), u)
    down = g("latent_down.weight")
    with phase("moe_latent"):
        lat = u if down is None else u @ down.T
    return idx, w, lat


def moe_routed(cfg: GPTConfig, params: dict, i: int, lat, idx, w, live):
    """The routed experts over the WHOLE token axis at once: one grouped
    matmul (``ops/moe_grouped.py``) over the assignments that are
    ``live`` [n] and fall on the experts held here, sorted by expert —
    each hit expert's weights are read once, a dead token or an expert
    nobody chose costs nothing, so the device time follows the routing.
    Returns ``(r [n, L] in ``lat``'s dtype, live tokens per held expert
    [held] int32)``."""
    with phase("moe_routed"):
        r, load = grouped_experts(
            lat, idx, w, live, params[f"h{i}.moe.experts.w1"],
            params[f"h{i}.moe.experts.w2"],
            params.get(f"h{i}.moe.experts.w3"),
            expert_offset=cfg.expert_offset, activation=cfg.activation)
        return r.astype(lat.dtype), load


def moe_up_shared(cfg: GPTConfig, params: dict, i: int, u, r):
    """An expert layer's way out, token by token: the routed part ``r``
    [n, L] back up to the hidden, plus the shared expert on ``u``."""
    g = lambda n: params.get(f"h{i}.moe.{n}")  # noqa: E731
    up = g("latent_up.weight")
    with phase("moe_latent"):
        out = r if up is None else r @ up.T
    s_up = g("shared.up.weight")
    if s_up is not None:
        act, gate = ACTIVATIONS[cfg.activation], g("shared.gate.weight")
        with phase("moe_shared"):
            hid = act(u @ s_up.T) if gate is None else \
                act(u @ gate.T) * (u @ s_up.T)
            out = out + hid @ g("shared.down.weight").T
    return out


def latent_moe(cfg: GPTConfig, params: dict, i: int, u, live):
    """The expert layer on ``u`` [n, H] (normed); ``live`` [n] marks real
    tokens: a dead one is given no routed expert and counts in no load.
    Returns ``(out [n, H], tokens per held expert [held] int32)``.  The
    serving step runs the three parts itself: the middle one once over
    its whole token axis, the outer two region by region."""
    idx, w, lat = moe_route_down(cfg, params, i, u)
    r, load = moe_routed(cfg, params, i, lat, idx, w, live)
    return moe_up_shared(cfg, params, i, u, r), load
