"""Hybrid stacks: one mixer per layer — Mamba-2 / attention / latent
attention (MLA; over an indexer's selection "dsa"; over a window "swa") /
MoE / dense gated MLP.

A ``GPTConfig`` with a ``layer_pattern`` runs ``x = x + mixer_l(RMSNorm_l
(x))`` for every layer, a final norm and an untied head.  This module is
the one place that knows the stack's PARAMETER NAMES and the mixers'
arithmetic; the serving step (``serving/decode.py``) lays them over the
ragged token axis and owns the state (K/V pages for the attention layers,
state slots for the mamba2 layers).  The plain float32 reference is
``benchmark/reference_hybrid.py``.

Tensors (a projection ``W`` is ``[out, in]``, used as ``x @ W.T``)::

    wte.weight [V, H]   lm_head.weight [V, H]   ln_f.weight [H]
    h{i}.norm.weight [H]                                  every layer
    h{i}.mamba.in_proj.weight  [2*inner + 2*G*N + heads, H]   z | xBC | dt
    h{i}.mamba.conv.weight [K, inner + 2*G*N]   .conv.bias [inner + 2*G*N]
    h{i}.mamba.dt_bias / .A_log / .D [heads]    (float32)
    h{i}.mamba.norm.weight [inner]    h{i}.mamba.out_proj.weight [H, inner]
    h{i}.attn.qkv.weight [(nh + 2*kv)*hd, H]    h{i}.attn.out.weight [H, nh*hd]
    h{i}.attn.q_norm.weight / .k_norm.weight [hd]   (``cfg.attn_qk_norm``)
    mla (``L``; nope / rope / v widths n / r / v, latent d_c, q rank R):
    h{i}.attn.q_a.weight [R, H]   .q_a_norm.weight [R]
    h{i}.attn.q_b.weight [nh*(n + r), R]    a head's rows: nope | rope
    h{i}.attn.kv_a.weight [d_c + r, H]      .kv_a_norm.weight [d_c]
    h{i}.attn.k_up.weight [nh, n, d_c]   .v_up.weight [nh, v, d_c]
                          (the two halves of W_kvb, as decode absorbs them)
    h{i}.attn.out.weight [H, nh*v]
    dsa / swa: the mla tensors at ``cfg.geometry(kind)``'s sizes, and
    h{i}.attn.gate.weight [nh, H]           one scalar a head
    h{i}.attn.index.q.weight [IH*ID, R]     .index.w.weight [IH, H]  (dsa)
    h{i}.attn.index.k.weight [ID, H]   .index.k_norm.weight / .bias [ID]
    h{i}.mlp.gate.weight / .up.weight [F, H]   h{i}.mlp.down.weight [H, F]
    h{i}.moe.router.weight [E_all, H]   .router.bias [E_all]  (float32)
    h{i}.moe.latent_down.weight [L, H]  h{i}.moe.latent_up.weight [H, L]
    h{i}.moe.experts.w1 [E_held, L, F]  h{i}.moe.experts.w2 [E_held, F, L]
    h{i}.moe.shared.up.weight [Fs, H]   h{i}.moe.shared.down.weight [H, Fs]
    gated experts (``cfg.moe_gated``): w1 is the gate, and beside it
    h{i}.moe.experts.w3 [E_held, L, F]  h{i}.moe.shared.gate.weight [Fs, H]

Without ``moe_latent_dim`` the experts work on the hidden itself (``L =
H``, no latent projections).  Without a router bias (a softmax router)
``router.bias`` is absent.

An MTP module (``cfg.mtp_pattern``) is the layers ``h{num_layers ..}`` of
the same names and, around them::

    mtp.enorm.weight / .hnorm.weight / .norm.weight [H]
    mtp.eh_proj.weight [H, 2H]     on [enorm(Emb(next token)) | hnorm(x_L)]

What the serving engine refuses depends on the pattern, not on the
stack being a pattern: the prefix cache and speculation for a pattern
with an ``M`` layer (a page prefix carries no recurrent state, a rejected
draft cannot be rolled out of it); speculation for latent layers (their
calls have no verify region); page quantisation for every pattern; ``L``
beside ``*`` (one pool, one layout).  ``mistral4_config`` is the
translation for the ``(L, E) x depth`` stacks of ``model_type: mistral4``,
``dots3_config`` for ``model_type: dots3_note`` (indexed and window latent
layers of different geometry, a leading dense layer),
``exaone_moe_config`` for ``model_type: exaone_moe`` (plain K/V attention
at its own head width behind QK-norms, window layers beside full ones,
an MTP module that drafts for the serving step's verify rows).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.phases import phase
from ..ops.moe_grouped import ACTIVATIONS, grouped_experts
from ..ops.ssd import causal_conv, ssd_chunk_scan, ssd_decode_slots
from .generate import norm_eps
from .gpt import GPTConfig, LatentGeometry

F32 = jnp.float32
LAYER_NORM_EPS = 1e-5           # the indexer's key norm (a LayerNorm)
_MASKED = -1e30                 # a score outside the selection / window
MIXER_OF = {"M": "mamba2", "*": "attention", "E": "moe", "L": "mla"}
# float32 whatever the model's dtype: the recurrence's own parameters and
# the router (its scores decide a top-k)
_F32_PARAMS = ("mamba.dt_bias", "mamba.A_log", "mamba.D",
               "moe.router.weight", "moe.router.bias")


def _refuse_unbuilt(pub: dict) -> None:
    """What no translation builds, refused where the keys are read."""
    if pub.get("n_group", 1) != 1 or pub.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")


def _expert_keys(pub: dict, shared_width: str) -> dict:
    """The expert layer's keys, alike in every published family the
    translations below read (as cut: ``n_routed_experts`` = experts held
    here, ``moe_router_outputs`` = the router's width)."""
    return dict(
        num_experts=pub.get("moe_router_outputs", pub["n_routed_experts"]),
        experts_held=pub["n_routed_experts"],
        expert_offset=pub.get("expert_offset", 0),
        moe_top_k=pub["num_experts_per_tok"],
        moe_router_scale=float(pub["routed_scaling_factor"]),
        moe_ffn_size=pub["moe_intermediate_size"],
        moe_shared_ffn_size=pub[shared_width] * pub["n_shared_experts"])


def hybrid_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of a
    ``nemotron_h``-type model (as cut: ``n_routed_experts`` = experts held
    here, ``moe_router_outputs`` = the router's width) to ``GPTConfig``."""
    pattern = tuple(MIXER_OF[ch] for ch in pub["hybrid_override_pattern"])
    if len(pattern) != pub["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    if pub["hidden_size"] != pub["num_attention_heads"] * pub["head_dim"]:
        raise ValueError(
            "head_dim other than hidden / heads is not built for this "
            "family's translation (its checkpoints have none); the "
            "attention mixer itself takes one (GPTConfig.attn_head_dim, "
            "exaone_moe_config)")
    _refuse_unbuilt(pub)
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
        moe_router="sigmoid_bias",
        moe_latent_dim=pub.get("moe_latent_size"),
        **_expert_keys(pub, "moe_shared_expert_intermediate_size"))
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
    _refuse_unbuilt(pub)
    if pub.get("first_k_dense_replace", 0):
        raise ValueError("leading dense layers (first_k_dense_replace > 0) "
                         "are built for dots3_note (dots3_config), not for "
                         "this family: every layer is an expert layer")
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
        moe_router="softmax",
        moe_norm_topk=bool(pub["norm_topk_prob"]), moe_gated=True,
        **_expert_keys(pub, "moe_intermediate_size"))
    kw.update(overrides)
    return GPTConfig(**kw)


def dots3_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of a
    ``dots3_note``-type model (as cut: ``n_routed_experts`` = experts held
    here, ``moe_router_outputs`` = the router's width, ``layer_types`` =
    the layers kept) to ``GPTConfig``.  A published layer is two pattern
    entries: its attention — "dsa" for ``full_attention`` (MLA at the
    plain keys' sizes, an indexer, a head gate), "swa" for
    ``sliding_attention`` (MLA at the ``swa_*`` sizes over
    ``sliding_window_size`` keys, a head gate) — then its FFN: "mlp" for
    the first ``first_k_dense_replace`` layers, "moe" after (sigmoid
    scores, top-k of score + bias, the chosen renormalised:
    ``noaux_tc`` with one group).  What the keys name and do not spell is
    the caller's to state (``pub["assumed"]``): the two latents behind
    their norms times ``sqrt(hidden / rank)``
    (``apply_mla_qkv_lora_rescale``), the head gate on the mixer's normed
    input, the indexer's LayerNorm / rotary split / weight scaling."""
    _refuse_unbuilt(pub)
    if pub["scoring_func"] != "sigmoid" or pub["topk_method"] != "noaux_tc":
        raise ValueError("the router is built for sigmoid scores with a "
                         "selection bias (noaux_tc)")
    if pub.get("rope_scaling"):
        raise ValueError("a scaled rotary stream is not built here")
    if pub.get("moe_layer_freq", 1) != 1:
        raise ValueError("moe_layer_freq other than 1 is not built")
    for key in ("attention_gate_type", "swa_attention_gate_type"):
        if pub.get(key) != "headwise":
            raise ValueError(f"{key} other than headwise is not built")
    kinds = {"full_attention": "dsa", "sliding_attention": "swa"}
    types = pub["layer_types"]
    if len(types) != pub["num_hidden_layers"] or set(types) - set(kinds):
        raise ValueError("layer_types names full_attention or "
                         "sliding_attention for each of num_hidden_layers")
    hidden = pub["hidden_size"]
    rescale = bool(pub.get("apply_mla_qkv_lora_rescale"))

    def geo(pre: str, **kw) -> LatentGeometry:
        g = lambda k: pub[pre + k]  # noqa: E731
        q_rank, latent = g("q_lora_rank"), g("kv_lora_rank")
        nope, rope = g("qk_nope_head_dim"), g("qk_rope_head_dim")
        return LatentGeometry(
            heads=g("num_attention_heads"), q_rank=q_rank, latent=latent,
            nope=nope, rope=rope, v=g("v_head_dim"),
            theta=float(g("rope_theta")), scale=(nope + rope) ** -0.5,
            interleave=bool(pub.get("rope_interleave", True)),
            q_rescale=math.sqrt(hidden / q_rank) if rescale else 1.0,
            kv_rescale=math.sqrt(hidden / latent) if rescale else 1.0,
            gate=True, **kw)

    geometry = {
        "dsa": geo("", index_heads=pub["index_n_heads"],
                   index_dim=pub["index_head_dim"],
                   index_rope=pub["qk_rope_head_dim"],
                   index_topk=pub["index_topk"]),
        "swa": geo("swa_", window=pub["sliding_window_size"])}
    dense = pub.get("first_k_dense_replace", 0)
    pattern = tuple(m for l, t in enumerate(types)
                    for m in (kinds[t], "mlp" if l < dense else "moe"))
    kw = dict(
        vocab_size=pub["vocab_size"], hidden_size=hidden,
        num_layers=len(pattern), num_heads=pub["num_attention_heads"],
        ffn_hidden_size=pub["intermediate_size"],
        max_seq_len=pub["max_position_embeddings"],
        activation=pub["hidden_act"], norm="rmsnorm", position="rotary",
        norm_eps=float(pub["rms_norm_eps"]),
        tie_embeddings=bool(pub["tie_word_embeddings"]), sp=False,
        dtype=pub.get("dtype", "bfloat16"), layer_pattern=pattern,
        mixer_geometry=geometry, moe_router="sigmoid_bias",
        moe_gated=True, **_expert_keys(pub, "moe_intermediate_size"))
    if not bool(pub["norm_topk_prob"]):
        raise ValueError("sigmoid routing is built with the chosen scores "
                         "renormalised (norm_topk_prob)")
    kw.update(overrides)
    return GPTConfig(**kw)


def exaone_moe_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of an
    ``exaone_moe``-type model (as cut: ``num_experts`` = experts held
    here, ``moe_router_outputs`` = the router's width, ``layer_types`` /
    ``mlp_layer_types`` = the layers kept) to ``GPTConfig``.  A published
    layer is two pattern entries: "attention" — plain K/V heads of
    ``head_dim`` lanes behind an RMSNorm on q and on k, rotated (by
    halves, base ``rope_theta``) and windowed to ``sliding_window`` keys
    in a ``sliding_attention`` layer, neither in a ``full_attention``
    one — then "mlp" for a ``dense`` entry of ``mlp_layer_types``, "moe"
    for a ``sparse`` one (sigmoid scores, top-k of score + bias, the
    chosen renormalised x ``routed_scaling_factor``, one shared expert).
    ``num_nextn_predict_layers`` MTP modules of ``mtp_layer_types``
    follow (one is built), each an attention layer and a sparse FFN.
    What the keys name and do not spell is the caller's to state
    (``pub["assumed"]``): pre-norm blocks, where the QK-norm and the
    rotation apply, that the window counts the query, the MTP module's
    form."""
    _refuse_unbuilt(pub)
    if pub.get("scoring_func", "sigmoid") != "sigmoid" or \
            not pub.get("norm_topk_prob", True):
        raise ValueError("the router is built for sigmoid scores, the "
                         "chosen renormalised")
    kinds = ("full_attention", "sliding_attention")
    types, ffns = pub["layer_types"], pub["mlp_layer_types"]
    if not (len(types) == len(ffns) == pub["num_hidden_layers"]) or \
            set(types) - set(kinds) or set(ffns) - {"dense", "sparse"}:
        raise ValueError("layer_types / mlp_layer_types name one attention "
                         "kind and one FFN kind for each of "
                         "num_hidden_layers")
    rp = pub["rope_parameters"]
    if rp.get("rope_type", "default") != "default":
        raise ValueError("a scaled rotary stream is not built here")
    mtp = pub.get("num_nextn_predict_layers", 0)
    if mtp > 1 or (mtp and pub.get("mtp_layer_types",
                                   ["full_attention"]) != ["full_attention"]):
        raise ValueError("one MTP module with a full_attention layer is "
                         "built")
    pattern = tuple(m for f in ffns
                    for m in ("attention", "mlp" if f == "dense" else "moe"))
    window = tuple(2 * l for l, t in enumerate(types) if t == kinds[1])
    kw = dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=len(pattern), num_heads=pub["num_attention_heads"],
        num_kv_heads=pub["num_key_value_heads"],
        attn_head_dim=pub["head_dim"], attn_qk_norm=True,
        attn_window=pub["sliding_window"] if window else 0,
        attn_window_layers=window, attn_rope="window",
        rope_theta=float(rp["rope_theta"]),
        ffn_hidden_size=pub["intermediate_size"],
        max_seq_len=pub["max_position_embeddings"],
        activation=pub["hidden_act"], norm="rmsnorm", position="rotary",
        norm_eps=float(pub["rms_norm_eps"]),
        tie_embeddings=bool(pub["tie_word_embeddings"]), sp=False,
        dtype=pub.get("dtype", "bfloat16"), layer_pattern=pattern,
        mtp_pattern=("attention", "moe") * mtp, moe_router="sigmoid_bias",
        moe_gated=True,
        **_expert_keys(dict(pub, n_routed_experts=pub["num_experts"],
                            n_shared_experts=pub["num_shared_experts"]),
                       "moe_intermediate_size"))
    kw.update(overrides)
    return GPTConfig(**kw)


def rotary_tables(theta: float, dim: int, max_len: int):
    """``(cos, sin) [max_len, dim]`` float32 of a plain rotary stream at
    base ``theta``, the halves laid ``[angles | angles]``
    (``rotate_halves``)."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.outer(np.arange(max_len, dtype=np.float64), inv)
    emb = np.concatenate([ang, ang], -1).astype(np.float32)
    return jnp.asarray(np.cos(emb)), jnp.asarray(np.sin(emb))


def mla_rotary_tables(cfg: GPTConfig, max_len: int, geo=None):
    """``(cos, sin [max_len, r], q_scale [max_len])`` of the rotary
    stream, float32: YaRN frequencies where ``cfg.rope_yarn`` (each
    frequency between its own and its ``1 / factor``, by how many turns
    it makes in the original positions), times the tables' factor; the
    halves laid ``[angles | angles]`` for the half-split rotation (an
    interleaved stream is brought into that order first: ``mla_rotate``).
    ``q_scale`` is the per-position factor on q (1 without
    ``cfg.q_pos_scale``).  ``geo`` (a dsa / swa layer's geometry) gives
    its own width and base, unscaled."""
    d, theta = (geo.rope, geo.theta) if geo else (cfg.rope_dim,
                                                  cfg.rope_theta)
    freqs = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv, table = 1.0 / freqs, 1.0
    if cfg.rope_yarn and geo is None:
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
    if cfg.q_pos_scale and geo is None:
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
    if c.mtp_pattern:
        for n in ("enorm", "hnorm", "norm"):
            out[f"mtp.{n}.weight"] = (hd,)
        out["mtp.eh_proj.weight"] = (hd, 2 * hd)
    for i, mixer in enumerate(c.stack_pattern):
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
            if c.attn_qk_norm:
                out[p + "attn.q_norm.weight"] = (c.head_dim,)
                out[p + "attn.k_norm.weight"] = (c.head_dim,)
        elif mixer in ("mla", "dsa", "swa"):
            g = c.geometry(mixer)
            nh, d_c, qk = g.heads, g.latent, g.nope + g.rope
            if g.q_rank:
                out[p + "attn.q_a.weight"] = (g.q_rank, hd)
                out[p + "attn.q_a_norm.weight"] = (g.q_rank,)
            out[p + "attn.q_b.weight"] = (nh * qk, g.q_rank or hd)
            out[p + "attn.kv_a.weight"] = (d_c + g.rope, hd)
            out[p + "attn.kv_a_norm.weight"] = (d_c,)
            out[p + "attn.k_up.weight"] = (nh, g.nope, d_c)
            out[p + "attn.v_up.weight"] = (nh, g.v, d_c)
            out[p + "attn.out.weight"] = (hd, nh * g.v)
            if g.gate:
                out[p + "attn.gate.weight"] = (nh, hd)
            if g.index_topk:
                out[p + "attn.index.q.weight"] = (
                    g.index_heads * g.index_dim, g.q_rank or hd)
                out[p + "attn.index.k.weight"] = (g.index_dim, hd)
                out[p + "attn.index.k_norm.weight"] = (g.index_dim,)
                out[p + "attn.index.k_norm.bias"] = (g.index_dim,)
                out[p + "attn.index.w.weight"] = (g.index_heads, hd)
        elif mixer == "mlp":
            for n in ("gate", "up"):
                out[p + f"mlp.{n}.weight"] = (c.ffn_hidden_size, hd)
            out[p + "mlp.down.weight"] = (hd, c.ffn_hidden_size)
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
    (``out_proj``, ``attn.out``, ``latent_up``, ``shared.down``,
    ``mlp.down``) scaled by
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
        if tail == "mamba.conv.bias" or tail.endswith("norm.bias"):
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
                             "moe.latent_up.weight", "mlp.down.weight",
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
               live, fresh, walk):
    """One token for each LIVE state slot: ``zxd`` [S, in_proj width] in
    SLOT order, ``conv_state`` [S, K-1, conv_dim] (a few MB: passed over
    whole, kept where not ``live``), ``ssm_state`` [S, H, P, N] float32 —
    the store, of which the recurrence reads and writes the slots of
    ``walk`` (``ops.ssd.live_slot_list(live)``, built once a step) in
    place and touches no other; a ``fresh`` slot (its sequence's first
    token) starts from zeros whatever it holds.  Returns ``(y [S, inner]
    float32 before the gate, zeros where not ``live``; new conv state;
    the store)``."""
    _, xbc, dt_raw = _split_zxd(cfg, zxd)
    with phase("ssm_conv"):
        tail = jnp.where(fresh[:, None, None], 0, conv_state)
        full = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], 1)
        conv = jnp.einsum("skc,kc->sc", full.astype(F32),
                          w.conv_w.astype(F32)) + w.conv_b.astype(F32)
        new_conv = jnp.where(live[:, None, None], full[:, 1:], conv_state)
    with phase("ssm_scan"):
        x, b, c, dt, a = _ssm_inputs(cfg, w, conv, dt_raw)
        y, new_ssm = ssd_decode_slots(x, dt, a, b, c, w.d, ssm_state, *walk,
                                      fresh)
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


def _rms_eps(eps: float, v, w):
    f = v.astype(F32)
    f = f * lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + eps)
    return (f * w.astype(F32)).astype(v.dtype)


def _rms(cfg: GPTConfig, v, w):
    return _rms_eps(norm_eps(cfg), v, w)


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


def latent_in(cfg: GPTConfig, params: dict, i: int, u,
              geo: LatentGeometry):
    """``mla_in`` at a mixer kind's own geometry (a dsa / swa layer): the
    two latents times their rescale behind their norms.  Returns ``(q
    [n, nh, nope + rope], c_kv [n, d_c], k_r [n, r], c_q [n, R])``; the
    indexer reads ``c_q`` too."""
    g = lambda n: params[f"h{i}.attn.{n}"]  # noqa: E731
    c_q = _rms(cfg, u @ g("q_a.weight").T, g("q_a_norm.weight"))
    c_q = c_q * jnp.asarray(geo.q_rescale, c_q.dtype)
    kv = u @ g("kv_a.weight").T
    c_kv = _rms(cfg, kv[:, :geo.latent], g("kv_a_norm.weight"))
    q = (c_q @ g("q_b.weight").T).reshape(
        u.shape[0], geo.heads, geo.nope + geo.rope)
    return (q, c_kv * jnp.asarray(geo.kv_rescale, c_kv.dtype),
            kv[:, geo.latent:], c_q)


def head_gate(params: dict, i: int, u):
    """``sigmoid(u W_g)`` [n, nh] float32: one scalar a head, on the
    mixer's normed input; it multiplies the head's output before
    ``W_o``."""
    return jax.nn.sigmoid(
        (u @ params[f"h{i}.attn.gate.weight"].T).astype(F32))


def index_queries(params: dict, i: int, u, c_q, geo: LatentGeometry):
    """The indexer's query side of tokens ``u`` [n, H] with their low-rank
    q ``c_q`` [n, R]: ``(q [n, IH, ID], w [n, IH] float32)``, before the
    rotation."""
    g = lambda n: params[f"h{i}.attn.index.{n}"]  # noqa: E731
    q = (c_q @ g("q.weight").T).reshape(
        u.shape[0], geo.index_heads, geo.index_dim)
    return q, (u @ g("w.weight").T).astype(F32) * (
        geo.index_heads ** -0.5 * geo.index_dim ** -0.5)


def index_keys(params: dict, i: int, u):
    """The indexer's key of tokens ``u`` [n, H]: ``LayerNorm(u W_Ik)``
    [n, ID] in ``u``'s dtype, before the rotation; what the pool caches
    (rotated) beside the layer's latent."""
    g = lambda n: params[f"h{i}.attn.index.{n}"]  # noqa: E731
    k = (u @ g("k.weight").T).astype(F32)
    k = (k - k.mean(-1, keepdims=True)) * lax.rsqrt(
        k.var(-1, keepdims=True) + LAYER_NORM_EPS)
    return (k * g("k_norm.weight").astype(F32) +
            g("k_norm.bias").astype(F32)).astype(u.dtype)


def rotate_index(x, cos_t, sin_t):
    """The indexer's rotation of ``x [T, ..., ID]``: the first ``r``
    lanes (the tables' width) by halves, the rest as they are."""
    r = cos_t.shape[-1]
    return jnp.concatenate(
        [rotate_halves(x[..., :r], cos_t, sin_t), x[..., r:]], -1)


def index_positions(cfg: GPTConfig, params: dict, i: int, u, positions):
    """The positions dsa layer ``i`` selects for the queries at
    ``positions`` of ONE sequence whose normed layer input is ``u`` [T,
    H]: the serving step's own indexer arithmetic (``index_queries`` /
    ``index_keys`` / ``rotate_index`` / ``index_scores`` /
    ``index_select`` in the model's dtype) on a whole sequence at once,
    for the check of the selection against the plain reference.
    Returns ``[len(positions), k]`` int32, an empty place holding ``T``."""
    geo = cfg.geometry("dsa")
    cos, sin, _ = mla_rotary_tables(cfg, u.shape[0], geo)
    layer = {k: v for k, v in params.items() if k.startswith(f"h{i}.attn.")}
    return _index_positions(
        layer, u.astype(param_dtype(cfg, "attn.q_a.weight")),
        jnp.asarray(positions, jnp.int32), cos, sin, i=i, geo=geo,
        eps=norm_eps(cfg))


@functools.partial(jax.jit, static_argnames=("i", "geo", "eps"))
def _index_positions(params, u, pos, cos, sin, *, i: int, geo, eps: float):
    g = lambda n: params[f"h{i}.attn.{n}"]  # noqa: E731
    uq = u[pos]
    c_q = _rms_eps(eps, uq @ g("q_a.weight").T, g("q_a_norm.weight"))
    c_q = c_q * jnp.asarray(geo.q_rescale, c_q.dtype)
    q, w = index_queries(params, i, uq, c_q, geo)
    q = rotate_index(q, cos[pos], sin[pos])
    keys = rotate_index(index_keys(params, i, u), cos, sin)

    def one(args):
        q_b, w_b, pos_b = args
        sel, valid = index_select(index_scores(q_b, keys, w_b), pos_b,
                                  geo.index_topk)
        return jnp.where(valid, sel, u.shape[0])

    return _by_blocks(one, (q, w, pos), 32)


def rotate_halves(x, cos_t, sin_t):
    """``x [T, ..., r]`` laid ``[first halves | second halves]`` turned by
    the per-token tables ``cos_t, sin_t [T, r]``, in float32."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    return (x.astype(F32) * cos_t.reshape(shape) +
            rot.astype(F32) * sin_t.reshape(shape)).astype(x.dtype)


def index_scores(q, keys, w):
    """The indexer's score of every (query, cached position) pair,
    float32: ``sum_j w[t, j] relu(q[t, j] . k[s])``, the products
    accumulated in float32.  ``q [n, IH, ID]``, ``w [n, IH]`` float32;
    ``keys`` is ``[S, ID]`` (one row's context, shared by the ``n``
    queries) or ``[n, S, ID]`` (a context a query)."""
    eq = "njd,sd->njs" if keys.ndim == 2 else "njd,nsd->njs"
    s = jnp.einsum(eq, q, keys, preferred_element_type=F32)
    return jnp.einsum("njs,nj->ns", jax.nn.relu(s), w)


def index_select(scores, qpos, topk: int):
    """The ``topk`` best positions of each query among ``s <= qpos``,
    EXACT: the set ``lax.top_k`` of the float32 scores gives (a tie at the
    k-th score to the lower position), found without a sort.  The k-th
    largest score is searched bit by bit on the scores' ordered integer
    image (32 counts over the row); the positions above it, and the first
    ties, are then read out in position order: a slot of the result finds
    its 128-position block by the blocks' running counts and its lane by
    the running count inside that block.  Returns ``(positions [n, k]
    int32 ascending, valid [n, k])``; a query with fewer than ``k``
    positions behind it reads them all (a full sort of 33,792 scores for
    each of a chunk's queries was the longest thing in a chunk step:
    PERF.md, PR 39)."""
    n, s = scores.shape
    k = min(topk, s)
    lanes = 128
    seen = jnp.arange(s)[None, :] <= qpos[:, None]
    bits = lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(F32), jnp.int32)
    # float order as unsigned order; 0 is kept for what a query cannot see
    key = jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31))
    key = jnp.where(seen, lax.bitcast_convert_type(key, jnp.uint32), 0)
    pad = -s % lanes
    key = jnp.pad(key, ((0, 0), (0, pad)))

    def refine(b, thr):
        cand = thr | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], -1) >= k
        return jnp.where(enough, cand, thr)

    thr = lax.fori_loop(0, 32, refine, jnp.zeros((n,), jnp.uint32))[:, None]
    above, tie = key > thr, (key == thr) & (key > 0)
    room = k - jnp.sum(above, -1, keepdims=True)
    member = above | (tie & (jnp.cumsum(tie, -1) <= room))
    blocks = member.reshape(n, -1, lanes)
    count = blocks.sum(-1)                                  # [n, nb]
    end = jnp.cumsum(count, -1)
    j = jnp.arange(k)
    blk = jnp.sum(end[:, None, :] <= j[None, :, None], -1)  # [n, k]
    blk = jnp.minimum(blk, blocks.shape[1] - 1)
    before = jnp.take_along_axis(end - count, blk, 1)
    inside = jnp.cumsum(jnp.take_along_axis(
        blocks, blk[:, :, None], 1).astype(jnp.int32), -1)  # [n, k, lanes]
    lane = jnp.sum(inside <= (j[None, :] - before)[:, :, None], -1)
    valid = j[None, :] < end[:, -1:]
    pos = jnp.where(valid, blk * lanes + lane, 0)
    return pos.astype(jnp.int32), valid


def selected_attention(q_cat, sel, d_c: int, valid, scale: float):
    """Absorbed attention of each query over ITS OWN gathered positions:
    ``q_cat [n, nh, w]`` float32 against ``sel [n, k, w]``, the pool's
    rows ``c_kv | k_r | zero lanes`` in its dtype (``q_cat``'s rotary part
    is padded alike), ``valid [n, k]``.  The products run in the pool's
    dtype with float32 accumulation, the softmax in float32.  Returns the
    latent output ``[n, nh, d_c]`` float32."""
    dt = sel.dtype
    s = jnp.einsum("nhw,nkw->nhk", q_cat.astype(dt), sel,
                   preferred_element_type=F32)
    s = jnp.where(valid[:, None, :], s * scale, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhk,nkc->nhc", p.astype(dt), sel[..., :d_c],
                      preferred_element_type=F32)


def _by_blocks(f, arrays, block: int):
    """``f`` over blocks of ``block`` leading rows of the arrays of
    ``arrays`` (one length ``n``), one block live at a time, the results
    joined: what bounds a step's temporaries by the block and not by the
    chunk."""
    n = arrays[0].shape[0]
    if n <= block:
        return f(arrays)
    pad = -n % block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)  # noqa: E731
                            ).reshape((-1, block) + a.shape[1:])
    out = lax.map(f, tuple(cut(a) for a in arrays))
    return out.reshape((-1,) + out.shape[2:])[:n]


def indexed_attention(geo: LatentGeometry, iq, iw, q_cat, qpos, table,
                      pools, block: int = 32):
    """A dsa layer's attention for ``n`` query tokens at positions
    ``qpos`` [n]: the indexer scores every cached position of the
    query's context, the ``index_topk`` best are gathered out of the
    pages and the absorbed attention runs over them alone.  ``table`` is
    ONE row's page table ``[maxp]`` (a chunk: the queries share a
    context, its index keys are gathered once) or ``[n, maxp]`` (decode
    rows: a context a query).  ``pools`` = the layer's latent stream
    (``c_kv | k_r | zero lanes``) and its index-key stream ``[P, 1, ps,
    w]``; ``iq [n, IH, ID]`` / ``iw [n, IH]`` the rotated index queries
    and head weights, ``q_cat [n, nh, w]`` the absorbed query, its rotary
    part padded as the page's.  ``block`` queries are live at a time.
    Returns the latent output ``[n, nh, d_c]`` float32.  The selection
    stays on the device: nothing of it leaves the step."""
    cp = pools[0].reshape(-1, pools[0].shape[-1])
    ps = pools[0].shape[2]
    shared = table.ndim == 1

    def keys_of(tab):
        """A context's index keys, gathered a page at a time (a page's
        rows lie together: one 16 KB copy, not 64 of 256 B)."""
        k = pools[1][tab]                        # [.., maxp, 1, ps, ID]
        return k.reshape(tab.shape[:-1] + (-1, k.shape[-1]))

    if shared:
        with phase("attn_index"):
            keys = keys_of(table)                               # [S, ID]

    def one(args):
        iq_b, iw_b, qc_b, qpos_b, *tab = args
        tab = table[None] if shared else tab[0]
        with phase("attn_index"):
            k = keys if shared else keys_of(tab)
            pos, valid = index_select(index_scores(iq_b, k, iw_b), qpos_b,
                                      geo.index_topk)
        with phase("attn_sparse"):
            page = jnp.take_along_axis(
                jnp.broadcast_to(tab, (pos.shape[0], tab.shape[-1])),
                pos // ps, axis=1)
            slot = page * ps + pos % ps                         # [n, k]
            return selected_attention(qc_b, cp[slot], geo.latent, valid,
                                      geo.scale)

    return _by_blocks(one, (iq, iw, q_cat, qpos) +
                      (() if shared else (table,)), block)


def window_attention(geo: LatentGeometry, q_cat, qpos, table, base, pool):
    """A swa layer's attention for ``n`` query tokens at positions
    ``qpos`` [n] over the window-space pages of ``table``, whose first
    slot holds position ``base``: ONE row's ``[wp]`` with a scalar
    ``base`` (a chunk) or ``[n, wp]`` with ``base [n]`` (decode rows).
    ``pool`` is the layer's stream ``c_kv | k_r | zero lanes``, ``q_cat
    [n, nh, w]`` padded alike.  A query reads the ``geo.window`` positions
    up to itself.  Returns the latent output ``[n, nh, d_c]`` float32."""
    shared = table.ndim == 1
    keys = pool[table].reshape(table.shape[:-1] + (-1, pool.shape[3]))
    kpos = jnp.asarray(base)[..., None] + jnp.arange(keys.shape[-2])
    kpos = jnp.broadcast_to(kpos, (qpos.shape[0], keys.shape[-2]))
    keep = (kpos <= qpos[:, None]) & (kpos > qpos[:, None] - geo.window)
    dt = keys.dtype
    s = jnp.einsum("nhw,kw->nhk" if shared else "nhw,nkw->nhk",
                   q_cat.astype(dt), keys, preferred_element_type=F32)
    p = jax.nn.softmax(jnp.where(keep[:, None, :], s * geo.scale, _MASKED),
                       axis=-1)
    return jnp.einsum("nhk,kc->nhc" if shared else "nhk,nkc->nhc",
                      p.astype(dt), keys[..., :geo.latent],
                      preferred_element_type=F32)


def kv_window_attention(q, qpos, table, base, k_pages, v_pages,
                        window: int, scale: float):
    """A plain K/V window layer's attention in XLA: ``rows`` rows of
    ``width`` query tokens each (``q [rows * width, nh, hd]`` at positions
    ``qpos``) over the window-space pages of ``table [rows, wp]``, whose
    first slot holds position ``base [rows]``; ``k_pages`` / ``v_pages
    [P, kv, ps, hd]``.  A query reads the ``window`` positions up to
    itself; query head ``h`` reads key-value head ``h // (nh / kv)``.  One
    gather of each row's few pages and a masked softmax over them: on the
    chip 2.5-3.5 times faster than a Pallas ragged call with a lower bound
    over the same table (PERF.md section 6, PR 42, step 0).  Returns ``[rows * width,
    nh, hd]`` float32."""
    (n, nh, hd), rows = q.shape, table.shape[0]
    kv, ps = k_pages.shape[1], k_pages.shape[2]
    k, v = k_pages[table], v_pages[table]           # [rows, wp, kv, ps, hd]
    kpos = (base[:, None, None] + ps * jnp.arange(table.shape[1])[:, None]
            + jnp.arange(ps))[:, None]                  # [rows, 1, wp, ps]
    qp = qpos.reshape(rows, -1)[:, :, None, None]
    keep = ((kpos <= qp) & (kpos > qp - window))[:, :, None, None]
    qg = q.reshape(rows, -1, kv, nh // kv, hd).astype(k.dtype)
    s = jnp.einsum("rqhgd,rwhkd->rqhgwk", qg, k,
                   preferred_element_type=F32) * scale
    shape = s.shape
    pr = jax.nn.softmax(jnp.where(keep, s, _MASKED).reshape(
        shape[:4] + (-1,)), axis=-1).reshape(shape)
    o = jnp.einsum("rqhgwk,rwhkd->rqhgd", pr.astype(v.dtype), v,
                   preferred_element_type=F32)
    return o.reshape(n, nh, hd)


def attention_qkv(cfg: GPTConfig, params: dict, i: int, u):
    """A plain attention layer's way in, token by token on ``u`` [n, H]
    (normed): ``(q [n, nh, hd], k, v [n, kv, hd])``, q and k behind their
    head-wise RMSNorm where the stack has one, before any rotation."""
    nh, kv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    n = u.shape[0]
    qkv = u @ params[f"h{i}.attn.qkv.weight"].T
    q = qkv[:, :nh * hd].reshape(n, nh, hd)
    k = qkv[:, nh * hd:(nh + kv) * hd].reshape(n, kv, hd)
    v = qkv[:, (nh + kv) * hd:].reshape(n, kv, hd)
    if cfg.attn_qk_norm:
        q = _rms(cfg, q, params[f"h{i}.attn.q_norm.weight"])
        k = _rms(cfg, k, params[f"h{i}.attn.k_norm.weight"])
    return q, k, v


def mtp_in(cfg: GPTConfig, params: dict, emb, x):
    """The MTP module's way in, token by token: ``[RMSNorm(Emb(next
    token)) | RMSNorm(x_L)] W_eh`` on ``emb``, ``x`` [n, H]."""
    g = lambda n: params[f"mtp.{n}.weight"]  # noqa: E731
    cat = jnp.concatenate([_rms(cfg, emb, g("enorm")),
                           _rms(cfg, x, g("hnorm"))], -1)
    return cat @ g("eh_proj").T


def gated_mlp(cfg: GPTConfig, params: dict, i: int, u):
    """A dense gated MLP layer on ``u`` [n, H] (normed)."""
    g = lambda n: params[f"h{i}.mlp.{n}.weight"]  # noqa: E731
    act = ACTIVATIONS[cfg.activation]
    return (act(u @ g("gate").T) * (u @ g("up").T)) @ g("down").T


def mla_rotate(cfg: GPTConfig, x, cos_t, sin_t, interleave=None):
    """The rotary stream of ``x [T, ..., r]`` at per-token tables ``cos_t,
    sin_t [T, r]`` (``mla_rotary_tables`` gathered by position).  An
    interleaved stream (pairs ``(2i, 2i + 1)``) is brought into ``[evens
    | odds]`` and rotated by halves; it stays in that order — q and the
    cached key take the same road, and only their product is read."""
    if cfg.rope_interleave if interleave is None else interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    return rotate_halves(x, cos_t, sin_t)


def mla_absorb_q(cfg: GPTConfig, params: dict, i: int, q, q_rot,
                 nope=None):
    """``W_kvb``'s k-half folded into q: ``q [T, nh, nope + r]`` with its
    rotary part already rotated as ``q_rot [T, nh, r]`` -> float32 ``[T,
    nh, d_c + r]``, a query against the cached ``c_kv | k_r`` itself.
    ``nope`` is the head's width outside the rotary stream where it is
    not the stack's (a dsa / swa layer)."""
    k_up = params[f"h{i}.attn.k_up.weight"]
    nope = cfg.nope_dim if nope is None else nope
    q_abs = jnp.einsum("thd,hdc->thc", q[..., :nope].astype(F32),
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
