"""Hybrid stacks: one mixer per layer — Mamba-2 / attention / latent
attention (MLA; over an indexer's selection "dsa"; over a window "swa") /
MoE / dense gated MLP.

A ``GPTConfig`` with a ``layer_pattern`` runs ``x = x + mixer_l(RMSNorm_l
(x))`` for every layer, a final norm and a head (``lm_head.weight``, or
``wte.weight`` again where ``cfg.tie_embeddings``).  This module is
the one place that knows the stack's PARAMETER NAMES and the mixers'
arithmetic; the serving step (``serving/decode.py``) lays them over the
ragged token axis and owns the state (K/V pages for the attention layers,
state slots for the recurrent layers).  The plain float32 references are
``benchmark/reference_hybrid.py`` / ``_jamba.py`` / ``_olmo_hybrid.py``.

Tensors (a projection ``W`` is ``[out, in]``, used as ``x @ W.T``)::

    wte.weight [V, H]   lm_head.weight [V, H]   ln_f.weight [H]
    h{i}.norm.weight [H]                                  every layer
    h{i}.mamba.in_proj.weight  [2*inner + 2*G*N + heads, H]   z | xBC | dt
    h{i}.mamba.conv.weight [K, inner + 2*G*N]   .conv.bias [inner + 2*G*N]
    h{i}.mamba.dt_bias / .A_log / .D [heads]    (float32)
    h{i}.mamba.norm.weight [inner]    h{i}.mamba.out_proj.weight [H, inner]
    mamba1 (``inner`` channels C, state N, dt rank R):
    h{i}.mamba.in_proj.weight [2*C, H]      xs | z
    h{i}.mamba.conv.weight [K, C]   .conv.bias [C]        (the x channels)
    h{i}.mamba.x_proj.weight [R + 2*N, C]   dt_r | B | C
    h{i}.mamba.dt_norm.weight [R]   .b_norm.weight / .c_norm.weight [N]
    h{i}.mamba.dt_proj.weight [C, R]   .dt_proj.bias [C]  (bias float32)
    h{i}.mamba.A_log [N, C] / .D [C]  (float32; the published [C, N] laid
                          as the scan walks it)   .out_proj.weight [H, C]
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
with a recurrent layer (a page prefix carries no recurrent state, a
rejected draft cannot be rolled out of it); speculation for latent layers
(their calls have no verify region); page quantisation for every pattern;
``L`` beside ``*`` (one pool, one layout).  The translations, one a
published family: ``hybrid_config`` (``nemotron_h``), ``mistral4_config``
(the ``(L, E) x depth`` stacks), ``dots3_config`` (``dots3_note``: indexed
and window latent layers of different geometry, a leading dense layer),
``exaone_moe_config`` (plain K/V attention at its own head width behind
QK-norms, window layers beside full ones, an MTP module that drafts for
the verify rows), ``sdar_moe_config`` (the same attention and gated
experts under a softmax router, generated by diffusion over blocks),
``jamba_config`` (mamba1 / attention, each followed by a gated MLP; a tied
head) and, with the gdn mixer at the END of the file, ``olmo_hybrid_config``.
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
from ..ops.gated_delta import gated_delta_chunk, gated_delta_slots
from ..ops.index_score import INDEX_SELECT_BLOCK
from ..ops.index_score import by_blocks as _by_blocks
from ..ops.index_score import index_score_pages, index_scores  # noqa: F401
from ..ops.moe_grouped import ACTIVATIONS, grouped_experts
from ..ops.selected_attention import attend_selected, selected_slots
from ..ops.selective_scan import selective_scan_chunk, selective_scan_slots
from ..ops.ssd import causal_conv, ssd_chunk_scan, ssd_decode_slots
from .generate import norm_eps
from .gpt import GPTConfig, LatentGeometry

F32 = jnp.float32
LAYER_NORM_EPS = 1e-5           # the indexer's key norm (a LayerNorm)
_MASKED = -1e30                 # a score outside the selection / window
MIXER_OF = {"M": "mamba2", "*": "attention", "E": "moe", "L": "mla"}
# float32 whatever the model's dtype: the recurrence's own parameters and
# the router (its scores decide a top-k)
_F32_PARAMS = (".dt_bias", "mamba.dt_proj.bias", ".A_log", "mamba.D",
               "moe.router.weight", "moe.router.bias")  # mamba.* and gdn.*


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


def sdar_moe_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of an
    ``sdar_moe``-type model (a Qwen3-MoE stack generated by diffusion over
    blocks) to ``GPTConfig``.  Every published layer is two pattern
    entries: "attention" — plain K/V heads of ``head_dim`` lanes behind an
    RMSNorm on q and on k, rotated by halves at base ``rope_theta`` in
    every layer — then "moe": softmax over all ``num_experts`` in float32,
    the ``num_experts_per_tok`` largest renormalised (``norm_topk_prob``),
    gated silu experts ``moe_intermediate_size`` wide, no shared expert,
    every expert held.  What is the MODEL'S and not the request's rides on
    the configuration: ``diffusion_block`` (the block length the model was
    trained under) and ``mask_token_id``; ``config.json`` carries neither,
    so the caller states them (``pub["assumed"]``: ``block_length``,
    ``mask_token_id``).  ``intermediate_size`` is read by no layer
    (``mlp_only_layers`` is empty and ``decoder_sparse_step`` 1)."""
    _refuse_unbuilt(pub)
    assumed = pub.get("assumed", {})
    unbuilt = [k for k, ok in (
        ("mlp_only_layers", not pub.get("mlp_only_layers")),
        ("decoder_sparse_step", pub.get("decoder_sparse_step", 1) == 1),
        ("use_sliding_window", not pub.get("use_sliding_window")),
        ("rope_scaling", pub.get("rope_scaling") is None),
        ("attention_bias", not pub.get("attention_bias")),
        ("norm_topk_prob", bool(pub.get("norm_topk_prob", True))),
        ("assumed.block_length", "block_length" in assumed),
        ("assumed.mask_token_id", "mask_token_id" in assumed)) if not ok]
    if unbuilt:
        raise ValueError(
            "sdar_moe is built with an expert layer in every layer, no "
            "window, no scaled rotary stream, no biases, the chosen experts "
            "renormalised, and the block length and mask id stated under "
            f"assumed; not built: {', '.join(unbuilt)}")
    layers = pub["num_hidden_layers"]
    kw = dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=2 * layers, num_heads=pub["num_attention_heads"],
        num_kv_heads=pub["num_key_value_heads"],
        attn_head_dim=pub["head_dim"], attn_qk_norm=True, attn_rope="all",
        rope_theta=float(pub["rope_theta"]),
        max_seq_len=pub["max_position_embeddings"],
        activation=pub["hidden_act"], norm="rmsnorm", position="rotary",
        norm_eps=float(pub["rms_norm_eps"]),
        tie_embeddings=bool(pub["tie_word_embeddings"]), sp=False,
        dtype=pub.get("dtype", "bfloat16"),
        layer_pattern=("attention", "moe") * layers,
        moe_router="softmax", moe_norm_topk=True, moe_gated=True,
        num_experts=pub["num_experts"], experts_held=pub["num_experts"],
        moe_top_k=pub["num_experts_per_tok"],
        moe_ffn_size=pub["moe_intermediate_size"],
        diffusion_block=int(assumed["block_length"]),
        mask_token_id=int(assumed["mask_token_id"]))
    kw.update(overrides)
    return GPTConfig(**kw)


def jamba_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of a
    ``jamba``-type model whose feed-forwards are all dense
    (``num_experts: 1``) to ``GPTConfig``.  A published layer is two
    pattern entries, each behind its own RMSNorm and residual: its mixer —
    "attention" where ``i % attn_layer_period == attn_layer_offset``
    (``num_key_value_heads`` K/V heads of ``hidden / heads`` lanes, no
    bias, NO positional encoding), else "mamba1" (``mamba_expand *
    hidden`` channels, ``mamba_d_state`` states, a time step out of a
    rank-``mamba_dt_rank`` projection, RMSNorms on ``dt``, ``B``, ``C``,
    no gated norm before the way out) — then "mlp", ``down(silu(gate u) *
    up u)`` of ``intermediate_size``.  The head is ``wte`` again where
    ``tie_word_embeddings``."""
    unbuilt = [k for k, ok in (
        ("num_experts", pub.get("num_experts", 1) == 1),
        ("sliding_window", pub.get("sliding_window") is None),
        ("mamba_proj_bias", not pub.get("mamba_proj_bias")),
        ("mamba_conv_bias", bool(pub.get("mamba_conv_bias", True)))) if not ok]
    if unbuilt:
        raise ValueError(
            "jamba is built with dense feed-forwards (num_experts 1), no "
            "window, a conv bias and no projection bias; not built: "
            + ", ".join(unbuilt))
    layers = pub["num_hidden_layers"]
    period, offset = pub["attn_layer_period"], pub["attn_layer_offset"]
    pattern = tuple(m for i in range(layers) for m in (
        "attention" if i % period == offset else "mamba1", "mlp"))
    kw = dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=len(pattern), num_heads=pub["num_attention_heads"],
        num_kv_heads=pub["num_key_value_heads"],
        ffn_hidden_size=pub["intermediate_size"],
        max_seq_len=pub["max_position_embeddings"],
        activation=pub["hidden_act"], norm="rmsnorm", position="none",
        norm_eps=float(pub["rms_norm_eps"]),
        tie_embeddings=bool(pub["tie_word_embeddings"]), sp=False,
        dtype=pub.get("dtype", "bfloat16"), layer_pattern=pattern,
        mamba1_inner=pub["mamba_expand"] * pub["hidden_size"],
        mamba1_dt_rank=pub["mamba_dt_rank"],
        mamba_state_dim=pub["mamba_d_state"],
        mamba_conv_kernel=pub["mamba_d_conv"])
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
    out = {"wte.weight": (c.vocab_size, hd), "ln_f.weight": (hd,)}
    if not c.tie_embeddings:
        out["lm_head.weight"] = (c.vocab_size, hd)
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
        elif mixer == "mamba1":
            ch, n, r = c.mamba1_inner, c.mamba_state_dim, c.mamba1_dt_rank
            out[p + "mamba.in_proj.weight"] = (2 * ch, hd)
            out[p + "mamba.conv.weight"] = (c.mamba_conv_kernel, ch)
            out[p + "mamba.conv.bias"] = (ch,)
            out[p + "mamba.x_proj.weight"] = (r + 2 * n, ch)
            out[p + "mamba.dt_norm.weight"] = (r,)
            out[p + "mamba.b_norm.weight"] = (n,)
            out[p + "mamba.c_norm.weight"] = (n,)
            out[p + "mamba.dt_proj.weight"] = (ch, r)
            out[p + "mamba.dt_proj.bias"] = (ch,)
            out[p + "mamba.A_log"] = (n, ch)
            out[p + "mamba.D"] = (ch,)
            out[p + "mamba.out_proj.weight"] = (hd, ch)
        elif mixer == "gdn":
            out.update(_gdn_param_shapes(c, p))
        elif mixer == "attention":
            q, kv = c.num_heads * c.head_dim, c.kv_heads * c.head_dim
            out[p + "attn.qkv.weight"] = (q + 2 * kv, hd)
            out[p + "attn.out.weight"] = (hd, q)
            if c.attn_qk_norm:
                out[p + "attn.q_norm.weight"] = (c.qk_norm_width(q),)
                out[p + "attn.k_norm.weight"] = (c.qk_norm_width(kv),)
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
    ``mlp.down``) scaled by ``1 / sqrt(num_layers)`` (the published
    ``rescale_prenorm_residual``); norms 1, conv bias 0; ``dt`` log-uniform
    in ``[time_step_min, time_step_max]`` floored at ``time_step_floor``,
    stored through the inverse softplus; ``A`` uniform 1..16 as ``A_log``
    (a gdn layer's 0..16; a mamba1 layer's [N, C] is ``log(1..N)`` along
    its state axis), ``D`` 1: the published initialisers.  The hardware bit
    generator (``rbg``) keeps the temporaries at the tensors' own size."""
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
        if tail == "mamba.A_log" and len(shape) == 2:
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=dt))[:, None], shape)
        if tail == "mamba.A_log":
            return jnp.log(jax.random.uniform(key, shape, dt, 1.0, 16.0))
        if tail == "gdn.A_log":
            return jnp.log(jax.random.uniform(key, shape, dt, 1e-4, 16.0))
        if tail.endswith((".dt_bias", "mamba.dt_proj.bias")):
            step = jnp.exp(jax.random.uniform(
                key, shape, dt, math.log(t_min), math.log(t_max)))
            step = jnp.maximum(step, t_floor)
            return step + jnp.log(-jnp.expm1(-step))
        s = down if tail.endswith((".out_proj.weight", "attn.out.weight",
                                   "moe.latent_up.weight", "mlp.down.weight",
                                   "moe.shared.down.weight")) else std
        if tail.endswith(".conv.weight"):           # fan-in K
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


def _conv_rows(w, x, conv_state, live, fresh):
    """The conv's one-token step for every slot: ``x`` [S, channels] in
    SLOT order behind ``conv_state`` [S, K-1, channels] (zeros where
    ``fresh``).  Returns ``(conv output [S, channels] float32, the new
    state: moved on where ``live``, kept elsewhere)``; ``w`` carries
    ``conv_w`` / ``conv_b``."""
    with phase("ssm_conv"):
        tail = jnp.where(fresh[:, None, None], 0, conv_state)
        full = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], 1)
        conv = jnp.einsum("skc,kc->sc", full.astype(F32),
                          w.conv_w.astype(F32)) + w.conv_b.astype(F32)
        return conv, jnp.where(live[:, None, None], full[:, 1:], conv_state)


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
    conv, new_conv = _conv_rows(w, xbc, conv_state, live, fresh)
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


class Mamba1Weights:
    """One mamba1 layer's tensors, looked up once."""

    def __init__(self, params: dict, i: int):
        g = lambda n: params[f"h{i}.mamba.{n}"]  # noqa: E731
        self.in_proj, self.out_proj = g("in_proj.weight"), g("out_proj.weight")
        self.conv_w, self.conv_b = g("conv.weight"), g("conv.bias")
        self.x_proj, self.dt_proj = g("x_proj.weight"), g("dt_proj.weight")
        self.dt_bias, self.a_log, self.d = g("dt_proj.bias"), g("A_log"), g("D")
        self.norms = tuple(g(n + "_norm.weight") for n in ("dt", "b", "c"))


def _scan_inputs(cfg: GPTConfig, w: Mamba1Weights, conv_out):
    """What the selective scan reads, from the conv's output [n, C]
    float32: ``xc = silu(conv)``; ``[dt_r | B | C] = W_x xc``, each behind
    its own RMSNorm; ``dt = softplus(W_dt dt_r + b_dt)``.  The two
    projections run in the model's dtype (phase ``ssm_proj``), the rest in
    float32 (``ssm_scan``: the phases follow one another, a nested one
    would count as its outer).  Returns
    ``(xc [n, C], dt [n, C], B [n, N], C [n, N], A [N, C])`` float32."""
    n, r = cfg.mamba_state_dim, cfg.mamba1_dt_rank
    with phase("ssm_scan"):
        xc = jax.nn.silu(conv_out)
    with phase("ssm_proj"):
        dbc = xc.astype(w.x_proj.dtype) @ w.x_proj.T
    with phase("ssm_scan"):
        parts = (dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:])
        dt_r, b, c = (_rms(cfg, v, nw) for v, nw in zip(parts, w.norms))
    with phase("ssm_proj"):
        dt_raw = dt_r @ w.dt_proj.T
    with phase("ssm_scan"):
        dt = jax.nn.softplus(dt_raw.astype(F32) + w.dt_bias.astype(F32))
        return (xc, dt, b.astype(F32), c.astype(F32),
                -jnp.exp(w.a_log.astype(F32)))


def mamba1_rows(cfg: GPTConfig, w: Mamba1Weights, xz, conv_state, ssm_state,
                live, fresh, walk):
    """``mamba_rows`` for a mamba1 layer: ``xz`` [S, 2 * C] (``xs | z``) in
    SLOT order, ``conv_state`` [S, K-1, C], ``ssm_state`` [S, N, C/128,
    128] float32 — the store, of which the scan reads and writes the slots
    of ``walk`` in place (``ops.selective_scan.selective_scan_slots``).
    Returns ``(y [S, C] float32 before the gate, zeros where not ``live``;
    new conv state; the store)``."""
    conv, new_conv = _conv_rows(w, xz[:, :cfg.mamba1_inner], conv_state,
                                live, fresh)
    xc, dt, b, c, a = _scan_inputs(cfg, w, conv)
    with phase("ssm_scan"):
        y, new_ssm = selective_scan_slots(xc, dt, a, b, c, w.d, ssm_state,
                                          *walk, fresh)
    return y, new_conv, new_ssm


def mamba1_chunk(cfg: GPTConfig, w: Mamba1Weights, xz, conv_state, ssm_state,
                 slot, length, fresh):
    """A run of ``length`` (<= T) consecutive tokens of ONE sequence whose
    state is slot ``slot`` of the stores: ``xz`` [T, 2 * C].  The conv
    tail is taken out and put back; the scan state is carried in place
    (``selective_scan_chunk``), tokens past ``length`` leaving both as
    they found them.  Returns ``(y [T, C] float32 before the gate, the
    conv store, the scan store)``."""
    xs = xz[:, :cfg.mamba1_inner]
    with phase("state_io"):
        tail0 = conv_state[slot]
    with phase("ssm_conv"):
        conv, tail1 = causal_conv(xs, w.conv_w, w.conv_b,
                                  jnp.where(fresh, 0, tail0), length)
    with phase("state_io"):
        conv_state = conv_state.at[slot].set(tail1)
    xc, dt, b, c, a = _scan_inputs(cfg, w, conv)
    with phase("ssm_scan"):
        y, ssm_state = selective_scan_chunk(xc, dt, a, b, c, w.d, ssm_state,
                                            slot, length, fresh)
    return y, conv_state, ssm_state


def mamba1_gate(y, z, dtype):
    """``y * silu(z)``: the gate, with no norm behind it."""
    return (y * jax.nn.silu(z.astype(F32))).astype(dtype)


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
    ``index_keys`` / ``rotate_index`` / ``index_score_pages`` /
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
    # the sequence's keys laid as pages (of the cells' 64 tokens) under an
    # identity table: the entry point that serves scores them
    ps, t = 64, u.shape[0]
    pages = jnp.pad(keys, ((0, -t % ps), (0, 0))).reshape(
        -1, 1, ps, keys.shape[-1])
    scores = index_score_pages(q, w, pages, jnp.arange(pages.shape[0]), t)

    def one(args):
        sel, valid = index_select(args[0], args[1], geo.index_topk)
        return jnp.where(valid, sel, t)

    return _by_blocks(one, (scores, pos), INDEX_SELECT_BLOCK)


def rotate_halves(x, cos_t, sin_t):
    """``x [T, ..., r]`` laid ``[first halves | second halves]`` turned by
    the per-token tables ``cos_t, sin_t [T, r]``, in float32."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    return (x.astype(F32) * cos_t.reshape(shape) +
            rot.astype(F32) * sin_t.reshape(shape)).astype(x.dtype)


def _prefix_counts(blocks):
    """Inclusive running counts along the 128 lanes of ``blocks [n, nb,
    128]`` (bool), float32: one matmul with a triangle of ones.  0 / 1
    operands and float32 accumulation: whole numbers up to 128, exact (in
    bfloat16 too, which is how they are multiplied again)."""
    lanes = blocks.shape[-1]
    tri = jnp.arange(lanes)[:, None] <= jnp.arange(lanes)[None, :]
    return jnp.einsum("nbl,lm->nbm", blocks.astype(jnp.bfloat16),
                      tri.astype(jnp.bfloat16), preferred_element_type=F32)


def index_select(scores, qpos, topk: int):
    """The ``topk`` best positions of each query among ``s <= qpos``,
    EXACT: the set ``lax.top_k`` of the float32 scores gives (a tie at the
    k-th score to the lower position), found without a sort, a gather or
    a scan.  The k-th largest score is searched bit by bit on the scores'
    ordered integer image (32 counts over the row); the positions above
    it, and the first ties, are then read out in position order: a slot
    of the result finds its 128-position block by the blocks' running
    counts and its lane by the running count inside that block.  Every
    running count inside a block is a product with a triangle of ones and
    a slot's block is picked by a one-hot product (:func:`_prefix_counts`:
    whole numbers up to 128, exact on the MXU), because a gather of 2,048
    rows a query costs the device ~15 ns a ROW and a ``cumsum`` a pass a
    step of its scan: 1.28 -> 0.40 ms for 32 queries over 33,792
    positions, 9.2 -> 1.4 ms a 256-token chunk (PERF.md section 6, PR 49;
    a full sort of the scores was the longest thing in a chunk step before
    that: PR 39).  Returns ``(positions [n, k] int32 ascending, valid [n,
    k])``; a query with fewer than ``k`` positions behind it reads them
    all."""
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
    by_block = lambda a: a.reshape(n, -1, lanes)             # noqa: E731
    above, tie = by_block(key > thr), by_block((key == thr) & (key > 0))
    room = k - jnp.sum(above, (1, 2))[:, None, None]
    # a tie's rank among the row's ties: inside its block + the blocks before
    rank = _prefix_counts(tie)
    ties = rank[..., -1]
    rank = rank + (jnp.cumsum(ties, -1) - ties)[..., None]
    inside = _prefix_counts(above | (tie & (rank <= room)))  # [n, nb, lanes]
    nb = inside.shape[1]
    end = jnp.cumsum(inside[..., -1].astype(jnp.int32), -1)  # [n, nb]
    j = jnp.arange(k)
    done = end[:, None, :] <= j[None, :, None]               # [n, k, nb]
    blk = jnp.minimum(jnp.sum(done, -1), nb - 1)             # [n, k]
    before = jnp.max(jnp.where(done, end[:, None, :], 0), -1)
    pick = jnp.arange(nb)[None, None, :] == blk[:, :, None]
    mine = jnp.einsum("nkb,nbl->nkl", pick.astype(jnp.bfloat16),
                      inside.astype(jnp.bfloat16),
                      preferred_element_type=F32)            # [n, k, lanes]
    lane = jnp.sum(mine <= (j[None, :] - before)[:, :, None].astype(F32), -1)
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


def indexed_attention(geo: LatentGeometry, iq, iw, q_cat, qpos, table,
                      pools, block: int = INDEX_SELECT_BLOCK,
                      use_kernel=None, live=None):
    """A dsa layer's attention for ``n`` query tokens at positions
    ``qpos`` [n]: the indexer scores every cached position of the
    query's context, the ``index_topk`` best are gathered out of the
    pages and the absorbed attention runs over them alone.  ``table`` is
    ONE row's page table ``[maxp]`` (a chunk: the queries share a
    context, which reaches to the last of them) or ``[n, maxp]`` (decode
    rows: a context a query, up to itself).  ``pools`` = the layer's
    latent stream (``c_kv | k_r | zero lanes``) and its index-key stream
    ``[P, 1, ps, w]``; ``iq [n, IH, ID]`` / ``iw [n, IH]`` the rotated
    index queries and head weights, ``q_cat [n, nh, w]`` the absorbed
    query, its rotary part padded as the page's.  The scores come out of
    the key pages in one call (``ops/index_score.py``) and the attention
    over the gathered rows is one more (``ops/selected_attention.py``):
    Pallas kernels on the TPU, ``use_kernel`` as everywhere, which reaches
    both.  Selection and read run ``block`` queries at a time.  ``live`` (a
    chunk only: a traced scalar, the row's ``q_len``): how many leading
    queries hold a token; the blocks behind the last of them are neither
    selected for nor read for and give zeros, which nothing reads (a dead
    token writes no page, none is sampled from it); the scoring call is as
    before.  Returns the latent output ``[n, nh, d_c]`` float32."""
    cp = pools[0].reshape(-1, pools[0].shape[-1])
    ps = pools[0].shape[2]
    shared = table.ndim == 1
    with phase("attn_index"):
        scores = index_score_pages(
            iq, iw, pools[1], table,
            jnp.max(qpos) + 1 if shared else qpos + 1, use_kernel=use_kernel)

    def one(args):
        sc_b, qc_b, qpos_b, *tab = args
        tab = table[None] if shared else tab[0]
        with phase("attn_index"):
            pos, valid = index_select(sc_b, qpos_b, geo.index_topk)
        with phase("attn_sparse"):
            # the pages by a one-hot product, not a gather of 2,048 words a
            # query out of the table: 0.67 -> 0.03 ms a block of 32 queries
            # (PERF.md section 6, PR 59, step 0)
            slot = selected_slots(tab, pos, ps)                 # [n, k]
            return attend_selected(
                qc_b, cp[slot], geo.latent, valid, geo.scale,
                xla=selected_attention, use_kernel=use_kernel)

    return _by_blocks(one, (scores, q_cat, qpos) +
                      (() if shared else (table,)), block,
                      live if shared else None)


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
    RMSNorm (``_qk_norm``) where the stack has one, before any rotation."""
    nh, kv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    n = u.shape[0]
    qkv = u @ params[f"h{i}.attn.qkv.weight"].T
    q = qkv[:, :nh * hd].reshape(n, nh, hd)
    k = qkv[:, nh * hd:(nh + kv) * hd].reshape(n, kv, hd)
    v = qkv[:, (nh + kv) * hd:].reshape(n, kv, hd)
    if cfg.attn_qk_norm:
        q = _qk_norm(cfg, q, params[f"h{i}.attn.q_norm.weight"])
        k = _qk_norm(cfg, k, params[f"h{i}.attn.k_norm.weight"])
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


# -- the gdn mixer (a gated delta rule) and its stack -------------------------
#
# Everything of this mixer stands BEHIND the code above, and what it had to
# change above keeps every other line where it was: a Pallas kernel's
# payload carries its call stack, line numbers and all, so a line that moves
# above another mixer's kernel call is another compile-cache key for that
# mixer's stacks.
#
# Tensors (``nh`` heads of key width ``dk`` and value width ``dv``)::
#
#     h{i}.gdn.in_proj.weight [2*nh*dk + 2*nh*dv + 2*nh, H]  q | k | v | z | a | b
#     h{i}.gdn.conv.weight [K, 2*nh*dk + nh*dv]     (over q | k | v; no bias)
#     h{i}.gdn.dt_bias / .A_log [nh]  (float32)   h{i}.gdn.norm.weight [dv]
#     h{i}.gdn.out_proj.weight [H, nh*dv]
#     h{i}.attn.q_norm.weight / .k_norm.weight [nh*hd] / [kv*hd]
#                                        (``cfg.attn_qk_norm_full``)

L2_NORM_EPS = 1e-6              # under the root of a gdn head's q / k norm


def _qk_norm(cfg: GPTConfig, t, w):
    """The RMSNorm on q (k) ``t`` [n, heads, hd]: head by head, or over the
    token's whole vector, all heads together (``attn_qk_norm_full``)."""
    if not cfg.attn_qk_norm_full:
        return _rms(cfg, t, w)
    return _rms(cfg, t.reshape(t.shape[0], -1), w).reshape(t.shape)


def _gdn_param_shapes(cfg: GPTConfig, p: str) -> Dict[str, Tuple[int, ...]]:
    c, hd = cfg, cfg.hidden_size
    nh, qk = c.linear_value_heads, c.linear_key_heads * c.linear_key_dim
    val = nh * c.linear_value_dim
    return {p + "gdn.in_proj.weight": (2 * qk + 2 * val + 2 * nh, hd),
            p + "gdn.conv.weight": (c.linear_conv_kernel, c.linear_conv_dim),
            p + "gdn.dt_bias": (nh,), p + "gdn.A_log": (nh,),
            p + "gdn.norm.weight": (c.linear_value_dim,),
            p + "gdn.out_proj.weight": (hd, val)}


class GdnWeights:
    """One gdn layer's tensors, looked up once."""

    def __init__(self, params: dict, i: int):
        g = lambda n: params[f"h{i}.gdn.{n}"]  # noqa: E731
        self.in_proj, self.out_proj = g("in_proj.weight"), g("out_proj.weight")
        # no conv bias: a zero where ``_conv_rows`` adds one
        self.conv_w, self.conv_b = g("conv.weight"), jnp.zeros((), F32)
        self.dt_bias, self.a_log = g("dt_bias"), g("A_log")
        self.norm = g("norm.weight")


def gdn_split(cfg: GPTConfig, proj):
    """``proj`` [n, in_proj width] -> ``(qkv [n, conv_dim], z [n, nh * dv],
    a [n, nh], b [n, nh])``."""
    cd, nh = cfg.linear_conv_dim, cfg.linear_value_heads
    val = nh * cfg.linear_value_dim
    return (proj[..., :cd], proj[..., cd:cd + val],
            proj[..., cd + val:cd + val + nh], proj[..., cd + val + nh:])


def _delta_inputs(cfg: GPTConfig, w: GdnWeights, conv_out, a_raw, b_raw):
    """What the delta rule reads, from the conv's output [n, conv_dim]
    float32: ``silu``, the split into heads, q and k to unit length (q x
    ``dk ** -0.5``), ``beta = sigmoid(b)`` (x 2 under ``linear_neg_
    eigval``), ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``."""
    c = cfg
    n, nh, dk, dv = (conv_out.shape[0], c.linear_value_heads,
                     c.linear_key_dim, c.linear_value_dim)
    x = jax.nn.silu(conv_out)
    q = x[:, :nh * dk].reshape(n, nh, dk)
    k = x[:, nh * dk:2 * nh * dk].reshape(n, nh, dk)
    v = x[:, 2 * nh * dk:].reshape(n, nh, dv)
    unit = lambda t: t * lax.rsqrt(                          # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + L2_NORM_EPS)
    beta = jax.nn.sigmoid(b_raw.astype(F32)) * \
        (2.0 if c.linear_neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(w.a_log.astype(F32)) * jax.nn.softplus(
        a_raw.astype(F32) + w.dt_bias.astype(F32)))
    return unit(q) * dk ** -0.5, unit(k), v, alpha, beta


def gdn_rows(cfg: GPTConfig, w: GdnWeights, proj, conv_state, ssm_state,
             live, fresh, walk):
    """``mamba_rows`` for a gdn layer: ``proj`` [S, in_proj width] in SLOT
    order, ``conv_state`` [S, K-1, conv_dim], ``ssm_state`` [S, nh / p, dk,
    p * dv] float32 — the store, of which the recurrence reads and writes
    the slots of ``walk`` in place (``ops.gated_delta.gated_delta_slots``).
    Returns ``(o [S, nh * dv] float32 before the gated norm, zeros where
    not ``live``; new conv state; the store)``."""
    qkv, _, a_raw, b_raw = gdn_split(cfg, proj)
    conv, new_conv = _conv_rows(w, qkv, conv_state, live, fresh)
    with phase("ssm_scan"):
        q, k, v, alpha, beta = _delta_inputs(cfg, w, conv, a_raw, b_raw)
        o, new_ssm = gated_delta_slots(q, k, v, alpha, beta, ssm_state,
                                       *walk, fresh)
    return o.reshape(o.shape[0], -1), new_conv, new_ssm


def gdn_chunk(cfg: GPTConfig, w: GdnWeights, proj, conv_state, ssm_state,
              slot, length, fresh):
    """A run of ``length`` (<= T) consecutive tokens of ONE sequence whose
    state is slot ``slot`` of the stores: ``proj`` [T, in_proj width].  The
    conv tail is taken out and put back; the matrix state is carried in
    place (``gated_delta_chunk``), tokens past ``length`` leaving both as
    they found them.  Returns ``(o [T, nh * dv] float32 before the gated
    norm, the conv store, the state store)``."""
    qkv, _, a_raw, b_raw = gdn_split(cfg, proj)
    with phase("state_io"):
        tail0 = conv_state[slot]
    with phase("ssm_conv"):
        conv, tail1 = causal_conv(qkv, w.conv_w, None,
                                  jnp.where(fresh, 0, tail0), length)
    with phase("state_io"):
        conv_state = conv_state.at[slot].set(tail1)
    with phase("ssm_scan"):
        q, k, v, alpha, beta = _delta_inputs(cfg, w, conv, a_raw, b_raw)
        o, ssm_state = gated_delta_chunk(q, k, v, alpha, beta, ssm_state,
                                         slot, length, fresh)
    return o.reshape(o.shape[0], -1), conv_state, ssm_state


def gdn_gate_norm(cfg: GPTConfig, w: GdnWeights, o, z, dtype):
    """``RMSNorm_dv(o; w) * silu(z)``, head by head: the norm comes before
    the gate (a mamba2 layer's gate comes before its norm)."""
    n = o.shape[0]
    v = _rms(cfg, o.reshape(n, -1, cfg.linear_value_dim), w.norm)
    return (v.reshape(n, -1) * jax.nn.silu(z.astype(F32))).astype(dtype)


# The mixers whose chunk form carries a row's state IN its slot of the
# store, as the serving step's one branch for them calls them: ``(weights,
# rows, chunk, gate)`` — ``rows(cfg, w, proj [S, width], conv store, state
# store, live, fresh, walk)`` and ``chunk(cfg, w, proj [T, width], conv
# store, state store, slot, length, fresh)`` both return ``(y before the
# gate, conv store, state store)``; ``gate(cfg, w, y, proj, dtype)`` what
# the way out reads.
IN_SLOT_MIXERS = {
    "mamba1": (Mamba1Weights, mamba1_rows, mamba1_chunk,
               lambda cfg, w, y, xz, dtype: mamba1_gate(
                   y, xz[..., cfg.mamba1_inner:], dtype)),
    "gdn": (GdnWeights, gdn_rows, gdn_chunk,
            lambda cfg, w, o, proj, dtype: gdn_gate_norm(
                cfg, w, o, gdn_split(cfg, proj)[1], dtype)),
}


def olmo_hybrid_config(pub: dict, **overrides) -> GPTConfig:
    """The one translation from the published ``config.json`` keys of an
    ``olmo_hybrid``-type model to ``GPTConfig``.  A published layer is two
    pattern entries, each with its own RMSNorm and residual: its mixer —
    "gdn" for a ``linear_attention`` entry of ``layer_types`` (a gated
    delta rule over ``linear_num_value_heads`` heads of ``linear_key_head_
    dim`` x ``linear_value_head_dim``, q | k | v behind one causal conv of
    ``linear_conv_kernel_dim`` taps, ``beta`` up to 2 where
    ``linear_allow_neg_eigval``), "attention" for a ``full_attention`` one
    (``num_key_value_heads`` K/V heads of ``hidden / heads`` lanes, no
    bias) — then "mlp", ``down(silu(gate u) * up u)`` of
    ``intermediate_size``.  What the keys do not spell is the family's
    convention, which the caller states (``pub["assumed"]``) and this
    translation builds: the norm on a sublayer's OUTPUT (``norm_position``
    "post"), the QK-norm over a token's whole q (k) vector, no rotation
    (``rope_theta`` null)."""
    kinds = {"linear_attention": "gdn", "full_attention": "attention"}
    types = pub["layer_types"]
    unbuilt = [k for k, ok in (
        ("layer_types", len(types) == pub["num_hidden_layers"]
         and not set(types) - set(kinds)),
        ("attention_bias", not pub.get("attention_bias")),
        ("rope_parameters.rope_theta",
         (pub.get("rope_parameters") or {}).get("rope_theta") is None),
        ("linear_num_key_heads", pub["linear_num_key_heads"]
         == pub["linear_num_value_heads"])) if not ok]
    if unbuilt:
        raise ValueError(
            "olmo_hybrid is built with linear_attention / full_attention "
            "layers, one for each of num_hidden_layers, no projection bias, "
            "no rotation and as many key heads as value heads; not built: "
            + ", ".join(unbuilt))
    pattern = tuple(m for t in types for m in (kinds[t], "mlp"))
    kw = dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=len(pattern), num_heads=pub["num_attention_heads"],
        num_kv_heads=pub["num_key_value_heads"],
        attn_qk_norm=True, attn_qk_norm_full=True, norm_position="post",
        ffn_hidden_size=pub["intermediate_size"],
        max_seq_len=pub["max_position_embeddings"],
        activation=pub["hidden_act"], norm="rmsnorm", position="none",
        norm_eps=float(pub["rms_norm_eps"]),
        tie_embeddings=bool(pub["tie_word_embeddings"]), sp=False,
        dtype=pub.get("dtype", "bfloat16"), layer_pattern=pattern,
        linear_key_heads=pub["linear_num_key_heads"],
        linear_value_heads=pub["linear_num_value_heads"],
        linear_key_dim=pub["linear_key_head_dim"],
        linear_value_dim=pub["linear_value_head_dim"],
        linear_conv_kernel=pub["linear_conv_kernel_dim"],
        linear_neg_eigval=bool(pub["linear_allow_neg_eigval"]))
    kw.update(overrides)
    return GPTConfig(**kw)
