"""GPT-2 / LLaMA model family on parallel layers.

TPU-native re-expression of the reference's canonical LLM workloads
(``examples/gpt/hetu_llama.py``, ``python/elastic/models/gpt/gpt_model.py``):
transformer blocks built from column/row-parallel linears, vocab-parallel
embedding + CE, parallel norms with SP, rotary or learned positions, and
flash attention (Pallas on TPU).  DP/TP/SP shardings are PartitionSpec
annotations over a named mesh; CP (ring attention over the ``cp_axis``)
dispatches to ``ops.parallel_attention`` when ``config.cp_axis`` is set.

Config mirrors the reference's argparse surface (examples/gpt/train_hetu.py
:479-588): hidden/layers/heads/seq/vocab, activation/norm variants.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

from .. import ops
from ..graph.ctor import NormalInitializer, parallel_parameter
from ..nn import (ColumnParallelLinear, Dropout, Module, ModuleList,
                  ParallelLayerNorm, ParallelRMSNorm, RowParallelLinear,
                  VocabParallelEmbedding, vocab_parallel_cross_entropy)
from ..nn.parallel import sharded
from ..obs.phases import phase
from jax.sharding import PartitionSpec as P


MIXERS = ("mamba2", "attention", "moe", "mla", "dsa", "swa", "mlp", "mamba1",
          "gdn")
# mixers that keep a recurrent state in the slot store; a pattern holds one
STATE_MIXERS = ("mamba2", "mamba1", "gdn")
# mixers that keep pages in the K/V pool
PAGED_MIXERS = ("attention", "mla", "dsa", "swa")


class LatentGeometry(NamedTuple):
    """One latent-attention mixer kind's sizes.  A stack may run several
    kinds side by side (``GPTConfig.mixer_geometry``): the geometry hangs
    on the mixer kind, not on the stack.  ``window`` counts the keys a
    query reads, itself included (0: the whole context); an indexer
    (``index_topk`` > 0) scores every cached position with
    ``index_heads`` heads of ``index_dim`` (rotary on the first
    ``index_rope``) and attention reads the ``index_topk`` best;
    ``q_rescale`` / ``kv_rescale`` multiply the two latents behind their
    norms; ``gate`` multiplies a head's output by ``sigmoid(u W_g)``."""
    heads: int
    q_rank: Optional[int]
    latent: int
    nope: int
    rope: int
    v: int
    theta: float
    scale: float
    interleave: bool = False
    q_rescale: float = 1.0
    kv_rescale: float = 1.0
    window: int = 0
    index_heads: int = 0
    index_dim: int = 0
    index_rope: int = 0
    index_topk: int = 0
    gate: bool = False

    @property
    def rope_lanes(self) -> int:
        """The rotary stream's page width: zero lanes up to a multiple
        of 128 (``GPTConfig.latent_page_dims`` says why)."""
        return -(-self.rope // 128) * 128


class PageLayer(NamedTuple):
    """One paged layer's page arrays: ``space`` is the page-id space it
    allocates from ("full": a page a ``page_size`` tokens of context;
    "window": only the pages a window can reach), then the widths of its
    latent, rotary and index-key streams (0: no such stream) — or, for a
    plain K/V layer (``kv_heads`` > 0), the full-head form: a K and a V
    array ``[P, kv_heads, page_size, head_dim]`` and no latent stream."""
    space: str
    latent: int
    rope: int
    index: int
    kv_heads: int = 0
    head_dim: int = 0


@dataclass
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None      # GQA; None -> = num_heads
    ffn_hidden_size: Optional[int] = None   # None -> 4h (gelu) or 8h/3 (swiglu)
    max_seq_len: int = 1024
    activation: str = "gelu"      # gelu (GPT) | swiglu (LLaMA) | relu2
    norm: str = "layernorm"                 # layernorm (GPT) | rmsnorm (LLaMA)
    position: str = "learned"     # learned (GPT) | rotary (LLaMA) | none
    dropout: float = 0.0
    sp: bool = True                         # Megatron sequence parallel
    tie_embeddings: bool = False
    init_std: float = 0.02
    dtype: str = "float32"
    dp_axis: str = "dp"
    tp_axis: str = "tp"
    cp_axis: Optional[str] = None   # context parallel axis
    cp_impl: str = "ring"           # "ring" (AttnCommRing) | "ulysses"
    # fuse lm_head matmul + CE so [B*S, V] logits are never stored
    # whole (HBM win; scratch/purejax.py "fusedce" variant)
    fused_lm_ce: bool = False
    # MoE (v1 MoELayer capability): >0 replaces the dense MLP with a
    # mixture of experts every `moe_every` blocks
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 1
    moe_aux_coef: float = 0.01
    ep_axis: Optional[str] = None   # expert-parallel mesh axis
    # MLA (multi-head latent attention, FlashMLA-ETAP arxiv 2506.01969):
    # when set, the decode/serving stack stores ONE [T, kv_latent_dim]
    # compressed KV stream per layer instead of [T, kv_heads, head_dim]
    # k + v, and attention runs weight-absorbed against the latent.
    # kv_rope_dim is the decoupled-RoPE key width (rotary configs only;
    # None -> head_dim); learned-position configs carry no rope stream.
    kv_latent_dim: Optional[int] = None
    kv_rope_dim: Optional[int] = None
    # A latent attention that is the MODEL'S OWN (the "mla" mixer of a
    # layer_pattern stack; models/hybrid.py), not a conversion of a
    # full-head checkpoint: a low-rank q with its norm (``mla_q_rank``),
    # a norm on the latent, per-head widths that differ (``mla_nope_dim``
    # for q/k outside the rotary stream, ``kv_rope_dim`` inside it,
    # ``mla_v_dim`` for v; None -> head_dim), the softmax scale as a
    # value, and the rotary stream's tables: ``rope_interleave`` rotates
    # the pairs (2i, 2i+1), ``rope_yarn`` = (factor, original positions,
    # beta_fast, beta_slow, table factor) stretches the frequencies, and
    # ``q_pos_scale`` = (beta, period) multiplies q by ``1 + beta *
    # ln(1 + floor(pos / period))``.
    mla_q_rank: Optional[int] = None
    mla_nope_dim: Optional[int] = None
    mla_v_dim: Optional[int] = None
    attn_scale: Optional[float] = None
    rope_theta: float = 10000.0
    rope_interleave: bool = False
    rope_yarn: Optional[Tuple[float, int, float, float, float]] = None
    q_pos_scale: Optional[Tuple[float, int]] = None
    # Hybrid stacks (serving path; models/hybrid.py owns the parameter
    # names): ``layer_pattern`` gives ONE mixer per layer behind one
    # norm (``norm_position``) and one residual — one of ``MIXERS``.  None
    # is today's block (attention + MLP in every layer).  The K/V pool
    # then holds the attention layers only and a state-slot store the
    # recurrent (``STATE_MIXERS``) layers (serving/kv_pool.py).  "dsa" (latent attention over
    # the positions an indexer picks) and "swa" (latent attention over a
    # window) take their sizes from ``mixer_geometry[kind]``, so one
    # stack holds latent layers of different geometry; "mlp" is a dense
    # gated MLP ``ffn_hidden_size`` wide (a leading dense layer).
    layer_pattern: Optional[Tuple[str, ...]] = None
    mixer_geometry: Optional[Dict[str, LatentGeometry]] = None
    # The plain "attention" mixer's geometry where it is not the block's
    # (a layer_pattern stack only): ``attn_head_dim`` a head's width where
    # it is not ``hidden / heads``; ``attn_qk_norm`` an RMSNorm over each
    # head's lanes of q and k (``attn.q_norm`` / ``attn.k_norm``);
    # ``attn_window`` the keys a query of the layers ``attn_window_layers``
    # (pattern indices) reads, itself included: those layers keep their K/V
    # in the WINDOW page-id space; ``attn_rope`` says which attention
    # layers rotate q and k (``position`` "rotary", base ``rope_theta``, by
    # halves over the whole head): "all" or "window" (the rest are not
    # rotated).
    attn_head_dim: Optional[int] = None
    attn_qk_norm: bool = False
    # the QK-norm over the whole q (k) vector of a token, all heads
    # together (weights [heads * head_dim]), not head by head
    attn_qk_norm_full: bool = False
    # where a pattern stack's sublayer norm sits: "pre" ``x + f(Norm(x))``,
    # "post" ``x + Norm(f(x))`` (the norm on the sublayer's OUTPUT)
    norm_position: str = "pre"
    attn_window: int = 0
    attn_window_layers: Tuple[int, ...] = ()
    attn_rope: str = "all"
    # A multi-token-prediction module behind the stack (models/hybrid.py
    # ``mtp_*``): its mixers, in the pattern's vocabulary, as the layers
    # ``num_layers ..`` of the tensors (``h{num_layers + j}.``); it reads
    # the stack's last hidden state and the NEXT token's embedding and
    # predicts the token after, sharing ``wte`` and ``lm_head``.  The
    # serving step runs it to draft for its own verify rows.
    mtp_pattern: Tuple[str, ...] = ()
    # Generation by diffusion over blocks (a layer_pattern stack, serving
    # path): the model's block length ``diffusion_block`` (0: one position
    # after another) and the id it was trained to read as "not yet known".
    # Key ``j`` is visible to the query at ``p`` iff ``j // B <= p // B``:
    # causal across blocks, both ways inside one; the logits at ``p`` score
    # the token AT ``p``.  The serving step's block region and the engine's
    # block loop follow from these two (DESIGN.md section 29).
    diffusion_block: int = 0
    mask_token_id: Optional[int] = None
    norm_eps: Optional[float] = None        # None -> the norm's own default
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_state_dim: int = 0                # ssm_state_size N
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128             # SSD chunk of the matmul form
    # the "mamba1" mixer (a selective scan: a decay for every (channel,
    # state) pair, ``ops/selective_scan.py``): its channels and the rank of
    # the time step's projection; ``mamba_state_dim`` / ``mamba_conv_kernel``
    # are shared with "mamba2".  The conv runs over the x channels alone and
    # ``dt``, ``B``, ``C`` each pass an RMSNorm.
    mamba1_inner: int = 0
    mamba1_dt_rank: int = 0
    # the "gdn" mixer (a gated delta rule, ``ops/gated_delta.py``: a
    # ``[key_dim, value_dim]`` matrix state a head whose update reads the
    # state it writes): the published ``linear_*`` sizes.  q, k and v pass
    # one causal conv of ``linear_conv_kernel`` taps; ``linear_neg_eigval``
    # lets ``beta`` reach 2 (a state eigenvalue of -1).
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_kernel: int = 4
    linear_neg_eigval: bool = False
    # expert layer of a hybrid stack: ``num_experts`` is the ROUTER's
    # width (all experts of the deployment), of which this program holds
    # ``experts_held`` starting at ``expert_offset`` (default: all) and
    # computes only their part of the result (expert parallelism without
    # the exchange).  "sigmoid_bias": sigmoid scores, top-k of score +
    # selection bias, chosen scores renormalised, x moe_router_scale.
    # ``moe_norm_topk`` renormalises a softmax router's chosen values to
    # sum 1; ``moe_gated`` experts (routed and shared) are ``down(act(
    # gate u) * up u)``, three matrices, where the plain ones are two.
    moe_router: str = "softmax"             # softmax | sigmoid_bias
    moe_norm_topk: bool = False
    moe_gated: bool = False
    moe_router_scale: float = 1.0
    moe_ffn_size: Optional[int] = None      # one routed expert's width
    moe_latent_dim: Optional[int] = None    # experts work in this width
    moe_shared_ffn_size: int = 0            # shared expert (0: none)
    experts_held: Optional[int] = None
    expert_offset: int = 0

    def __post_init__(self):
        if self.layer_pattern is not None:
            self.layer_pattern = tuple(self.layer_pattern)
            bad = set(self.layer_pattern) - set(MIXERS)
            if bad or len(self.layer_pattern) != self.num_layers:
                raise ValueError(
                    f"layer_pattern must name one of {MIXERS} for each of "
                    f"the {self.num_layers} layers, got {self.layer_pattern}")
            if "mla" in self.layer_pattern and (
                    self.kv_latent_dim is None
                    or len(set(self.layer_pattern) & set(PAGED_MIXERS)) > 1):
                raise ValueError(
                    "an mla layer needs kv_latent_dim, and one page pool "
                    "holds one layout of it: not beside attention, dsa or "
                    "swa layers")
            for kind in ("dsa", "swa"):
                if kind in self.layer_pattern and (
                        "attention" in self.layer_pattern
                        or kind not in (self.mixer_geometry or {})):
                    raise ValueError(
                        f"a {kind} layer takes its sizes from "
                        f"mixer_geometry[{kind!r}], and latent pages do "
                        f"not stand beside plain attention layers")
            if len(set(self.layer_pattern) & set(STATE_MIXERS)) > 1:
                raise ValueError(
                    "one pattern holds one kind of recurrent mixer "
                    f"({' / '.join(STATE_MIXERS)}): the slot store has one "
                    "layout")
            if "gdn" in self.layer_pattern and not (
                    self.linear_key_heads == self.linear_value_heads > 0
                    and self.linear_key_dim > 0 and self.linear_value_dim > 0):
                raise ValueError(
                    "a gdn layer needs linear_key_dim, linear_value_dim and "
                    "as many key heads as value heads (linear_key_heads == "
                    "linear_value_heads: value heads that share a key head "
                    "are not built)")
            if self.norm_position not in ("pre", "post"):
                raise ValueError(
                    f"unknown norm_position {self.norm_position!r}")
            if "mlp" in self.layer_pattern and not self.ffn_hidden_size:
                raise ValueError("an mlp layer needs ffn_hidden_size")
            if "moe" in self.layer_pattern:
                held, off = self.held_experts, self.expert_offset
                if not (self.num_experts > 0 and held >= 1 and off >= 0
                        and off + held <= self.num_experts):
                    raise ValueError(
                        f"experts held [{off}, {off + held}) must lie in "
                        f"the router's {self.num_experts}")
            self.attn_window_layers = tuple(self.attn_window_layers)
            self.mtp_pattern = tuple(self.mtp_pattern)
            if any(self.layer_pattern[i] != "attention"
                   for i in self.attn_window_layers) or \
                    bool(self.attn_window) != bool(self.attn_window_layers):
                raise ValueError(
                    "attn_window_layers names attention layers of the "
                    "pattern, and attn_window their keys a query")
            if set(self.mtp_pattern) - {"attention", "moe"}:
                raise ValueError(
                    "an MTP module is built of attention and moe mixers, "
                    f"got {self.mtp_pattern}")
            if self.attn_rope not in ("all", "window"):
                raise ValueError(f"unknown attn_rope {self.attn_rope!r}")
            if self.diffusion_block and not (
                    self.diffusion_block >= 2 and self.mask_token_id
                    is not None
                    and 0 <= self.mask_token_id < self.vocab_size):
                raise ValueError(
                    "diffusion_block is a block of >= 2 positions and comes "
                    "with the mask_token_id (a row of the vocabulary) its "
                    "masked positions are fed as")
        elif self.attn_head_dim or self.attn_qk_norm or self.attn_window \
                or self.mtp_pattern or self.diffusion_block \
                or self.norm_position != "pre":
            raise ValueError(
                "attn_head_dim / attn_qk_norm / attn_window / mtp_pattern / "
                "diffusion_block / norm_position "
                "describe the attention mixer of a layer_pattern stack; the "
                "plain block (training, generate) keeps head_dim = hidden / "
                "heads, has no MTP module and no block mask")
        if self.moe_router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        if self.layer_pattern is None and (
                self.moe_router != "softmax" or self.moe_latent_dim
                or self.moe_shared_ffn_size or self.experts_held is not None
                or self.moe_norm_topk or self.moe_gated):
            raise ValueError(
                "moe_router / moe_latent_dim / moe_shared_ffn_size / "
                "experts_held / moe_norm_topk / moe_gated describe the "
                "expert layer of a layer_pattern stack; the plain block's "
                "MoE is softmax top-k over all its experts")
        if "mla" not in (self.layer_pattern or ()) and (
                self.mla_q_rank or self.mla_nope_dim or self.mla_v_dim
                or self.attn_scale is not None or self.rope_interleave
                or self.rope_yarn or self.q_pos_scale):
            raise ValueError(
                "mla_q_rank / mla_nope_dim / mla_v_dim / attn_scale / "
                "rope_interleave / rope_yarn / q_pos_scale describe the mla "
                "mixer of a layer_pattern stack")
        assert self.hidden_size % self.num_heads == 0, \
            f"hidden {self.hidden_size} not divisible by heads {self.num_heads}"
        kv = self.num_kv_heads or self.num_heads
        assert self.num_heads % kv == 0, \
            f"num_heads {self.num_heads} not divisible by kv_heads {kv}"
        if self.kv_latent_dim is not None:
            assert self.kv_latent_dim >= 1, \
                f"kv_latent_dim must be >= 1, got {self.kv_latent_dim}"
            if self.position == "rotary":
                r = self.rope_dim
                assert r > 0 and r % 2 == 0, \
                    f"MLA decoupled rope dim must be positive even, got {r}"
        elif self.kv_rope_dim is not None:
            raise ValueError("kv_rope_dim requires kv_latent_dim (MLA mode)")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def is_hybrid(self) -> bool:
        return self.layer_pattern is not None

    @property
    def nope_dim(self) -> int:
        """An mla head's q/k width outside the rotary stream."""
        return self.mla_nope_dim or self.head_dim

    @property
    def v_dim(self) -> int:
        """An mla head's v width."""
        return self.mla_v_dim or self.head_dim

    @property
    def mla_softmax_scale(self) -> float:
        return float(self.attn_scale) if self.attn_scale is not None \
            else (self.nope_dim + self.rope_dim) ** -0.5

    @property
    def latent_page_dims(self) -> Tuple[int, int]:
        """``(latent_dim, rope_dim)`` of the page pool's latent layout.
        The ``mla`` mixer's rotary stream is padded with zero lanes to a
        multiple of 128: an array whose minor dimension does not fill
        the lanes gets a device layout that is not the kernels', and XLA
        re-lays the whole pool out on entry and exit of every step (11 ms
        of a 72 ms step at a 64-wide stream: PERF.md, PR 35)."""
        if "mla" in (self.layer_pattern or ()):
            return self.kv_latent_dim, -(-self.rope_dim // 128) * 128
        return self.kv_latent_dim, self.rope_dim

    @property
    def stack_pattern(self) -> Tuple[str, ...]:
        """The pattern's mixers, then the MTP module's: the tensors'
        ``h{i}.`` order."""
        return (self.layer_pattern or ()) + self.mtp_pattern

    @property
    def paged_layers(self) -> Tuple[int, ...]:
        """Layers that keep pages in the K/V pool, in pool order (an MTP
        module's attention layer behind the stack's)."""
        if self.layer_pattern is None:
            return tuple(range(self.num_layers))
        return tuple(i for i, m in enumerate(self.stack_pattern)
                     if m in PAGED_MIXERS)

    def window_of(self, i: int) -> int:
        """Keys a query of plain attention layer ``i`` reads (0: all)."""
        return self.attn_window if i in self.attn_window_layers else 0

    def geometry(self, kind: str) -> LatentGeometry:
        """The sizes of latent mixer ``kind``: the stack's own scalars
        for "mla", ``mixer_geometry[kind]`` for "dsa" / "swa"."""
        if kind != "mla":
            return self.mixer_geometry[kind]
        return LatentGeometry(
            heads=self.num_heads, q_rank=self.mla_q_rank,
            latent=self.kv_latent_dim, nope=self.nope_dim,
            rope=self.rope_dim, v=self.v_dim, theta=self.rope_theta,
            scale=self.mla_softmax_scale, interleave=self.rope_interleave)

    @property
    def page_layers(self) -> Optional[Tuple[PageLayer, ...]]:
        """Each paged layer's page arrays, in pool order, where the
        stack's latent layers differ by kind or its attention mixer has
        a geometry of its own (``attn_*``, ``mtp_pattern``: full-head K/V
        by layer, window layers in the window space; the serving step
        applies the QK-norm and the rotation on this path alone).  None:
        one layout for every paged layer, ``latent_page_dims`` or the
        full-head one."""
        if self.attn_head_dim or self.attn_qk_norm or self.attn_window \
                or self.mtp_pattern:
            return tuple(PageLayer(
                "window" if self.window_of(i) else "full", 0, 0, 0,
                self.kv_heads, self.head_dim) for i in self.paged_layers)
        if not (self.layers_of("dsa") or self.layers_of("swa")):
            return None
        out = []
        for i in self.paged_layers:
            g = self.geometry(self.layer_pattern[i])
            out.append(PageLayer("window" if g.window else "full", g.latent,
                                 g.rope_lanes, g.index_dim))
        return tuple(out)

    @property
    def window_tokens(self) -> int:
        """Keys a window layer's query reads (0: no window layer)."""
        if self.attn_window:
            return self.attn_window
        return self.mixer_geometry["swa"].window \
            if self.layers_of("swa") else 0

    def layers_of(self, mixer: str) -> Tuple[int, ...]:
        """Layer indices running ``mixer``; a plain stack has attention
        (and nothing else from the pattern's vocabulary) in every layer."""
        if self.layer_pattern is None:
            return tuple(range(self.num_layers)) if mixer == "attention" \
                else ()
        return tuple(i for i, m in enumerate(self.layer_pattern)
                     if m == mixer)

    @property
    def state_mixer(self) -> Optional[str]:
        """The pattern's recurrent mixer kind (None: it keeps no state)."""
        for kind in STATE_MIXERS:
            if self.layers_of(kind):
                return kind
        return None

    @property
    def held_experts(self) -> int:
        return self.num_experts if self.experts_held is None \
            else int(self.experts_held)

    def qk_norm_width(self, full: int) -> int:
        """Lanes a QK-norm's weight spans: a head's, or (``attn_qk_norm_
        full``) the ``full`` vector's."""
        return full if self.attn_qk_norm_full else self.head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels a gdn layer's causal conv runs over: q | k | v."""
        return 2 * self.linear_key_heads * self.linear_key_dim + \
            self.linear_value_heads * self.linear_value_dim

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the causal conv runs over: x | B | C."""
        return self.mamba_inner + \
            2 * self.mamba_n_groups * self.mamba_state_dim

    @property
    def is_mla(self) -> bool:
        return self.kv_latent_dim is not None

    @property
    def rope_dim(self) -> int:
        """Decoupled-RoPE key width d_r: 0 for non-MLA and for
        learned-position MLA (no positional content in the cache)."""
        if self.kv_latent_dim is None or self.position != "rotary":
            return 0
        return self.kv_rope_dim if self.kv_rope_dim is not None \
            else self.head_dim

    def is_moe_layer(self, layer_idx: int) -> bool:
        """Single source of truth for MoE placement — used by both the
        training blocks (GPTBlock) and the decode engine (generate.py)."""
        return self.num_experts > 0 and \
            layer_idx % max(1, self.moe_every) == 0

    @property
    def ffn_size(self) -> int:
        if self.ffn_hidden_size:
            return self.ffn_hidden_size
        if self.activation == "swiglu":
            return int(8 * self.hidden_size / 3 / 64) * 64 or 64
        return 4 * self.hidden_size


def llama_config(**kw) -> GPTConfig:
    kw.setdefault("activation", "swiglu")
    kw.setdefault("norm", "rmsnorm")
    kw.setdefault("position", "rotary")
    return GPTConfig(**kw)


def draft_config(cfg: GPTConfig, num_layers: int) -> GPTConfig:
    """A shallow draft-model config for speculative decoding
    (serving/spec.py): identical tokenizer/embedding/head geometry —
    the draft and target MUST share the vocab so draft proposals are
    target token ids — with only the layer count reduced."""
    if not 1 <= num_layers <= cfg.num_layers:
        raise ValueError(
            f"draft num_layers must be in [1, {cfg.num_layers}] (the "
            f"target's layer count), got {num_layers}")
    import dataclasses
    return dataclasses.replace(cfg, num_layers=int(num_layers))


def draft_state_from(state, cfg: GPTConfig, num_layers: int):
    """Build a truncated draft ``(state, config)`` from a target
    checkpoint: the first ``num_layers`` transformer blocks plus the
    shared embeddings / final norm / lm head.  A self-distilled
    truncation like this shares the residual-stream geometry with its
    target, which is what makes its greedy proposals land — any
    separately-trained model with the same vocab works through the same
    ``SpecConfig`` entry point."""
    from .generate import _Params
    dcfg = draft_config(cfg, num_layers)
    keep = {}
    for k, v in state.items():
        nk = _Params._norm(k)
        if nk.startswith("h"):
            idx = nk[1:].split(".", 1)[0]
            if idx.isdigit() and int(idx) >= num_layers:
                continue
        keep[k] = v
    return keep, dcfg


def mla_config(cfg: GPTConfig, kv_latent_dim: int,
               kv_rope_dim: Optional[int] = None) -> GPTConfig:
    """The MLA twin of a full-head config: identical everywhere except
    the cache layout fields (decode-cache keys treat these as part of
    the config identity, so full-head and latent executables never
    collide)."""
    import dataclasses
    return dataclasses.replace(cfg, kv_latent_dim=int(kv_latent_dim),
                               kv_rope_dim=kv_rope_dim)


def mla_state_from(state, cfg: GPTConfig, kv_latent_dim: int,
                   kv_rope_dim: Optional[int] = None, seed: int = 0):
    """Convert a full-head checkpoint into an MLA ``(state, config)``.

    Per layer, the fused ``attn.qkv`` projection is split and re-factored
    into the weight-absorbed MLA schema:

    - ``attn.q.weight``  [nh*(hd+d_r), H] — per-head ``[q_nope | q_rope]``
      rows; the nope rows are the source query projection verbatim.
    - ``attn.kv_a.weight`` [d_c+d_r, H] — shared latent down-projection
      (plus the decoupled rope key rows when d_r > 0).
    - ``attn.k_up.weight`` / ``attn.v_up.weight`` [nh, hd, d_c] — the
      up-projections that decode ABSORBS into q / out (FlashMLA-ETAP):
      ``score_h = (q_h @ k_up_h) . c`` and ``out_h = (probs @ C) @
      v_up_h.T``, so no cached token is ever decompressed.

    The factorization is the truncated SVD of the stacked per-head
    ``[W_k; W_v]`` — EXACT (up to fp rounding) whenever that stack has
    rank <= d_c, which is how tests/test_mla_serving.py builds its
    equivalence witness.  Learned-position configs convert losslessly;
    rotary sources are approximate by construction (full-head rope
    content cannot live in a position-free latent — the decoupled rope
    rows are freshly initialized) and are gated by measured accuracy,
    not bitwise claims.  K/V projection biases are least-squares-folded
    into ``kv_a.bias`` (exact when they lie in the latent column span).
    """
    from .generate import _Params
    d_c = int(kv_latent_dim)
    ncfg = mla_config(cfg, d_c, kv_rope_dim)
    d_r = ncfg.rope_dim
    nh, kvh, hd, H = (cfg.num_heads, cfg.kv_heads, cfg.head_dim,
                      cfg.hidden_size)
    g = nh // kvh
    q_size, kv_size = nh * hd, kvh * hd
    rng = np.random.RandomState(seed)
    flat = {_Params._norm(k): v for k, v in state.items()}
    out = {k: v for k, v in flat.items()
           if ".attn.qkv." not in k}
    for i in range(cfg.num_layers):
        w = np.asarray(flat[f"h{i}.attn.qkv.weight"], np.float32)
        b = flat.get(f"h{i}.attn.qkv.bias")
        b = None if b is None else np.asarray(b, np.float32)
        wq, wk, wv = (w[:q_size], w[q_size:q_size + kv_size],
                      w[q_size + kv_size:])
        # -- latent factorization: [W_k; W_v] = U @ (S Vt), keep d_c --
        m = np.concatenate([wk, wv], axis=0)          # [2*kv_size, H]
        u, s, vt = np.linalg.svd(m, full_matrices=False)
        r = min(d_c, s.shape[0])
        kv_a = np.zeros((d_c + d_r, H), np.float32)
        kv_a[:r] = s[:r, None] * vt[:r]
        up = np.zeros((2 * kv_size, d_c), np.float32)
        up[:, :r] = u[:, :r]
        k_up = up[:kv_size].reshape(kvh, hd, d_c)
        v_up = up[kv_size:].reshape(kvh, hd, d_c)
        # GQA: expand kv-head up-projections to query heads so decode
        # absorbs per query head against the single shared latent
        k_up = np.repeat(k_up, g, axis=0)
        v_up = np.repeat(v_up, g, axis=0)
        # -- query: source nope rows + fresh decoupled-rope rows --
        q_w = np.zeros((nh, hd + d_r, H), np.float32)
        q_w[:, :hd] = wq.reshape(nh, hd, H)
        if d_r:
            q_w[:, hd:] = rng.normal(
                0.0, cfg.init_std, (nh, d_r, H)).astype(np.float32)
            kv_a[d_c:] = rng.normal(
                0.0, cfg.init_std, (d_r, H)).astype(np.float32)
        out[f"h{i}.attn.q.weight"] = q_w.reshape(nh * (hd + d_r), H)
        out[f"h{i}.attn.kv_a.weight"] = kv_a
        out[f"h{i}.attn.k_up.weight"] = k_up
        out[f"h{i}.attn.v_up.weight"] = v_up
        if b is not None:
            q_b = np.zeros((nh, hd + d_r), np.float32)
            q_b[:, :hd] = b[:q_size].reshape(nh, hd)
            out[f"h{i}.attn.q.bias"] = q_b.reshape(-1)
            kv_b = np.zeros((d_c + d_r,), np.float32)
            kv_b[:d_c] = up.T @ b[q_size:]   # least-squares fold
            out[f"h{i}.attn.kv_a.bias"] = kv_b
    return out, ncfg


def _norm(config: GPTConfig, name: str):
    if config.norm == "rmsnorm":
        return ParallelRMSNorm(config.hidden_size, sp=config.sp,
                               dp_axis=config.dp_axis, tp_axis=config.tp_axis,
                               seq_axis=config.cp_axis,
                               dtype=config.dtype, name=name)
    return ParallelLayerNorm(config.hidden_size, sp=config.sp,
                             dp_axis=config.dp_axis, tp_axis=config.tp_axis,
                             seq_axis=config.cp_axis,
                             dtype=config.dtype, name=name)


class ParallelAttentionBlock(Module):
    """Self-attention with TP head split (reference ParallelAttention op +
    qkv column-parallel / out row-parallel layout)."""

    def __init__(self, config: GPTConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        c = config
        if c.kv_latent_dim is not None:
            raise NotImplementedError(
                "MLA (kv_latent_dim) is a decode/serving cache layout; "
                "train full-head and convert with models.gpt.mla_state_from")
        q_size = c.num_heads * c.head_dim
        kv_size = c.kv_heads * c.head_dim
        self.qkv = ColumnParallelLinear(
            c.hidden_size, q_size + 2 * kv_size, bias=(c.activation == "gelu"),
            dp_axis=c.dp_axis, tp_axis=c.tp_axis, seq_axis=c.cp_axis,
            dtype=c.dtype,
            init=NormalInitializer(0.0, c.init_std),
            name=f"h{layer_idx}.attn.qkv")
        self.out = RowParallelLinear(
            q_size, c.hidden_size, bias=(c.activation == "gelu"), sp=c.sp,
            dp_axis=c.dp_axis, tp_axis=c.tp_axis, seq_axis=c.cp_axis,
            dtype=c.dtype,
            init=NormalInitializer(0.0, c.init_std / math.sqrt(2 * c.num_layers)),
            name=f"h{layer_idx}.attn.out")
        self.dropout = Dropout(c.dropout) if c.dropout else None
        self._rotary_cache = {}

    def _rotary(self, seq_len: int):
        if seq_len not in self._rotary_cache:
            d = self.config.head_dim
            inv = 1.0 / (10000.0 ** (np.arange(0, d, 2, dtype=np.float32) / d))
            ang = np.outer(np.arange(seq_len, dtype=np.float32), inv)
            emb = np.concatenate([ang, ang], axis=-1)
            cos = np.cos(emb)[None, :, None, :].astype(np.float32)
            sin = np.sin(emb)[None, :, None, :].astype(np.float32)
            self._rotary_cache[seq_len] = (cos, sin)
        return self._rotary_cache[seq_len]

    def forward(self, x, seq_len: int, segment_ids=None):
        c = self.config
        with phase("attn_proj"):
            # [b, s, (nh + 2*nkv) * hd], tp-sharded on last dim
            qkv = self.qkv(x)
        with phase("attn_core"):
            attn = self._core(qkv, seq_len, segment_ids)
        with phase("attn_proj"):
            out = self.out(attn)
            if self.dropout is not None:
                out = self.dropout(out)
        return out

    def _core(self, qkv, seq_len: int, segment_ids):
        """Head split, positions and the attention itself."""
        c = self.config
        b_spec = P(c.dp_axis, c.cp_axis, c.tp_axis, None)
        q_size = c.num_heads * c.head_dim
        kv_size = c.kv_heads * c.head_dim
        mesh = qkv.graph.mesh
        tp = mesh.shape.get(c.tp_axis, 1) if mesh is not None else 1
        if (c.position != "rotary" and c.kv_heads == c.num_heads
                and not c.cp_axis and tp == 1):
            # nothing stands between the projection and the attention:
            # the kernel takes q, k and v out of the fused tensor by block
            # index and no slice is cut (under tp > 1 the fused axis is
            # sharded across q | k | v, so there the slices stay)
            attn = ops.attention_qkv(qkv, c.num_heads, causal=True,
                                     segment_ids=segment_ids)
            return sharded(attn, P(c.dp_axis, c.cp_axis, c.tp_axis))
        q = ops.getitem(qkv, (Ellipsis, slice(0, q_size)))
        k = ops.getitem(qkv, (Ellipsis, slice(q_size, q_size + kv_size)))
        v = ops.getitem(qkv, (Ellipsis, slice(q_size + kv_size, None)))
        q = sharded(q.reshape((-1, seq_len, c.num_heads, c.head_dim)), b_spec)
        k = k.reshape((-1, seq_len, c.kv_heads, c.head_dim))
        v = v.reshape((-1, seq_len, c.kv_heads, c.head_dim))
        if c.position == "rotary":
            cos, sin = self._rotary(seq_len)
            q = ops.rotary_embed(q, cos, sin)
            k = ops.rotary_embed(k, cos, sin)
        if c.kv_heads != c.num_heads:
            # repeat BEFORE constraining: kv_heads may be < tp size, and a
            # head-dim constraint there forces SPMD full rematerialization
            k = ops.repeat_kv(k, c.num_heads // c.kv_heads)
            v = ops.repeat_kv(v, c.num_heads // c.kv_heads)
        k = sharded(k, b_spec)
        v = sharded(v, b_spec)
        if c.cp_axis:
            attn = ops.parallel_attention(
                q, k, v, causal=True, cp_axis=c.cp_axis,
                batch_axis=c.dp_axis, head_axis=c.tp_axis,
                segment_ids=segment_ids, cp_impl=c.cp_impl)
        else:
            attn = ops.attention(q, k, v, causal=True,
                                 segment_ids=segment_ids)
        attn = sharded(attn, b_spec)
        attn = attn.reshape((-1, seq_len, q_size))
        return sharded(attn, P(c.dp_axis, c.cp_axis, c.tp_axis))


class ParallelMLP(Module):
    def __init__(self, config: GPTConfig, layer_idx: int = 0):
        super().__init__()
        c = config
        mult = 2 if c.activation == "swiglu" else 1
        self.up = ColumnParallelLinear(
            c.hidden_size, c.ffn_size * mult, bias=(c.activation == "gelu"),
            dp_axis=c.dp_axis, tp_axis=c.tp_axis, seq_axis=c.cp_axis,
            dtype=c.dtype,
            init=NormalInitializer(0.0, c.init_std),
            name=f"h{layer_idx}.mlp.up")
        self.down = RowParallelLinear(
            c.ffn_size, c.hidden_size, bias=(c.activation == "gelu"), sp=c.sp,
            dp_axis=c.dp_axis, tp_axis=c.tp_axis, seq_axis=c.cp_axis,
            dtype=c.dtype,
            init=NormalInitializer(0.0, c.init_std / math.sqrt(2 * c.num_layers)),
            name=f"h{layer_idx}.mlp.down")
        self.activation = c.activation
        self.dropout = Dropout(c.dropout) if c.dropout else None

    def forward(self, x):
        h = self.up(x)
        if self.activation == "swiglu":
            h = ops.swiglu(h)
        elif self.activation == "silu":
            h = ops.silu(h)
        elif self.activation == "relu":
            h = ops.relu(h)
        else:
            h = ops.gelu(h)
        out = self.down(h)
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class MoEMLP(Module):
    """MoE feed-forward block (reference v1 MoELayer in a transformer,
    v1/examples/moe): token dispatch + stacked experts; the aux balance
    loss is accumulated on the module for the LM head to pick up."""

    def __init__(self, config: GPTConfig, layer_idx: int = 0):
        super().__init__()
        from ..nn.moe import make_moe_layer
        c = config
        # experts use the config activation directly; swiglu (gated, 2x
        # fc1 width) has no stacked-expert form here, so it maps to its
        # silu nonlinearity
        moe_act = "silu" if c.activation == "swiglu" else c.activation
        if moe_act not in ("relu", "gelu", "silu"):
            raise ValueError(
                f"MoE experts do not support activation {c.activation!r}")
        self.moe = make_moe_layer(
            c.hidden_size, c.ffn_size, num_experts=c.num_experts,
            gate_type="topk", k=c.moe_top_k,
            capacity_factor=c.moe_capacity_factor,
            activation=moe_act,
            ep_axis=c.ep_axis, dtype=c.dtype, name=f"h{layer_idx}.moe")
        self.last_aux = None

    def forward(self, x):
        out, aux = self.moe(x)
        self.last_aux = aux
        return out


class GPTBlock(Module):
    def __init__(self, config: GPTConfig, layer_idx: int):
        super().__init__()
        self.ln_1 = _norm(config, f"h{layer_idx}.ln_1")
        self.attn = ParallelAttentionBlock(config, layer_idx)
        self.ln_2 = _norm(config, f"h{layer_idx}.ln_2")
        use_moe = config.is_moe_layer(layer_idx)
        self.mlp = MoEMLP(config, layer_idx) if use_moe \
            else ParallelMLP(config, layer_idx)

    def forward(self, x, seq_len: int, segment_ids=None):
        with phase("norm"):
            h = self.ln_1(x)
        a = self.attn(h, seq_len, segment_ids=segment_ids)
        with phase("attn_proj"):       # the residual rides the out-proj
            x = x + a
        with phase("norm"):
            h = self.ln_2(x)
        with phase("mlp"):
            x = x + self.mlp(h)
        return x


class GPTModel(Module):
    """Backbone: embeddings + blocks + final norm
    (reference LLamaModel, examples/gpt/hetu_llama.py)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        c = config
        self.wte = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, dp_axis=c.dp_axis, tp_axis=c.tp_axis,
            seq_axis=c.cp_axis,
            dtype=c.dtype, init=NormalInitializer(0.0, c.init_std), name="wte")
        if c.position == "learned":
            self.wpe = parallel_parameter(
                NormalInitializer(0.0, c.init_std),
                (c.max_seq_len, c.hidden_size), pspec=P(), dtype=c.dtype,
                name="wpe")
        self.drop = Dropout(c.dropout) if c.dropout else None
        self.h = ModuleList([GPTBlock(c, i) for i in range(c.num_layers)])
        self.ln_f = _norm(config, "ln_f")

    def forward(self, input_ids, seq_len: Optional[int] = None,
                segment_ids=None):
        c = self.config
        if seq_len is None:
            seq_len = input_ids.shape[-1]
            if hasattr(seq_len, "get"):
                seq_len = seq_len.get()
        with phase("embed"):
            x = self.wte(input_ids)
            if c.position == "learned":
                pos = ops.getitem(self.wpe, slice(0, seq_len))
                x = x + pos
            if self.drop is not None:
                x = self.drop(x)
        for block in self.h:
            x = block(x, seq_len, segment_ids=segment_ids)
        with phase("norm"):
            return self.ln_f(x)


class GPTLMHeadModel(Module):
    """LM head + vocab-parallel CE loss (reference LLamaLMHeadModel)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        c = config
        self.transformer = GPTModel(config)
        if c.tie_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                c.hidden_size, c.vocab_size, bias=False,
                dp_axis=c.dp_axis, tp_axis=c.tp_axis, seq_axis=c.cp_axis,
                dtype=c.dtype,
                init=NormalInitializer(0.0, c.init_std), name="lm_head")

    def logits(self, input_ids, seq_len: Optional[int] = None,
               segment_ids=None):
        c = self.config
        x = self.transformer(input_ids, seq_len, segment_ids=segment_ids)
        with phase("lm_head_ce"):
            if self.lm_head is None:
                logits = ops.matmul(x, self.transformer.wte.weight,
                                    trans_b=True)
                logits = sharded(logits, P(c.dp_axis, c.cp_axis, c.tp_axis))
            else:
                logits = self.lm_head(x)
        return logits

    def forward(self, input_ids, labels=None,
                seq_len: Optional[int] = None, segment_ids=None):
        """``segment_ids``: [b, s] packed doc ids (-1 pad) — the
        reference's cu_seqlens varlen path (ops/Attention.h:286),
        Hydraulis packed training."""
        c = self.config
        if labels is not None and c.fused_lm_ce and c.num_experts == 0:
            x = self.transformer(input_ids, seq_len,
                                 segment_ids=segment_ids)
            w = self.lm_head.weight if self.lm_head is not None \
                else self.transformer.wte.weight
            with phase("lm_head_ce"):
                return ops.fused_lm_cross_entropy(x, w, labels,
                                                  ignore_index=-100)
        logits = self.logits(input_ids, seq_len, segment_ids=segment_ids)
        if labels is None:
            return logits
        with phase("lm_head_ce"):
            loss = vocab_parallel_cross_entropy(
                logits, labels, dp_axis=c.dp_axis, tp_axis=c.tp_axis,
                seq_axis=c.cp_axis, ignore_index=-100)
        if c.num_experts > 0 and c.moe_aux_coef:
            for block in self.transformer.h:
                if isinstance(block.mlp, MoEMLP) and \
                        block.mlp.last_aux is not None:
                    loss = loss + c.moe_aux_coef * block.mlp.last_aux
        return loss


# Reference-compatible aliases
LLamaLMHeadModel = GPTLMHeadModel
LLamaModel = GPTModel
