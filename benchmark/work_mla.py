"""Work functions of the latent-attention / gated-expert configuration's
layers: the operations and bytes a step NEEDS, from the published sizes
and from what the program's ``unified_step`` span says the step held.
Only work certainly done WHATEVER implements it is counted, so a share of
a roofline computed from these cannot pass 100 % and a later kernel is
judged by the same yardstick: a distinct physical page of the latent
cache read once a layer (not once a row that attends it), an exact
attention's least arithmetic (the non-absorbed count), a hit expert's
three matrices read once, an assignment's three matmuls once."""
from __future__ import annotations


def latent_token_bytes(model: dict) -> float:
    """One cached token of ONE layer: ``c_kv | k_r`` in bf16."""
    return 2.0 * (model["kv_lora_rank"] + model["qk_rope_head_dim"])


def latent_attn_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the latent attention over ALL layers in one step.
    Bytes: ``latent_pages_distinct`` pages x page size x a token's bytes,
    plus the step's queries in (bf16, ``nope + rope`` a head: what a
    non-absorbed call reads) and outputs out (bf16, ``v`` a head).  FLOPs:
    ``attn_pairs`` x heads x 2 x (qk width + v width): 512 a head a pair
    at 64 | 64 | 128, the non-absorbed count, the least an exact attention
    does (absorbed, as the program runs it, is 2 x (320 + 256) = 1,152)."""
    layers, heads = model["num_hidden_layers"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    page = model["serve"]["page_size"] * latent_token_bytes(model)
    tokens = float(attrs.get("tokens", 0))
    nbytes = float(attrs.get("latent_pages_distinct", 0)) * page + \
        tokens * heads * (qk + model["v_head_dim"]) * 2.0
    flops = float(attrs.get("attn_pairs", 0)) * heads * 2.0 * \
        (qk + model["v_head_dim"])
    return layers * flops, layers * nbytes


def gated_expert_bytes(model: dict) -> float:
    """One routed expert's three matrices (gate, up, down) in bf16."""
    return 3.0 * model["hidden_size"] * model["moe_intermediate_size"] * 2


def moe_gated_routed_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the gated routed experts over ALL expert layers
    in one step: ``moe_experts_hit`` (held experts with >= 1 live token,
    summed over the expert layers) x one expert's weights read once;
    ``moe_local`` live assignments x three matmuls of ``hidden x width``
    at 2 FLOPs a multiply-add."""
    flops = float(attrs.get("moe_local", 0)) * 6.0 * model["hidden_size"] * \
        model["moe_intermediate_size"]
    return flops, float(attrs.get("moe_experts_hit", 0)) * \
        gated_expert_bytes(model)


WORK_FNS = {"latent_attn_work": latent_attn_work,
            "moe_gated_routed_work": moe_gated_routed_work}
