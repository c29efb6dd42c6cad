"""Work functions of the block-diffusion K/V-attention configuration's
layers: the operations and bytes a step NEEDS, from the published sizes and
from what the program's ``unified_step`` span says the step held.  Only
work certainly done WHATEVER implements it is counted, so a share of a
roofline computed from these cannot pass 100 % and a later kernel is judged
by the same yardstick: a distinct physical page of a layer's K and V read
once a layer (not once a row or once a query head), an exact attention's
arithmetic over the pairs INSIDE THE BLOCK-WISE MASK alone — counted from
the mask (a query sees its row's keys up to the end of its own block),
whatever implements it."""
from __future__ import annotations


def kv_token_bytes(model: dict) -> float:
    """One cached token of ONE layer: K and V of every kv head in bf16."""
    return 2.0 * model["num_key_value_heads"] * model["head_dim"] * 2.0


def gqa_block_attn_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the attention layers in one step, every region's
    call (the prompt's chunk and the block rows run under one mask):
    ``kv_pages_distinct`` pages x page size x a token's bytes + the step's
    queries in and outputs out (bf16), a layer; ``attn_pairs`` pairs
    inside the block-wise mask, QK^T and PV of every query head."""
    layers, heads = model["num_hidden_layers"], model["num_attention_heads"]
    hd = model["head_dim"]
    nbytes = float(attrs.get("kv_pages_distinct", 0)) * \
        model["serve"]["page_size"] * kv_token_bytes(model) + \
        float(attrs.get("tokens", 0)) * heads * hd * 4.0
    flops = float(attrs.get("attn_pairs", 0)) * heads * 4.0 * hd
    return layers * flops, layers * nbytes


WORK_FNS = {"gqa_block_attn_work": gqa_block_attn_work}
