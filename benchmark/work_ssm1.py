"""Work functions of the Mamba-1 / attention configuration's layers: the
operations and bytes a step NEEDS, from the published sizes and from what
the program's ``unified_step`` span says the step held.  Only work
certainly done WHATEVER implements it is counted, so a share of a roofline
computed from these cannot pass 100 %: a live token's ``dt``, ``xc``,
``B``, ``C`` read once and its ``y`` written once in float32 (a token past
the row's length costs nothing), a walked row's state read once and
written once a step (not once a token), the recurrence's two multiply-adds
a (channel, state) pair at 2 FLOPs each (its ``exp`` and the products that
feed it are left out); a distinct K/V page read once a layer and the
causal pairs' arithmetic, as ``work_gqa.py`` counts them."""
from __future__ import annotations

import work_gqa


def scan_sizes(model: dict):
    """``(mamba layers, channels, states)`` from the published keys."""
    layers = model["num_hidden_layers"]
    attn = len(range(model["attn_layer_offset"], layers,
                     model["attn_layer_period"]))
    return (layers - attn, model["mamba_expand"] * model["hidden_size"],
            model["mamba_d_state"])


def selective_scan_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the selective scan over ALL mamba1 layers in one
    step: ``ssm_chunk_tokens`` live tokens of the chunk rows and
    ``ssm_decode_rows`` decode rows (a token each) walked; ``ssm_chunk_rows``
    + ``ssm_decode_rows`` states moved in and out."""
    layers, ch, n = scan_sizes(model)
    rows = float(attrs.get("ssm_chunk_rows", 0)) + \
        float(attrs.get("ssm_decode_rows", 0))
    tokens = float(attrs.get("ssm_chunk_tokens", 0)) + \
        float(attrs.get("ssm_decode_rows", 0))
    flops = tokens * ch * n * 4.0
    nbytes = tokens * (3 * ch + 2 * n) * 4.0 + rows * 2.0 * n * ch * 4.0
    return layers * flops, layers * nbytes


def mqa_full_attn_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the attention layers' K/V call in one step, as
    ``work_gqa.gqa_full_attn_work`` counts: ``kv_pages_distinct`` pages x
    page size x a token's K and V + queries in and outputs out, a layer;
    ``attn_pairs`` causal pairs a layer.  The published config has neither
    ``layer_types`` nor ``head_dim``: the layers come from
    ``attn_layer_offset`` / ``attn_layer_period`` and a head is ``hidden /
    heads`` wide."""
    layers = model["num_hidden_layers"] - scan_sizes(model)[0]
    sized = dict(model, head_dim=model["hidden_size"]
                 // model["num_attention_heads"])
    kv = float(attrs.get("kv_pages_distinct", 0)) * \
        model["serve"]["page_size"] * work_gqa.kv_token_bytes(sized)
    return (layers * work_gqa._pair_flops(
                sized, float(attrs.get("attn_pairs", 0))),
            layers * (kv + work_gqa._query_bytes(
                sized, float(attrs.get("tokens", 0)))))


WORK_FNS = {"selective_scan_work": selective_scan_work,
            "mqa_full_attn_work": mqa_full_attn_work}
