"""Work functions of the gated-delta / attention configuration's layers:
the operations and bytes a step NEEDS, from the published sizes and from
what the program's ``unified_step`` span says the step held.  Only work
certainly done WHATEVER implements it is counted, so a share of a roofline
computed from these cannot pass 100 %: a walked row's matrix state (heads x
key width x value width, float32) read once and written once a layer a
step (not once a token), a live token's q, k, v, alpha and beta read once
and its output written once in float32 (a token past the row's length costs
nothing), the recurrence's three multiply-adds a state element a token at 2
FLOPs each (``S^T k``, the rank-one update, ``S^T q``; the chunk form's
solve and the decay's ``exp`` are left out: another form need not do
them); a distinct K/V page read once a layer and the causal pairs'
arithmetic, as ``work_gqa.py`` counts them."""
from __future__ import annotations

import work_gqa


def gdn_sizes(model: dict):
    """``(linear layers, heads, key width, value width)`` from the
    published keys (as cut: ``layer_types`` lists the layers held)."""
    return (model["layer_types"].count("linear_attention"),
            model["linear_num_value_heads"], model["linear_key_head_dim"],
            model["linear_value_head_dim"])


def _walk(attrs: dict):
    """(rows whose state moves, live tokens) of one layer in one step."""
    rows = float(attrs.get("ssm_chunk_rows", 0)) + \
        float(attrs.get("ssm_decode_rows", 0))
    tokens = float(attrs.get("ssm_chunk_tokens", 0)) + \
        float(attrs.get("ssm_decode_rows", 0))
    return rows, tokens


def gated_delta_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the gated delta rule over ALL linear layers in
    one step: ``ssm_chunk_tokens`` live tokens of the chunk rows and
    ``ssm_decode_rows`` decode rows (a token each) walked; ``ssm_chunk_rows``
    + ``ssm_decode_rows`` states moved in and out."""
    layers, h, dk, dv = gdn_sizes(model)
    rows, tokens = _walk(attrs)
    flops = tokens * h * 6.0 * dk * dv
    nbytes = rows * 2.0 * h * dk * dv * 4.0 + \
        tokens * h * (2 * dk + 2 * dv + 2) * 4.0
    return layers * flops, layers * nbytes


def gdn_state_work(model: dict, attrs: dict):
    """(0, bytes): the same bytes, for the whole of the state's way through
    a layer (conv, recurrence, the rows' way in and out of slot order);
    the arithmetic is left out: the bytes bound it."""
    return 0.0, gated_delta_work(model, attrs)[1]


def mha_full_attn_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the full-attention layers' K/V call in one step,
    as ``work_gqa.gqa_full_attn_work`` counts: ``kv_pages_distinct`` pages x
    page size x a token's K and V of every K/V head + queries in and
    outputs out, a layer; ``attn_pairs`` causal pairs a layer.  The
    published config has no ``head_dim``: a head is ``hidden / heads``
    wide."""
    layers = model["layer_types"].count("full_attention")
    sized = dict(model, head_dim=model["hidden_size"]
                 // model["num_attention_heads"])
    kv = float(attrs.get("kv_pages_distinct", 0)) * \
        model["serve"]["page_size"] * work_gqa.kv_token_bytes(sized)
    return (layers * work_gqa._pair_flops(
                sized, float(attrs.get("attn_pairs", 0))),
            layers * (kv + work_gqa._query_bytes(
                sized, float(attrs.get("tokens", 0)))))


WORK_FNS = {"gated_delta_work": gated_delta_work,
            "gdn_state_work": gdn_state_work,
            "mha_full_attn_work": mha_full_attn_work}
