"""Work functions of the indexed / window latent-attention configuration's
new layers: the operations and bytes a step NEEDS, from the published
sizes and from what the program's ``unified_step`` span says the step
held.  ``work_mla.py``'s rule: only work certainly done WHATEVER
implements it is counted, so a share of a roofline computed from these
cannot pass 100 % and a later kernel is judged by the same yardstick.
One layer's attributes times the number of layers of the kind."""
from __future__ import annotations


def _layers(model: dict, kind: str) -> int:
    return sum(t == kind for t in model["layer_types"])


def index_score_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the indexer's scoring over ALL full layers in one
    step.  FLOPs: ``index_pairs`` (query, position) pairs x
    ``index_n_heads`` x ``index_head_dim`` x 2 — every pair inside the
    causal mask is scored, there is no cheaper exact top-k.  Bytes: the
    distinct index-key pages under the rows' contexts read once
    (``index_pages_distinct`` x page x ``index_head_dim`` bf16: rows on one
    document share them) plus the step's index queries in (``tokens`` x
    heads x dim bf16).  The projections, the weighting and the top-k itself
    are left out (a floor)."""
    heads, dim = model["index_n_heads"], model["index_head_dim"]
    flops = float(attrs.get("index_pairs", 0)) * heads * dim * 2.0
    nbytes = float(attrs.get("index_pages_distinct", 0)) * \
        model["serve"]["page_size"] * dim * 2.0 + \
        float(attrs.get("tokens", 0)) * heads * dim * 2.0
    n = _layers(model, "full_attention")
    return n * flops, n * nbytes


def sparse_attn_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the attention over the selected positions, ALL
    full layers in one step.  FLOPs: ``index_selected`` (query, selected
    position) pairs x heads x 2 x (qk width + v width), the non-absorbed
    count (640 a head a pair at 128 | 64 | 128; absorbed, as the program
    runs it, is 2 x (576 + 512)).  Bytes: rows that share a document may
    select the same positions, so rows x 2,048 x a token's bytes is NOT a
    floor; counted is the floor the host can PROVE
    (``index_selected_floor``: rows whose tables start with one page form
    a group, a group counts its largest selection once, groups are
    disjoint) x ``c_kv | k_r`` bf16, plus the step's queries in (bf16,
    ``nope + rope`` a head) and outputs out (bf16, ``v`` a head)."""
    heads = model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    v = model["v_head_dim"]
    token = 2.0 * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
    flops = float(attrs.get("index_selected", 0)) * heads * 2.0 * (qk + v)
    nbytes = float(attrs.get("index_selected_floor", 0)) * token + \
        float(attrs.get("tokens", 0)) * heads * (qk + v) * 2.0
    n = _layers(model, "full_attention")
    return n * flops, n * nbytes


def window_attn_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the window attention over ALL window layers in
    one step.  FLOPs: ``window_pairs`` (query, key) pairs inside the
    windows x heads x 2 x (qk width + v width) at the ``swa_*`` sizes, the
    non-absorbed count.  Bytes: the distinct window-space pages in the
    rows' tables read once (``window_pages_distinct`` x page x ``c_kv |
    k_r`` bf16: a page partly outside every window is still a page the
    program holds, so this counts a little more than the keys read and is
    reported as such: the reading stays far under 100 %) plus queries in
    and outputs out."""
    heads = model["swa_num_attention_heads"]
    qk = model["swa_qk_nope_head_dim"] + model["swa_qk_rope_head_dim"]
    v = model["swa_v_head_dim"]
    token = 2.0 * (model["swa_kv_lora_rank"] + model["swa_qk_rope_head_dim"])
    flops = float(attrs.get("window_pairs", 0)) * heads * 2.0 * (qk + v)
    nbytes = float(attrs.get("window_tokens_distinct", 0)) * token + \
        float(attrs.get("tokens", 0)) * heads * (qk + v) * 2.0
    n = _layers(model, "sliding_attention")
    return n * flops, n * nbytes


WORK_FNS = {"index_score_work": index_score_work,
            "sparse_attn_work": sparse_attn_work,
            "window_attn_work": window_attn_work}
