"""Checks of what the indexed / window latent-attention configuration's
cell added to the benchmark (``dots3-ep8.serve-longctx``), CPU, tiny sizes,
seconds.  A new file: the other test files are not this PR's to edit.
Collected for tier-1 by ``tests/test_benchmark_dsa.py``.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_benchmark_dsa.py -q
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import traffic  # noqa: E402
import work  # noqa: E402
import work_dsa  # noqa: E402
import work_mla  # noqa: E402

CELL = "dots3-ep8.serve-longctx"
# the catalog row's config, copied by hand from the published config.json:
# no number of the file may differ but the four keys that are reduced
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512, "max_position_embeddings": 524288,
    "model_type": "dots3_note", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 128, "q_lora_rank": 1024,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128,
}
PATTERN = ["full_attention", "full_attention"] + \
    ["sliding_attention"] * 3 + (["full_attention"] +
                                 ["sliding_attention"] * 3) * 10 + \
    ["full_attention"]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _json(HERE, "configs", "dots3-ep8.json")


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reader_{name}", os.path.join(HERE, "readers", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_keeps_every_published_width(bench, config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    entry = next(c for c in bench["configs"] if c["name"] == "dots3-ep8")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == \
        ["layer_types", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert entry["source"] in config["source"]
    assert entry["file"] == "benchmark/configs/dots3-ep8.json"
    # the cut: the leading dense layer + one period F S S S of 46 layers,
    # 32 of 256 experts, an eighth of the vocabulary
    assert len(PATTERN) == 46 and PATTERN.count("full_attention") == 13
    assert config["layer_types"] == PATTERN[:5]
    assert config["num_hidden_layers"] == 5
    assert config["published"]["num_hidden_layers"] == 46
    assert (config["n_routed_experts"], config["moe_router_outputs"],
            config["expert_offset"]) == (32, 256, 0)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] \
        == 152064
    assert "8 chips" in config["deployment"]
    assert {"a_mla_qkv_lora_rescale", "b_attention_gate", "c_indexer",
            "d_sliding_window_size"} <= set(config["assumed"])
    assert len(config["left_out"]) == 2
    s = config["serve"]
    assert (s["page_size"], s["max_batch"], s["chunk_size"],
            s["max_model_len"], s["pool_gb"]) == (64, 32, 256, 33792, 3.0)
    # the program's own translation of this file
    from hetu_tpu.models.hybrid import dots3_config, param_shapes
    import numpy as np
    cfg = dots3_config(config)
    assert cfg.layer_pattern == ("dsa", "mlp", "dsa", "moe") + \
        ("swa", "moe") * 3
    full, win = cfg.geometry("dsa"), cfg.geometry("swa")
    assert (full.heads, full.latent, full.nope, full.rope, full.v) == \
        (128, 512, 128, 64, 128)
    assert (win.heads, win.latent, win.nope, win.rope, win.v, win.window) \
        == (64, 1024, 192, 64, 128, 513)
    assert full.q_rescale == pytest.approx(5 ** 0.5)
    assert full.kv_rescale == pytest.approx(10 ** 0.5)
    assert win.kv_rescale == pytest.approx(5 ** 0.5)
    n = sum(int(np.prod(s)) for s in param_shapes(cfg).values())
    assert abs(n - 4.087e9) < 0.01e9           # 8.17 GB in bf16


def test_cell_is_listed_where_its_readers_find_something(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("dots3-ep8", "longctx-replay", 1)
    assert len(cell["why"]) <= 200 and "8x" in cell["why"]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "setup_s", "step_ms.replay",
            "peak_hbm_gb.replay", "prefix_hit_token_share.replay",
            "kv_scatter_time_share.replay", "moe_routed_roofline.replay",
            "dev_mla_absorb_share.replay", "dev_attn_proj_share.replay",
            "dev_moe_routed_share.replay", "dev_moe_shared_share.replay",
            "moe_block_fill.replay", "moe_local_assign_share.replay",
            "moe_expert_load_peak.replay", "dev_attn_index_share.replay",
            "dev_attn_sparse_share.replay", "dev_attn_window_share.replay",
            "dev_mlp_dense_share.replay", "index_score_roofline.replay",
            "sparse_attn_roofline.replay", "window_attn_roofline.replay",
            "index_selected_share.replay",
            "window_pages_held_share.replay"} <= listed
    # one geometry times the layer count, no window, no selection; and no
    # call with ragged_paged_attention in its name
    assert not {"latent_attn_roofline.replay", "ragged_time_share.replay",
                "latent_pages_shared_share.replay",
                "latent_pages_per_grid_step.replay", "tbt_p95_ms"} & listed
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "serve_tokens_per_s", m["name"]
            assert os.path.exists(os.path.join(
                HERE, "layer_metrics", m["name"] + ".json")), m["name"]
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(new) == 9 and bench["per_layer"][-9:] == new
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_traffic_is_the_named_mix_and_repeats_per_seed():
    mix = traffic.load("longctx-replay")
    assert mix["driver"] == "serve_replay_dsa"
    assert mix["arrivals"] == {"process": "at_zero", "count": 600}
    assert mix["shared_prefix"] == {"documents": 8, "tokens": 32768,
                                    "zipf_a": 1.1}
    (cls,) = mix["classes"]
    assert cls["prompt"] == {"dist": "uniform", "min": 64, "max": 512}
    assert cls["output"] == {"dist": "uniform", "min": 64, "max": 256}
    assert mix["max_total"] == 33536 and mix["trace_seconds"] == 2
    others = {traffic.load(n)["shape_seed"] for n in
              ("chat", "chat-ssm", "prefix-replay", "longdoc-replay")}
    assert mix["shape_seed"] not in others
    small = dict(mix, arrivals={"process": "at_zero", "count": 24})
    big = 2 ** 31 + 12345
    a, docs = traffic.serve_requests(small, big, 51, 19008)
    b, _ = traffic.serve_requests(small, big, 51, 19008)
    c, _ = traffic.serve_requests(small, 7, 51, 19008)
    key = lambda rs: [(r.due_s, r.prompt, r.max_new_tokens) for r in rs]
    assert key(a) == key(b) and [r.prompt for r in a] != [r.prompt for r in c]
    sched = lambda rs: [(len(r.prompt), r.max_new_tokens, r.document)
                        for r in rs]
    assert sched(a) == sched(c)
    assert len(docs) == 8 and all(len(d) == 32768 for d in docs)
    for r in a:
        assert r.prompt[:32768] == docs[r.document]
        assert 64 <= len(r.prompt) - 32768 <= 512
        assert 64 <= r.max_new_tokens <= 256
        assert len(r.prompt) + r.max_new_tokens <= 33536
        assert max(r.prompt) < 19008


def test_work_functions_against_hand_counts(config):
    # 2 full layers; an index key is 128 bf16 numbers, a page 64 tokens
    fl, by = work_dsa.index_score_work(
        config, {"index_pairs": 1000, "index_pages_distinct": 30,
                 "tokens": 10})
    assert fl == 2 * 1000 * 64 * 128 * 2
    assert by == 2 * (30 * 64 * 256 + 10 * 64 * 128 * 2)
    # a selected pair: 128 heads x 2 x (192 + 128); a cached token 1,152 B
    fl, by = work_dsa.sparse_attn_work(
        config, {"index_selected": 4096, "index_selected_floor": 2048,
                 "tokens": 2})
    assert fl == 2 * 4096 * 128 * 640
    assert by == 2 * (2048 * 1152 + 2 * 128 * 320 * 2)
    # 3 window layers; 64 heads x 2 x (256 + 128); a cached token 2,176 B
    fl, by = work_dsa.window_attn_work(
        config, {"window_pairs": 513, "window_tokens_distinct": 576,
                 "tokens": 1})
    assert fl == 3 * 513 * 64 * 768
    assert by == 3 * (576 * 2176 + 1 * 64 * 384 * 2)
    # the routed experts' function takes its sizes from the configuration
    fl, by = work_mla.moe_gated_routed_work(
        config, {"moe_local": 100, "moe_experts_hit": 7})
    assert fl == 100 * 6 * 5120 * 1536 and by == 7 * 3 * 5120 * 1536 * 2
    # nothing in the span: no work, never a guess
    for fn in work_dsa.WORK_FNS.values():
        assert fn(config, {}) == (0.0, 0.0)


def test_a_share_from_the_work_functions_cannot_pass_100_on_a_synthetic_step(
        config):
    """A step that could not be faster: 32 decode rows on ONE 32,768-token
    document, every index-key page read once a layer at the chip's full
    bandwidth, one selection's latents read once (every row selecting the
    same 2,048 positions: the provable floor), one window's pages a row;
    the readers then give 100 %, and any real step reads lower."""
    peaks = work.peaks_for("TPU v5 lite")
    rows, doc_pages = 32, 512
    attrs = {"tokens": rows, "rows": rows,
             "index_pairs": rows * 32800, "index_selected": rows * 2048,
             "index_selected_floor": 2048,
             "index_pages_distinct": doc_pages + rows,
             "window_pairs": rows * 513,
             "window_tokens_distinct": rows * 9 * 64}
    span = types.SimpleNamespace
    spans = [span(name="unified_step", ts=11.0 + i, attrs=attrs)
             for i in range(3)]
    ns = lambda s: int(round(s * 1e9))                       # noqa: E731
    table, args_of = {}, {}
    for metric, phase_name in (("index_score_roofline", "attn_index"),
                               ("sparse_attn_roofline", "attn_sparse"),
                               ("window_attn_roofline", "attn_window")):
        args_of[metric] = _json(HERE, "layer_metrics",
                                metric + ".replay.json")["args"]
        fn = work_dsa.WORK_FNS[args_of[metric]["work_fn"]]
        table[phase_name] = ns(sum(work.roofline_seconds(
            *fn(config, attrs), peaks)[0] for _ in spans))
    facts = {"trace": {"events": []}, "_time_by_phase": table,
             "values": {"trace_host_window": (10.0, 20.0)},
             "device_kind": "TPU v5 lite", "config": config,
             "host_spans": spans + [span(name="unified_step", ts=25.0,
                                         attrs=attrs)]}     # outside
    rd = _reader("span_work_share")
    # the reader takes its times from trace_phase_time's table
    import readers.trace_phase_time as tpt
    real = tpt.read
    tpt.read = lambda a, f: 1.0
    try:
        for metric, args in args_of.items():
            assert rd.read(args, facts) == pytest.approx(100.0, rel=1e-6), \
                metric
        slow = dict(facts, _time_by_phase={k: 20 * v
                                           for k, v in table.items()})
        assert rd.read(args_of["sparse_attn_roofline"], slow) == \
            pytest.approx(5.0, rel=1e-6)
        # a program without the attributes or a trace: nothing
        bare = dict(facts, host_spans=[span(name="unified_step", ts=11.0,
                                            attrs={"rows": 3})])
        assert rd.read(args_of["index_score_roofline"], bare) is None
        assert rd.read(args_of["index_score_roofline"],
                       {"trace": None}) is None
    finally:
        tpt.read = real
    ctr = _reader("engine_counter")
    spec = _json(HERE, "layer_metrics", "index_selected_share.replay.json")
    assert ctr.read(spec["args"], {"counters": {
        "index_positions_selected": 2048.0,
        "index_pairs_scored": 32768.0}}) == 6.25
    assert ctr.read(spec["args"], {"counters": {}}) is None
    spec = _json(HERE, "layer_metrics", "window_pages_held_share.replay.json")
    assert ctr.read(spec["args"], {"counters": {
        "window_pages_held": 490.0, "full_pages_held": 4900.0}}) == 10.0


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contract_line_without_values(bench, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 99), "--seconds", "3", "--trace", trace,
         "--rehearse"], capture_output=True, text=True, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values())
    known = {m["name"] for m in bench["end_to_end" if trace == "0"
                                      else "per_layer"]
             if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) <= known
    if trace == "0":
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    else:       # what needs no device trace is read on the CPU too
        assert {"step_ms.replay", "rows_per_step.replay",
                "prefix_hit_token_share.replay",
                "index_selected_share.replay",
                "window_pages_held_share.replay",
                "moe_local_assign_share.replay", "moe_block_fill.replay",
                "moe_expert_load_peak.replay"} <= set(line["metrics"])
    notes = json.loads(next(l for l in p.stdout.splitlines()
                            if l.startswith("bench: notes "))[13:])
    assert notes["compiled_in_window"] == 0 and notes["queue_left"] > 0
    assert notes["prefix_cache_tokens_saved"] > notes["prefill_tokens"]
    assert notes["index_select_overlap"] >= 0.9
    assert notes["index_pairs_scored"] > \
        notes["index_positions_selected"] > 0
    assert 0 < notes["window_pages_in_use"] <= notes["window_pages"]
    assert abs(notes["moe_assignments_local"] / notes["moe_assignments_total"]
               - 0.25) < 0.06
