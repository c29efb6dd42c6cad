"""Checks of what the latent-attention configuration's cell added to the
benchmark (``mistral4-ep4.serve-longdoc``), CPU, tiny sizes, seconds.  A
new file: ``test_benchmark.py`` is not this PR's to edit.  Collected for
tier-1 by ``tests/test_benchmark_mla.py``.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_benchmark_mla.py -q
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

import jax.numpy as jnp  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
import work_mla  # noqa: E402

CELL = "mistral4-ep4.serve-longdoc"
# the catalog row's config, copied by hand from the published config.json:
# no number of the file may differ but the three that are reduced
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
    "kv_lora_rank": 256, "max_position_embeddings": 1048576,
    "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_key_value_heads": 32,
    "q_lora_rank": 1024, "qk_head_dim": 128, "qk_nope_head_dim": 64,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    "routed_scaling_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _json(HERE, "configs", "mistral4-ep4.json")


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reader_{name}", os.path.join(HERE, "readers", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_keeps_every_published_width(bench, config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    entry = next(c for c in bench["configs"] if c["name"] == "mistral4-ep4")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    was = config["published"]
    assert (was["num_hidden_layers"], was["n_routed_experts"],
            was["vocab_size"]) == (36, 128, 131072)
    # the cut: 6 of 36 layers (the floor is four), a quarter of the experts
    # and of the vocabulary
    assert config["num_hidden_layers"] == 6
    assert (config["n_routed_experts"], config["moe_router_outputs"],
            config["expert_offset"]) == (32, 128, 0)
    assert config["vocab_size"] * 4 == was["vocab_size"]
    assert any("vision" in s for s in config["left_out"])
    for key in ("router_scoring", "softmax_scale", "llama_4_scaling_beta",
                "weights"):
        assert config["assumed"][key]
    assert "4 chips" in config["deployment"]
    assert config["serve"] == {
        "page_size": 64, "max_batch": 32, "chunk_size": 256,
        "prefill_rows": 1, "prefix_cache": True, "max_model_len": 17408,
        "pool_gb": 2.0}
    # weights fill >= 60 % of a 16.9 GB chip in bf16
    from hetu_tpu.models import hybrid as hy
    cfg = hy.mistral4_config(config)
    n = sum(int(__import__("numpy").prod(s)) * (4 if hy.param_dtype(
        cfg, k) == jnp.float32 else 2) for k, s in hy.param_shapes(cfg).items())
    assert 0.60 <= n / 16.9e9 <= 0.70, n
    assert cfg.mla_softmax_scale == pytest.approx(128 ** -0.5 * 1.4852 ** 2,
                                                  rel=1e-4)


def test_cell_is_listed_where_its_readers_find_something(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("mistral4-ep4", "longdoc-replay", 1)
    assert len(cell["why"]) <= 200 and "4x" in cell["why"]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "setup_s", "step_ms.replay",
            "peak_hbm_gb.replay", "ragged_time_share.replay",
            "prefix_hit_token_share.replay", "kv_scatter_time_share.replay",
            "latent_attn_roofline.replay", "moe_routed_roofline.replay",
            "dev_mla_absorb_share.replay", "dev_attn_proj_share.replay",
            "dev_moe_routed_share.replay", "dev_moe_shared_share.replay",
            "latent_pages_shared_share.replay", "moe_block_fill.replay",
            "moe_local_assign_share.replay",
            "moe_expert_load_peak.replay"} <= listed
    assert "tbt_p95_ms" not in listed
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "serve_tokens_per_s", m["name"]
            assert os.path.exists(os.path.join(
                HERE, "layer_metrics", m["name"] + ".json")), m["name"]
    # a quarter of the cells, rounded down, may take four chips: still one
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_traffic_is_the_named_mix_and_repeats_per_seed():
    mix = traffic.load("longdoc-replay")
    assert mix["driver"] == "serve_replay_mla"
    assert mix["arrivals"] == {"process": "at_zero", "count": 1200}
    assert mix["shared_prefix"] == {"documents": 8, "tokens": 16384,
                                    "zipf_a": 1.1}
    (cls,) = mix["classes"]
    assert cls["prompt"] == {"dist": "uniform", "min": 64, "max": 512}
    assert cls["output"] == {"dist": "uniform", "min": 64, "max": 256}
    assert mix["max_total"] == 17408 and mix["trace_seconds"] == 2
    others = {traffic.load(n)["shape_seed"] for n in
              ("chat", "chat-ssm", "prefix-replay")}
    assert mix["shape_seed"] not in others
    small = dict(mix, arrivals={"process": "at_zero", "count": 40})
    big = 2 ** 31 + 12345
    a, docs = traffic.serve_requests(small, big, 51, 32768)
    b, _ = traffic.serve_requests(small, big, 51, 32768)
    c, _ = traffic.serve_requests(small, 7, 51, 32768)
    key = lambda rs: [(r.due_s, r.prompt, r.max_new_tokens) for r in rs]
    assert key(a) == key(b) and [r.prompt for r in a] != [r.prompt for r in c]
    sched = lambda rs: [(len(r.prompt), r.max_new_tokens, r.document)
                        for r in rs]
    assert sched(a) == sched(c)
    assert len(docs) == 8 and all(len(d) == 16384 for d in docs)
    for r in a:
        assert r.prompt[:16384] == docs[r.document]
        assert 64 <= len(r.prompt) - 16384 <= 512
        assert 64 <= r.max_new_tokens <= 256
        assert len(r.prompt) + r.max_new_tokens <= 17408
        assert max(r.prompt) < 32768


def test_work_functions_against_hand_counts(config):
    # a cached token of one layer: 256 + 64 numbers in bf16
    assert work_mla.latent_token_bytes(config) == 640
    # one gated expert: three matrices of 4096 x 2048 in bf16
    assert work_mla.gated_expert_bytes(config) == 3 * 4096 * 2048 * 2 \
        == 50_331_648
    fl, by = work_mla.moe_gated_routed_work(
        config, {"moe_local": 100, "moe_experts_hit": 7})
    assert by == 7 * 50_331_648 and fl == 100 * 6 * 4096 * 2048
    fl, by = work_mla.latent_attn_work(
        config, {"latent_pages_distinct": 300, "attn_pairs": 1000,
                 "tokens": 10})
    # 6 layers; a page is 64 tokens x 640 B; q in 128 + output 128 a head
    assert by == 6 * (300 * 64 * 640 + 10 * 32 * 256 * 2)
    assert fl == 6 * 1000 * 32 * 512
    # nothing in the span: no work, never a guess
    assert work_mla.moe_gated_routed_work(config, {}) == (0.0, 0.0)
    assert work_mla.latent_attn_work(config, {}) == (0.0, 0.0)


def test_a_share_from_the_work_functions_cannot_pass_100_on_a_synthetic_step(
        config):
    """A step that could not be faster: 32 decode rows on ONE 16,384-token
    document plus private tails, every page read exactly once a layer at
    the chip's full bandwidth and every hit expert's bytes likewise; the
    readers then give 100 %, and any real step (pages re-read by each row,
    absorbed FLOPs, tiles, gaps) reads lower."""
    peaks = work.peaks_for("TPU v5 lite")
    rows, doc_pages, own = 32, 256, 4
    attrs = {"tokens": rows, "rows": rows,
             "latent_pages": rows * (doc_pages + own),
             "latent_pages_distinct": doc_pages + rows * own,
             "latent_ctx_tokens": rows * (doc_pages + own) * 64,
             "attn_pairs": rows * (doc_pages + own) * 64,
             "moe_local": rows * 6, "moe_experts_hit": 6 * 30}
    span = types.SimpleNamespace
    spans = [span(name="unified_step", ts=11.0 + i, attrs=attrs)
             for i in range(3)]
    t_attn = sum(work.roofline_seconds(*work_mla.latent_attn_work(
        config, attrs), peaks)[0] for _ in spans)
    t_moe = sum(work.roofline_seconds(*work_mla.moe_gated_routed_work(
        config, attrs), peaks)[0] for _ in spans)
    ns = lambda s: int(round(s * 1e9))                       # noqa: E731
    ev = [(0, ns(t_attn), "latent_ragged_paged_attention_decode", ""),
          (ns(t_attn), ns(t_moe), "moe_grouped_experts", "")]
    facts = {"trace": {"events": ev},
             "_time_by_phase": {"moe_routed": ns(t_moe)},
             "values": {"trace_host_window": (10.0, 20.0)},
             "device_kind": "TPU v5 lite", "config": config,
             "host_spans": spans + [span(name="unified_step", ts=25.0,
                                         attrs=attrs)]}     # outside
    rd = _reader("span_work_share")
    attn = rd.read(_json(HERE, "layer_metrics",
                         "latent_attn_roofline.replay.json")["args"], facts)
    moe = rd.read(_json(HERE, "layer_metrics",
                        "moe_routed_roofline.replay.json")["args"], facts)
    assert attn == pytest.approx(100.0, rel=1e-6)
    assert moe == pytest.approx(100.0, rel=1e-6)
    # each row reading its own copy of the document: 18 x the pages, a
    # kernel that takes that long reads under 100
    slow = dict(facts, trace={"events": [
        (0, ns(t_attn * 12), "latent_ragged_paged_attention_decode", "")]})
    assert rd.read({"work_module": "work_mla", "work_fn": "latent_attn_work",
                    "match": "latent_ragged_paged_attention"}, slow) \
        == pytest.approx(100.0 / 12, rel=1e-6)
    # a program without the attributes, the kernel or a trace: nothing
    bare = dict(facts, host_spans=[span(name="unified_step", ts=11.0,
                                        attrs={"rows": 3})])
    args = _json(HERE, "layer_metrics",
                 "latent_attn_roofline.replay.json")["args"]
    assert rd.read(args, bare) is None
    assert rd.read(args, dict(facts, trace={"events": []})) is None
    assert rd.read(args, {"trace": None}) is None
    rest = _reader("engine_counter_rest")
    assert rest.read({"part": ["a"], "whole": ["b"], "scale": 100.0},
                     {"counters": {"a": 25.0, "b": 100.0}}) == 75.0
    assert rest.read({"part": ["a"], "whole": ["b"]}, {"counters": {}}) is None


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
                "latent_pages_shared_share.replay",
                "moe_local_assign_share.replay", "moe_block_fill.replay",
                "moe_expert_load_peak.replay"} <= set(line["metrics"])
    notes = json.loads(next(l for l in p.stdout.splitlines()
                            if l.startswith("bench: notes "))[13:])
    assert notes["compiled_in_window"] == 0 and notes["queue_left"] > 0
    assert notes["prefix_cache_tokens_saved"] > notes["prefill_tokens"]
    assert notes["latent_pages_attended"] > \
        notes["latent_pages_attended_distinct"] > 0
    assert abs(notes["moe_assignments_local"] / notes["moe_assignments_total"]
               - 0.25) < 0.05
