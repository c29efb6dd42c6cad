"""Checks of what the Mamba-1 / attention configuration's cell added to the
benchmark (``jamba2-3b.serve-longdoc``), CPU, tiny sizes, seconds.  A new
file: the other self-tests are not this PR's to edit.  Collected for
tier-1 by ``tests/test_benchmark_jamba.py``.  It does not hold its cell to
be the last of ``workloads``, nor its metrics to be the last of
``per_layer``.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_benchmark_jamba.py -q
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
import work_ssm1  # noqa: E402

CELL = "jamba2-3b.serve-longdoc"
# the catalog row's config, copied by hand: every key, no value changed
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}
NEW = ["dev_ssm_scan_share.replay", "dev_ssm_proj_share.replay",
       "dev_state_io_share.replay", "selective_scan_roofline.replay",
       "gqa_full_attn_roofline.replay", "kv_page_heads_per_block.replay",
       "ssm_chunk_pad_share.replay"]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _json(HERE, "configs", "jamba2-3b.json")


def test_configuration_holds_the_published_keys_unchanged(bench, config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == config["reduced"] == []
    assert entry["file"] == "benchmark/configs/jamba2-3b.json"
    assert len(entry["source"]) <= 200 and \
        entry["source"].endswith("AI21-Jamba2-3B/blob/main/config.json")
    for key in ("layer_order", "head_dim", "ssm_state_dtype", "weights",
                "initializer_range"):
        assert config["assumed"][key]
    assert "26 : 2" in config["assumed"]["layer_order"]
    assert "one chip holds the model whole" in config["deployment"]
    assert config["serve"] == {"page_size": 64, "max_batch": 16,
                               "max_model_len": 33792, "chunk_size": 1024,
                               "prefill_rows": 1, "prefix_cache": False}
    tiny = config["tiny"]
    assert tiny["num_hidden_layers"] == 2 * tiny["attn_layer_period"]
    assert "mamba_d_state" not in tiny          # N 16: the real layout


def test_cell_is_listed_where_its_readers_find_something(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("jamba2-3b", "longdoc-ssm", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    replay = {n + ".replay" for n in (
        "engine_host_ms", "step_ms", "rows_per_step", "ttft_p90_ms",
        "tbt_p95_ms", "peak_hbm_gb", "idle_sched_ms", "idle_launch_ms",
        "idle_commit_ms", "idle_unspanned_ms", "kv_scatter_time_share",
        "launch_ms", "dev_gap_ms", "fetch_tail_ms", "h2d_ms",
        "pack_arrays_ms", "tap_ms", "account_ms", "gc_ms_per_step",
        "stall_share", "dev_mlp_dense_share", "dev_attn_proj_share")}
    assert {"serve_tokens_per_s", "setup_s"} | replay | set(NEW) <= listed
    # no request shares a token run: the hit share would read 0
    assert "prefix_hit_token_share.replay" not in listed
    assert "tbt_p95_ms" not in listed
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "serve_tokens_per_s", m["name"]
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]] == NEW


@pytest.mark.parametrize("name", NEW)
def test_new_layer_metric_names_a_reader_that_exists(bench, name):
    spec = _json(HERE, "layer_metrics", name + ".json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert set(spec) == {"layer", "unit", "moves", "what", "reader", "args"}
    assert (spec["layer"], spec["unit"], spec["moves"]) == \
        (entry["layer"], entry["unit"], entry["moves"])
    path = os.path.join(HERE, "readers", spec["reader"] + ".py")
    assert os.path.exists(path), spec["reader"]
    if "work_module" in spec["args"]:
        mod = importlib.import_module(spec["args"]["work_module"])
        assert spec["args"]["work_fn"] in mod.WORK_FNS
    if name.endswith("_roofline.replay"):
        assert entry["unit"] == "%" and "match" in spec["args"]


def test_traffic_is_the_named_mix_and_repeats_per_seed():
    mix = traffic.load("longdoc-ssm")
    assert mix["driver"] == "serve_replay_ssm"
    assert mix["arrivals"] == {"process": "at_zero", "count": 128}
    assert "shared_prefix" not in mix and mix["max_total"] == 33792
    (cls,) = mix["classes"]
    assert cls["prompt"] == {"dist": "uniform", "min": 16384, "max": 32768}
    assert cls["output"] == {"dist": "uniform", "min": 64, "max": 256}
    small = dict(mix, arrivals={"process": "at_zero", "count": 6})
    big = 2 ** 31 + 12345
    a, docs = traffic.serve_requests(small, big, 51, 65536)
    b, _ = traffic.serve_requests(small, big, 51, 65536)
    c, _ = traffic.serve_requests(small, 7, 51, 65536)
    assert docs == []
    assert [r.prompt for r in a] == [r.prompt for r in b] != \
        [r.prompt for r in c]
    sched = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens)  # noqa
                        for r in rs]
    assert sched(a) == sched(c) and all(r.due_s == 0 for r in a)
    assert all(16384 <= len(r.prompt) <= 32768 and
               64 <= r.max_new_tokens <= 256 for r in a)
    assert max(max(r.prompt) for r in a) > 60000    # the whole vocabulary
    # no two requests share a run of tokens: not even a first page
    assert len({tuple(r.prompt[:64]) for r in a}) == len(a)


def test_work_functions_against_hand_counts(config):
    assert work_ssm1.scan_sizes(config) == (26, 5120, 16)
    # one 1,024-token chunk of one row, 26 layers: float32 dt, xc and y of
    # 5,120 channels and B, C of 16 a token; the row's 16 x 5,120 float32
    # state in and out once; two multiply-adds a (channel, state) pair
    fl, by = work_ssm1.selective_scan_work(config, {
        "ssm_chunk_tokens": 1024, "ssm_chunk_rows": 1, "ssm_decode_rows": 0})
    assert by == 26 * (1024 * (3 * 5120 + 2 * 16) * 4 + 2 * 16 * 5120 * 4) \
        == 26 * 63_700_992
    assert fl == 26 * 1024 * 5120 * 16 * 4 == 26 * 335_544_320
    # eight decode rows: a token and a state each
    fl, by = work_ssm1.selective_scan_work(config, {
        "ssm_chunk_tokens": 0, "ssm_chunk_rows": 0, "ssm_decode_rows": 8})
    assert by == 26 * 8 * ((3 * 5120 + 32) * 4 + 2 * 16 * 5120 * 4)
    assert fl == 26 * 8 * 5120 * 16 * 4
    assert work_ssm1.selective_scan_work(config, {}) == (0.0, 0.0)
    # the bytes bound it: 63.7 MB over 819 GB/s against 0.34 GFLOP over the
    # MXU's 197 TFLOP/s
    peaks = work.peaks_for("TPU v5 lite")
    t, bound = work.roofline_seconds(335_544_320.0, 63_700_992.0, peaks)
    assert bound == "memory" and t == pytest.approx(63_700_992 / 819e9)
    # attention, two layers: 100 distinct pages x 64 tokens x (K + V) x 128
    # lanes in bf16, + 1,030 tokens' q in and output out over 20 heads; 4
    # FLOPs a (pair, head, lane)
    fl, by = work_ssm1.mqa_full_attn_work(config, {
        "kv_pages_distinct": 100, "attn_pairs": 5000, "tokens": 1030})
    assert by == 2 * (100 * 64 * 2 * 128 * 2 + 1030 * 20 * 128 * 4)
    assert fl == 2 * 5000 * 20 * 4 * 128


def test_roofline_reader_on_a_hand_made_trace(config):
    """``span_work_share`` with the new work module: the spans' least time
    over the device time of the calls whose name matches; None where the
    span carries no count or the trace holds no such call."""
    spec = importlib.util.spec_from_file_location(
        "bench_reader_span_work_share",
        os.path.join(HERE, "readers", "span_work_share.py"))
    rd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rd)
    args = _json(HERE, "layer_metrics",
                 "selective_scan_roofline.replay.json")["args"]
    span = types.SimpleNamespace
    ev = [(0, 2_000_000, "selective_scan.3", ""),
          (2_000_000, 1_000_000, "fusion.7", ""),
          (3_000_000, 2_000_000, "selective_scan.9", "")]
    attrs = {"ssm_chunk_tokens": 1024, "ssm_chunk_rows": 1,
             "ssm_decode_rows": 0}
    facts = {"trace": {"events": ev},
             "values": {"trace_host_window": (10.0, 20.0)},
             "device_kind": "TPU v5 lite", "config": config,
             "host_spans": [span(name="unified_step", ts=11.0, attrs=attrs),
                            span(name="unified_step", ts=25.0, attrs=attrs),
                            span(name="unified_step", ts=12.0,
                                 attrs={"rows": 3})]}
    got = rd.read(args, facts)
    assert got == pytest.approx(100 * (26 * 63_700_992 / 819e9) / 4e-3)
    assert rd.read(args, {**facts, "trace": {"events": ev[1:2]}}) is None
    assert rd.read(args, {**facts, "host_spans": []}) is None
    assert rd.read(args, {"trace": None}) is None


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
    assert line["rehearsal"] is True
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
                "kv_page_heads_per_block.replay",
                "ssm_chunk_pad_share.replay"} <= set(line["metrics"])
    notes = json.loads(next(l for l in p.stdout.splitlines()
                            if l.startswith("bench: notes "))[13:])
    assert notes["compiled_in_window"] == 0 and notes["queue_left"] > 0
    assert notes["checked_tokens"] > 0 and notes["beyond_share"] == 0
    assert 0 < notes["ssm_chunk_tokens_walked"] <= \
        notes["ssm_chunk_tokens_padded"]
