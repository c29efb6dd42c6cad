"""Checks of what the gated-delta / attention configuration's cell added to
the benchmark (``olmohybrid-pp2.serve-reason``), CPU, tiny sizes, seconds.
A new file: the other self-tests are not this PR's to edit.  Collected for
tier-1 by ``tests/test_benchmark_gdn.py``.  It does not hold its cell to be
the last of ``workloads``, nor its metrics to be the last of ``per_layer``.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_benchmark_gdn.py -q
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
import work_gdn  # noqa: E402

CELL = "olmohybrid-pp2.serve-reason"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalog row's config, copied by hand: every key, no value changed
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
REDUCED = ["num_hidden_layers", "layer_types"]
NEW = ["gated_delta_roofline.chat", "gdn_state_roofline.chat",
       "mha_full_attn_roofline.chat"]
STATE = 30 * 96 * 192 * 4          # one row's state in one layer, bytes


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _json(HERE, "configs", "olmohybrid-pp2.json")


def test_configuration_holds_the_published_keys_but_the_depth(bench, config):
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 16
    assert config["layer_types"] == PERIOD * 4          # whole periods
    entry = next(c for c in bench["configs"] if c["name"] == "olmohybrid-pp2")
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/olmohybrid-pp2.json"
    assert len(entry["source"]) <= 200 and \
        entry["source"].endswith("Olmo-Hybrid-7B/blob/main/config.json")
    for key in ("norm_position", "qk_norm", "attention_positions",
                "head_dim", "bias", "linear_layer", "state_dtype", "weights",
                "initializer_range"):
        assert config["assumed"][key]
    assert config["deployment"].startswith(
        "two chips, 16 layers each; this is one of them; nothing of a "
        "layer is shared")
    s = config["serve"]
    assert {k: s[k] for k in s if k != "num_pages"} == {
        "page_size": 64, "max_batch": 48, "max_model_len": 4096,
        "chunk_size": 256, "prefill_rows": 1, "prefix_cache": False}
    tiny = config["tiny"]
    assert tiny["layer_types"] == PERIOD * 2
    assert tiny["num_hidden_layers"] == len(tiny["layer_types"])


def test_cell_is_listed_where_its_readers_find_something(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("olmohybrid-pp2", "reason-lin", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    chat = {n + ".chat" for n in (
        "step_ms", "rows_per_step", "peak_hbm_gb", "engine_host_ms",
        "ttft_p90_ms", "out_tokens_per_s", "queue_wait_p90_ms",
        "queue_depth_mean", "idle_sched_ms", "idle_launch_ms",
        "idle_commit_ms", "idle_unspanned_ms", "launch_ms", "fetch_tail_ms",
        "h2d_ms", "pack_arrays_ms", "account_ms", "stall_share",
        "dev_ssm_scan_share", "dev_ssm_proj_share", "dev_state_io_share",
        "ssm_slots_walked_share", "dev_mlp_dense_share",
        "dev_attn_proj_share", "kv_scatter_time_share", "ragged_time_share",
        "kv_page_heads_per_block")}
    assert {"tbt_p95_ms", "setup_s"} | chat | set(NEW) <= listed
    # the work functions of these read another configuration's keys
    assert not {"ssm_state_roofline.chat", "gqa_full_attn_roofline.chat",
                "serve_tokens_per_s"} & listed
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "tbt_p95_ms", m["name"]
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]] == NEW


@pytest.mark.parametrize("name", NEW)
def test_new_layer_metric_names_a_reader_that_exists(bench, name):
    spec = _json(HERE, "layer_metrics", name + ".json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert set(spec) == {"layer", "unit", "moves", "what", "reader", "args"}
    assert (spec["layer"], spec["unit"], spec["moves"]) == \
        (entry["layer"], entry["unit"], entry["moves"])
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    path = os.path.join(HERE, "readers", spec["reader"] + ".py")
    assert os.path.exists(path), spec["reader"]
    mod = importlib.import_module(spec["args"]["work_module"])
    assert spec["args"]["work_fn"] in mod.WORK_FNS
    assert ("match" in spec["args"]) != ("phases" in spec["args"])


def test_traffic_is_the_named_mix_and_repeats_per_seed():
    mix = traffic.load("reason-lin")
    assert mix["driver"] == "serve_open_loop_gdn"
    assert mix["arrivals"]["process"] == "gamma" and \
        mix["arrivals"]["cv"] == 1.0
    assert "shared_prefix" not in mix and mix["max_total"] == 4096
    (cls,) = mix["classes"]
    assert cls["prompt"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.6, "min": 128, "max": 2048}
    assert cls["output"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.5, "min": 256, "max": 2048}
    assert mix["checked"] == {"pad_to": 4096, "max_new": 2048}
    big = 2 ** 31 + 12345
    a, docs = traffic.serve_requests(mix, big, 51, 100352)
    b, _ = traffic.serve_requests(mix, big, 51, 100352)
    c, _ = traffic.serve_requests(mix, 7, 51, 100352)
    assert docs == []
    assert len(a) == round(mix["arrivals"]["rate_per_s"] * 51)
    assert [r.prompt for r in a] == [r.prompt for r in b] != \
        [r.prompt for r in c]
    sched = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens)  # noqa
                        for r in rs]
    assert sched(a) == sched(c) and 0 < a[0].due_s < a[-1].due_s < 51
    assert all(128 <= len(r.prompt) <= 2048 and
               256 <= r.max_new_tokens <= 2048 and
               len(r.prompt) + r.max_new_tokens <= 4096 for r in a)
    assert max(max(r.prompt) for r in a) > 99000    # the whole vocabulary
    assert len({tuple(r.prompt[:64]) for r in a}) == len(a)
    # the rate is what the sweep's rule gave
    knee = mix["knee"]
    assert mix["arrivals"]["rate_per_s"] == \
        pytest.approx(0.8 * knee["knee_per_s"])


def test_work_functions_against_hand_counts(config):
    assert work_gdn.gdn_sizes(config) == (12, 30, 96, 192)
    # 40 decode rows, 12 layers: a row's 2.21 MB state in and out, its
    # token's q, k (96), v, o (192), alpha, beta of 30 heads in float32;
    # 6 FLOPs a state element
    fl, by = work_gdn.gated_delta_work(config, {
        "ssm_chunk_tokens": 0, "ssm_chunk_rows": 0, "ssm_decode_rows": 40})
    assert STATE == 2_211_840
    assert by == 12 * 40 * (2 * STATE + 30 * (2 * 96 + 2 * 192 + 2) * 4)
    assert fl == 12 * 40 * 30 * 6 * 96 * 192
    # one 256-token chunk of one row beside them: one more state, 256 more
    # tokens
    fl2, by2 = work_gdn.gated_delta_work(config, {
        "ssm_chunk_tokens": 256, "ssm_chunk_rows": 1, "ssm_decode_rows": 40})
    assert by2 - by == 12 * (2 * STATE + 256 * 30 * 578 * 4)
    assert fl2 - fl == 12 * 256 * 30 * 6 * 96 * 192
    assert work_gdn.gated_delta_work(config, {}) == (0.0, 0.0)
    assert work_gdn.gdn_state_work(config, {"ssm_decode_rows": 40}) == \
        (0.0, by)
    # the bytes bound it: 2.1 GB over 819 GB/s against 1.6 GFLOP over the
    # MXU's 197 TFLOP/s
    peaks = work.peaks_for("TPU v5 lite")
    t, bound = work.roofline_seconds(fl, by, peaks)
    assert bound == "memory" and t == pytest.approx(by / 819e9)
    # attention, four layers: 700 distinct pages x 64 tokens x (K + V) x 30
    # heads x 128 lanes in bf16 = 3.93 MB a page over the four, + 296
    # tokens' q in and output out over 30 heads; 4 FLOPs a (pair, head,
    # lane)
    fl, by = work_gdn.mha_full_attn_work(config, {
        "kv_pages_distinct": 700, "attn_pairs": 90000, "tokens": 296})
    assert 4 * 64 * 2 * 30 * 128 * 2 == 3_932_160
    assert by == 700 * 3_932_160 + 4 * 296 * 30 * 128 * 4
    assert fl == 4 * 90000 * 30 * 4 * 128


@pytest.mark.parametrize("name", NEW[:2])
def test_roofline_readers_on_a_hand_made_trace(config, name):
    """``span_work_share`` with the new work module: the spans' least time
    over the device time of the two kernels' calls (or of the state's
    phases); None where the span carries no count, the trace holds no such
    call, or the program has no such phase."""
    spec = importlib.util.spec_from_file_location(
        "bench_reader_span_work_share",
        os.path.join(HERE, "readers", "span_work_share.py"))
    rd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rd)
    args = _json(HERE, "layer_metrics", name + ".json")["args"]
    span = types.SimpleNamespace
    ev = [(0, 2_000_000, "gated_delta_decode.3", ""),
          (2_000_000, 1_000_000, "fusion.7", ""),
          (3_000_000, 2_000_000, "gated_delta_chunk.9", "")]
    attrs = {"ssm_chunk_tokens": 0, "ssm_chunk_rows": 0,
             "ssm_decode_rows": 40}
    facts = {"trace": {"events": ev},
             "values": {"trace_host_window": (10.0, 20.0)},
             "device_kind": "TPU v5 lite", "config": config,
             "host_spans": [span(name="unified_step", ts=11.0, attrs=attrs),
                            span(name="unified_step", ts=25.0, attrs=attrs),
                            span(name="unified_step", ts=12.0,
                                 attrs={"rows": 3})]}
    least = 12 * 40 * (2 * STATE + 30 * 578 * 4) / 819e9
    if "match" in args:
        assert rd.read(args, facts) == pytest.approx(100 * least / 4e-3)
        assert rd.read(args, {**facts, "trace": {"events": ev[1:2]}}) is None
        assert rd.read(args, {**facts, "host_spans": []}) is None
    else:       # a trace without the program's phases reads nothing
        assert rd.read(args, facts) is None
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
        assert set(line["metrics"]) == {"tbt_p95_ms", "setup_s"}
    else:       # what needs no device trace is read on the CPU too
        assert {"step_ms.chat", "rows_per_step.chat",
                "kv_page_heads_per_block.chat",
                "ssm_slots_walked_share.chat"} <= set(line["metrics"])
    notes = json.loads(next(l for l in p.stdout.splitlines()
                            if l.startswith("bench: notes "))[13:])
    assert notes["compiled_in_window"] == 0 and notes["done"] >= 4
    assert notes["checked_tokens"] > 0 and \
        notes["beyond_share"] <= 0.035
    assert 0 < notes["ssm_chunk_tokens_walked"] <= \
        notes["ssm_chunk_tokens_padded"]
    assert 0 < notes["ssm_slots_walked"] < notes["ssm_slots_store"]
