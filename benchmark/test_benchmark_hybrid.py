"""Checks of what the hybrid configuration's cell added to the benchmark
(``nemotron3s-ep4.serve-chat``), CPU, tiny sizes, seconds.  A new file:
``test_benchmark.py`` is not this PR's to edit.  Collected for tier-1 by
``tests/test_benchmark_hybrid.py``.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_benchmark_hybrid.py -q
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
import work_hybrid  # noqa: E402

CELL = "nemotron3s-ep4.serve-chat"
# the catalog row's widths and counts, copied by hand from the published
# config.json: no width of the file may differ
PUBLISHED = {
    "hidden_size": 4096, "head_dim": 128, "num_attention_heads": 32,
    "num_key_value_heads": 2, "mamba_num_heads": 128, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
    "expand": 2, "intermediate_size": 2688, "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "n_shared_experts": 1, "num_experts_per_tok": 22,
    "routed_scaling_factor": 5, "layer_norm_epsilon": 1e-5,
    "max_position_embeddings": 262144, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001, "n_group": 1,
    "topk_group": 1, "rope_theta": 10000, "partial_rotary_factor": 1,
}
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _json(HERE, "configs", "nemotron3s-ep4.json")


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reader_{name}", os.path.join(HERE, "readers", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_keeps_every_published_width(bench, config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    entry = next(c for c in bench["configs"] if c["name"] == "nemotron3s-ep4")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    was = config["published"]
    assert set(was) == set(config["reduced"])
    assert was["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 88
    # the cut: one whole period, layers 25-35; a quarter of the experts
    # and of the vocabulary; the floors of a model_config cut
    assert config["hybrid_override_pattern"] == PATTERN[25:36] == "*EMEMEMEMEM"
    assert config["num_hidden_layers"] == 11
    assert (config["n_routed_experts"], config["moe_router_outputs"]) == \
        (128, was["n_routed_experts"]) == (128, 512)
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 4 == was["vocab_size"] == 131072
    assert config["num_nextn_predict_layers"] == 0
    for key in ("attention_positions", "router_input", "ssm_state_dtype",
                "weights"):
        assert config["assumed"][key]
    assert "4 chips" in config["deployment"]
    assert config["serve"] == {"page_size": 64, "max_batch": 64,
                               "chunk_size": 256, "prefill_rows": 1,
                               "prefix_cache": False, "max_model_len": 2048}


def test_cell_is_listed_where_its_readers_find_something(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron3s-ep4", "chat-ssm", 1)
    assert len(cell["why"]) <= 200 and "4x" in cell["why"]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"tbt_p95_ms", "setup_s", "step_ms.chat", "peak_hbm_gb.chat",
            "dev_ssm_scan_share.chat", "dev_ssm_proj_share.chat",
            "dev_state_io_share.chat", "dev_moe_routed_share.chat",
            "dev_moe_shared_share.chat", "moe_routed_roofline.chat",
            "ssm_state_roofline.chat", "moe_local_assign_share.chat",
            "moe_expert_load_peak.chat"} <= listed
    # work.WORK_FNS reads GPT-2 keys: not this cell's
    assert "ragged_attn_roofline.chat" not in listed
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "tbt_p95_ms", m["name"]


def test_traffic_repeats_per_seed_and_keeps_chats_sizes():
    mix, chat = traffic.load("chat-ssm"), traffic.load("chat")
    assert mix["driver"] == "serve_open_loop_hybrid"
    assert mix["classes"] == chat["classes"]        # chat's own shapes
    assert mix["max_total"] == chat["max_total"] == 2048
    assert mix["trace_seconds"] <= 6
    big = 2 ** 31 + 12345
    a, _ = traffic.serve_requests(mix, big, 20, 32768)
    b, _ = traffic.serve_requests(mix, big, 20, 32768)
    c, _ = traffic.serve_requests(mix, 7, 20, 32768)
    key = lambda rs: [(r.due_s, r.prompt, r.max_new_tokens) for r in rs]
    assert key(a) == key(b) and [r.prompt for r in a] != [r.prompt for r in c]
    sched = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens)
                        for r in rs]
    assert sched(a) == sched(c)
    assert all(len(r.prompt) + r.max_new_tokens <= 2048 for r in a)
    assert max(max(r.prompt) for r in a) < 32768    # ids from the slice
    assert len(a) == round(mix["arrivals"]["rate_per_s"] * 20)


def test_work_functions_against_hand_counts(config):
    # one expert: W1 [1024, 2688] + W2 [2688, 1024] in bf16
    assert work_hybrid.expert_bytes(config) == 2 * 1024 * 2688 * 2 \
        == 11_010_048
    fl, by = work_hybrid.moe_routed_work(
        config, {"moe_local": 100, "moe_experts_hit": 7})
    assert by == 7 * 11_010_048
    assert fl == 100 * 2 * (2 * 1024 * 2688)    # two matmuls, 2 FLOPs a MAC
    # float32 state 128 x 64 x 128 and a bf16 tail 3 x (8192 + 2*8*128)
    assert work_hybrid.state_slot_bytes(config) == \
        128 * 64 * 128 * 4 + 3 * 10240 * 2 == 4_255_744
    fl, by = work_hybrid.ssm_state_work(config, {"rows": 10})
    assert fl == 0 and by == 10 * 5 * 2 * 4_255_744
    # nothing in the span: no work, never a guess
    assert work_hybrid.moe_routed_work(config, {}) == (0.0, 0.0)
    peaks = work.peaks_for("TPU v5 lite")
    t, bound = work.roofline_seconds(0.0, 819e9, peaks)
    assert (round(t, 6), bound) == (1.0, "memory")


def test_phase_readers_on_a_hand_made_table(config):
    """``trace_phase_sum`` adds shares of busy; ``span_work_roofline``
    divides the spans' least time by the phases' device time; both give
    None where there is nothing to read."""
    ev = [(0, 1_000_000, "fusion.1", ""), (1_000_000, 3_000_000,
                                            "fusion.2", "")]
    span = types.SimpleNamespace
    facts = {"trace": {"events": ev},
             "_time_by_phase": {"moe_routed": 2_000_000, "ssm_scan": 500_000,
                                "ssm_conv": 500_000},
             "values": {"trace_host_window": (10.0, 20.0)},
             "device_kind": "TPU v5 lite", "config": config,
             "host_spans": [
                 span(name="unified_step", ts=11.0,
                      attrs={"moe_local": 0, "moe_experts_hit": 37,
                             "rows": 3}),
                 span(name="unified_step", ts=25.0,       # outside
                      attrs={"moe_local": 0, "moe_experts_hit": 500,
                             "rows": 64})]}
    rd = _reader("trace_phase_sum")
    assert rd.read({"phases": ["ssm_scan", "ssm_conv"],
                    "as": "share_of_busy"}, facts) == pytest.approx(25.0)
    assert rd.read({"phases": ["state_io"], "as": "share_of_busy"},
                   facts) == 0.0
    rf = _reader("span_work_roofline")
    got = rf.read({"phases": ["moe_routed"], "work_fn": "moe_routed_work"},
                  facts)
    assert got == pytest.approx(100 * (37 * 11_010_048 / 819e9) / 2e-3)
    got = rf.read({"phases": ["ssm_conv", "ssm_scan", "state_io"],
                   "work_fn": "ssm_state_work"}, facts)
    assert got == pytest.approx(100 * (3 * 5 * 2 * 4_255_744 / 819e9) / 1e-3)
    assert rf.read({"phases": ["state_io"], "work_fn": "ssm_state_work"},
                   facts) is None                      # no device time
    assert rd.read({"phases": ["moe_routed"], "as": "share_of_busy"},
                   {"trace": None}) is None
    assert rf.read({"phases": ["moe_routed"], "work_fn": "moe_routed_work"},
                   {"trace": None}) is None


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contract_line_without_values(bench, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 99), "--seconds", "2", "--trace", trace,
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
        assert set(line["metrics"]) == {"tbt_p95_ms", "setup_s"}
    else:       # what needs no device trace is read on the CPU too
        assert {"step_ms.chat", "rows_per_step.chat",
                "moe_local_assign_share.chat",
                "moe_expert_load_peak.chat"} <= set(line["metrics"])
    notes = json.loads(next(l for l in p.stdout.splitlines()
                            if l.startswith("bench: notes "))[13:])
    assert notes["compiled_in_window"] == 0
    assert notes["moe_assignments_total"] == 4 * notes["moe_assignments_local"] \
        or abs(notes["moe_assignments_local"] / notes["moe_assignments_total"]
               - 0.25) < 0.05
