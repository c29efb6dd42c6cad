"""CPU checks of the block-diffusion cell (``sdar30b-pp8.serve-chat``): its
configuration against the catalog row, its entries in ``BENCHMARK.json``,
its traffic file, its work function against hand counts, every metric it
lists against its file and reader, and the cell walked at its ``tiny``
sizes.  ``tests/test_benchmark_block.py`` collects these cases for tier-1."""
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
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)
import traffic  # noqa: E402
import work  # noqa: E402
import work_mla  # noqa: E402
import work_sdar  # noqa: E402

CELL = "sdar30b-pp8.serve-chat"
# the catalog row's config, copied by hand from the published config.json:
# no number of the file may differ but the key that is reduced
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
NEW = ["block_tokens_per_pass.chat", "block_commit_pass_share.chat",
       "block_masked_share.chat", "kv_provisional_share.chat",
       "dev_block_head_share.chat", "gqa_block_attn_roofline.chat"]
SHARED = ["step_ms", "engine_host_ms", "ttft_p90_ms", "queue_wait_p90_ms",
          "rows_per_step", "out_tokens_per_s", "idle_sched_ms",
          "idle_launch_ms", "idle_commit_ms", "idle_unspanned_ms",
          "launch_ms", "fetch_tail_ms", "dev_gap_ms", "h2d_ms",
          "pack_arrays_ms", "tap_ms", "account_ms", "gc_ms_per_step",
          "stall_share", "queue_depth_mean", "peak_hbm_gb",
          "ragged_time_share", "kv_scatter_time_share",
          "dev_attn_proj_share", "dev_moe_routed_share",
          "moe_local_assign_share", "moe_expert_load_peak", "moe_block_fill",
          "kv_page_heads_per_block", "moe_gated_roofline"]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return _json(HERE, "configs", "sdar30b-pp8.json")


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reader_{name}", os.path.join(HERE, "readers", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_keeps_every_published_width(bench, config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    entry = next(c for c in bench["configs"] if c["name"] == "sdar30b-pp8")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] in config["source"] and \
        1 <= len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/sdar30b-pp8.json"
    # the cut: depth alone, six whole layers = one of eight pipeline stages
    assert config["num_hidden_layers"] == 6
    assert config["published"] == {"num_hidden_layers": 48}
    assert "eight pipeline stages" in config["deployment"]
    a = config["assumed"]
    assert (a["block_length"], a["mask_token_id"], a["denoising_steps"],
            a["remasking_strategy"], a["confidence_threshold"]) == \
        (4, 151669, 4, "low_confidence_dynamic", 0.9)
    assert {"a_generation", "b_stack", "c_logits", "d_commit",
            "e_noise_schedule", "weights"} <= set(a)
    s = config["serve"]
    assert (s["page_size"], s["max_batch"], s["chunk_size"],
            s["prefill_rows"], s["prefix_cache"], s["pool_gb"]) == \
        (64, 64, 256, 1, False, 4.0)
    assert s["denoise"] == {"steps": 2, "rule": "low_confidence_static"}
    assert s["page_size"] % a["block_length"] == 0 == \
        s["chunk_size"] % a["block_length"]
    # the arithmetic of the cut, in bf16: a layer 623.1 M, embedding and
    # head 622.3 M, 4.36 B parameters = 8.72 GB; K/V 12,288 B a token
    h, hd = config["hidden_size"], config["head_dim"]
    attn = h * (config["num_attention_heads"] * hd) * 2 + \
        2 * h * config["num_key_value_heads"] * hd + 2 * hd + h
    layer = attn + config["num_experts"] * (
        h + 3 * h * config["moe_intermediate_size"]) + h
    total = 6 * layer + 2 * config["vocab_size"] * h + h
    assert round(layer / 1e6, 1) == 623.1 and round(total / 1e9, 2) == 4.36
    from hetu_tpu.models.hybrid import param_shapes, sdar_moe_config
    import numpy as np
    cfg = sdar_moe_config(config)
    assert sum(int(np.prod(v)) for v in param_shapes(cfg).values()) == total
    assert (cfg.diffusion_block, cfg.mask_token_id, cfg.held_experts,
            cfg.moe_top_k, cfg.layer_pattern) == \
        (4, 151669, 128, 8, ("attention", "moe") * 6)


def test_cell_is_listed_where_its_readers_find_something(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("sdar30b-pp8", "chat-block", 1)
    assert 1 <= len(cell["why"]) <= 200 and "COST" in cell["why"]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"tbt_p95_ms", "setup_s", *NEW,
            *(n + ".chat" for n in SHARED)} == listed
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "tbt_p95_ms", m["name"]
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == NEW
    # one configuration, one cell, no four-chip cell more
    assert sum(w["config"] == "sdar30b-pp8" for w in bench["workloads"]) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == names.index("kexaone-ep8.serve-reason") + 1


@pytest.mark.parametrize("name", NEW + [n + ".chat" for n in SHARED])
def test_every_metric_of_the_cell_has_a_file_and_a_reader(bench, name):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = _json(HERE, "layer_metrics", name + ".json")
    assert (spec["layer"], spec["unit"], spec["moves"]) == \
        (entry["layer"], entry["unit"], entry["moves"])
    assert callable(_reader(spec["reader"]).read)
    if name in NEW:
        assert set(spec) == {"layer", "unit", "moves", "what", "reader",
                             "args"}
        assert entry["source"] == {
            "engine_counter": "program_counter",
            "trace_phase_sum": "device_trace",
            "span_work_share": "device_trace"}[spec["reader"]]


def test_traffic_is_the_named_mix_and_repeats_per_seed(config):
    mix = traffic.load("chat-block")
    assert mix["driver"] == "serve_open_loop_block"
    assert mix["arrivals"]["process"] == "gamma" and \
        mix["arrivals"]["cv"] == 1.0
    (cls,) = mix["classes"]
    assert cls["prompt"] == traffic.load("chat")["classes"][0]["prompt"] == \
        {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
         "max": 1536}
    assert cls["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.6, "min": 64, "max": 1024}
    assert (mix["output_multiple"], mix["vocab_below"],
            mix["trace_seconds"]) == (4, 151643, 2)
    assert mix["vocab_below"] < config["assumed"]["mask_token_id"]
    assert mix["max_total"] + mix["output_multiple"] <= \
        config["serve"]["max_model_len"] == mix["checked"]["pad_to"]
    assert mix["checked"]["min_passes"] == 32 <= mix["checked"]["passes"]
    others = {traffic.load(n)["shape_seed"] for n in
              ("chat", "chat-ssm", "prefix-replay", "longdoc-replay",
               "longctx-replay", "reason-mixed")}
    assert mix["shape_seed"] not in others
    # the rate is 0.8 x the recorded knee, the knee the rule's on the sweep
    knee = mix["knee"]
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * knee["knee_per_s"])
    sweep = knee["sweep"]
    ok = [r for r, w, run, miss in zip(
        sweep["rate_per_s"], sweep["waiting_at_end"],
        sweep["running_at_end"], sweep["missed_of_judged"])
        if w <= 2 and run < config["serve"]["max_batch"]
        and miss.startswith("0/")]
    assert max(ok) == knee["knee_per_s"]
    big = 2 ** 31 + 12345
    make = lambda seed: traffic.serve_requests(  # noqa: E731
        mix, seed, 51, mix["vocab_below"])[0]
    a, b, c = make(big), make(big), make(7)
    key = lambda rs: [(r.due_s, r.prompt, r.max_new_tokens) for r in rs]
    assert key(a) == key(b) and [r.prompt for r in a] != [r.prompt for r in c]
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in c]
    for r in a:
        assert max(r.prompt) < mix["vocab_below"]
        assert len(r.prompt) + r.max_new_tokens <= mix["max_total"]


def _driver():
    spec = importlib.util.spec_from_file_location(
        "bench_driver_block", os.path.join(HERE, "drivers",
                                           "serve_open_loop_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_drivers_own_counts():
    """What the driver recounts and samples, on hand-made logs."""
    d = _driver()
    assert [d.passes_of(m, [2, 2]) for m in (1, 2, 3, 4)] == [1, 1, 2, 2]
    assert [d.passes_of(m, [1, 1, 1, 1]) for m in (1, 4)] == [1, 4]
    req = types.SimpleNamespace
    rs = [req(prompt=[0] * 9), req(prompt=[0] * 8), req(prompt=[0] * 6)]
    hs = [req(out_tokens=[0] * 7, block=None, block_pass=0),      # 3 + 4
          req(out_tokens=[], block=[1, 2, 3, 4], block_pass=1),   # open
          req(out_tokens=[0] * 6, block=None, block_pass=0)]      # 2 + 4
    # 9: (2 passes + 1) + (2 + 1); 8: one pass so far; 6: (1 + 1) + (2 + 1)
    assert d.expected_row_passes(rs, hs, 4, [2, 2]) == 6 + 1 + 5
    m = 99
    log = [(8, (5, m, m, m), (2, 3), (6, 7), (.1, .3, .2)),
           (8, (5, m, 6, 7), (1,), (8,), (.4,)),
           (8, (5, 8, 6, 7), (), (), ()),
           (12, (m, m, m, m), (0, 1), (1, 2), (.5, .5, .1, .2)),
           (12, (1, 2, m, m), (2, 3), (3, 4), (.1, .2)),
           (12, (1, 2, 3, 4), (), (), ())]
    spec = {"block": 4, "mask_id": m}
    rule = {"steps": 2, "rule": "low_confidence_static"}
    assert d.rule_holds(log, spec, rule)
    wrong = list(log)
    wrong[0] = (8, (5, m, m, m), (1, 2), (6, 7), (.1, .3, .2))
    wrong[1] = (8, (5, 6, 7, m), (3,), (8,), (.4,))
    assert not d.rule_holds(wrong, spec, rule)
    assert not d.rule_holds(log, spec, {"steps": 4,
                                        "rule": "low_confidence_static"})
    assert d.sample_passes(log, m, 64) == [e for e in log if m in e[1]]
    few = d.sample_passes(log * 10, m, 8)
    assert len(few) == 8 and all(m in e[1] for e in few)
    assert {tuple(t == m for t in e[1]) for e in few} == \
        {tuple(t == m for t in e[1]) for e in log if m in e[1]}


def test_work_function_against_hand_counts(config):
    # 6 layers; a cached token is K and V of 4 heads x 128 in bf16 = 2,048
    # B; a page 64 tokens; q in and output out of 32 heads x 128 in bf16
    fl, by = work_sdar.gqa_block_attn_work(
        config, {"kv_pages_distinct": 30, "attn_pairs": 1000, "tokens": 10})
    assert fl == 6 * 1000 * 32 * 4 * 128
    assert by == 6 * (30 * 64 * 2048 + 10 * 32 * 128 * 4)
    assert work_sdar.gqa_block_attn_work(config, {}) == (0.0, 0.0)
    # the routed experts' function takes its sizes from the configuration
    fl, by = work_mla.moe_gated_routed_work(
        config, {"moe_local": 100, "moe_experts_hit": 7})
    assert fl == 100 * 6 * 2048 * 768 and by == 7 * 3 * 2048 * 768 * 2


def test_a_share_from_the_work_functions_cannot_pass_100_on_a_synthetic_step(
        config):
    """A step that could not be faster: every page under the rows' contexts
    read once a layer at the chip's full bandwidth; the reader then gives
    100 %, and any real step reads lower."""
    peaks = work.peaks_for("TPU v5 lite")
    attrs = {"tokens": 512, "rows": 65, "kv_pages_distinct": 700,
             "attn_pairs": 64 * 4 * 600 + 256 * 500, "moe_local": 4096,
             "moe_experts_hit": 768}
    span = types.SimpleNamespace
    spans = [span(name="unified_step", ts=11.0 + i, attrs=attrs)
             for i in range(3)]
    events, table, args_of = [], {}, {}
    for metric, module in (("gqa_block_attn_roofline", work_sdar),
                           ("moe_gated_roofline", work_mla)):
        args = args_of[metric] = _json(
            HERE, "layer_metrics", metric + ".chat.json")["args"]
        assert args["work_module"] == module.__name__
        least = sum(work.roofline_seconds(
            *module.WORK_FNS[args["work_fn"]](config, attrs), peaks)[0]
            for _ in spans)
        if "match" in args:
            events.append((0, int(round(least * 1e9)),
                           args["match"] + "_block", 0))
        else:
            table[args["phases"][0]] = int(round(least * 1e9))
    facts = {"trace": {"events": events}, "_time_by_phase": table,
             "values": {"trace_host_window": (10.0, 20.0)},
             "device_kind": "TPU v5 lite", "config": config,
             "host_spans": spans + [span(name="unified_step", ts=25.0,
                                         attrs=attrs)]}     # outside
    rd = _reader("span_work_share")
    import readers.trace_phase_time as tpt
    real = tpt.read
    tpt.read = lambda a, f: 1.0
    try:
        for metric, args in args_of.items():
            assert rd.read(args, facts) == pytest.approx(100.0, rel=1e-6), \
                metric
        bare = dict(facts, host_spans=[span(name="unified_step", ts=11.0,
                                            attrs={"rows": 3})])
        for args in args_of.values():       # a program without the attrs
            assert rd.read(args, bare) is None
            assert rd.read(args, {"trace": None}) is None
    finally:
        tpt.read = real
    ctr = _reader("engine_counter")
    window = {"block_tokens_unmasked": 400.0, "block_row_passes": 300.0,
              "block_commit_passes": 100.0, "block_positions": 1200.0,
              "block_positions_masked": 600.0,
              "kv_tokens_provisional": 800.0, "kv_tokens_written": 1600.0}
    for name, want in (("block_tokens_per_pass", 400 / 300),
                       ("block_commit_pass_share", 100 / 3),
                       ("block_masked_share", 50.0),
                       ("kv_provisional_share", 50.0)):
        spec = _json(HERE, "layer_metrics", name + ".chat.json")
        assert ctr.read(spec["args"], {"counters": window}) == \
            pytest.approx(want)
        # the parent's program has no such counter: nothing, not an error
        assert ctr.read(spec["args"], {"counters": {}}) is None


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contract_line_without_values(bench, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 99), "--seconds", "4", "--trace", trace,
         "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT)
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
                "block_tokens_per_pass.chat", "block_commit_pass_share.chat",
                "block_masked_share.chat", "kv_provisional_share.chat",
                "moe_local_assign_share.chat", "moe_block_fill.chat",
                "kv_page_heads_per_block.chat"} <= set(line["metrics"])
    notes = json.loads(next(l for l in p.stdout.splitlines()
                            if l.startswith("bench: notes "))[13:])
    assert notes["compiled_in_window"] == 0 and notes["control"] is None
    assert notes["block_row_passes"] == \
        notes["expected_block_row_passes"] > 0
    assert notes["block_commit_passes"] == notes["blocks_committed"] > 0
    assert notes["block_row_passes_ok"] and notes["unmask_sets_ok"] and \
        notes["emitted_ok"] and notes["enough_passes"]
    assert notes["checked_positions"] > 0 < notes["checked_confidences"]
    # float32 against float32: nothing beyond
    assert notes["beyond_share"] == 0.0 == notes["conf_beyond_share"]
    # every expert is held: every assignment is local
    assert notes["moe_assignments_local"] == notes["moe_assignments_total"]


@pytest.mark.parametrize("control", ["float8", "skipped_pass"])
def test_a_planted_fault_is_reported(control):
    """``--set control=...``: a run that drops a denoise pass comes out
    ``correct: false`` by the recount and by the rule; the float8 reading
    takes the served choices' place (at the rehearsal's widths it may stay
    inside the limits: the chip run is its judge)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 98), "--seconds", "3", "--trace", "0",
         "--rehearse", "--set", f'control="{control}"'],
        capture_output=True, text=True, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    notes = json.loads(next(l for l in p.stdout.splitlines()
                            if l.startswith("bench: notes "))[13:])
    assert notes["control"] == control and notes["compiled_in_window"] == 0
    if control == "float8":
        assert "CONTROL float8" in p.stdout
        assert "what was served:" in p.stdout   # ... and was not judged
        assert notes["worst_conf_log_gap"] > 1e-4
    else:
        assert line["correct"] is False
        assert not notes["block_row_passes_ok"]
        assert not notes["unmask_sets_ok"]
        assert notes["block_row_passes"] < notes["expected_block_row_passes"]
