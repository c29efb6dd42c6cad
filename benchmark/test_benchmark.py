"""The harness's own checks, CPU, tiny sizes, seconds.

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_benchmark.py -q

They live beside the benchmark because a benchmark PR may add files only
under the benchmark's own directories (``BENCHMARK.json`` ``paths``); a
later PR moves or mirrors them under ``tests/`` so tier-1 counts them
(PERF.md, Open questions).  No libtpu call at import time.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import stats  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
import xplane  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(*args, env=None):
    e = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run([sys.executable, os.path.join(HERE, "run.py"),
                           *args], capture_output=True, text=True, env=e,
                          cwd=ROOT)


def test_schema_names_units_and_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 4)
    for c in configs.values():
        assert c["file"].startswith(tuple(bench["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["config"] in configs and w["chips"] in (1, 4)
        mix = traffic.load(w["traffic"])
        assert os.path.exists(os.path.join(HERE, "drivers",
                                           mix["driver"] + ".py"))


def test_every_cell_reports_what_its_metrics_move(bench):
    def cells_of(m):
        return set(m.get("workloads", [w["name"] for w in bench["workloads"]]))
    e2e = {m["name"]: cells_of(m) for m in bench["end_to_end"]}
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert cells_of(m) <= e2e[m["moves"]], m["name"]
        spec_path = os.path.join(HERE, "layer_metrics", m["name"] + ".json")
        with open(spec_path) as f:
            spec = json.load(f)
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"]
        assert os.path.exists(os.path.join(HERE, "readers",
                                           spec["reader"] + ".py"))
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in bench["workloads"]:
        mine = [n for n, c in e2e.items() if w["name"] in c]
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        assert any(w["name"] in cells_of(m) for m in bench["per_layer"])


@pytest.mark.parametrize("mix_name", ["chat", "prefix-replay"])
def test_traffic_repeats_per_seed_and_keeps_its_sizes(mix_name):
    mix = traffic.load(mix_name)
    big = 2 ** 31 + 12345                       # past 32 signed bits
    a, _ = traffic.serve_requests(mix, big, 20, 50257)
    b, _ = traffic.serve_requests(mix, big, 20, 50257)
    c, _ = traffic.serve_requests(mix, 7, 20, 50257)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    sched = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens,
                         r.document) for r in rs]
    assert sched(a) == sched(c)         # one schedule, other token ids
    assert all(len(r.prompt) + r.max_new_tokens <= mix["max_total"]
               for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert a[-1].due_s <= 20


def test_token_stream_is_seeded():
    import run
    mix = run.merge_tiny(traffic.load("pretrain-32k"))
    x = traffic.token_stream(mix, 2 ** 31 + 5, 512)
    assert (x == traffic.token_stream(mix, 2 ** 31 + 5, 512)).all()
    assert (x != traffic.token_stream(mix, 6, 512)).any()
    assert x.min() >= 0 and x.max() < 512


def test_trace_reduction_on_a_hand_made_trace():
    # one chip, ns: a while [0,100) holding a fusion [10,40) and a kernel
    # [50,90); an all-gather [150,200) half hidden by a fusion [180,220)
    ev = [(0, 100, "while.1", ""), (10, 30, "fusion.1", ""),
          (50, 40, "jvp_flash_fwd_.3", ""), (150, 50, "all-gather.2", ""),
          (180, 40, "fusion.2", "")]
    assert xplane.busy_ns(ev) == 100 + 70
    assert xplane.idle_gaps(ev, 0, 300) == [(100, 150), (220, 300)]
    assert xplane.op_time_ns(ev, "flash_fwd") == 40
    assert xplane.exposed_collective_ns(ev) == 30
    st = xplane.self_times(ev)
    assert st["while"] == pytest.approx(30e-9)      # 100 - 30 - 40
    assert st["fusion"] == pytest.approx(70e-9)
    assert st["all-gather"] == pytest.approx(30e-9)
    gaps = xplane.label_gaps([(100, 150), (220, 300)],
                             [(90, 100, "g.run"), (120, 10, "fetch")])
    assert gaps == {"fetch": pytest.approx(10e-9),
                    "g.run": pytest.approx(40e-9),
                    "unlabelled": pytest.approx(80e-9)}
    s = xplane.summarize({"devices": {0: ev}, "host": [(0, 300, "window")]}, 1)
    assert s["window_s"] == pytest.approx(300e-9)
    assert s["busy_s"] == pytest.approx(170e-9)


def test_trace_reduction_on_the_recorded_trace():
    """The head of a real ``cgpt590m.train`` step on a v5e (PR 24, chip
    call 1): 1500 operations of chip 0 and the host spans over them."""
    tr = xplane.load_fixture(os.path.join(HERE, "fixtures",
                                          "train_step_head.json.gz"))
    ev = tr["devices"][0]
    assert len(ev) == 1500 and ev == sorted(ev)
    calls = xplane.op_calls(ev, "flash_fwd")
    assert len(calls) == 16
    assert xplane.op_time_ns(ev, "flash_fwd|flash_bwd") == 36500344
    assert xplane.busy_ns(ev) == 1062620670        # the while spans the step
    assert {h[2] for h in tr["host"]} >= {"window", "g.run", "next_loader"}
    t0, t1 = xplane.window_of(tr)
    assert t0 == 45747554 and t1 - t0 == 6531383362


def test_work_functions_and_peaks():
    with open(os.path.join(HERE, "configs", "cgpt590m.json")) as f:
        c = json.load(f)
    h, L, ffn, v = 1536, 18, 6144, 50257
    params = L * (4 * h * h + 2 * h * ffn) + h * v
    assert work.train_flops_per_token(c, 2048) == \
        6 * params + 6 * L * 2048 * h
    assert work.flash_flops(c, 4, 2048, False) == 2 * 4 * 2048 ** 2 * h
    assert work.flash_flops(c, 4, 2048, True) == 4 * 4 * 2048 ** 2 * h
    assert work.ragged_attention_bytes(c, 1000, 10) == (2000 + 20) * h * 2
    assert work.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9")
    assert work.roofline_seconds(197e12, 1.0, work.PEAKS["TPU v5 lite"]) == \
        (1.0, "compute")


def test_percentiles():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 90) == 90
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_share([9, 10, 11, 9, 10, 11]) == pytest.approx(0.2)


def test_reference_agrees_with_the_model_at_toy_size():
    import jax.numpy as jnp
    import hetu_tpu as ht
    import reference
    from drivers_util import gpt_config
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.models.generate import _Params
    from serve_common import make_weights
    with open(os.path.join(HERE, "configs", "cgpt590m.json")) as f:
        c = json.load(f)
    c = {**c, **c["tiny"], "dtype": "float32"}
    cfg = gpt_config(c)
    state = make_weights(c, 2 ** 31 + 3)
    # the benchmark's weights carry the program's own names and shapes
    with ht.graph("eager", create_new=True):
        model = GPTLMHeadModel(cfg)
        own = {_Params._norm(k): v.shape
               for k, v in model.state_dict().items()}
        assert own == {k: tuple(v.shape) for k, v in state.items()}
        model.load_state_dict({f"transformer.{k}": np.asarray(v)
                               for k, v in own_names(state).items()})
        ids = np.random.RandomState(0).randint(0, 512, (1, 48))
        labels = np.roll(ids, -1, 1)
        sys_logits = np.asarray(model.logits(jnp.asarray(ids)).numpy())[0]
        sys_loss = float(np.asarray(model(jnp.asarray(ids),
                                          jnp.asarray(labels)).numpy()))
    ref_logits = np.asarray(reference.logits(state, ids[0], c["n_layer"],
                                             c["n_head"]))
    np.testing.assert_allclose(sys_logits, ref_logits, atol=2e-4, rtol=2e-4)
    ref_loss = reference.loss(state, ids[0], labels[0], c["n_layer"],
                              c["n_head"])
    assert abs(sys_loss - ref_loss) < 1e-4
    # a right token has gap 0, a wrong one is far beyond the tolerance
    best = int(ref_logits[20].argmax())
    seq = list(ids[0][:21]) + [best]
    assert reference.greedy_logit_gaps(state, seq, 21, c["n_layer"],
                                       c["n_head"], 128, 8)[0] == 0.0
    seq[-1] = int(ref_logits[20].argmin())
    assert reference.greedy_logit_gaps(state, seq, 21, c["n_layer"],
                                       c["n_head"], 128, 8)[0] > \
        reference.LOGIT_GAP_TOL


def own_names(state):
    """``h0.attn.qkv.weight`` -> ``h.0.attn.qkv.weight`` (module paths)."""
    return {re.sub(r"^h(\d+)\.", r"h.\1.", k): v for k, v in state.items()}


def test_run_refuses_to_measure_without_a_tpu(bench):
    cell = bench["workloads"][0]["name"]
    p = _run("--workload", cell, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0 and "needs" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contract_line_without_values(bench, trace):
    cell = bench["workloads"][0]
    p = _run("--workload", cell["name"], "--seed", str(2 ** 31 + 99),
             "--seconds", "2", "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(m["value"] is None for m in line["metrics"].values())
    known = {m["name"] for m in bench["end_to_end" if trace == "0"
                                      else "per_layer"]}
    assert set(line["metrics"]) <= known
