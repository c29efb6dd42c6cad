"""The chip benchmark's own checks, in tier-1.

``benchmark/test_benchmark.py`` lives beside the harness (a benchmark PR
may add files only there); its cases are collected here by path so that
tier-1 counts them.  Below them: the checks of what ISSUE 25 added to
the benchmark — two trace readers on hand-made traces, and every
``layer_metrics`` file naming a reader and a cell that exist.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_selftests = _load(os.path.join(BENCH, "test_benchmark.py"),
                   "benchmark_selftests")       # puts benchmark/ on sys.path
bench = _selftests.bench                        # the module's fixture
globals().update({k: v for k, v in vars(_selftests).items()
                  if k.startswith("test_")})

import xplane  # noqa: E402  (benchmark/xplane.py)


def _reader(name: str):
    return _load(os.path.join(BENCH, "readers", f"{name}.py"),
                 f"bench_reader_{name}")


def _summary(events, host, t0, t1):
    """What ``xplane.summarize`` hands the readers, for chip 0."""
    return {"t0": t0, "t1": t1, "window_s": (t1 - t0) / 1e9,
            "busy_s": xplane.busy_ns(events) / 1e9, "events": events,
            "host": host}


def test_idle_by_phase_on_a_hand_made_trace():
    """gaps x program spans -> labels: the shortest covering span wins,
    what no span covers is unspanned, and the listed phases add up."""
    rd = _reader("trace_idle_by_phase")
    # ns; chip 0 runs [100,400) and [600,900) of the window [0,1000)
    ev = [(100, 300, "fusion.1", ""), (600, 300, "fusion.2", "")]
    # two engine steps, tiled by their phases; 950.. is outside any span
    spans = [(0, 450, "engine_step"), (0, 40, "step.admit"),
             (40, 30, "step.pack"), (70, 50, "step.dispatch"),
             (120, 290, "step.fetch"), (410, 40, "step.commit"),
             (500, 450, "engine_step"), (500, 120, "step.dispatch"),
             (620, 290, "step.fetch"), (910, 40, "step.commit")]
    got = rd.idle_by_span(ev, 0, 1000, spans)
    want = {"step.admit": 40, "step.pack": 30, "step.dispatch": 30 + 100,
            "step.fetch": 10 + 10, "step.commit": 40 + 40,
            "unlabelled": 50 + 50}
    assert {k: round(v * 1e9) for k, v in got.items()} == want
    assert sum(want.values()) == 1000 - xplane.busy_ns(ev)
    # through read(): per bench:engine.step call, ms
    facts = {"trace": _summary(ev, [(0, 1000, "window"),
                                    (0, 450, "engine.step"),
                                    (500, 450, "engine.step")], 0, 1000),
             "_idle_by_span": got}
    per = lambda phases: rd.read(                       # noqa: E731
        {"phases": phases, "per_span": "engine.step"}, facts)
    assert per(["step.admit", "step.pack"]) == pytest.approx(70e-6 / 2)
    assert per(["step.dispatch"]) == pytest.approx(130e-6 / 2)
    assert per([]) == pytest.approx(100e-6 / 2)
    assert per(["step.never"]) == 0.0
    # nothing to read: no trace (rehearsal), no program span (a parent
    # that writes none), no benchmark span to divide by
    assert rd.read({"phases": [], "per_span": "engine.step"},
                   {"trace": None}) is None
    assert rd.read({"phases": [], "per_span": "engine.step"},
                   {**facts, "_idle_by_span": {}}) is None
    assert rd.read({"phases": [], "per_span": "g.run"}, facts) is None


def test_idle_by_phase_reads_the_profilers_host_planes(tmp_path):
    """The ``hetu:`` annotations of a live profiler session come back
    from the .xplane.pb, and the newest trace under the root is found."""
    import jax
    from hetu_tpu import obs
    rd = _reader("trace_idle_by_phase")
    assert rd.newest_xplane(str(tmp_path)) is None
    tr = obs.SpanTracer()
    jax.profiler.start_trace(str(tmp_path / "cell"))
    try:
        with tr.span("engine_step", track="engine"):
            with tr.span("step.pack"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = rd.newest_xplane(str(tmp_path))
    assert path is not None and path.endswith(".xplane.pb")
    spans = rd.load_program_spans(path)
    assert [s[2] for s in spans] == ["engine_step", "step.pack"]
    (p0, pd, _), (c0, cd, _) = spans
    assert p0 <= c0 and c0 + cd <= p0 + pd


def test_phase_time_on_a_hand_made_trace():
    """events x map -> shares: self time (a while keeps what its body
    does not cover), names outside the map are unmapped."""
    rd = _reader("trace_phase_time")
    ev = [(0, 100, "while.1", ""), (10, 30, "fusion.1", ""),
          (50, 40, "jvp_flash_fwd_.3", ""), (150, 50, "fusion.2", ""),
          (200, 20, "copy.9", "")]
    phase_of = {"fusion.1": "mlp", "fusion.2": "mlp",
                "jvp_flash_fwd_.3": "attn_core", "while.1": "unmapped"}
    self_ns = rd.self_time_by_name(ev)
    assert self_ns == {"while.1": 30, "fusion.1": 30, "jvp_flash_fwd_.3": 40,
                       "fusion.2": 50, "copy.9": 20}
    got = rd.time_by_phase(self_ns, phase_of)
    assert got == {"mlp": 80, "attn_core": 40, "unmapped": 30 + 20}
    assert sum(got.values()) == xplane.busy_ns(ev) == 170
    facts = {"trace": _summary(ev, [], 0, 300), "_time_by_phase": got}
    share = lambda ph: rd.read(                          # noqa: E731
        {"phase": ph, "as": "share_of_busy"}, facts)
    assert share("mlp") == pytest.approx(100 * 80 / 170)
    assert share("unmapped") == pytest.approx(100 * 50 / 170)
    assert share("optimizer") == 0.0
    assert rd.read({"phase": "mlp", "as": "share_of_busy"},
                   {"trace": None}) is None
    # no executable named in the window (or a program without
    # obs.device_phases): nothing to read, nothing raised
    assert rd.read({"phase": "mlp", "as": "share_of_busy"},
                   {"trace": facts["trace"], "host_spans": [],
                    "values": {}}) is None


def _new_metric_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    first = next(i for i, m in enumerate(doc["per_layer"])
                 if m["name"] == "idle_sched_ms.chat")
    return doc, doc["per_layer"][first:]


@pytest.mark.parametrize("name", [m["name"] for m in _new_metric_files()[1]])
def test_new_layer_metric_names_a_reader_and_a_cell(name):
    doc, new = _new_metric_files()
    entry = next(m for m in new if m["name"] == name)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert set(spec) == {"layer", "unit", "moves", "what", "reader", "args"}
    assert (spec["layer"], spec["unit"], spec["moves"]) == \
        (entry["layer"], entry["unit"], entry["moves"])
    reader = _reader(spec["reader"])
    assert callable(reader.read)
    cells = {w["name"] for w in doc["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    moved = next(m for m in doc["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    suffix = name.rsplit(".", 1)[1]
    # a suffix names the cells of one traffic family; a metric lists
    # those of them its reader finds something to read in (a drawn
    # configuration's own metrics list its cell alone)
    own = {"nemotron3s-ep4.serve-chat", "mistral4-ep4.serve-longdoc"}
    family = {"chat": {"cgpt590m.serve-chat", "nemotron3s-ep4.serve-chat"},
              "replay": {"cgpt590m.serve-prefix",
                         "mistral4-ep4.serve-longdoc"},
              "train": {"cgpt590m.train", "cgpt1.3b.train-dp4z3"}}[suffix]
    assert set(entry["workloads"]) <= family
    if spec["reader"] not in ("trace_phase_sum", "span_work_roofline",
                              "span_work_share", "engine_counter_rest") \
            and not name.startswith(("moe_", "latent_")):
        assert family - own <= set(entry["workloads"])
    if spec["reader"] == "trace_idle_by_phase":
        assert spec["args"]["per_span"] in ("engine.step", "g.run")
        assert isinstance(spec["args"]["phases"], list)
    if spec["reader"] == "trace_phase_time":
        from hetu_tpu.obs.phases import PHASES, UNMAPPED
        assert spec["args"]["phase"] in PHASES + (UNMAPPED,)


def test_idle_metrics_of_a_cell_cover_every_program_span():
    """The idle_* metrics of one suffix partition the idle: every span
    name the program emits around a step is in exactly one list, and
    one metric takes what no span covers."""
    _, new = _new_metric_files()
    specs = {}
    for m in new:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "trace_idle_by_phase":
            specs[m["name"]] = spec["args"]["phases"]
    serve = ["engine_step", "step.admit", "step.pages", "step.pack",
             "step.tap", "step.h2d", "step.dispatch", "step.fetch",
             "step.commit"]
    train = ["train_step", "plan", "feed", "assemble", "executable",
             "commit"]
    for suffix, emitted in ((".chat", serve), (".replay", serve),
                            (".train", train)):
        lists = [v for k, v in specs.items() if k.endswith(suffix)]
        flat = [n for v in lists for n in v]
        assert sorted(flat) == sorted(emitted), suffix
        assert sum(1 for v in lists if v == []) == 1, suffix
