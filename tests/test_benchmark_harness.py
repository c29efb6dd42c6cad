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


def _ops(start, *ops):
    """Device operations (start_ns, dur_ns, name, text) from (offset,
    dur) pairs after ``start``."""
    return [(start + o, d, "fusion.%d" % i, "") for i, (o, d) in
            enumerate(ops)]


def test_step_edges_on_a_hand_written_event_list():
    """Two executions with known launch, gaps and tail: the three
    numbers tile the idle between ``step.dispatch`` start and
    ``step.fetch`` end, and are the same whether the compiled call
    returns before or after the execution's first operation."""
    rd = _reader("trace_step_edges")

    def edges(dispatch_len_a, dispatch_len_b):
        spans = []
        for base, dl in ((1000, dispatch_len_a), (11000, dispatch_len_b)):
            spans += [(base, base + 300, "step.h2d"),
                      (base + 300, base + 300 + dl, "step.dispatch"),
                      (base + 300 + dl, base + 5000, "step.fetch")]
        return {"spans": sorted(spans), "steps": [(900, 5500, 7),
                                                  (10900, 15500, 8)],
                "extents": [(1500, 4200), (11700, 14000)],
                "enqueued": [], "seen_done": []}

    # execution A: first operation 200 after dispatch starts, operations
    # [1500, 2000) [2100, 3000) [3000, 4200): one gap of 100, tail 1800;
    # execution B: launch 400, gaps 50 + 150 (the last before the
    # extent's end), tail 2000
    events = _ops(1500, (0, 500), (600, 900), (1500, 1200)) + \
        _ops(11700, (0, 1000), (1050, 500), (200, 300), (1550, 600))
    events.sort()
    want = {"launch": (200 + 400) / 2, "dev_gap": (100 + 50 + 150) / 2,
            "fetch_tail": (1800 + 2000) / 2}
    for lens in ((100, 100), (700, 900), (100, 900)):
        got = rd.step_edges(edges(*lens), events, 0, 20000)
        assert got["steps"] == 2 and got["bracket_ns"] is None \
            and got["offset_ns"] == 0
        assert {k: got[k] for k in want} == want
        assert got["raw"] == (want["launch"], want["dev_gap"],
                              want["fetch_tail"])
        # they tile the idle of [dispatch start, fetch end)
        idle = sum(xplane.total(xplane.idle_gaps(events, a, b))
                   for a, b in ((1300, 6000), (11300, 16000)))
        assert sum(want.values()) * 2 == idle
        assert got["worst"] == (2000, 8)
    # a step cut by the window's edge is left out; none left: None
    assert rd.step_edges(edges(100, 100), events, 0, 15000)["steps"] == 1
    assert rd.step_edges(edges(100, 100), events, 7000, 9000) is None
    assert rd.step_edges({**edges(100, 100), "extents": []}, events, 0,
                         20000) is None
    # the runtime's events bracket the clocks' offset: the device's
    # times go to the middle, launch grows by it and the tail shrinks
    e = edges(100, 100)
    e["enqueued"] = [(1420, 1460), (11500, 11560)]
    e["seen_done"] = [(4380, 4400), (14300, 14310)]
    got = rd.step_edges(e, events, 0, 20000)
    assert got["bracket_ns"] == (-40, 180) and got["offset_ns"] == 70
    assert (got["launch"], got["dev_gap"], got["fetch_tail"]) == \
        (want["launch"] + 70, want["dev_gap"], want["fetch_tail"] - 70)
    assert got["raw"] == (want["launch"], want["dev_gap"],
                          want["fetch_tail"])
    # read() hands out the cached table and says nothing without a trace
    facts = {"trace": {}, "_step_edges": {"launch": 1.5}}
    assert rd.read({"part": "launch"}, facts) == 1.5
    assert rd.read({"part": "launch"}, {"trace": None}) is None
    assert rd.read({"part": "launch"}, {"trace": {},
                                        "_step_edges": None}) is None


def test_step_edges_on_the_recorded_fixture():
    """Three steps of ``nemotron3s-ep4.serve-chat`` recorded on the chip
    (PR 37, Step 0): the device plane's clock ran ~1.6 ms early there,
    so on the trace's own clock the executions start before their
    ``step.dispatch``; the runtime's events put them back."""
    rd = _reader("trace_step_edges")
    edges, events = rd.load_fixture(os.path.join(
        BENCH, "fixtures", "step_edges_hybrid.json.gz"))
    assert len(edges["extents"]) == 3 and len(rd.host_steps(
        edges["spans"])) == 3
    assert len(events) > 6000 and events == sorted(events)
    got = rd.step_edges(edges, events, 0, max(e for _, e, _ in
                                              edges["spans"]))
    assert got["steps"] == 3
    lo, hi = got["bracket_ns"]
    assert 1.4e6 < lo < got["offset_ns"] < hi < 1.8e6 and hi - lo < 0.4e6
    assert got["raw"][0] == 0.0                  # "started" under h2d
    assert 0.4e6 < got["launch"] < 1.0e6
    assert 3e3 < got["dev_gap"] < 2e4
    assert 0.9e6 < got["fetch_tail"] < 1.6e6
    # moving the clock moves time between the two ends, not in or out
    assert got["launch"] + got["fetch_tail"] == pytest.approx(
        got["raw"][2] - (got["offset_ns"] - got["launch"]), rel=1e-6)
    # the parent's engine_step carried no step index
    assert edges["steps"] == [] and got["worst"][1] is None


def test_host_span_sum_reader():
    from hetu_tpu.obs import SpanTracer
    rd = _reader("host_span_sum")
    tr = SpanTracer()
    for ts, dur in ((0.5, 0.25), (1.0, 0.002), (1.5, 0.004), (2.5, 0.5)):
        tr.complete("gc", ts, dur, track="runtime", generation=0)
    tr.complete("account", 1.2, 0.1)
    facts = {"host_spans": tr.events(),
             "values": {"steps": 4, "host_window": (1.0, 2.0)}}
    assert rd.read({"name": "gc"}, facts) == pytest.approx(6.0 / 4)
    assert rd.read({"name": "gc"}, {**facts, "values": {
        "steps": 4, "host_window": (3.0, 4.0)}}) == 0.0
    assert rd.read({"name": "pause"}, facts) is None
    assert rd.read({"name": "gc"}, {**facts, "host_spans": []}) is None


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
    own = {"nemotron3s-ep4.serve-chat", "mistral4-ep4.serve-longdoc",
           "dots3-ep8.serve-longctx", "kexaone-ep8.serve-reason",
           "sdar30b-pp8.serve-chat", "jamba2-3b.serve-longdoc",
           "olmohybrid-pp2.serve-reason"}
    family = {"chat": {"cgpt590m.serve-chat", "nemotron3s-ep4.serve-chat",
                       "kexaone-ep8.serve-reason", "sdar30b-pp8.serve-chat",
                       "olmohybrid-pp2.serve-reason"},
              "replay": {"cgpt590m.serve-prefix",
                         "mistral4-ep4.serve-longdoc",
                         "dots3-ep8.serve-longctx",
                         "jamba2-3b.serve-longdoc"},
              "train": {"cgpt590m.train", "cgpt1.3b.train-dp4z3"}}[suffix]
    assert set(entry["workloads"]) <= family
    # (trace_step_edges: the dense cells' traced run, 287 s of the
    # driver's 360, has no room for a third parse of the trace until
    # ROADMAP B0(iv); it is listed on the two 2 s cells alone)
    if spec["reader"] not in ("trace_phase_sum", "span_work_roofline",
                              "span_work_share", "engine_counter_rest",
                              "trace_step_edges") \
            and not name.startswith(("moe_", "latent_", "index_",
                                     "window_", "ssm_", "mtp_",
                                     "kv_page_", "block_",
                                     "kv_provisional_")):
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
