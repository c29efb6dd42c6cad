"""Runtime trace plane (ISSUE 9): tracer semantics, Perfetto export
schema, Prometheus exposition, percentile interpolation, and the
lint_graph-marked per-request timeline gate.

The timeline gate is the serving contract the trace plane exists to
check: on an ADVERSARIAL trace (late arrivals + recompute preemption +
prefix-cache eviction under a starved page pool, synthetic clock) every
admitted request's ``queued``/``running`` state spans tile
``[submit, finish]`` gaplessly and every event timeline is monotonic —
a scheduling bug that loses a request mid-flight, or an instrumentation
bug that misses a transition, breaks the tiling.
"""
import json

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import obs
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.obs import (NULL_TRACER, SpanTracer, chrome_trace,
                          events_to_jsonl, get_tracer, install_tracer,
                          reconcile, request_timelines, timeline_summary,
                          trace, validate_chrome_trace, write_jsonl)
from hetu_tpu.serving import Engine
from hetu_tpu.utils.metrics import (Counter, Gauge, Histogram,
                                    load_jsonl, make_instrument,
                                    render_prometheus)

CFG_KW = dict(vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64, sp=False, dropout=0.0)


@pytest.fixture(scope="module")
def tiny_state():
    cfg = GPTConfig(**CFG_KW)
    ht.set_seed(7)
    with ht.graph("eager", create_new=True):
        model = GPTLMHeadModel(cfg)
        model.logits(np.zeros((1, 4), np.int32))
        state = {k: np.asarray(v) for k, v in model.state_dict().items()}
    return state, cfg


def _traced_engine(state, cfg, **kw):
    clock = [0.0]
    tracer = SpanTracer(time_fn=lambda: clock[0])
    kw.setdefault("time_fn", lambda: clock[0])
    eng = Engine(state, cfg, tracer=tracer, debug=True, **kw)
    return eng, tracer, clock


def _drain(eng, clock, tick=1.0, max_steps=500):
    steps = 0
    while eng.has_work and steps < max_steps:
        eng.step()
        clock[0] += tick
        steps += 1
    assert not eng.has_work, "engine failed to drain the trace"


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------


def test_span_nesting_and_track_inheritance():
    tr = SpanTracer()
    with tr.span("outer", track="work", a=1) as outer:
        with tr.span("inner") as inner:
            assert inner.parent == "outer"
            assert inner.track == "work"       # inherited
        tr.instant("mark")                     # inherits track too
    assert outer.parent is None
    evs = tr.events()
    assert [e.name for e in evs] == ["inner", "mark", "outer"]
    assert all(e.track == "work" for e in evs)
    assert tr.open_count() == 0
    inner_ev = evs[0]
    outer_ev = evs[-1]
    assert outer_ev.ts <= inner_ev.ts
    assert inner_ev.end_ts <= outer_ev.end_ts + 1e-9


def test_ring_buffer_caps_and_counts_drops():
    tr = SpanTracer(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 8
    assert tr.dropped == 12
    # oldest dropped, newest kept
    assert [e.name for e in evs] == [f"e{i}" for i in range(12, 20)]
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_disabled_tracing_is_noop():
    # the shared null tracer records nothing and returns the shared
    # no-op span (no allocation per call)
    sp = NULL_TRACER.span("x", attr=1)
    with sp:
        NULL_TRACER.instant("y")
    assert sp is NULL_TRACER.begin("z")
    assert NULL_TRACER.events() == []
    # a real tracer switched off in place behaves the same without
    # losing its buffer
    tr = SpanTracer()
    tr.instant("kept")
    tr.enabled = False
    with tr.span("dropped"):
        tr.instant("dropped-too")
    tr.complete("dropped-three", 0.0, 1.0)
    assert [e.name for e in tr.events()] == ["kept"]


def test_out_of_order_end_tolerated():
    tr = SpanTracer()
    a = tr.begin("a")
    b = tr.begin("b")
    tr.end(a)          # ends b's scope implicitly, never raises
    assert tr.open_count() == 0
    assert [e.name for e in tr.events()] == ["a"]
    tr.end(b)          # already discarded: recorded as closed event
    assert len(tr.events()) == 2


def test_retroactive_complete_and_explicit_ts():
    tr = SpanTracer(time_fn=lambda: 100.0)
    tr.complete("past", ts=3.0, dur=2.0, track="t", k=1)
    tr.instant("then", ts=5.0, track="t")
    (c, i) = tr.events()
    assert (c.ts, c.dur, c.end_ts) == (3.0, 2.0, 5.0)
    assert i.ts == 5.0 and i.ph == "i"


def test_end_is_idempotent():
    tr = SpanTracer()
    sp = tr.begin("a")
    tr.end(sp)
    tr.end(sp)                 # finally-style re-end: no double commit
    assert len(tr.events()) == 1


def test_traced_run_failure_closes_spans(tiny_state):
    """A raising step must not leave the step span open on the thread
    stack (a retried training loop would otherwise nest every later
    span under the dead step)."""
    _, cfg = tiny_state
    ht.set_seed(0)
    with trace() as tr:
        with ht.graph("define_and_run", create_new=True,
                      prefix="obs_fail") as g:
            from hetu_tpu import optim
            ids = ht.placeholder("int32", (2, 8), name="ids")
            lbl = ht.placeholder("int32", (2, 8), name="lbl")
            model = GPTLMHeadModel(cfg)
            loss = model(ids, lbl)
            train_op = optim.AdamOptimizer(lr=1e-3).minimize(loss)
            data = np.zeros((2, 8), np.int32)
            with pytest.raises(AssertionError):
                # 3 micro-batches don't divide batch 2: raises inside
                # the traced feed phase
                g.run(loss, [loss, train_op], {ids: data, lbl: data},
                      num_micro_batches=3)
            assert tr.open_count() == 0
            g.run(loss, [loss, train_op], {ids: data, lbl: data})
            assert tr.open_count() == 0
    steps = [e for e in tr.events() if e.name in ("train_step",)]
    assert len(steps) == 2                   # failed + succeeded
    # the successful step's children nest under train_step, not under
    # a stale span leaked by the failed one
    ok_exec = [e for e in tr.events() if e.name == "executable"]
    assert len(ok_exec) == 1 and ok_exec[0].parent == "train_step"


def test_clear_executables_evicts_prediction_cache(tiny_state):
    """Retiring an engine (unregister_analysis / same-name rebuild)
    must drop its prediction-cache entry too — the cached handle's meta
    closes over the KV pool and would pin it forever."""
    from hetu_tpu.obs.reconcile import _PRED_CACHE, predicted_stats
    state, cfg = tiny_state
    eng = Engine(state, cfg, num_pages=16, page_size=8, max_batch=2,
                 name="obs_evict")
    assert predicted_stats("obs_evict/unified")["peak_hbm_bytes"] > 0
    assert "obs_evict/unified" in _PRED_CACHE
    eng.unregister_analysis()
    assert "obs_evict/unified" not in _PRED_CACHE


def test_trace_context_installs_and_restores():
    assert get_tracer() is NULL_TRACER
    with trace() as tr:
        assert get_tracer() is tr
        prev = install_tracer(None)
        assert prev is tr and get_tracer() is NULL_TRACER
        install_tracer(tr)
    assert get_tracer() is NULL_TRACER


# ---------------------------------------------------------------------------
# histogram percentile interpolation (satellite)
# ---------------------------------------------------------------------------


def test_percentile_linear_interpolation_pinned():
    h = Histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    # rank = p/100 * (n-1); linear between floor/ceil ranks
    assert h.percentile(0) == 1.0
    assert h.percentile(100) == 4.0
    assert h.percentile(50) == pytest.approx(2.5)
    assert h.percentile(90) == pytest.approx(3.7)
    assert h.percentile(99) == pytest.approx(3.97)
    # the old int(round(...)) nearest-index would give 3.0 / 4.0 / 4.0
    h2 = Histogram("one")
    h2.observe(5.0)
    assert h2.percentile(90) == 5.0
    assert Histogram("empty").percentile(90) == 0.0


def test_percentile_matches_numpy_linear():
    rng = np.random.RandomState(0)
    xs = rng.rand(37)
    h = Histogram("r")
    for v in xs:
        h.observe(float(v))
    for p in (10, 50, 90, 99):
        assert h.percentile(p) == pytest.approx(
            float(np.percentile(xs, p)), rel=1e-12)


# ---------------------------------------------------------------------------
# Prometheus text exposition (satellite)
# ---------------------------------------------------------------------------


def test_render_prometheus_round_trip():
    c = Counter("tokens_generated")
    c.inc(42)
    g = Gauge("page_utilization")
    g.set(0.625)
    h = Histogram("ttft", buckets=[0.1, 1.0])
    for v in (0.05, 0.5, 2.0, 3.0):
        h.observe(v)
    text = render_prometheus({"tokens_generated": c,
                              "page_utilization": g, "ttft": h})
    lines = [ln for ln in text.splitlines() if ln]
    assert "# TYPE tokens_generated counter" in lines
    assert "tokens_generated 42" in lines
    assert "page_utilization 0.625" in lines
    # histogram triple: cumulative buckets match bucket_counts exactly
    want = h.bucket_counts()
    got = {}
    for ln in lines:
        if ln.startswith("ttft_bucket"):
            le = ln.split('le="')[1].split('"')[0]
            got[le] = int(ln.split()[-1])
    assert got == {"0.1": 1, "1.0": 2, "+Inf": 4}
    assert got["+Inf"] == want["+Inf"] == h.count
    assert f"ttft_count {h.count}" in lines
    assert any(ln.startswith("ttft_sum") for ln in lines)
    # the no-op instrument exposes nothing (not fake zeros)
    assert render_prometheus(
        {"off": make_instrument("counter", "off", enabled=False)}) == ""


def test_engine_metrics_text(tiny_state):
    state, cfg = tiny_state
    eng = Engine(state, cfg, num_pages=16, page_size=8, max_batch=4)
    eng.add_request([5, 9, 2], 3, arrival_time=0.0)
    eng.run()
    text = eng.metrics_text()
    assert "# TYPE tokens_generated counter" in text
    assert "tokens_generated 3" in text
    assert 'ttft_bucket{le="+Inf"} 1' in text
    assert "ttft_count 1" in text
    assert "# TYPE page_utilization gauge" in text


# ---------------------------------------------------------------------------
# chrome trace schema from a real serving run
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_from_serving_run(tiny_state):
    state, cfg = tiny_state
    eng, tracer, clock = _traced_engine(state, cfg, num_pages=16,
                                        page_size=8, max_batch=4)
    rng = np.random.RandomState(1)
    for i in range(3):
        eng.add_request(rng.randint(1, 61, size=5).tolist(), 4,
                        arrival_time=float(i))
    _drain(eng, clock)
    events = tracer.events()
    assert tracer.open_count() == 0          # all spans properly closed
    doc = chrome_trace(events)
    validate_chrome_trace(doc)               # pid/tid/ts/ph on EVERY event
    txt = json.dumps(doc)                    # must be pure-JSON clean
    doc2 = json.loads(txt)
    # per-request tracks present as named thread rows
    names = [ev["args"]["name"] for ev in doc2["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"]
    for i in range(3):
        assert f"req {i}" in names
    assert "engine" in names
    # every request has a complete lifecycle in the trace
    tls = request_timelines(events)
    for i in range(3):
        kinds = [e.name for e in tls[i]]
        assert kinds[0] == "enqueue" and kinds[-1] == "finish"
        assert "queued" in kinds and "running" in kinds \
            and "admit" in kinds and "prefill_chunk" in kinds
        assert sum(1 for k in kinds if k == "token") == 4
    # unified_step spans carry the reconciliation join key; the
    # predictions are looked up by it at report time, not emitted
    un = [e for e in events if e.name == "unified_step"]
    assert un and all(e.attrs["exec"] == "serving/unified" for e in un)
    assert not any(k.startswith("predicted_") for e in un for k in e.attrs)
    row = next(r for r in reconcile(events).rows
               if r.executable == "serving/unified")
    assert row.calls == len(un) and row.predicted_peak_hbm_bytes > 0
    assert timeline_summary(events)          # renders without error


def test_jsonl_journal_round_trips(tmp_path, tiny_state):
    state, cfg = tiny_state
    eng, tracer, clock = _traced_engine(state, cfg, num_pages=16,
                                        page_size=8, max_batch=2)
    eng.add_request([3, 1, 4], 2, arrival_time=0.0)
    _drain(eng, clock)
    path = str(tmp_path / "journal.jsonl")
    write_jsonl(tracer.events(), path)
    back = load_jsonl(path)                  # utils.metrics reader
    assert len(back) == len(tracer.events())
    assert [r["step"] for r in back] == list(range(len(back)))
    assert all({"name", "track", "ph", "ts", "attrs"} <= set(r)
               for r in back)
    assert events_to_jsonl(tracer.events())[0]["step"] == 0


def test_untraced_engine_stays_silent(tiny_state):
    state, cfg = tiny_state
    eng = Engine(state, cfg, num_pages=16, page_size=8, max_batch=2)
    assert eng.tracer is NULL_TRACER
    eng.add_request([2, 4], 2, arrival_time=0.0)
    eng.run()
    assert NULL_TRACER.events() == []


# ---------------------------------------------------------------------------
# the gapless-timeline CI gate (lint_graph)
# ---------------------------------------------------------------------------


@pytest.mark.lint_graph
def test_adversarial_trace_timelines_gapless(tiny_state):
    """Late arrivals + preemption + prefix-cache eviction under a
    starved pool: every admitted request's state spans must tile
    [submit, finish] with no gap and its event stream must be
    time-monotonic."""
    state, cfg = tiny_state
    eng, tracer, clock = _traced_engine(
        state, cfg, num_pages=10, page_size=4, max_batch=3,
        chunk_size=8, prefill_rows=1, prefix_cache=True)
    rng = np.random.RandomState(2)
    shared = rng.randint(1, 61, size=8).tolist()     # cacheable header
    arrivals = [0.0, 0.0, 2.0, 6.0, 9.0, 13.0]
    for i, at in enumerate(arrivals):
        prompt = shared[:4] + rng.randint(1, 61, size=4).tolist() \
            if i % 2 else shared
        eng.add_request(prompt, 8, arrival_time=at)
    _drain(eng, clock)
    # the trace must actually be adversarial, or the gate is vacuous
    m = eng.metrics_summary()
    assert m["preemptions"] >= 1, "pool never starved: gate is vacuous"
    assert m["prefix_cache_evictions"] >= 1, \
        "cache never evicted: gate is vacuous"
    assert len(eng.finished) == len(arrivals)
    timelines = request_timelines(tracer.events())
    for rid, req in eng.finished.items():
        evs = timelines[rid]
        # monotonic: events ordered by start, intervals inside the life
        ts = [e.ts for e in evs]
        assert ts == sorted(ts), f"req {rid}: non-monotonic timeline"
        assert evs[0].name == "enqueue" and evs[0].ts == req.submit_time
        assert evs[-1].name == "finish" \
            and evs[-1].ts == req.finish_time
        # gapless state tiling: queued/running segments chain exactly
        # from submit to finish (preemptions included)
        segs = [e for e in evs if e.ph == "X"
                and e.name in ("queued", "running")]
        assert segs[0].name == "queued" and segs[0].ts == req.submit_time
        for prev, nxt in zip(segs, segs[1:]):
            assert abs(nxt.ts - prev.end_ts) < 1e-9, \
                f"req {rid}: gap between {prev.name} and {nxt.name}"
            assert prev.name != nxt.name, \
                f"req {rid}: {prev.name} repeated without transition"
        assert segs[-1].name == "running" \
            and abs(segs[-1].end_ts - req.finish_time) < 1e-9
        # lifecycle counters agree with the trace
        assert sum(1 for e in evs if e.name == "preempt") \
            == req.n_preemptions
        assert sum(1 for e in evs if e.name == "token") \
            == req.n_generated
    # scheduler pack decisions stay inside the token budget
    packs = [e for e in tracer.events() if e.name == "engine_step"
             and e.attrs["rows"]]
    assert packs
    for p in packs:
        assert p.attrs["tokens"] <= p.attrs["token_budget"]
        assert p.attrs["decode_slots"] <= eng.scheduler.max_batch
    # cache eviction shows up on the engine track
    assert any(e.name == "prefix_cache_evict" for e in tracer.events())


# ---------------------------------------------------------------------------
# predicted-vs-observed reconciliation
# ---------------------------------------------------------------------------


def test_reconcile_joins_two_executable_families(tiny_state):
    """Serving + a train step traced in one session: the report must
    join observed wall time against the static predictions for BOTH
    executable families (CPU-honest: the HBM column is n/a here)."""
    state, cfg = tiny_state
    with trace() as tr:
        # family 1: the serving unified step (ambient tracer picked up)
        eng = Engine(state, cfg, num_pages=16, page_size=8, max_batch=2,
                     name="obs_serving")
        eng.add_request([7, 3, 9, 1], 3, arrival_time=0.0)
        eng.run()
        # family 2: a train-step plan
        ht.set_seed(0)
        with ht.graph("define_and_run", create_new=True,
                      prefix="obs_train") as g:
            from hetu_tpu import optim
            ids = ht.placeholder("int32", (2, 8), name="ids")
            lbl = ht.placeholder("int32", (2, 8), name="lbl")
            model = GPTLMHeadModel(GPTConfig(**CFG_KW))
            loss = model(ids, lbl)
            opt = optim.AdamOptimizer(lr=1e-3)
            train_op = opt.minimize(loss)
            data = np.random.RandomState(0).randint(
                0, 61, size=(2, 8)).astype(np.int32)
            for _ in range(2):
                g.run(loss, [loss, train_op], {ids: data, lbl: data})
        rep = reconcile(tr.events())
    assert rep.families >= 2
    by_name = {r.executable: r for r in rep.rows}
    srv = by_name["obs_serving/unified"]
    trn = next(r for r in rep.rows if "obs_train" in r.executable)
    assert srv.calls >= 1 and srv.mean_wall_s > 0
    assert trn.calls == 2 and trn.total_wall_s > 0
    # static predictions joined per family
    assert srv.predicted_peak_hbm_bytes > 0
    assert trn.predicted_peak_hbm_bytes > 0
    assert srv.predicted_wire_bytes == 0     # single-device: zero-edge claim
    # CPU honesty: no allocator stats -> explicit n/a, never a fake pass
    assert srv.hbm_check == "n/a" and rep.observed_peak_hbm_bytes == 0
    assert "n/a" in rep.summary()
    # ISSUE 10: the step-time prediction joins as a RATIO-only column —
    # off-TPU the chip-spec model has no absolute meaning, so the table
    # reports wall/pred with no pass/fail verdict
    assert srv.predicted_step_s is not None and srv.predicted_step_s > 0
    assert trn.predicted_step_s is not None and trn.predicted_step_s > 0
    assert srv.wall_ratio == pytest.approx(
        srv.mean_wall_s / srv.predicted_step_s)
    assert trn.predicted_bound in ("compute", "hbm", "comm")
    summary = rep.summary()
    assert "wall/pred" in summary and "RATIO" in summary
    d = rep.to_dict()
    assert len(d["rows"]) == rep.families
    assert d["rows"][0]["predicted_step_s"] is not None
    json.dumps(d)                            # JSON-serializable


# ---------------------------------------------------------------------------
# ISSUE 25: host phases on the profiler's clock, device time by phase
# ---------------------------------------------------------------------------


def _profiler_hetu_spans(trace_dir):
    """(start_ns, end_ns, name, stats) of the ``hetu:`` annotations in
    the one .xplane.pb under ``trace_dir``, in start order."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PROFILER_PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name[len(obs.PROFILER_PREFIX):],
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[0], -e[1]))


def test_profiler_mirror_nests_spans_as_tracer_events(tmp_path):
    """Under a live jax.profiler session the real-time spans land in
    the profiler's trace, nested and ordered as the tracer has them."""
    import jax
    import jax.numpy as jnp
    with trace() as tr:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("outer", track="work", rows=3, label="x",
                         skipped=[1, 2]):
                with tr.span("first"):
                    jnp.ones(8).sum().block_until_ready()
                tr.instant("point")              # not mirrored
                tr.complete("retro", tr.now() - 1.0, 0.5)   # not mirrored
                with tr.span("second", ts=123.0):    # labelled, mirrored
                    pass
        finally:
            jax.profiler.stop_trace()
    got = _profiler_hetu_spans(tmp_path)
    assert [g[2] for g in got] == ["outer", "first", "second"]
    by = {g[2]: g for g in got}
    # nesting: both children inside the parent, in the tracer's order
    assert by["outer"][0] <= by["first"][0] <= by["first"][1] \
        <= by["second"][0] <= by["second"][1] <= by["outer"][1]
    spans = [e for e in tr.events() if e.ph == "X" and e.name != "retro"]
    assert sorted(e.name for e in spans) == sorted(g[2] for g in got)
    assert [e.parent for e in spans if e.name != "outer"] == ["outer"] * 2
    # scalar attributes ride along as stats, containers do not
    assert by["outer"][3] == {"rows": 3, "label": "x"}
    # the mirror's duration is the span's own, on another clock
    first = next(e for e in spans if e.name == "first")
    assert (by["first"][1] - by["first"][0]) / 1e9 == \
        pytest.approx(first.dur, rel=0.5, abs=2e-3)
    assert tr.open_count() == 0


STEP_PHASES = ("step.admit", "step.pages", "step.pack", "step.tap",
               "step.h2d", "step.dispatch", "step.fetch", "step.commit")


def test_engine_step_phases_tile_engine_step(tiny_state):
    """Every traced step that runs the executable emits each ``step.*``
    phase once, and the children tile ``engine_step`` gaplessly."""
    import itertools
    state, cfg = tiny_state
    ticks = itertools.count()
    now = lambda: float(next(ticks))                     # noqa: E731
    tracer = SpanTracer(time_fn=now)
    eng = Engine(state, cfg, tracer=tracer, time_fn=now, num_pages=16,
                 page_size=8, max_batch=2, name="obs_phases")
    eng.add_request([7, 3, 9, 1, 5], 3, arrival_time=0.0)
    eng.add_request([2, 4], 2, arrival_time=0.0)
    steps = 0
    while eng.has_work and steps < 50:
        eng.step()
        steps += 1
    assert not eng.has_work and tracer.open_count() == 0
    events = tracer.events()
    parents = [e for e in events if e.name == "engine_step"]
    assert len(parents) == steps
    assert all(e.track == "engine" for e in parents)
    unified = [e for e in events if e.name == "unified_step"]
    assert len(unified) == eng.executable_calls == steps
    for par, un in zip(parents, unified):
        kids = sorted((e for e in events if e.name.startswith("step.")
                       and par.ts <= e.ts and e.end_ts <= par.end_ts),
                      key=lambda e: e.ts)
        assert tuple(e.name for e in kids) == STEP_PHASES
        assert all(e.parent == "engine_step" and e.track == "engine"
                   for e in kids)
        assert kids[0].ts == par.ts and kids[-1].end_ts == par.end_ts
        assert all(a.end_ts == b.ts for a, b in zip(kids, kids[1:]))
        # the old span keeps its bounds: copy-in through the fetch
        assert un.ts == kids[4].ts and un.end_ts == kids[6].end_ts
        assert par.attrs["rows"] == un.attrs["rows"] >= 1
        assert par.attrs["tokens"] == un.attrs["tokens"]
        # every fed token is written, at least one a row; the spans
        # of one step share its index
        assert un.attrs["rows"] <= un.attrs["tokens"]
        assert un.attrs["step"] == par.attrs["step"]
        assert {"queue_depth", "queue_due", "running",
                "free_pages"} <= set(par.attrs)
        assert 0 <= par.attrs["queue_due"] <= par.attrs["queue_depth"]
    # names the benchmark reads are still there, none shadowed
    names = {e.name for e in events}
    assert {"admit", "queued", "running", "token"} <= names
    assert eng.counters["kv_tokens_written"].value == sum(
        e.attrs["tokens"] for e in unified) == 5 + 2 + (3 - 1) + (2 - 1)
    # the first step packs both prompts: two chunk rows' worth of slots
    assert parents[0].attrs["tokens"] == unified[0].attrs["tokens"] \
        <= parents[0].attrs["token_budget"]
    # an idle step (nothing to run) still tiles: admit, pages, pack, commit
    eng.step()
    idle = [e for e in tracer.events() if e.ts >= parents[-1].end_ts
            and e.name.startswith("step.")]
    assert [e.name for e in idle] == ["step.admit", "step.pages",
                                      "step.pack", "step.commit"]
    # untraced: the same engine goes silent and keeps no span open
    eng.set_tracer(None)
    n = len(tracer.events())
    eng.add_request([1, 2, 3], 2, arrival_time=0.0)
    eng.run()
    assert len(tracer.events()) == n and eng._phase_sp is None


class _TickClock:
    """A clock that moves ``tick`` at every reading, so a step has a
    wall of its own; ``t`` can be moved by hand."""

    def __init__(self, tick=0.001):
        self.t, self.tick = 0.0, tick

    def __call__(self):
        self.t += self.tick
        return self.t


def _stalling(eng, clock, at_call, seconds=0.0, collect=False):
    """Make the engine's ``at_call``-th compiled call lose ``seconds``
    of the clock (and run a full collection) before it returns."""
    import gc
    fn, calls = eng._compiled["unified"], [0]

    def stalled(*args):
        calls[0] += 1
        if calls[0] == at_call:
            clock.t += seconds
            if collect:
                gc.collect()
        return fn(*args)

    eng._compiled["unified"] = stalled


def test_slow_step_is_named_once_and_the_parts_sum(tiny_state, caplog):
    """The always-on clock, tracing off: one injected 3 s step is the
    head of ``slow_steps`` with its part, ``slow_step_s`` is what lies
    beyond the rule, the warning comes once, and the four parts sum to
    the window the caller spent in and between its steps."""
    import logging
    from hetu_tpu.serving.engine import SLOW_STEP_FACTOR, SLOW_STEPS_KEPT
    state, cfg = tiny_state
    clock = _TickClock()
    eng = Engine(state, cfg, time_fn=clock, num_pages=16, page_size=8,
                 max_batch=2, name="obs_clock")
    assert eng.tracer is NULL_TRACER
    eng.add_request([7, 3, 9, 1, 5], 12, arrival_time=0.0)
    eng.add_request([2, 4], 14, arrival_time=0.0)
    _stalling(eng, clock, at_call=9, seconds=3.0)
    walls, first = [], None
    with caplog.at_level(logging.WARNING, logger="hetu_tpu.serving"):
        while eng.has_work:
            entry = clock.t + clock.tick
            first = entry if first is None else first
            eng.step()
            walls.append(clock.t - entry)
    m = eng.metrics_summary()
    parts = [m[k] for k in ("host_before_s", "call_s", "host_after_s",
                            "between_steps_s")]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(clock.t - first, rel=1e-9)
    head = eng.slow_steps[0]
    assert head["step"] == 8 and head["wall_s"] > 3.0
    assert max(("between_steps_s", "host_before_s", "call_s",
                "host_after_s"), key=head.get) == "call_s"
    assert head["rows"] == 2 and head["tokens"] == 2
    assert head["full_collections"] == 0
    assert len(eng.slow_steps) == SLOW_STEPS_KEPT
    assert [r["wall_s"] for r in eng.slow_steps] == sorted(
        (r["wall_s"] for r in eng.slow_steps), reverse=True)
    # beyond 10 x the mean wall of the eight steps before it (a step's
    # wall here counts the tick between two steps too)
    mean = (sum(walls[:8]) + 7 * clock.tick) / 8
    assert m["slow_step_s"] == pytest.approx(
        head["wall_s"] - SLOW_STEP_FACTOR * mean, rel=1e-9)
    assert m["slow_step_s"] == pytest.approx(3.0, abs=0.5)
    warned = [r for r in caplog.records if r.name == "hetu_tpu.serving"]
    assert len(warned) == 1
    text = warned[0].getMessage()
    assert "step 8," in text and "most of it in call_s" in text \
        and "full collections in it 0" in text
    # the reset clears the record with the counters
    eng.reset_metrics()
    assert eng.slow_steps == [] and \
        eng.metrics_summary()["slow_step_s"] == 0.0


def test_sleeping_caller_with_an_empty_engine_is_no_stall(tiny_state,
                                                           caplog):
    """``between_steps_s`` counts the way from one step to the next only
    while requests were running at the first one's exit."""
    import logging
    state, cfg = tiny_state
    clock = _TickClock()
    eng = Engine(state, cfg, time_fn=clock, num_pages=16, page_size=8,
                 max_batch=2, name="obs_sleeper")
    with caplog.at_level(logging.WARNING, logger="hetu_tpu.serving"):
        eng.add_request([2, 4, 6], 3, arrival_time=0.0)
        eng.run()
        between = eng.metrics_summary()["between_steps_s"]
        assert between == pytest.approx(2 * clock.tick)   # three steps
        clock.t += 100.0                  # the caller sleeps, engine empty
        eng.add_request([1, 3], 2, arrival_time=0.0)
        eng.run()
    assert eng.metrics_summary()["between_steps_s"] == \
        pytest.approx(between + clock.tick)
    assert eng.metrics_summary()["slow_step_s"] == 0.0
    assert not caplog.records


def test_pack_arrays_account_and_gc_spans(tiny_state):
    """Traced steps: ``pack_arrays`` and ``account`` carry their step's
    index and lie inside ``step.pack`` / ``step.commit``; a collection
    inside a step is one ``gc`` span and a full collection in the slow
    record; the hook goes when the tracer goes."""
    import gc
    state, cfg = tiny_state
    clock = _TickClock()
    tracer = SpanTracer(time_fn=clock)
    eng = Engine(state, cfg, time_fn=clock, num_pages=16, page_size=8,
                 max_batch=2, name="obs_account")
    hooks = len(gc.callbacks)
    eng.set_tracer(tracer)
    eng.set_tracer(tracer)                       # asked twice, one hook
    assert len(gc.callbacks) == hooks + 1
    eng.add_request([7, 3, 9, 1, 5], 3, arrival_time=0.0)
    eng.add_request([2, 4], 2, arrival_time=0.0)
    _stalling(eng, clock, at_call=2, collect=True)
    gc.disable()                     # no collection but the forced one
    try:
        eng.run()
    finally:
        gc.enable()
    eng.set_tracer(None)
    assert len(gc.callbacks) == hooks and eng.tracer is NULL_TRACER
    events = tracer.events()
    by = lambda n: [e for e in events if e.name == n]       # noqa: E731
    parents = by("engine_step")
    assert [e.attrs["step"] for e in parents] == list(range(eng.steps))
    for name, phase in (("pack_arrays", "step.pack"),
                        ("account", "step.commit")):
        spans, homes = by(name), by(phase)
        assert len(spans) == len(homes) == len(parents) == eng.steps
        for sp, home, par in zip(spans, homes, parents):
            assert sp.attrs["step"] == par.attrs["step"]
            assert sp.track == "engine" and sp.dur > 0
            assert home.ts <= sp.ts and sp.end_ts <= home.end_ts
    # one chunk row a step: the first prompt, then the second beside
    # the first's decode row; each holds one 8-token page
    assert [(e.attrs["rows"], e.attrs["page_slots"])
            for e in by("pack_arrays")[:2]] == [(1, 1), (2, 2)]
    # the account span starts after the pools are swapped in
    assert all(a.ts > c.ts for a, c in zip(by("account"),
                                           by("step.commit")))
    (coll,) = by("gc")
    assert coll.track == "runtime" and coll.attrs["generation"] == 2
    assert coll.attrs["collected"] >= 0
    disp = by("step.dispatch")[1]
    assert disp.ts <= coll.ts and coll.end_ts <= disp.end_ts
    assert [r["full_collections"] for r in sorted(
        eng.slow_steps, key=lambda r: r["step"])][:3] == [0, 1, 0]


def test_trace_context_watches_the_collector():
    """``obs.trace()`` brings the ``gc`` spans and takes the hook away;
    with no tracer no callback exists."""
    import gc
    hooks = len(gc.callbacks)
    with trace() as tr:
        assert len(gc.callbacks) == hooks + 1
        with trace(tracer=tr):               # nested on one tracer
            gc.collect()
        assert len(gc.callbacks) == hooks + 1
    assert len(gc.callbacks) == hooks
    assert [e.attrs["generation"] for e in tr.events()
            if e.name == "gc" and e.attrs["generation"] == 2] == [2]
    assert NULL_TRACER.watch_gc() is False and len(gc.callbacks) == hooks


def _tiny_train_graph(cfg, prefix):
    from hetu_tpu import optim
    g_ctx = ht.graph("define_and_run", create_new=True, prefix=prefix)
    g = g_ctx.__enter__()
    try:
        ids = ht.placeholder("int32", (2, 8), name="ids")
        lbl = ht.placeholder("int32", (2, 8), name="lbl")
        loss = GPTLMHeadModel(cfg)(ids, lbl)
        train_op = optim.AdamOptimizer(lr=1e-3).minimize(loss)
    finally:
        g_ctx.__exit__(None, None, None)
    data = np.random.RandomState(0).randint(0, 61, (2, 8)).astype(np.int32)
    return g, loss, train_op, {ids: data, lbl: data}


def test_traced_train_step_never_blocks(tiny_state, monkeypatch):
    """A traced ``g.run`` issues the untraced host schedule: no
    ``block_until_ready``, and its phases in order under the step."""
    import jax
    _, cfg = tiny_state
    ht.set_seed(0)
    g, loss, train_op, feed = _tiny_train_graph(cfg, "obs_noblock")
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (calls.append(1), real(x))[1])
    with trace() as tr:
        out = g.run(loss, [loss, train_op], feed)
    assert calls == []
    assert np.isfinite(float(out[0]))
    names = [e.name for e in sorted(tr.events(), key=lambda e: e.ts)
             if e.parent == "train_step"]
    assert names == ["plan", "feed", "assemble", "executable", "commit"]
    ex = next(e for e in tr.events() if e.name == "executable")
    assert ex.attrs["exec"].startswith("obs_noblock")
    assert not any(k.startswith("predicted_") for k in ex.attrs)


def test_device_phases_toy_function():
    """Two scopes on a jitted function: forward and backward
    instructions map to their phase, the rest to ``unmapped``."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.graph.graph import clear_executables, register_executable
    from hetu_tpu.obs.phases import UNMAPPED, hlo_phase_map, phase

    def f(w1, w2, x):
        def loss(w1, w2):
            with phase("attn_proj"):
                h = jnp.tanh(x @ w1)
            with phase("mlp"):
                h = jnp.sin(h @ w2)
            return jnp.sum(h * h)
        return jax.value_and_grad(loss, argnums=(0, 1))(w1, w2)

    sds = jax.ShapeDtypeStruct((16, 16), np.float32)
    register_executable("obs_toy/f", jax.jit(f), (sds, sds, sds))
    try:
        m = obs.device_phases("obs_toy/f")
    finally:
        clear_executables("obs_toy/")
    phases = set(m.values())
    assert {"attn_proj", "mlp"} <= phases <= {"attn_proj", "mlp", UNMAPPED}
    with pytest.raises(KeyError):
        obs.device_phases("obs_toy/never_registered")
    with pytest.raises(ValueError):
        with phase("not_a_phase"):
            pass
    # the text rules, on a hand-made module: path word, backward wrapper,
    # alias, fusion by its root / its relabelling user / its members,
    # Mosaic kernel by name, unknown
    hlo = '''HloModule m
%fused_computation.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %mul.3 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(s)/norm/mul"}
  ROOT %add.4 = f32[4]{0} add(%mul.3, %p0), metadata={op_name="jit(s)/norm/add"}
}
%fused_computation.2 (p1: f32[4]) -> f32[4] {
  %p1 = f32[4]{0} parameter(0)
  %neg.12 = f32[4]{0} negate(%p1), metadata={op_name="jit(s)/attn_core/neg"}
  ROOT %scatter.13 = f32[4]{0} scatter(%p1, %neg.12, %neg.12), to_apply=%r
}
ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %dot.1 = f32[4]{0} dot(%a, %a), metadata={op_name="jit(s)/transpose(jvp(mlp))/dot_general"}
  %ag.2 = f32[4]{0} all-gather(%a), metadata={op_name="jit(s)/param_comm/bucket0/all_gather"}
  %fusion.5 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %fusion.10 = f32[4]{0} fusion(%a), kind=kCustom, calls=%fused_computation.2
  %bitcast.11 = f32[2,2]{1,0} bitcast(%fusion.10), metadata={op_name="jit(s)/kv_scatter/scatter"}
  %flash_fwd.7 = f32[4]{0} custom-call(%a), custom_call_target="tpu_custom_call"
  %copy.8 = f32[4]{0} copy(%a), metadata={op_name="jit(s)/reshape"}
  ROOT %opt.6 = f32[4]{0} add(%a, %a), metadata={op_name="jit(s)/optimizer/grad_comm/add"}
}
'''
    got = hlo_phase_map(hlo)
    assert got["dot.1"] == "mlp" and got["ag.2"] == "param_gather"
    assert got["mul.3"] == got["add.4"] == got["fusion.5"] == "norm"
    assert got["flash_fwd.7"] == "flash_fwd"
    # a rewritten scatter lost its metadata: the fusion goes by the
    # bitcast that consumes it, not by the operands fused into it
    assert got["scatter.13"] == UNMAPPED and got["neg.12"] == "attn_core"
    assert got["fusion.10"] == got["bitcast.11"] == "kv_scatter"
    assert got["opt.6"] == "optimizer"           # the FIRST word wins
    assert got["copy.8"] == got["a"] == UNMAPPED
    assert got.get("fusion.999", UNMAPPED) == UNMAPPED


def test_device_phases_tiny_train_plan(tiny_state):
    """The registered train plan: forward and backward instructions of
    the model's phases and the optimizer all carry their names."""
    _, cfg = tiny_state
    ht.set_seed(0)
    g, loss, train_op, feed = _tiny_train_graph(cfg, "obs_devph")
    with trace() as tr:
        g.run(loss, [loss, train_op], feed)
    name = next(e.attrs["exec"] for e in tr.events()
                if e.name == "executable")
    m = obs.device_phases(name)
    by_phase = {}
    for inst, ph in m.items():
        by_phase.setdefault(ph, []).append(inst)
    assert {"embed", "norm", "attn_proj", "attn_core", "mlp",
            "lm_head_ce", "optimizer"} <= set(by_phase)
    from hetu_tpu.graph.graph import get_executable
    text = get_executable(name).compiled_text()
    # backward instructions inherit the phase through transpose(jvp(.))
    assert "transpose(jvp(mlp))" in text and "jvp(attn_proj)" in text
    # most of the program is named: the tiny plan's own bookkeeping
    # (parameters, tuples, copies) is what stays unmapped
    dots = [i for i in m if i.startswith(("dot", "fusion"))]
    named = [i for i in dots if m[i] != "unmapped"]
    assert len(named) >= 0.9 * len(dots) > 0
