"""Reader ``trace_step_edges``: the device idle of a serving step split
where the device starts and stops — ``launch`` (``hetu:step.dispatch``
start to the execution's first instant), ``dev_gap`` (idle inside the
execution's extent: the executable's own gaps, which no host repair
takes) and ``fetch_tail`` (the execution's last instant to
``hetu:step.fetch`` end), mean ms a step over the steps that lie whole in
the traced window.

An execution's extent is its event on chip 0's ``XLA Modules`` line (one
a run of the executable); the steps are the program's ``hetu:step.h2d`` /
``step.dispatch`` / ``step.fetch`` annotations; the idle inside an extent
comes from chip 0's operations in ``facts["trace"]``.  All of it in one
sorted sweep, linear in events + spans; this run's ``.xplane.pb`` is
parsed once and the table kept in ``facts``.

The device plane's clock is not the host plane's: their offset differs
by a millisecond from one profiler session to the next (PERF.md, PR 37),
which moves ``launch`` against ``fetch_tail`` by as much.  Two events of
the runtime bracket it in every step — the program is enqueued
(``DoEnqueueProgram`` ends) before the execution starts, and the
execution ends before the runtime sees it end (``ReadSyncFlag`` starts) —
and the device's times are moved to the middle of the bracket.  A trace
without those events is read on its own clock, and the log says so.  The
log also has the three numbers on the trace's own clock beside the idle
that ``trace_idle_by_phase`` puts under ``step.dispatch`` +
``step.fetch``: the same time, cut differently.

args: part = launch | dev_gap | fetch_tail.
Nothing to read (no trace, no ``hetu:`` step span, no ``XLA Modules``
line) gives None.
"""
import gzip
import json
import time

import xplane
from readers import trace_idle_by_phase

MODULES_LINE = "XLA Modules"
STEP_SPANS = ("step.h2d", "step.dispatch", "step.fetch")
ENQUEUED, SEEN_DONE = "DoEnqueueProgram", "ReadSyncFlag"
PARTS = ("launch", "dev_gap", "fetch_tail")


def load_edges(path: str) -> dict:
    """From one ``.xplane.pb``: ``extents`` [(start_ns, end_ns)] of chip
    0's executions, ``spans`` [(start_ns, end_ns, name)] of the step's
    three host phases, ``steps`` [(start_ns, end_ns, step index)] of the
    ``hetu:engine_step`` annotations that carry one, and the runtime's
    ``enqueued`` / ``seen_done`` [(start_ns, end_ns)]; each sorted."""
    from jax.profiler import ProfileData
    prefix = trace_idle_by_phase.PREFIX
    wanted = {prefix + n: n for n in STEP_SPANS}
    out = {"extents": [], "spans": [], "steps": [], "enqueued": [],
           "seen_done": []}
    chips = {}
    for plane in ProfileData.from_file(path).planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    at = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    if name in wanted:
                        out["spans"].append(at + (wanted[name],))
                    elif name == prefix + "engine_step":
                        step = dict(ev.stats).get("step")
                        if step is not None:
                            out["steps"].append(at + (int(step),))
                    elif name == ENQUEUED:
                        out["enqueued"].append(at)
                    elif name == SEEN_DONE:
                        out["seen_done"].append(at)
    if chips:
        for line in chips[min(chips)].lines:
            if line.name == MODULES_LINE:
                out["extents"] = [(int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns))
                                  for ev in line.events]
    return {k: sorted(v) for k, v in out.items()}


def save_fixture(edges: dict, events, path: str, steps: int) -> None:
    """The first ``steps`` executions with the spans, runtime events and
    device operations up to the end of the host step that holds the last
    of them, as JSON — a recorded trace small enough to keep."""
    fetches = [s for s in edges["spans"] if s[2] == "step.fetch"]
    last = edges["extents"][steps - 1][1]
    end = min(e for _, e, _ in fetches if e >= last)
    cut = {k: [x for x in v if x[0] <= end] for k, v in edges.items()}
    cut["events"] = [(s, d, n) for s, d, n, *_ in events if s <= end]
    with gzip.open(path, "wt") as f:
        json.dump(cut, f)


def load_fixture(path: str):
    """(edges, events) of a ``save_fixture`` file."""
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    events = [tuple(e) for e in raw.pop("events")]
    return {k: [tuple(x) for x in v] for k, v in raw.items()}, events


def host_steps(spans):
    """[(h2d start, dispatch start, fetch end)] of the steps whose three
    phases are all in ``spans`` (sorted by start), in order."""
    out, h2d, disp = [], None, None
    for start, end, name in spans:
        if name == "step.h2d":
            h2d, disp = start, None
        elif name == "step.dispatch" and h2d is not None:
            disp = start
        elif name == "step.fetch" and disp is not None:
            out.append((h2d, disp, end))
            h2d = disp = None
    return out


def _first_from(items, j: int, lo: int, hi: int):
    """Index of the first of the sorted ``items`` from ``j`` on that
    starts in [lo, hi), and the index to go on from; (None, j') if none."""
    while j < len(items) and items[j][0] < lo:
        j += 1
    if j < len(items) and items[j][0] < hi:
        return j, j + 1
    return None, j


def step_edges(edges: dict, events, t0: int, t1: int):
    """The split, from ``load_edges``' lists and chip 0's operations
    [(start_ns, dur_ns, ...)] sorted by start.  Returns None without a
    step that has an execution, else {"steps", "launch", "dev_gap",
    "fetch_tail", "raw": the three on the trace's own clock,
    "offset_ns", "bracket_ns": (lo, hi) or None, "worst": (fetch tail ns,
    step index or None)} — times in ns a step, means over the steps
    whose host phases lie inside [t0, t1)."""
    extents = edges["extents"]
    rows = []                     # (h2d, dispatch, fetch end, ext, gap)
    j = k = e = s = 0
    lo, hi = None, None           # the bracket of the clocks' offset
    for h2d, disp, fend in host_steps(edges["spans"]):
        # the execution that overlaps this step's h2d..fetch most
        while j < len(extents) and extents[j][1] <= h2d:
            j += 1
        best, n = None, j
        while n < len(extents) and extents[n][0] < fend:
            over = min(extents[n][1], fend) - max(extents[n][0], h2d)
            if best is None or over > best[0]:
                best = (over, n)
            n += 1
        if best is None:
            continue
        j = best[1] + 1
        ext = extents[best[1]]
        # idle inside the extent: what no operation covers
        while k < len(events) and events[k][0] < ext[0]:
            k += 1
        covered, gap = ext[0], 0
        while k < len(events) and events[k][0] < ext[1]:
            start, dur = events[k][0], events[k][1]
            if start > covered:
                gap += start - covered
            covered = max(covered, start + dur)
            k += 1
        gap += max(0, ext[1] - covered)
        rows.append((h2d, disp, fend, ext, gap))
        # enqueued before it started, seen done after it ended
        i, e = _first_from(edges["enqueued"], e, disp, fend)
        if i is not None:
            d = edges["enqueued"][i][1] - ext[0]
            lo = d if lo is None else max(lo, d)
            i, s = _first_from(edges["seen_done"], s,
                               edges["enqueued"][i][1], fend)
            if i is not None:
                d = edges["seen_done"][i][0] - ext[1]
                hi = d if hi is None else min(hi, d)
    rows = [r for r in rows if t0 <= r[0] and r[2] <= t1]
    if not rows:
        return None
    bracket = (lo, hi) if lo is not None and hi is not None else None
    offset = (lo + hi) // 2 if bracket else 0

    def split(off):
        launch = tail = 0
        worst = (-1, None)
        for h2d, disp, fend, ext, _ in rows:
            launch += max(0, min(ext[0] + off, fend) - disp)
            t = max(0, fend - max(ext[1] + off, disp))
            tail += t
            worst = max(worst, (t, h2d))
        return launch / len(rows), tail / len(rows), worst

    launch, tail, worst = split(offset)
    gap = sum(r[4] for r in rows) / len(rows)
    raw_launch, raw_tail, _ = split(0)
    index = next((n for a, b, n in edges["steps"] if a <= worst[1] < b),
                 None)
    return {"steps": len(rows), "launch": launch, "dev_gap": gap,
            "fetch_tail": tail, "raw": (raw_launch, gap, raw_tail),
            "offset_ns": offset, "bracket_ns": bracket,
            "worst": (worst[0], index)}


def _measure(tr: dict, facts: dict):
    t = time.monotonic()
    path = trace_idle_by_phase.newest_xplane()
    if path is None:
        return None
    got = step_edges(load_edges(path), tr["events"], tr["t0"], tr["t1"])
    if got is None:
        return None
    ms = 1e-6
    clock = "device clock %+.3f ms, bracket %.3f..%.3f" % (
        got["offset_ns"] * ms, got["bracket_ns"][0] * ms,
        got["bracket_ns"][1] * ms) if got["bracket_ns"] else \
        "no runtime events to set the device clock by: taken as it is"
    line = ("bench: step edges (ms a step over %d steps; %s): launch %.4f, "
            "dev_gap %.4f, fetch_tail %.4f; widest fetch tail %.4f at "
            "step %s" % (got["steps"], clock, got["launch"] * ms,
                         got["dev_gap"] * ms, got["fetch_tail"] * ms,
                         got["worst"][0] * ms, got["worst"][1]))
    raw = sum(got["raw"]) * ms
    table = facts.get("_idle_by_span") or {}
    calls = xplane.spans_in_window(tr, "engine.step")
    if calls and ("step.dispatch" in table or "step.fetch" in table):
        idle = (table.get("step.dispatch", 0.0) +
                table.get("step.fetch", 0.0)) * 1e3 / calls
        line += ("; on the trace's own clock %.4f + %.4f + %.4f = %.4f "
                 "against %.4f of idle under step.dispatch + step.fetch "
                 "(residual %+.2f %%)" % (*(v * ms for v in got["raw"]), raw,
                                          idle, 100 * (raw - idle) / idle
                                          if idle else 0.0))
    print(line + "; read in %.2f s" % (time.monotonic() - t), flush=True)
    return {p: got[p] * ms for p in PARTS}


def read(args, facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    if "_step_edges" not in facts:               # one parse per run
        facts["_step_edges"] = _measure(tr, facts)
    table = facts["_step_edges"]
    return None if table is None else table[args["part"]]
