"""Reader ``trace_idle_by_phase``: device idle time on chip 0 inside the
traced window that falls under the PROGRAM's own spans of the given
names, per benchmark span — which host phase the device was waiting for.

The program's ``obs.SpanTracer`` mirrors its real-time spans into the
profiler as ``hetu:<name>`` annotations, so they sit on the device
trace's clock.  They are read from this run's ``.xplane.pb`` host
planes; the idle gaps come from ``facts["trace"]["events"]``; each
instant of a gap goes to the shortest span that covers it
(``xplane.label_gaps``).
args: phases [span names; [] = idle under NO ``hetu:`` span]; per_span
      (a ``bench:`` span name, e.g. engine.step).
Nothing to read (no trace, no ``hetu:`` span in it, as from a program
that does not write them) gives None.
"""
import glob
import os

import xplane

PREFIX = "hetu:"
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_out", "trace")


def newest_xplane(root: str = TRACE_ROOT):
    """The trace this process just wrote: ``run.py`` empties the cell's
    trace directory before it starts the profiler, so the newest file
    under any cell is this run's."""
    paths = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_program_spans(path: str):
    """[(start_ns, dur_ns, name)] of the ``hetu:`` host annotations."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((int(ev.start_ns), int(ev.duration_ns),
                                ev.name[len(PREFIX):]))
    return sorted(out)


def idle_by_span(events, t0: int, t1: int, program_spans) -> dict:
    """span name -> seconds of chip-0 idle in [t0, t1) under it;
    ``unlabelled`` is the idle under no program span."""
    return xplane.label_gaps(xplane.idle_gaps(events, t0, t1),
                             program_spans)


def read(args, facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    table = facts.get("_idle_by_span")          # one parse per run
    if table is None:
        path = newest_xplane()
        spans = load_program_spans(path) if path else []
        table = idle_by_span(tr["events"], tr["t0"], tr["t1"], spans) \
            if spans else {}
        facts["_idle_by_span"] = table
        if table:
            idle = tr["window_s"] - xplane.busy_ns(tr["events"]) / 1e9
            print("bench: idle by program span (s of %.4f idle on chip 0): "
                  % idle + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
                      table.items(), key=lambda kv: -kv[1])), flush=True)
    steps = xplane.spans_in_window(tr, args["per_span"])
    if not table or not steps:
        return None
    names = args["phases"] or ["unlabelled"]
    return sum(table.get(n, 0.0) for n in names) * 1e3 / steps
