"""Reader ``host_span_sum``: the total length of the PROGRAM's own spans
of one name (``obs.SpanTracer``, host clock) that start inside the
window, per step of the window — what something that runs now and then
(the collector's ``gc`` spans) costs a step.
args: name.  A program that writes no span of that name gives None."""


def read(args, facts):
    evs = [e for e in facts.get("host_spans", []) if e.name == args["name"]]
    steps = facts["values"]["steps"]
    if not evs or not steps:
        return None
    t0, t1 = facts["values"]["host_window"]
    return sum(e.dur or 0.0 for e in evs if t0 <= e.ts < t1) * 1e3 / steps
