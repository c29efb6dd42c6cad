"""Reader ``host_span``: a statistic of the PROGRAM's own spans
(``obs.SpanTracer``, host clock), taken inside the window.
args: name; stat = median_ms | mean_attr (with attr) |
      outside_ms_per_span (window minus the spans' total, per span: the
      host time the loop spends outside them)."""
import stats


def read(args, facts):
    t0, t1 = facts["values"]["host_window"]
    evs = [e for e in facts.get("host_spans", []) if e.name == args["name"]
           and t0 <= e.ts < t1]
    if not evs:
        return None
    if args["stat"] == "median_ms":
        return stats.median([(e.dur or 0.0) * 1e3 for e in evs])
    if args["stat"] == "mean_attr":
        return sum(float(e.attrs[args["attr"]]) for e in evs) / len(evs)
    if args["stat"] == "outside_ms_per_span":
        inside = sum(e.dur or 0.0 for e in evs)
        return ((t1 - t0) - inside) * 1e3 / len(evs)
    raise ValueError(args["stat"])
