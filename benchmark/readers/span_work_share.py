"""Reader ``span_work_share``: a layer's share of its roofline — the least
time the chip could take for the work the program's ``unified_step`` spans
describe (a work function of the module ``work_module``, found by name
beside ``work.py``: larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, span by span, inside the traced host window) over the device time
the layer took in the traced window: the self time of its ``phases``
(``trace_phase_time``'s table), or the time of the operations whose name
matches ``match`` (a kernel of its own name).  Never clamped.
args: work_module; work_fn (the module's WORK_FNS); phases [names] or
match (regex); span (default unified_step).  Nothing to read — no trace, a
program without the phases, the kernel or the span's attributes (``needs``)
— gives None."""
import importlib

import work
import xplane
from readers import trace_phase_time


def read(args, facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    if "match" in args:
        took = xplane.op_time_ns(tr["events"], args["match"]) / 1e9
    else:
        if trace_phase_time.read({"phase": args["phases"][0],
                                  "as": "share_of_busy"}, facts) is None:
            return None
        table = facts["_time_by_phase"]
        took = sum(table.get(ph, 0) for ph in args["phases"]) / 1e9
    t0, t1 = facts["values"]["trace_host_window"]
    spans = [e for e in facts.get("host_spans", [])
             if e.name == args.get("span", "unified_step") and t0 <= e.ts < t1
             and all(k in e.attrs for k in args.get("needs", []))]
    if not took or not spans:
        return None
    peaks = work.peaks_for(facts["device_kind"])
    fn = importlib.import_module(args["work_module"]).WORK_FNS[args["work_fn"]]
    least, bounds = 0.0, {}
    for e in spans:
        s, bound = work.roofline_seconds(*fn(facts["config"], e.attrs), peaks)
        least += s
        bounds[bound] = bounds.get(bound, 0) + 1
    what = args.get("match") or "+".join(args["phases"])
    print(f"bench: roofline {what}: {len(spans)} steps, {took:.4f} s on the "
          f"device, least {least:.4f} s, bound by {bounds}", flush=True)
    return 100.0 * least / took
