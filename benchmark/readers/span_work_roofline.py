"""Reader ``span_work_roofline``: a layer's share of its roofline where
the layer is plain XLA code (no kernel of its own name): the least time
the chip could take for the work the program's ``unified_step`` spans
describe (``work_hybrid.py``: larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s, span by span, inside the traced host window) over the
device self time of the layer's phases (``trace_phase_time``'s table).
Never clamped.  args: phases [names]; work_fn (work_hybrid.WORK_FNS);
span (default unified_step).  Nothing to read gives None."""
import work
import work_hybrid
from readers import trace_phase_time


def read(args, facts):
    if facts.get("trace") is None:
        return None
    if trace_phase_time.read({"phase": args["phases"][0],
                              "as": "share_of_busy"}, facts) is None:
        return None
    table = facts["_time_by_phase"]
    took = sum(table.get(ph, 0) for ph in args["phases"]) / 1e9
    t0, t1 = facts["values"]["trace_host_window"]
    spans = [e for e in facts.get("host_spans", [])
             if e.name == args.get("span", "unified_step") and t0 <= e.ts < t1]
    if not took or not spans:
        return None
    peaks = work.peaks_for(facts["device_kind"])
    fn = work_hybrid.WORK_FNS[args["work_fn"]]
    least, bounds = 0.0, {}
    for e in spans:
        s, bound = work.roofline_seconds(*fn(facts["config"], e.attrs), peaks)
        least += s
        bounds[bound] = bounds.get(bound, 0) + 1
    print(f"bench: roofline {'+'.join(args['phases'])}: {len(spans)} steps, "
          f"{took:.4f} s on the device, least {least:.4f} s, bound by "
          f"{bounds}", flush=True)
    return 100.0 * least / took
