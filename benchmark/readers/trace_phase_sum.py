"""Reader ``trace_phase_sum``: the device self time of SEVERAL model
phases together, as ``trace_phase_time`` takes one (same table, same
units).  args: phases [names]; as = share_of_busy (%).
Nothing to read (no trace, a program without ``obs.device_phases``) gives
None."""
from readers import trace_phase_time


def read(args, facts):
    parts = [trace_phase_time.read({"phase": ph, "as": args["as"]}, facts)
             for ph in args["phases"]]
    return None if any(v is None for v in parts) else sum(parts)
