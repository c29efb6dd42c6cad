"""Reader ``trace_idle``: device idle time inside the traced window (no
operation running on chip 0), per host span of a given name — the host's
gap per step.  args: per_span (a ``bench:`` span name, e.g. g.run)."""
import xplane


def read(args, facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    steps = xplane.spans_in_window(tr, args["per_span"])
    if not steps:
        return None
    idle_s = tr["window_s"] - tr["busy_s"]
    return idle_s * 1e3 / steps
