"""Reader ``trace_op_time``: time of the device operations whose name or
scope matches a pattern, on chip 0 inside the traced window.
args: match (regex); as = share_of_busy (%) | ms_per_span (with per_span)."""
import xplane


def read(args, facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    ns = xplane.op_time_ns(tr["events"], args["match"])
    if args["as"] == "share_of_busy":
        busy = xplane.busy_ns(tr["events"])
        return 100.0 * ns / busy if busy else None
    if args["as"] == "ms_per_span":
        n = xplane.spans_in_window(tr, args["per_span"])
        return ns / 1e6 / n if n else None
    raise ValueError(args["as"])
