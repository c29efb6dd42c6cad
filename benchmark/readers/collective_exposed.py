"""Reader ``collective_exposed``: time per step in which a collective ran
on chip 0 and no other operation did.  args: per_span."""
import xplane


def read(args, facts):
    tr = facts.get("trace")
    if tr is None or facts["chips"] < 2:
        return None
    n = xplane.spans_in_window(tr, args["per_span"])
    if not n:
        return None
    return xplane.exposed_collective_ns(tr["events"]) / 1e6 / n
