"""Reader ``engine_counter_rest``: what is left of a whole after a part,
from the engine's own counters (``Engine.metrics_summary()``, reset at the
window's start): ``scale * (1 - sum(part) / sum(whole))``.
args: part [counter names], whole [counter names], scale."""


def read(args, facts):
    c = facts.get("counters") or {}
    if not all(k in c for k in args["part"] + args["whole"]):
        return None
    whole = sum(c[k] for k in args["whole"])
    if not whole:
        return None
    return args.get("scale", 1.0) * (1.0 - sum(c[k] for k in args["part"])
                                     / whole)
