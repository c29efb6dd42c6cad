"""Reader ``engine_counter``: a ratio of sums of the engine's own
counters (``Engine.metrics_summary()``, reset at the window's start).
args: num [counter names], den [counter names], scale."""


def read(args, facts):
    c = facts.get("counters") or {}
    if not all(k in c for k in args["num"] + args["den"]):
        return None
    den = sum(c[k] for k in args["den"])
    if not den:
        return None
    return args.get("scale", 1.0) * sum(c[k] for k in args["num"]) / den
