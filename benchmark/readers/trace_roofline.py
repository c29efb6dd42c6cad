"""Reader ``trace_roofline``: a kernel's share of its roofline — the least
time the chip could take for the calls (larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s, both from ``work.py``) over the time the
trace shows for them.  Never clamped: above 100 % means the work is
over-counted or the time leaves part of it out.
args: match (regex on the operation NAME); flops_fn, bytes_fn (work.py);
      shape = micro_batch (each call covers one micro-batch of whole
      sequences; call_args are passed on) | per_step_contexts (each
      traced step covers the contexts the driver recorded)."""
import work
import xplane


def read(args, facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    calls = xplane.op_calls(tr["events"], args["match"])
    took = xplane.total(xplane.union(xplane.spans(calls)))
    if not took:
        return None
    peaks = work.peaks_for(facts["device_kind"])
    model, v = facts["config"], facts["values"]
    if args["shape"] == "micro_batch":
        kw = args.get("call_args", {})
        fl = work.WORK_FNS[args["flops_fn"]](model, v["micro_batch"],
                                             v["seq_len"], **kw)
        by = work.WORK_FNS[args["bytes_fn"]](model, v["micro_batch"],
                                             v["seq_len"], **kw)
        least, bound = work.roofline_seconds(fl, by, peaks)
        least *= len(calls)
    elif args["shape"] == "per_step_contexts":
        # (host time, context tokens, query tokens, attended pairs) per
        # engine step, by the driver; those inside the traced window
        t0, t1 = facts["values"]["trace_host_window"]
        least, bound = 0.0, "memory"
        for t, ctx, qt, pairs in facts["values"]["step_contexts"]:
            if t0 <= t < t1:
                s, bound = work.roofline_seconds(
                    work.WORK_FNS[args["flops_fn"]](model, pairs),
                    work.WORK_FNS[args["bytes_fn"]](model, ctx, qt), peaks)
                least += s * model["n_layer"]
    else:
        raise ValueError(args["shape"])
    facts.setdefault("roofline_bounds", {})[args["match"]] = bound
    print(f"bench: roofline {args['match']}: {len(calls)} calls, "
          f"{took / 1e9:.4f} s on the device, least {least:.4f} s, "
          f"bound by {bound}", flush=True)
    return 100.0 * least / (took / 1e9)
