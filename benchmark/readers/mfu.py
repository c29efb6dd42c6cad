"""Reader ``mfu``: model FLOP/s utilization, end to end — tokens/s of the
window times the FLOPs one token needs (``work.py``, no recomputed
operation) over chips times the published peak.  Not a kernel's roofline
share and blind to idle time.  args: work_fn."""
import work


def read(args, facts):
    if facts.get("trace") is None:       # a device number: chip runs only
        return None
    v = facts["values"]
    flops = work.WORK_FNS[args["work_fn"]](facts["config"], v["seq_len"])
    peak = work.peaks_for(facts["device_kind"])["flops_bf16"]
    return 100.0 * v["tokens_per_s"] * flops / (facts["chips"] * peak)
