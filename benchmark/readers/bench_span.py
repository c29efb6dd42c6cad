"""Reader ``bench_span``: a statistic of the benchmark's own spans (taken
on the host clock around calls into the system).
args: name; stat = mean_ms_per_step | median_ms | p90_ms
      (mean_ms_per_step divides the spans' total by the window's steps)."""
import stats


def read(args, facts):
    spans = facts["bench_spans"].get(args["name"])
    if not spans:
        return None
    ms = [d * 1e3 for _, d in spans]
    if args["stat"] == "mean_ms_per_step":
        return sum(ms) / facts["values"]["steps"]
    if args["stat"] == "median_ms":
        return stats.median(ms)
    if args["stat"] == "p90_ms":
        return stats.percentile(ms, 90)
    raise ValueError(args["stat"])
