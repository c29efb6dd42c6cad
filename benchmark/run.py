"""The benchmark's one command.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: load the cell (``BENCHMARK.json`` names its configuration
and traffic mix; their files are found by name), build the system through
its normal entry points, warm the cell's own shapes, measure ``--seconds``,
check the outputs against the plain reference, and print ONE JSON object
as the last line of stdout: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, when traced, ``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, each taken by the reader its
``layer_metrics/<metric>.json`` names.

It measures on a TPU or not at all: without one, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.  ``--rehearse``
(CPU, each file's ``tiny`` sizes, kernels interpreted) walks the same code
for debugging; its last line carries no value, because a CPU number is
never written under a device metric's name.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

OUT_DIR = os.path.join(ROOT, ".bench_out")      # traces; git-ignored


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_plugin(kind: str, name: str):
    """``readers/<name>.py`` or ``drivers/<name>.py``, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge_tiny(d: dict) -> dict:
    """A file's ``tiny`` group laid over it (rehearsal sizes)."""
    out = dict(d)
    for k, v in d.get("tiny", {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class Context:
    """What a driver gets: the cell's data, the clock of the window, the
    benchmark's own spans, and the profiler for the traced part."""

    def __init__(self, cell, config, traffic, args):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.trace_seconds = min(float(traffic.get("trace_seconds", 6.0)),
                                 self.seconds)
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        self.bench_spans = {}            # name -> [(start, seconds)]
        self.setup_s = None
        self.t0 = self.t_end = None
        self._tracing = False
        self._traced = False
        self._window_ann = None
        self.trace_started_at = None     # host time just before the profiler

    def log(self, *a):
        print("bench:", *a, flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark span around a call into the system: kept for the
        ``bench_span`` reader and, while the profiler runs, written into
        its trace so idle gaps on the device can be named."""
        import jax
        ann = jax.profiler.TraceAnnotation("bench:" + name) \
            if self._tracing else contextlib.nullcontext()
        t = time.monotonic()
        with ann:
            yield
        if self.t0 is not None:
            self.bench_spans.setdefault(name, []).append(
                (t, time.monotonic() - t))

    def begin_window(self):
        self.t0 = time.monotonic()
        self.t_end = self.t0 + self.seconds
        self.setup_s = self.t0 - T_PROCESS_START
        return self.t0

    def in_window(self) -> bool:
        """True while the window lasts.  In a traced run it also starts
        the profiler ``trace_seconds`` before the end, so that writing
        the trace out falls after the window."""
        now = time.monotonic()
        if now >= self.t_end:
            return False
        if self.trace and not self._traced and \
                now >= self.t_end - self.trace_seconds:
            self.trace_started_at = now
            self._start_trace()
        return True

    def _start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = self._traced = True
        self._window_ann = jax.profiler.TraceAnnotation("bench:window")
        self._window_ann.__enter__()

    def end_window(self) -> float:
        """Seconds the window really lasted (its last step runs out)."""
        import jax
        elapsed = time.monotonic() - self.t0
        if self._tracing:
            self._window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._tracing = False
        return elapsed


def device_block(chips: int, trace_summary) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": str(devs[0].platform), "kind": str(devs[0].device_kind),
           "count": len(devs), "memory_peak_bytes": peak}
    if trace_summary is not None:
        out["busy_s"] = trace_summary["busy_s"]
        out["window_s"] = trace_summary["window_s"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="a.b=v",
                    help="override a traffic parameter (knee_sweep.py)")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]
    config = load_json(HERE, "configs", f"{cell['config']}.json")
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    if args.rehearse:
        config, traffic = merge_tiny(config), merge_tiny(traffic)

    for item in args.set:            # a sweep's one-off parameter
        path, _, val = item.partition("=")
        node = traffic
        *head, leaf = path.split(".")
        for k in head:
            node = node[k]
        node[leaf] = json.loads(val)

    import jax
    devs = jax.devices()
    if args.rehearse:
        if devs[0].platform == "tpu":
            sys.exit("--rehearse is for a machine without the chip")
    elif devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        sys.exit(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
                 f"chip(s); JAX found {len(devs)} x {devs[0].platform!r} "
                 f"({devs[0].device_kind!r}). Nothing was measured.")
    from hetu_tpu.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()   # JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache

    ctx = Context(cell, config, traffic, args)
    ctx.log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
            f"trace {args.trace}; {len(devs)} x {devs[0].device_kind}; "
            f"compile cache {cache}")
    driver = load_plugin("drivers", traffic["driver"])
    result = driver.run(ctx)
    # result: correct, attempted, failed, end_to_end {name: value}, facts
    result["end_to_end"]["setup_s"] = ctx.setup_s
    facts = result["facts"]
    facts.update(config=config, traffic=traffic, bench_spans=ctx.bench_spans,
                 chips=cell["chips"], device_kind=str(devs[0].device_kind))

    summary = None
    if ctx.trace and not args.rehearse:
        import xplane
        summary = xplane.summarize(xplane.load(xplane.find_xplane(
            ctx.trace_dir)), cell["chips"])
    facts["trace"] = summary

    def listed(m):
        return "workloads" not in m or cell["name"] in m["workloads"]

    metrics = {}
    if not ctx.trace:
        for m in bench["end_to_end"]:
            if listed(m):
                metrics[m["name"]] = {"value": result["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if not listed(m):
                continue
            spec = load_json(HERE, "layer_metrics", f"{m['name']}.json")
            value = load_plugin("readers", spec["reader"]).read(
                spec.get("args", {}), facts)
            if value is not None:    # nothing to read: left out of the line
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device_block(cell["chips"], summary)}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    if args.rehearse:
        print("bench: rehearsal values (CPU, not measurements): " +
              json.dumps({k: v["value"] for k, v in metrics.items()}),
              file=sys.stderr, flush=True)
        for v in metrics.values():
            v["value"] = None
        line["rehearsal"] = True
    print("bench: notes " + json.dumps(result.get("notes", {})), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
