"""Reduction from a profiler trace to numbers: device busy time, per-name
operation time, idle gaps and what the host was doing in them, exposed
collective time.  The benchmark's own, checked on the recorded traces in
``fixtures/`` by ``selftest.py``.

A trace is reduced in two steps.  ``load(path)`` reads the profiler's
``.xplane.pb`` with nothing but JAX (``jax.profiler.ProfileData``) into
plain tuples; every function after that works on those tuples, so it can
be checked by hand on a small list.

  device event  (start_ns, dur_ns, name, text)   one executed operation on
                one chip's "XLA Ops" line.  The profiler names an event by
                its whole HLO instruction (``%fusion.12 = bf16[..] fusion(
                ...operands)``): ``name`` is the instruction's own name
                (``fusion.12``; a Pallas kernel's carries the kernel's
                ``name``, e.g. ``jvp_flash_fwd_.35``) and ``text`` the whole
                instruction.  Matching is on ``name``: the text also names
                the operands, so a consumer of a kernel's output would match
                the kernel's pattern.
  host span     (start_ns, dur_ns, name)  a ``bench:*`` TraceAnnotation
                written by the benchmark's drivers around their calls.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
# HLO opcodes of cross-chip exchange as they appear in operation names
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast")


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{"devices": {chip index: [device events]}, "host": [host spans]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    events.append((int(ev.start_ns), int(ev.duration_ns),
                                   short_name(ev.name), ev.name))
            devices[int(m.group(1))] = sorted(events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((int(ev.start_ns), int(ev.duration_ns),
                                     ev.name[len(HOST_PREFIX):]))
    return {"devices": devices, "host": sorted(host)}


def save_fixture(trace: dict, path: str, max_events: int) -> None:
    """The first ``max_events`` device events per chip and the host spans
    that overlap them, as JSON — a recorded trace small enough to keep."""
    devices = {str(k): v[:max_events] for k, v in trace["devices"].items()}
    end = max((e[0] + e[1] for v in devices.values() for e in v), default=0)
    host = [h for h in trace["host"] if h[0] <= end]
    with gzip.open(path, "wt") as f:
        json.dump({"devices": devices, "host": host}, f)


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "host": [tuple(h) for h in raw["host"]]}


# -- interval arithmetic ------------------------------------------------------

def union(intervals):
    """Merged, sorted, disjoint [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(events, t0: int, t1: int):
    """Device events cut to the window [t0, t1)."""
    out = []
    for s, d, name, text in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b - a, name, text))
    return out


def spans(events):
    return [(s, s + d) for s, d, *_ in events]


# -- the numbers --------------------------------------------------------------

def busy_ns(events) -> int:
    """Time in which at least one operation ran (union, so a ``while`` and
    the operations of its body count once)."""
    return total(union(spans(events)))


def op_time_ns(events, pattern: str) -> int:
    """Time covered by the operations whose name matches
    ``pattern`` (union: a matching parent and its matching children count
    once)."""
    rx = re.compile(pattern)
    return total(union(spans([e for e in events if rx.search(e[2])])))


def op_calls(events, pattern: str):
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[2])]


def self_times(events) -> dict:
    """name -> seconds of SELF time: an operation's duration minus the
    part covered by operations nested inside it (a ``while`` wrapper is
    left with what its body does not cover).  Trailing ``.N`` instance
    numbers are dropped so the calls of one kernel add up."""
    out = {}
    stack = []                       # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, self_ns = stack.pop()
            out[name] = out.get(name, 0) + max(self_ns, 0)

    for s, d, name, _ in sorted(events, key=lambda e: (e[0], -e[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, re.sub(r"[_.\d]+$", "", name) or name, d])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def idle_gaps(events, t0: int, t1: int):
    """Disjoint [start, end) intervals of the window with no operation."""
    return subtract([(t0, t1)], union(spans(clip(events, t0, t1))))


def label_gaps(gaps, host_spans) -> dict:
    """label -> seconds of idle time, each gap split among the host spans
    open during it (innermost first is not known: the SHORTEST covering
    span wins each instant); time under no span is ``unlabelled``."""
    out = {}
    ordered = sorted(host_spans, key=lambda h: h[1])      # shortest first
    for gap in gaps:
        left = [gap]
        for s, d, name in ordered:
            if not left:
                break
            hit = [(max(a, s), min(b, s + d)) for a, b in left
                   if min(b, s + d) > max(a, s)]
            if hit:
                out[name] = out.get(name, 0) + total(hit)
                left = subtract(left, union(hit))
        if left:
            out["unlabelled"] = out.get("unlabelled", 0) + total(left)
    return {k: v / 1e9 for k, v in out.items()}


def exposed_collective_ns(events) -> int:
    """Time in which a collective ran on the chip and no other operation
    did: the exchange the compute did not hide."""
    coll = [e for e in events if COLLECTIVE.search(e[2])]
    rest = [e for e in events if not COLLECTIVE.search(e[2])
            and not e[2].startswith(("while", "conditional", "call"))]
    return total(subtract(union(spans(coll)), union(spans(rest))))


def window_of(trace: dict, name: str = "window"):
    """[t0, t1) of the ``bench:window`` host span; where the host and the
    device clocks were not written on one base (no device event inside
    it), the extent of the device events themselves."""
    evs = [e for v in trace["devices"].values() for e in v]
    lo = min(e[0] for e in evs)
    hi = max(e[0] + e[1] for e in evs)
    for s, d, n in trace["host"]:
        if n == name and s < hi and s + d > lo:
            return s, s + d
    return lo, hi


def spans_in_window(summary: dict, name: str) -> int:
    """How many ``bench:<name>`` host spans began inside the traced window
    (the per-step / per-call divisor of the trace readers)."""
    return sum(1 for s, _, n in summary["host"]
               if n == name and summary["t0"] <= s < summary["t1"])


def summarize(trace: dict, chips: int) -> dict:
    """What the result line and the readers need from one traced window."""
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane: no operation "
                         "ran on a TPU in the traced window")
    t0, t1 = window_of(trace)
    used = sorted(trace["devices"])[:chips]
    per_chip = {c: clip(trace["devices"][c], t0, t1) for c in used}
    busy = [busy_ns(ev) for ev in per_chip.values()]
    first = per_chip[used[0]]
    ops = sorted(self_times(first).items(), key=lambda kv: -kv[1])
    gaps = label_gaps(idle_gaps(first, t0, t1), trace["host"])
    return {
        "t0": t0, "t1": t1, "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "events": first,            # chip 0, clipped to the window
        "host": trace["host"],
        "device_ops": [[k, v] for k, v in ops[:10]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }
