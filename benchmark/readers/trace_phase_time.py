"""Reader ``trace_phase_time``: device time by model phase — the self
time of chip 0's operations in the traced window whose HLO instruction
the program's ``obs.device_phases(executable)`` maps to ``phase``.  The
executables are those the window's program spans name (``exec=``);
``phase`` "unmapped" collects the operations found in no map and those
the map could not name.
args: phase; as = share_of_busy (%).
A program without ``obs.device_phases`` or without ``exec=`` spans in
the window gives None.
"""
import xplane

UNMAPPED = "unmapped"


def self_time_by_name(events) -> dict:
    """instruction name -> ns of SELF time: an operation's duration
    minus what the operations nested inside it cover (as
    ``xplane.self_times``, but keyed by the whole instruction name)."""
    out, stack = {}, []                  # stack: [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            out[name] = out.get(name, 0) + max(self_ns, 0)

    for s, d, name, _ in sorted(events, key=lambda e: (e[0], -e[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, name, d])
    close(float("inf"))
    return out


def time_by_phase(self_ns: dict, phase_of: dict) -> dict:
    """phase -> ns, from ``self_time_by_name``'s table; a name missing
    from ``phase_of`` is ``unmapped``."""
    out = {}
    for name, ns in self_ns.items():
        ph = phase_of.get(name, UNMAPPED)
        out[ph] = out.get(ph, 0) + ns
    return out


def _phase_map(facts):
    try:
        from hetu_tpu.obs import device_phases
    except ImportError:
        return None
    t0, t1 = facts["values"].get("host_window", (float("-inf"),
                                                 float("inf")))
    names = sorted({e.attrs["exec"] for e in facts.get("host_spans", [])
                    if "exec" in e.attrs and t0 <= e.ts < t1})
    merged = {}
    for name in names:
        try:
            phases = device_phases(name)
        except Exception as e:       # said, not raised: the line goes on
            print(f"bench: device_phases({name!r}) failed: "
                  f"{type(e).__name__}: {e}", flush=True)
            continue
        for inst, ph in phases.items():
            merged.setdefault(inst, ph)
    return merged or None


def read(args, facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    if "_time_by_phase" not in facts:            # one compile per run
        phase_of = _phase_map(facts)
        facts["_time_by_phase"] = None
        if phase_of is not None:
            self_ns = self_time_by_name(tr["events"])
            table = facts["_time_by_phase"] = time_by_phase(self_ns, phase_of)
            by_time = lambda d: sorted(d.items(),        # noqa: E731
                                       key=lambda kv: -kv[1])
            print("bench: device self time by phase (s): " + ", ".join(
                f"{k} {v / 1e9:.4f}" for k, v in by_time(table)),
                flush=True)
            print("bench: longest instructions (phase s): " + ", ".join(
                f"{n} {phase_of.get(n, UNMAPPED)} {v / 1e9:.4f}"
                for n, v in by_time(self_ns)[:12]), flush=True)
    table = facts["_time_by_phase"]
    busy = xplane.busy_ns(tr["events"])
    if table is None or not busy:
        return None
    if args["as"] == "share_of_busy":
        return 100.0 * table.get(args["phase"], 0) / busy
    raise ValueError(args["as"])
