"""Measure a cell's run-to-run spread the way the bounds are set from:
two sets of N runs with the same seeds in both, each run a new
``run.py`` process (this script never touches JAX), then per metric the
wider of the two sets' spreads (inter-quartile distance over the median,
``statistics.quantiles(n=4)``).  Optionally one traced run at the end.

  python3 benchmark/spread_sets.py --workload cgpt590m.train --runs 6 \
      --seconds 51 --traced --out chiprun_out/sets_train.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import stats  # noqa: E402

SEED0 = 2147483700          # large on purpose: past 31 signed bits


def one(workload, seed, seconds, trace, out, tag):
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)], capture_output=True, text=True)
    rec = {"tag": tag, "seed": seed, "rc": p.returncode}
    if p.returncode == 0:
        lines = p.stdout.strip().splitlines()
        rec["line"] = json.loads(lines[-1])
        rec["notes"] = next((json.loads(l[len("bench: notes "):])
                             for l in lines if l.startswith("bench: notes ")),
                            None)
    else:
        rec["stderr"] = p.stderr[-2000:]
    with open(out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced", action="store_true")
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    sets = []
    for s in range(a.sets):
        vals = {}
        for i in range(a.runs):
            rec = one(a.workload, SEED0 + 17 * i, a.seconds, 0, a.out,
                      f"set{s}")
            if rec["rc"] or not rec["line"]["correct"]:
                print(f"set {s} run {i}: rc {rec['rc']} "
                      f"{rec.get('stderr', 'correct: false')} "
                      f"{json.dumps(rec.get('notes'))}", flush=True)
                if s == 0 and i == 0:
                    return 1        # nothing to measure: stop the call
                continue
            for k, m in rec["line"]["metrics"].items():
                vals.setdefault(k, []).append(m["value"])
            print(f"set {s} run {i} seed {rec['seed']}: " + ", ".join(
                f"{k} {m['value']:.6g}"
                for k, m in rec["line"]["metrics"].items()) +
                f"; failed {rec['line']['failed']}/"
                f"{rec['line']['attempted']}", flush=True)
        sets.append(vals)
    for k in sets[0]:
        row = []
        for s, vals in enumerate(sets):
            v = vals.get(k, [])
            if len(v) >= 2:
                row.append(f"set{s}: median {statistics.median(v):.6g} "
                           f"spread {100 * stats.iqr_share(v):.3f} %")
        print(f"{a.workload} {k}: " + "; ".join(row), flush=True)
    if a.traced:
        rec = one(a.workload, SEED0 + 5, a.seconds, 1, a.out, "traced")
        print("traced:", json.dumps(rec.get("line", rec))[:6000], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
