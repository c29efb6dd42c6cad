"""Find the knee of an open-loop serving cell again, the way PR 24 did.

  python3 benchmark/knee_sweep.py --workload cgpt590m.serve-chat \
      --rates 2 3 4 5 6 8 --seconds 30 --seed 1

One ``run.py`` process per rate (this script never touches JAX, so each
child has the chip to itself), the cell's traffic with only
``arrivals.rate_per_s`` overridden.  Knee = the highest swept rate at
which at most 2 due requests are still waiting when the window closes,
none of the judged requests missed its first token and the batch is not
full (PERF.md section 4 says why the weaker "waiting <= max_batch" failed).  The cell's fixed rate is 0.8 x the knee,
written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    print("| rate/s | waiting at end | running at end | ttft_p90_ms | "
          "tbt_p95_ms | out tokens/s | missed/judged |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for rate in args.rates:
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", str(args.seed), "--seconds",
             str(args.seconds), "--trace", "0", "--set",
             f"arrivals.rate_per_s={rate}"],
            capture_output=True, text=True)
        if out.returncode:
            print(f"| {rate} | run failed rc {out.returncode}: "
                  f"{out.stderr.strip().splitlines()[-1:]} |")
            continue
        notes = next(json.loads(l[len("bench: notes "):])
                     for l in out.stdout.splitlines()
                     if l.startswith("bench: notes "))
        line = json.loads(out.stdout.strip().splitlines()[-1])
        m = line["metrics"]
        print(f"| {rate} | {notes['waiting_at_end']} | "
              f"{notes['running_at_end']} | {notes['ttft_p90_ms']:.1f} | "
              f"{notes['tbt_p95_ms']:.1f} | "
              f"{notes['out_tokens_per_s']:.1f} | "
              f"{line['failed']}/{line['attempted']} | setup "
              f"{m['setup_s']['value']:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
