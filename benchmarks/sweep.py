#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip: the cell run at a few
fixed rates, each in a process of its own, with what tells a growing
backlog from a steady one printed for each. The number found is written
into the traffic file by hand; no run of the benchmark searches for it.

    python3 benchmarks/sweep.py --workload mistral-chat --seconds 30 --rates 2 3 4 5

With `--one RATE` it runs that rate in this process (what the loop above
starts for each rate)."""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def summary(workload: str, seed: int, rate: float) -> str:
    path = os.path.join(ROOT, "chiprun_out",
                        f"bench_{workload}_s{seed}_t0.json")
    with open(path) as f:
        rec = json.load(f)
    n = rec["numbers"]
    return (f"rate {rate:6.2f}/s: elapsed {rec['elapsed_s']:6.1f} s, "
            f"failed {n['failed']}/{n['attempted']}, ttft p50 "
            f"{n.get('ttft_p50_ms', -1):8.1f} p95 "
            f"{n.get('ttft_p95_ms', -1):8.1f} ms, by thirds "
            f"{[round(x) for x in rec['ttft_p50_by_third_ms']]}, itl p50 "
            f"{n.get('itl_p50_ms', -1):6.1f} p95 "
            f"{n.get('itl_p95_ms', -1):6.1f} ms, in flight at most "
            f"{rec['router']['max_pending']}, shed {rec['router']['shed']}, "
            f"tokens/s {rec['tokens_out_per_s']:.0f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=101)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--one", type=float)
    args = ap.parse_args()
    if args.one is not None:
        from benchmarks import run

        run.main(["--workload", args.workload, "--seed", str(args.seed),
                  "--seconds", str(args.seconds), "--trace", "0"],
                 rehearsal=run.Rehearsal({}, {"rate_rps": args.one},
                                         cpu=False))
        return
    for rate in args.rates:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seconds", str(args.seconds), "--seed",
             str(args.seed), "--one", str(rate)],
            cwd=ROOT, stdout=subprocess.PIPE, text=True)
        if proc.returncode != 0:
            print(f"rate {rate}: the run failed ({proc.returncode})",
                  flush=True)
            continue
        print(summary(args.workload, args.seed, rate), flush=True)
        print("   " + proc.stdout.strip()[:400], flush=True)


if __name__ == "__main__":
    main()
