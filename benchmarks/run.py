#!/usr/bin/env python3
"""One cell, once, in a new process:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of stdout is the result, one JSON object with
exactly `correct`, `attempted`, `failed`, `metrics`, `device` (and
`breakdown` in a traced run); everything else goes to stderr. With no
accelerator, too few chips or no program beside it the run exits with
another code than 0 and prints no result: nothing falls back to the CPU.

The harness is driven by data. `BENCHMARK.json` names the cell's
configuration (`benchmarks/configs/<name>.json`), its traffic mix
(`benchmarks/traffic/<name>.json`) and its metrics; each per-layer metric
has a reader of its own (`benchmarks/layer_metrics/<name>.py`). A cell is
added by files and one entry of `workloads`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

T_PROCESS_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import lastline  # noqa: E402

# a run has 360 s; leave before the driver has to end it
RUN_LIMIT_S = 340.0


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: Dict[str, Any], workload: str, section: str
                 ) -> List[Dict[str, Any]]:
    """The metrics of `section` that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


class Rehearsal:
    """Changes to a cell's data files for one run, reached only by calling
    `main(argv, rehearsal=...)` from Python: no argument or environment
    variable of the command leads here. Two callers: the tests
    (tests/yardstick/rehearse.py: a toy size on the CPU, `cpu=True`, so
    that the whole command can be exercised without a chip), and
    `benchmarks/sweep.py` (other rates on the chip, to find a cell's knee
    once)."""

    def __init__(self, conf_overrides: Dict[str, Any],
                 traffic_overrides: Dict[str, Any], cpu: bool = True):
        self.conf_overrides = conf_overrides
        self.traffic_overrides = traffic_overrides
        self.cpu = cpu


def main(argv: Optional[List[str]] = None,
         rehearsal: Optional[Rehearsal] = None) -> None:
    lastline.take_stdout()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    watchdog = threading.Timer(
        RUN_LIMIT_S, lastline.fail,
        args=(f"the run passed {RUN_LIMIT_S:.0f} s", 3))
    watchdog.daemon = True
    watchdog.start()
    try:
        result = run_cell(args, rehearsal)
    except BaseException:  # noqa: BLE001 - every failure ends the run
        traceback.print_exc()
        lastline.fail(f"cell {args.workload} raised (traceback above)")
    lastline.emit_and_exit(result, traced=bool(args.trace))


def run_cell(args: argparse.Namespace, rehearsal: Optional[Rehearsal]
             ) -> Dict[str, Any]:
    from benchmarks.harness import traffic as traffic_mod
    from benchmarks.harness.configs import load_config

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[args.workload]
    conf = load_config(cell["config"])
    traffic = traffic_mod.load_json("traffic", cell["traffic"])
    if rehearsal is not None:
        conf = {**conf, **rehearsal.conf_overrides}
        traffic = {**traffic, **rehearsal.traffic_overrides}
    for key, value in (traffic.get("env") or {}).items():
        os.environ[key] = str(value)  # the program's deployment settings
    if args.trace:
        # keep every request's phases with their times, for the gaps
        os.environ["RAY_TPU_REQTRACE_SAMPLE"] = "1.0"
        os.environ["RAY_TPU_REQTRACE_KEPT"] = "100000"
    os.environ.setdefault("RAY_TPU_REQTRACE_WINDOW", "100000")

    run = {
        "cell": cell, "conf": conf, "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace),
        "rehearsal": rehearsal is not None and rehearsal.cpu,
        "t_start": T_PROCESS_START,
        "end_to_end": cell_metrics(bench, cell["name"], "end_to_end"),
        "per_layer": cell_metrics(bench, cell["name"], "per_layer"),
    }
    if traffic["kind"] == "train":
        from benchmarks.harness.train_cell import run_train as runner
    elif traffic["kind"] == "serve":
        from benchmarks.harness.serve_cell import run_serve as runner
    else:
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    return runner(run)


if __name__ == "__main__":
    main()
