#!/usr/bin/env python3
"""What do a serving cell's limits tell apart that belongs to a model
with a learned sparse-attention indexer? Once, on the chip, outside any
measured window: the serving cell's own reference check
(`serve_cell.reference_check`'s prompts, lengths and gaps) with the ENGINE
as configured and the float32 reference given a full layer that does
something else:

    --selection dense   every query attends every row it can see
    --selection first   every query attends the FIRST `index_topk` rows,
                        not the best (a wrong selection)
    --selection topk    the reference as the cell runs it (the control)

    python3 benchmarks/probe_dsa_selection.py --workload dots3-note-docnotes-32k --seed 1300000003 --selection dense

The gap between a model that selects and one that does not is the same
whichever side is given the fault, and the engine has no switch for it
(`references/<family>.py` reads `conf["reference_selection"]`, which no
configuration file holds). The gaps it prints stand beside the configured
ones of the same seeds in the traffic file's `tolerances.why`. The 8-bit
reading of the same cell is `probe_state_precision.py --what weights`.
One engine a process: one call per seed; every `--selection` given is
scored against the one engine's tokens."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--selection", nargs="+", default=["dense", "first"],
                    choices=("topk", "dense", "first"))
    args = ap.parse_args()

    import numpy as np

    from benchmarks import run as run_mod
    from benchmarks.harness import common, reference, traffic
    from benchmarks.harness.configs import (init_params, load_config,
                                            program_config)

    bench = run_mod.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    mix = traffic.load_json("traffic", cell["traffic"])
    conf = load_config(cell["config"])
    run = {"cell": cell, "conf": conf, "traffic": mix, "seed": args.seed,
           "rehearsal": False, "t_start": time.perf_counter()}
    common.require_devices(run)
    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = program_config(conf, int(mix["max_seq_len"]))
    params = init_params(conf, cfg, args.seed)
    engine = ContinuousBatchingEngine(params, cfg,
                                      max_batch=int(mix["max_batch"]))
    n_new = int(mix.get("reference_new_tokens", 8))
    length = traffic.prompt_lengths(mix)[0]
    rows = []
    try:
        for k in range(2):      # serve_cell.reference_check's two prompts
            prompt = traffic.prompt_tokens(args.seed, 20_000_000 + k,
                                           length, cfg.vocab_size)
            stream = engine.stream(prompt, n_new, timeout_s=600.0)
            emitted = [int(t) for t in stream]
            rows.append((prompt, emitted, [float(s) for s in stream.scores]))
    finally:
        engine.stop()
    tol = mix["tolerances"]
    for how in args.selection:
        given = dict(conf, reference_selection=how)
        gaps, margins = [], []
        for prompt, emitted, scores in rows:
            for s, r in zip(scores, reference.score_emitted(
                    given, params, prompt, emitted)):
                gaps.append(abs(s - r["logprob"]))
                margins.append(r["margin"])
        ok = (max(gaps) <= tol["logprob_abs"]
              and float(np.mean(gaps)) <= tol["logprob_mean_abs"]
              and max(margins) <= tol["margin_abs"])
        print(json.dumps({
            "workload": args.workload, "seed": args.seed,
            "reference_selection": how, "ok": ok,
            "worst_logprob_gap": max(gaps),
            "mean_logprob_gap": float(np.mean(gaps)),
            "worst_margin": max(margins)}), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
