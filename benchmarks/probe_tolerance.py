#!/usr/bin/env python3
"""Does `correct` tell a lower precision from the stated one? Once, on the
chip, outside any measured window: the serving cell's own reference check
with the ENGINE's weights rounded to `--bits` bits (symmetric, one scale
per output channel, stored back in the served type) while the float32
reference keeps the true weights. The gaps it prints stand beside the
unrounded ones of the same seeds in the traffic file's `tolerances.why`.

    python3 benchmarks/probe_tolerance.py --workload mistral-chat --seed 1000000007 --bits 8

One engine a process (the slab is not given back): one call per seed."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def fake_quantize(params, bits: int):
    import jax
    import jax.numpy as jnp

    top = float(2 ** (bits - 1) - 1)

    def one(w):
        if w.ndim < 2:
            return w
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / top
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.round(w32 / scale) * scale).astype(w.dtype)

    return jax.jit(lambda p: jax.tree.map(one, p))(params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--bits", type=int, default=8)
    args = ap.parse_args()

    from benchmarks import run as run_mod
    from benchmarks.harness import common, serve_cell, traffic
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
    engine = ContinuousBatchingEngine(
        fake_quantize(params, args.bits), cfg,
        max_batch=int(mix["max_batch"]))
    try:
        check = serve_cell.reference_check(run, engine, params, cfg)
    finally:
        engine.stop()
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "bits": args.bits,
        **{k: check[k] for k in ("ok", "worst_logprob_gap",
                                 "mean_logprob_gap", "worst_margin")}}),
          flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
