#!/usr/bin/env python3
"""Does `correct` tell the next precision down from the stated one, in a
cell of a family that keeps recurrent state and sparse experts? Once, on
the chip, outside any measured window: the serving cell's own reference
check (`serve_cell.reference_check`'s prompts, lengths and gaps) with the
ENGINE one step lower and the float32 reference as it is:

    --what state     the recurrence state kept in bfloat16, not float32
    --what experts   the routed experts' weights rounded to --bits bits
                     (`probe_tolerance.fake_quantize`'s rounding: symmetric,
                     one scale per output channel, stored back in bf16)
    --what weights   EVERY matrix rounded so, as `probe_tolerance.py` does

    python3 benchmarks/probe_state_precision.py --workload nemotron-3-super-reason --seed 1000000007 --what state

`probe_tolerance.py` keeps both copies of the weights, which 10.9 GB of
them leave no room for; here they are rounded in place (the true values
are drawn again from the seed for the reference, after the engine has
given its memory back). The gaps it prints stand beside
the unrounded ones of the same seeds in the traffic file's
`tolerances.why`. One engine a process: one call per seed and --what."""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def round_in_place(params, bits: int, least_ndim: int):
    """The leaves of at least `least_ndim` axes (3: the routed experts'
    [experts, in, out] stacks; 2: every matrix) rounded in place, leaf by
    leaf (each donated); every other leaf as it was."""
    import jax
    import jax.numpy as jnp

    top = float(2 ** (bits - 1) - 1)

    @functools.partial(jax.jit, donate_argnums=0)
    def one(w):
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / top
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.round(w32 / scale) * scale).astype(w.dtype)

    return jax.tree.map(lambda w: one(w) if w.ndim >= least_ndim else w,
                        params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--what", choices=("state", "experts", "weights"),
                    required=True)
    ap.add_argument("--bits", type=int, default=8)
    args = ap.parse_args()

    import jax.numpy as jnp
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
    if args.what == "state":
        low_cfg, low = dataclasses.replace(
            cfg, state_dtype=jnp.bfloat16), params
    else:
        low_cfg, low = cfg, round_in_place(
            params, args.bits, 3 if args.what == "experts" else 2)
        del params      # the true values went with the rounding
    engine = ContinuousBatchingEngine(low, low_cfg,
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
    if args.what != "state":
        del engine, low, stream
        gc.collect()
        params = init_params(conf, cfg, args.seed)
    gaps, margins = [], []
    for prompt, emitted, scores in rows:
        for s, r in zip(scores, reference.score_emitted(conf, params, prompt,
                                                        emitted)):
            gaps.append(abs(s - r["logprob"]))
            margins.append(r["margin"])
    tol = mix["tolerances"]
    ok = (max(gaps) <= tol["logprob_abs"]
          and float(np.mean(gaps)) <= tol["logprob_mean_abs"]
          and max(margins) <= tol["margin_abs"])
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "what": args.what,
        "bits": 16 if args.what == "state" else args.bits, "ok": ok,
        "worst_logprob_gap": max(gaps),
        "mean_logprob_gap": float(np.mean(gaps)),
        "worst_margin": max(margins)}), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
