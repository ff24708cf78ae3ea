"""The fused cross-entropy's backward ALONE on the chip: `fused_ce_dx` and
`fused_ce_dw` of `ops/fused_ce.py` `_ce_bwd_pallas` at GPT-2's training
shape (32 x 1,024 rows of 768, a vocabulary of 50,257 padded to 50,304,
bfloat16), whole and, with `--budgets`, walked in super-blocks of rows
under a smaller budget for P (PERF.md section 6, PR 59).

    chiprun --chips 1 -- python3 examples/fused_ce_backward_sweep.py \
        [--root _checkout/parent] [--budgets 0,1000000000]

A time is the DEVICE's: the backward runs `CALLS` times under one
profiler trace; `ms` is the mean duration of its program (the one-hot
terms and the scatter in XLA included), `dx_ms` and `dw_ms` the summed
durations of each kernel's events a call, `floor_ms` what the 4 N V d
of dx's and dW's products need at 197 TFLOP/s. `--root` takes
`ray_tpu` from another checkout (the parent's backward has no budget:
leave `--budgets` out). Fails without a TPU; `--toy 1` walks the same
code at a toy shape in interpret mode, on any backend, without the
profiler, and gives no times.
"""
from __future__ import annotations

import argparse
import glob
import inspect
import json
import os
import shutil
import sys
import tempfile

PEAK_FLOPS = 197e12
CALLS = 5
KERNELS = ("fused_ce_dx", "fused_ce_dw")


def device_times(trace_dir):
    """{program: ([durations of its events], {kernel: [summed durations of
    its events inside each]})} from the trace's first device plane, ms."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    plane = min((p for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
    lines = {line.name: [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events] for line in plane.lines}
    times = {}
    for name, start, dur in lines["XLA Modules"]:
        if not name.startswith("jit_bwd_"):
            continue
        whole, kernels = times.setdefault(
            name.split("(")[0][len("jit_"):], ([], {k: [] for k in KERNELS}))
        whole.append(dur / 1e6)
        for k in KERNELS:
            kernels[k].append(sum(
                d for n, s, d in lines["XLA Ops"]
                if k in n and start <= s < start + dur) / 1e6)
    return times


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default="", help="another checkout's ray_tpu")
    ap.add_argument("--budgets", default="0",
                    help="bytes of P at once; 0: the module's own")
    ap.add_argument("--toy", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.root) if args.root else here)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import fused_ce

    dev = jax.devices()[0]
    toy = bool(args.toy)
    if not toy and dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    n, d, v, vocab = (256, 128, 768, 700) if toy else (
        32 * 1024, 768, 50304, 50257)
    block_n = 64 if toy else fused_ce.DEFAULT_BLOCK_N
    dtype = jnp.bfloat16
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(key[0], (n, d), jnp.float32).astype(dtype)
    w = (0.02 * jax.random.normal(key[1], (v, d), jnp.float32)).astype(dtype)
    t = jax.random.randint(key[2], (n,), 0, vocab)
    g = jax.random.uniform(key[3], (n,), jnp.float32) / n
    lse = jax.jit(lambda x, w: fused_ce._ce_reference(x, w, t, vocab)[1]
                  if toy else fused_ce._ce_fwd_pallas(
                      x, w, t, vocab, block_n, fused_ce._pick_block_v(v),
                      False)[1])(x, w)
    has_budget = "p_budget_bytes" in inspect.signature(
        fused_ce._ce_bwd_pallas).parameters
    jitted = {}
    for budget in [int(b) for b in args.budgets.split(",")]:
        extra = (budget,) if budget and has_budget else ()

        def bwd(x, w, lse, extra=extra):
            return fused_ce._ce_bwd_pallas(
                x, w, t, lse, g, vocab, block_n, fused_ce._pick_block_v(v),
                toy, *extra)

        bwd.__name__ = f"bwd_{budget}"
        jitted[bwd.__name__] = (budget, jax.jit(bwd))
    records = []
    for name, (budget, fn) in jitted.items():
        dx, dw = fn(x, w, lse)
        rec = {"shape": [n, d, v], "budget": budget, "root": args.root,
               "floor_ms": 1e3 * 4 * n * v * d / PEAK_FLOPS,
               "dx_abs_sum": float(jnp.abs(dx.astype(jnp.float32)).sum()),
               "dw_abs_sum": float(jnp.abs(dw).sum())}
        del dx, dw
        if not toy:
            rec["temp_bytes"] = int(fn.lower(x, w, lse).compile()
                                    .memory_analysis().temp_size_in_bytes)
        records.append(rec)
    if not toy:
        trace_dir = tempfile.mkdtemp(prefix="fused_ce_backward_sweep_")
        try:
            jax.profiler.start_trace(trace_dir)
            for _budget, fn in jitted.values():
                for _ in range(CALLS):
                    jax.block_until_ready(fn(x, w, lse))
            jax.profiler.stop_trace()
            times = device_times(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        for rec, name in zip(records, jitted):
            whole, kernels = times[name]
            rec.update(ms=float(np.mean(whole)), calls=len(whole),
                       dx_ms=float(np.mean(kernels["fused_ce_dx"])),
                       dw_ms=float(np.mean(kernels["fused_ce_dw"])))
    for rec in records:
        print(json.dumps(rec), flush=True)
    out = args.out or "chiprun_out/fused_ce_backward_sweep.json"
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"toy": toy, "device": {"platform": dev.platform,
                                          "kind": dev.device_kind},
                   "records": records}, f, indent=1)


if __name__ == "__main__":
    main()
