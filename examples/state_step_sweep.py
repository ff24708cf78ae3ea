"""A tick's state step ALONE on the chip: the live-slot walk of
`ops/mamba2.py` (`ssd_step_live`, `--op mamba2`) or of `ops/kda.py`
(`kda_step_live`, `--op kda`) against the XLA expression over every slot
(`_step_all`) at the served slab's shape, by how many slots are live and
by the heads of a visit's block. The block `step_heads_block` chooses
stands on this table (PERF.md section 6, PR 47 and PR 51).

    chiprun --chips 1 -- python3 examples/state_step_sweep.py [--op kda]

A time is the DEVICE's: each path runs `CALLS` times under one profiler
trace with the state donated, as a tick donates it; `ms` is the mean
duration of its program (the map's sort, the small operands' transposes
and the mask on y included), `kernel_ms` of the kernel's own event, and
`floor_ms` what the live slots' state, read and written, needs at 819
GB/s. The compiled program is asked (`as_text()`) that the state is
aliased to its output and nowhere copied. Fails without a TPU; `--toy
1` walks the same code at toy widths in interpret mode, on any backend,
without the profiler, and gives no times.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import kda, mamba2

HBM_BYTES_PER_S = 819e9
CALLS = 10


def _mamba2_inputs(shape, seed=0):
    b, h, p, g, n = shape
    key = jax.random.split(jax.random.PRNGKey(seed), 7)
    rand = lambda k, *s: jax.random.normal(key[k], s, jnp.float32)
    return (rand(0, b, h, p).astype(jnp.bfloat16),
            jax.nn.softplus(rand(1, b, h)), -jnp.exp(rand(2, h)),
            rand(3, b, g, n).astype(jnp.bfloat16),
            rand(4, b, g, n).astype(jnp.bfloat16), rand(5, h),
            rand(6, b, h, p, n))


def _kda_inputs(shape, seed=0):
    b, h, dk, dv = shape
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    rand = lambda k, *s: jax.random.normal(key[k], s, jnp.float32)
    return (kda.l2_normalize(rand(0, b, h, dk)) * dk ** -0.5,
            kda.l2_normalize(rand(1, b, h, dk)),
            rand(2, b, h, dv).astype(jnp.bfloat16),
            -0.1 * jax.nn.softplus(rand(3, b, h, dk)),
            jax.nn.sigmoid(rand(4, b, h)), rand(5, b, h, dk, dv))


# per op: the served slab's shape and its cell, the toy shape, the
# module, the kernel's name, the inputs (the state last), the least heads
# a visit's block may hold (a group that shares B and C; a head), the
# swept blocks and live counts
OPS = {
    "mamba2": dict(
        served=(96, 128, 64, 8, 128), toy=(6, 8, 16, 2, 16), mod=mamba2,
        kernel="ssd_step_live", inputs=_mamba2_inputs,
        rep=lambda shape: shape[1] // shape[3], heads="32,64,128",
        live="0,1,13,26,48,96"),    # `nemotron-3-super-reason`, 96 slots
    "kda": dict(
        served=(128, 32, 128, 128), toy=(6, 8, 16, 16), mod=kda,
        kernel="kda_step_live", inputs=_kda_inputs, rep=lambda shape: 1,
        heads="8,16,32",
        live="0,8,29,64,128"),      # `kimi-linear-generate`, 128 slots
}


def device_times(trace_dir, kernel):
    """{program: ([durations of its events], [those of the kernel's events
    inside each])} from the trace's first device plane, in ms."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    plane = min((p for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
    lines = {line.name: [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events] for line in plane.lines}
    ops = [ev for ev in lines["XLA Ops"] if kernel in ev[0]]
    times = {}
    for name, start, dur in lines["XLA Modules"]:
        m = re.match(r"jit_(v\d+)\b", name)
        if m:
            whole, kernel = times.setdefault(m.group(1), ([], []))
            whole.append(dur / 1e6)
            kernel.append(sum(d for _n, s, d in ops
                              if start <= s < start + dur) / 1e6)
    return times


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", default="mamba2", choices=sorted(OPS))
    ap.add_argument("--live", default="", help="default: the op's own")
    ap.add_argument("--heads", default="",
                    help="heads of a visit's block (whole groups)")
    ap.add_argument("--toy", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    op = OPS[args.op]
    mod = op["mod"]
    args.out = args.out or f"chiprun_out/state_step_sweep_{args.op}.json"
    dev = jax.devices()[0]
    toy = bool(args.toy)
    if not toy and dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    shape = op["toy"] if toy else op["served"]
    b, h = shape[:2]
    rep = op["rep"](shape)
    *small, state = op["inputs"](shape)
    chosen = mamba2.step_heads_block(h, rep, *state.shape[2:])
    blocks = sorted({chosen} | {int(x) for x in
                                (args.heads or op["heads"]).split(",")
                                if x and h % int(x) == 0
                                and int(x) % rep == 0})
    rng = np.random.default_rng(0)
    records = []

    def reference(state, live):
        del live
        return mod._step_all(*small, state)

    def kernel_at(hb):
        return lambda state, live: mod._step_pallas(
            *small, state, live, hb, toy)

    paths = {"every slot (XLA)": (reference, None)}
    paths.update({f"walk, {hb} heads a visit": (kernel_at(hb), hb)
                  for hb in blocks})
    jitted = {}
    for i, (label, (fn, hb)) in enumerate(paths.items()):
        fn.__name__ = f"v{i}"
        jitted[label] = jax.jit(fn, donate_argnums=(0,))
        if hb is not None and not toy:
            text = jitted[label].lower(
                state, jnp.ones(b, jnp.int32)).compile().as_text()
            at = text.index("input_output_alias")
            print(json.dumps({"path": label, "alias": text[at:at + 60],
                              "state_copies": len(re.findall(
                                  r"f32\[" + ",".join(map(str, state.shape))
                                  + r"\][^ ]* copy\(", text))}), flush=True)
    lives = args.live or (f"0,1,{b // 2},{b}" if toy else op["live"])
    for live_n in [int(x) for x in lives.split(",") if int(x) <= b]:
        mask = np.zeros(b, np.int32)
        mask[rng.permutation(b)[:live_n]] = 1
        live = jnp.asarray(mask)
        want_y, want_s = jax.jit(reference)(state, live)
        recs = {}
        for label, fn in jitted.items():
            hb = paths[label][1]
            rec = {"shape": list(shape), "live": live_n, "path": label,
                   "chosen": hb == chosen,
                   "floor_ms": 8e3 * (live_n if hb else b) * state[0].size
                   / HBM_BYTES_PER_S}
            y, s = fn(jnp.copy(state), live)
            lv = mask.astype(bool)
            rec["y_max_abs_diff"] = float(np.abs(
                np.asarray(y)[lv] - np.asarray(want_y)[lv]).max(initial=0))
            rec["state_max_abs_diff"] = float(np.abs(
                np.asarray(s)[lv] - np.asarray(want_s)[lv]).max(initial=0))
            if hb:
                rec["dead_state_untouched"] = bool(np.array_equal(
                    np.asarray(s)[~lv], np.asarray(state)[~lv]))
            recs[label] = rec
            del y, s
        if not toy:
            trace_dir = tempfile.mkdtemp(prefix="state_step_sweep_")
            try:
                work = jnp.copy(state)
                jax.block_until_ready(work)
                jax.profiler.start_trace(trace_dir)
                for fn in jitted.values():
                    for _ in range(CALLS):
                        _y, work = fn(work, live)
                    jax.block_until_ready(work)
                jax.profiler.stop_trace()
                times = device_times(trace_dir, op["kernel"])
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            for i, label in enumerate(jitted):
                whole, kernel = times.get(f"v{i}", ([], []))
                recs[label].update(ms=float(np.mean(whole)),
                                   kernel_ms=float(np.mean(kernel)),
                                   calls=len(whole))
                recs[label]["floor_share"] = \
                    recs[label]["floor_ms"] / recs[label]["ms"]
            del work
        for rec in recs.values():
            records.append(rec)
            print(json.dumps(rec), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"op": args.op, "toy": toy,
                   "device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "records": records}, f, indent=1)


if __name__ == "__main__":
    main()
