"""A tick's attention ALONE on the chip: `ops/swa.py`'s decode kernel
(`gqa_decode_t<t>`) against the slab form it replaces
(`ops/swa.py` `slab_attention`: float32 scores of every query
against ALL rows of ALL slots, masked afterwards) at the slab shapes the
served cells hold, with slots as full as their cells leave them. The
block `_decode_block` chooses, and the rule by which a shape keeps the
slab form, stand on this table (PERF.md section 6, PR 41).

    chiprun --chips 1 -- python3 examples/decode_attention_sweep.py

A time is the wall clock of one jitted chain of `--chain` calls, each
fed the one before's output (as a tick's layers are), over the calls;
`floor_us` is what the rows the walk visits (keys and values, whole
blocks) need at 819 GB/s. Fails without a TPU; `--toy 1` walks the same
code at toy widths in interpret mode, on any backend, and its times mean
nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import swa

HBM_BYTES_PER_S = 819e9


def _positions(rng, slots, live, lo, hi, parked=0):
    pos = np.full((slots,), parked, np.int64)
    pos[:live] = rng.integers(lo, hi, size=live)
    return pos


# name: ((B, t, H, G, d, S), live slots, their positions from .. to, where
# a dead slot stands). The live shares are the cells' (PERF.md section 5).
CASES = {
    "mistral-chat": ((32, 1, 32, 8, 128, 2304), 3, 430, 640, 0),
    "mistral-chat-unparked": ((32, 1, 32, 8, 128, 2304), 3, 430, 640, 530),
    "mistral-chat-verify": ((32, 5, 32, 8, 128, 2304), 3, 430, 640, 0),
    "mistral-summarize": ((8, 1, 32, 8, 128, 4160), 8, 1000, 3340, 0),
    "smallthinker-global": ((16, 1, 28, 4, 128, 16384), 16, 8000, 15800, 0),
    "smallthinker-ring": ((16, 1, 28, 4, 128, 4096), 16, 4095, 4096, 0),
    "jamba2-docqa": ((8, 1, 20, 1, 128, 33280), 8, 4000, 18500, 0),
    "nemotron-reason": ((96, 1, 32, 2, 128, 1536), 27, 300, 1100, 0),
}
TOY = {name: ((4, t, 2 * h // g, 2, 32, 96), 2, 40, 90, parked)
       for name, ((_, t, h, g, _, _), _, _, _, parked) in CASES.items()}


def chained(fn, n):
    """`n` calls in one program, each fed the last one's output."""
    def run(q, ck, cv, pos):
        for _ in range(n):
            q = fn(q, ck, cv, pos).reshape(q.shape)
        return q
    return jax.jit(run)


def timed(fn, args, n):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def kernel_at(block, toy):
    return lambda q, ck, cv, pos: swa._decode_pallas(q, ck, cv, pos, block,
                                                     toy)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--blocks", default="128,256,512,1024,2048")
    ap.add_argument("--chain", type=int, default=16)
    ap.add_argument("--toy", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/decode_attention_sweep.json")
    args = ap.parse_args()
    dev = jax.devices()[0]
    toy = bool(args.toy)
    if not toy and dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    cases = TOY if toy else CASES
    dtype = jnp.float32 if toy else jnp.bfloat16
    rng = np.random.default_rng(0)
    records = []
    for name in args.cases.split(","):
        (b, t, h, g, d, s), live, lo, hi, parked = cases[name]
        key = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(key[0], (b, t, h, d), dtype)
        ck = jax.random.normal(key[1], (b, s, g, d), dtype)
        cv = jax.random.normal(key[2], (b, s, g, d), dtype)
        base = _positions(rng, b, live, lo, min(hi, s - t + 1), parked)
        pos = jnp.asarray(base[:, None] + np.arange(t)[None], jnp.int32)
        chosen = swa._decode_block(s, g, d, ck.dtype.itemsize)
        paths = {"slab": (swa.slab_attention, None)}
        for block in sorted({chosen} | {int(x) for x in
                                        args.blocks.split(",") if x}):
            if block <= s and (toy or block % 128 == 0):
                paths[f"kernel {block}"] = (kernel_at(block, toy), block)
        want = None
        for label, (fn, block) in paths.items():
            rec = {"case": name, "shape": [b, t, h, g, d, s], "live": live,
                   "live_rows": int(base[:live].sum()), "path": label,
                   "chosen": block == chosen}
            rows = b * s if block is None else swa.decode_rows_read(
                np.asarray(pos), block, s)
            rec["rows_read"] = rows
            rec["floor_us"] = rows * 2 * g * d * ck.dtype.itemsize \
                / HBM_BYTES_PER_S * 1e6
            try:
                one = np.asarray(jax.jit(fn)(q, ck, cv, pos).reshape(
                    b, t, h * d).astype(jnp.float32))
                if want is None:
                    want = one
                rec["max_abs_diff"] = float(np.abs(one - want).max())
                sec = timed(chained(fn, args.chain), (q, ck, cv, pos),
                            args.chain)
                rec["us"] = sec * 1e6
                rec["floor_share"] = rec["floor_us"] / rec["us"]
            except Exception as e:  # a block the compiler refuses
                rec["error"] = str(e)[:300]
            records.append(rec)
            print(json.dumps(rec), flush=True)
        del q, ck, cv
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"toy": toy, "device": {"platform": dev.platform,
                                          "kind": dev.device_kind},
                   "records": records}, f, indent=1)


if __name__ == "__main__":
    main()
