"""A tick's attention ALONE on the chip: `ops/swa.py`'s decode kernel
(`gqa_decode_t<t>`) against the slab form it replaces
(`ops/swa.py` `slab_attention`: float32 scores of every query
against ALL rows of ALL slots, masked afterwards) at the slab shapes the
served cells hold, with slots as full as their cells leave them. The
block `_decode_block` chooses, and the rule by which a shape keeps the
slab form, stand on this table (PERF.md section 6, PR 41). And the
absorbed form over latent rows (`ops/mla.py` `absorbed_attention`): the
walk `mla_decode_t<t>` against the plain form over every row, at the two
served latent shapes with 5, 13, 43 and 100% of the entry's rows live
(PERF.md section 6, PR 48).

    chiprun --chips 1 -- python3 examples/decode_attention_sweep.py

A grouped-query time is the wall clock of one jitted chain of `--chain`
calls, each fed the one before's output (as a tick's layers are), over
the calls; `floor_us` is what the rows the walk visits (keys and values,
whole blocks) need at 819 GB/s. A latent time is the DEVICE's: each path
runs `CALLS` times under one profiler trace, `us` is the mean duration of
its program (the fold of W_uk into the query and W_uv's product
included, the same in every path) and `kernel_us` of the kernel's own
event. Fails without a TPU; `--toy 1` walks the same code at toy widths
in interpret mode, on any backend, and its times mean nothing (the
latent cases then take none).
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import dispatch, mla, swa

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
    # four heads of 64 packed to a row of 256 lanes: the entry lies head
    # by head (PR 61; the slab form's scale is d's here, a time all the same)
    "gpt2-chat": ((64, 1, 12, 3, 256, 1024), 3, 100, 640, 0),
}
TOY = {name: ((4, t, 2 * h // g, 2, 32, 96), 2, 40, 90, parked)
       for name, ((_, t, h, g, _, _), _, _, _, parked) in CASES.items()}


# name: ((B, t, H, d_n, d_r, d_v, rank, S), the slots its cell holds live,
# the softmax scale). A share f of the entry's rows is live in
# max(those, f x B) slots at one position each, the others parked at 0
LATENT = {
    "kimi-linear": ((128, 1, 32, 128, 64, 128, 512, 2816), 34, None),
    "deepseek-v2": ((16, 1, 128, 128, 64, 128, 512, 8448), 16, 0.1147),
}
LATENT_TOY = {name: ((4, 1, h // 8, 16, 8, 16, 128, 384), 2, scale)
              for name, ((_, _, h, *_), _, scale) in LATENT.items()}
SHARES = (0.05, 0.13, 0.43, 1.0)
CALLS = 10


def device_times(trace_dir):
    """{program: ([durations of its events], [those of the walk's kernel
    inside each])} from the trace's first device plane, in us."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    plane = min((p for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
    lines = {line.name: [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events] for line in plane.lines}
    ops = [ev for ev in lines["XLA Ops"] if "mla_decode" in ev[0]]
    times = {}
    for name, start, dur in lines["XLA Modules"]:
        m = re.match(r"jit_(v\d+)\b", name)
        if m:
            whole, kernel = times.setdefault(m.group(1), ([], []))
            whole.append(dur / 1e3)
            kernel.append(sum(d for _n, s, d in ops
                              if start <= s < start + dur) / 1e3)
    return times


def latent_case(name, blocks, toy, rng):
    """One latent shape: the plain form and the walk at each block, at
    each share of live rows. The block is the one thing of the walk a
    shape chooses (`ops/swa.decode_block`); another is had by moving the
    bytes it is sized to, for the length of a trace."""
    (b, t, h, d_n, d_r, d_v, rank, s), cell_live, scale = \
        (LATENT_TOY if toy else LATENT)[name]
    dtype = jnp.float32 if toy else jnp.bfloat16
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    q_n = jax.random.normal(key[0], (b, t, h, d_n), dtype)
    q_r = jax.random.normal(key[1], (b, t, h, d_r), dtype)
    width = mla.row_width(rank, d_r)
    rows = mla.latent_row(jax.random.normal(key[2], (b, s, rank), dtype),
                          jax.random.normal(key[3], (b, s, d_r), dtype),
                          width, dtype)
    w_kvb = (0.05 * jax.random.normal(key[4], (rank, h, d_n + d_v))
             ).astype(dtype)
    row_bytes = width * rows.dtype.itemsize
    chosen = swa.decode_block(rows.shape, rows.dtype)
    served = swa._DECODE_BLOCK_BYTES

    def plain(q_n, q_r, rows, pos):
        seen = jnp.arange(s)[None, None, :] <= pos[:, :, None]
        return mla.absorbed_attention(q_n, q_r, rows, None, w_kvb, scale,
                                      seen)

    def walk(q_n, q_r, rows, pos):
        return mla.absorbed_attention(q_n, q_r, rows, pos, w_kvb, scale)

    paths = {"plain (every row, twice)": (plain, None, served)}
    for block in sorted({chosen} | set(blocks)):
        if block <= s and (toy or block % 128 == 0):
            # one latent array a step: the rule doubles a block while
            # the one it has fits the bytes, so half of `block` just does
            paths[f"walk {block}"] = (walk, block, served if block == chosen
                                      else block // 2 * row_bytes)

    def program(fn, name):
        """A program of its own a path, found in the trace by its name."""
        run = lambda *a: fn(*a)
        run.__name__ = name
        return jax.jit(run)

    jitted = {label: program(fn, f"v{i}")
              for i, (label, (fn, _, _)) in enumerate(paths.items())}
    records = []
    for share in SHARES:
        live = min(b, max(cell_live, int(np.ceil(share * b))))
        base = np.zeros((b,), np.int64)
        base[rng.permutation(b)[:live]] = min(
            int(share * b * s / live), s - t + 1) - 1
        pos = jnp.asarray(base[:, None] + np.arange(t)[None], jnp.int32)
        args = (q_n, q_r, rows, pos)
        recs, want = {}, None
        for label, fn in jitted.items():
            _, block, sized_to = paths[label]
            swa._DECODE_BLOCK_BYTES = sized_to
            try:
                assert block in (None, swa.decode_block(rows.shape,
                                                        rows.dtype))
                with (dispatch.pallas_interpret() if toy
                      else contextlib.nullcontext()):
                    one = np.asarray(fn(*args).astype(jnp.float32))
            finally:
                swa._DECODE_BLOCK_BYTES = served
            want = one if want is None else want
            read = b * s * 2 if block is None else swa.decode_rows_read(
                np.asarray(pos), block, s)
            recs[label] = {
                "case": name, "shape": [b, t, h, width, rank, s],
                "live_share": share, "live": live,
                "live_rows": int(base.sum() + live), "path": label,
                "chosen": block == chosen, "rows_read": read,
                "floor_us": read * row_bytes / HBM_BYTES_PER_S * 1e6,
                "max_abs_diff": float(np.abs(one - want).max())}
        if not toy:
            trace_dir = tempfile.mkdtemp(prefix="decode_attention_sweep_")
            try:
                jax.profiler.start_trace(trace_dir)
                for fn in jitted.values():
                    for _ in range(CALLS):
                        out = fn(*args)
                    jax.block_until_ready(out)
                jax.profiler.stop_trace()
                times = device_times(trace_dir)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            for i, label in enumerate(jitted):
                whole, kernel = times.get(f"v{i}", ([], []))
                recs[label].update(us=float(np.mean(whole)),
                                   kernel_us=float(np.mean(kernel)),
                                   calls=len(whole))
                recs[label]["floor_share"] = recs[label]["floor_us"] / (
                    recs[label]["kernel_us"] or recs[label]["us"])
        for rec in recs.values():
            records.append(rec)
            print(json.dumps(rec), flush=True)
    return records


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
    ap.add_argument("--cases", default=",".join(list(CASES) + list(LATENT)))
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
        if name in LATENT:
            records += latent_case(
                name, [int(x) for x in args.blocks.split(",") if x], toy,
                rng)
            continue
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
