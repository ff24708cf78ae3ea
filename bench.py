"""Headline benchmark: GPT-2 125M training throughput, tokens/sec/chip.

Runs the full JaxTrainer TrainStep (fwd+bwd+adamw, donated state, bf16
params, flash attention) on all local devices with a dp mesh, and prints
ONE JSON line {metric, value, unit, vs_baseline, ...} as the LAST stdout
line.

Self-checking: the script computes the implied model FLOP/s from the
transformer FLOP count and the measured token rate, prints `implied_mfu`,
hard-fails if it exceeds 1.0 of the chip's bf16 peak, and runs the timing
loop twice requiring agreement within 10%.

`vs_baseline` divides by REF_TOKENS_PER_SEC_PER_CHIP, an assumed constant
and no measurement (ROADMAP D4 drops both).

A chip belongs to one process at a time, so the parent never touches JAX
and runs its stages as child processes, strictly one after another, all
on the compile cache of ray_tpu.util.compile_cache:
  1. the MEASURE child with the module defaults (no autotune sweep, no
     fused-backward probe);
  2. optional kernel exploration (fused-backward probe, block autotune),
     each in its own child, only AFTER the headline record has been
     printed; an improved record is printed as a later line, so
     exploration can only improve the result. BENCH_EXPLORE=0 disables;
  3. the SERVE child, which replays a Zipf shared-system-prompt workload
     through the continuous-batching engine; its record rides in the
     final line under "serve". BENCH_SERVE=0 disables.
The record embeds the step-time oracle's predicted-vs-measured numbers
("oracle") and attributes any regression against the most recent prior
BENCH_r*.json to the step_breakdown phase that moved ("regression").

No chip, a failed measurement or a failed stage, serve included, is exit
code non-zero: nothing here falls back to the CPU.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

REF_TOKENS_PER_SEC_PER_CHIP = 140_000.0

# Child exit code for a measurement the bench itself declared invalid
# (implied-MFU over chip peak, unstable timing). The supervisor exits
# non-zero on it, as on any other failed stage.
INVALID_MEASUREMENT_RC = 3

def _chip_peak(device) -> float:
    """bf16 peak FLOP/s per chip — the per-generation table lives in
    ray_tpu.observability.flops (the flight recorder's MFU denominator);
    a TPU that is not in it raises."""
    from ray_tpu.observability.flops import device_peak_flops

    return device_peak_flops(device)


def _model_flops_per_token(cfg) -> float:
    """Training FLOPs per token: 6*N_active for the matmuls plus the
    attention score/value terms (12*L*d*T per token fwd+bwd)."""
    n_params = (cfg.padded_vocab * cfg.d_model            # wte (tied head)
                + cfg.max_seq_len * cfg.d_model           # wpe
                + cfg.num_layers * (4 * cfg.d_model * cfg.d_model  # attn
                                    + 8 * cfg.d_model * cfg.d_model))  # mlp
    return 6.0 * n_params


def _attn_flops_per_token(cfg, seq: int, causal: bool = True) -> float:
    # per token: 2 matmuls (QK^T, PV) * 2 * d_model * seq, fwd+bwd = 3x,
    # halved for causal masking.
    per = 12.0 * cfg.num_layers * cfg.d_model * seq
    return per / 2 if causal else per


def _time_loop(step, state, batch, iters: int) -> tuple:
    # float() forces a device-to-host read: the loop is timed to the end
    # of the last step's execution, not of its dispatch.
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, batch)
    float(metrics["loss"])
    return time.perf_counter() - t0, state, metrics


def _probe_fused_flash_bwd() -> bool:
    """Turn the fused single-pass flash backward on after checking it
    against the two-pass backward on this chip. A kernel that does not
    compile or does not match raises: the explore child fails, and the
    headline record, already printed, stands."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    os.environ["RAY_TPU_FLASH_FUSED_BWD"] = "0"
    ref = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    os.environ["RAY_TPU_FLASH_FUSED_BWD"] = "1"
    got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-2, atol=3e-2)
    return True


def _autotune_flash_blocks(make_step, params, batch, warmup: int = 2,
                           iters: int = 6):
    """On-chip sweep of flash-attention block sizes: time the FULL train
    step under each candidate and leave the winner as the module default
    (the attention kernel is the known MFU limiter — BENCH_BLOCKS="q,k"
    pins without sweeping). Each candidate pays one recompile; a
    candidate that fails to compile or run fails the sweep."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    pinned = os.environ.get("BENCH_BLOCKS")
    if pinned:
        bq, bk = (int(x) for x in pinned.split(","))
        attention.set_default_blocks(bq, bk)
        return (bq, bk)
    configs = ((1024, 1024), (512, 1024), (1024, 512), (512, 512),
               (256, 512))
    orig = (attention.DEFAULT_BLOCK_Q, attention.DEFAULT_BLOCK_K)
    best = (0.0, None)
    for bq, bk in configs:
        attention.set_default_blocks(bq, bk)
        try:
            step = make_step()
            state = step.init_state(jax.tree.map(jnp.copy, params))
            _, state, _ = _time_loop(step, state, batch, warmup)
            dt, state, _ = _time_loop(step, state, batch, iters)
        except BaseException:
            attention.set_default_blocks(*orig)
            raise
        rate = iters / dt
        print(f"bench: blocks ({bq},{bk}) -> {rate:.2f} steps/s",
              file=sys.stderr)
        if rate > best[0]:
            best = (rate, (bq, bk))
    attention.set_default_blocks(*best[1])
    return best[1]


def _start_on_chip():
    """First thing in a child: the shared compile cache, and a TPU or no
    run. Returns the platform's devices."""
    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: JAX found platform {devices[0].platform!r} "
                 f"({devices[0].device_kind}), not 'tpu': this benchmark "
                 "measures the chip and does not fall back")
    return devices


def main() -> None:
    devices = _start_on_chip()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init, gpt2_loss,
                                     gpt2_partition_specs)
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.trainer import TrainStep

    # the fused-backward probe runs only when asked for (the explore
    # child asks): it costs two extra kernel compiles
    fused_bwd = False
    if os.environ.get("RAY_TPU_FLASH_FUSED_BWD") == "1":
        fused_bwd = _probe_fused_flash_bwd()
    cfg = GPT2Config.small()
    seq = cfg.max_seq_len
    per_chip_batch = int(os.environ.get("BENCH_BATCH", "32"))
    # remat off: with the fused-CE and flash kernels activation memory
    # fits at batch 32, and rematerialization only adds recompute FLOPs
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    warmup, iters = 5, 60

    mesh = make_mesh(MeshConfig(dp=-1), devices=devices)
    n_chips = len(devices)

    def make_step():
        return TrainStep(
            lambda p, b: gpt2_loss(p, b["tokens"], b["targets"], cfg,
                                   remat=remat),
            optax.adamw(3e-4, weight_decay=0.1), mesh,
            gpt2_partition_specs(cfg))

    params0 = gpt2_init(cfg, jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    batch_np = rng.integers(
        0, cfg.vocab_size, (per_chip_batch * n_chips, seq + 1),
        dtype=np.int32)
    batch = {"tokens": jnp.asarray(batch_np[:, :-1]),
             "targets": jnp.asarray(batch_np[:, 1:])}
    tokens_per_step = per_chip_batch * n_chips * seq

    # Autotune is opt-in (BENCH_AUTOTUNE=1): the sweep's several
    # recompiles belong in an explore child that runs only after a
    # headline number exists. BENCH_BLOCKS="q,k" pins. Nothing a run
    # finds is kept for the next one: every run starts from the module
    # defaults.
    from ray_tpu.ops import attention

    if os.environ.get("BENCH_AUTOTUNE", "0") == "1" \
            or os.environ.get("BENCH_BLOCKS"):
        flash_blocks = _autotune_flash_blocks(make_step, params0, batch)
    else:
        flash_blocks = (attention.DEFAULT_BLOCK_Q, attention.DEFAULT_BLOCK_K)

    step = make_step()
    state = step.init_state(jax.tree.map(jnp.copy, params0))

    # first call timed apart: it is compile + one step, and the compile
    # share belongs in the record's step_breakdown, not in the average
    compile_dt, state, metrics = _time_loop(step, state, batch, 1)
    if warmup > 1:
        _, state, metrics = _time_loop(step, state, batch, warmup - 1)

    dt1, state, _ = _time_loop(step, state, batch, iters)
    dt2, state, _ = _time_loop(step, state, batch, iters)
    if abs(dt1 - dt2) / max(dt1, dt2) > 0.10:
        print(f"bench: timing runs disagree >10% ({dt1:.3f}s vs {dt2:.3f}s)"
              " — rerunning once", file=sys.stderr)
        dt1, state, _ = _time_loop(step, state, batch, iters)
        dt2, state, _ = _time_loop(step, state, batch, iters)
        if abs(dt1 - dt2) / max(dt1, dt2) > 0.10:
            print(f"bench: unstable measurement ({dt1:.3f}s vs {dt2:.3f}s)",
                  file=sys.stderr)
            sys.exit(INVALID_MEASUREMENT_RC)
    dt = (dt1 + dt2) / 2

    # flight-recorder derivation shared with the oracle harness and the
    # conductor's train_progress: one record per timing run, summarized
    # by step_timer.summarize_records instead of re-deriving inline
    from ray_tpu.observability.step_timer import summarize_records

    run_records = [{"device_step_ms": dt1 / iters * 1e3},
                   {"device_step_ms": dt2 / iters * 1e3}]
    device_summary = summarize_records(run_records)["phases"]["device_step"]

    tok_per_sec_per_chip = tokens_per_step * iters / dt / n_chips
    flops_per_token = (_model_flops_per_token(cfg)
                       + _attn_flops_per_token(cfg, seq))
    implied_flops = tok_per_sec_per_chip * flops_per_token
    peak = _chip_peak(devices[0])
    implied_mfu = implied_flops / peak
    if implied_mfu > 1.0:
        print(
            f"bench: implied {implied_flops / 1e12:.1f} TFLOP/s/chip exceeds "
            f"chip peak {peak / 1e12:.0f} TFLOP/s (MFU {implied_mfu:.2f}) — "
            "measurement invalid, refusing to report", file=sys.stderr)
        sys.exit(INVALID_MEASUREMENT_RC)

    # Step-time oracle (observability.roofline): the analytic roofline's
    # predicted-vs-measured for this dp layout, embedded so every BENCH
    # record names how far reality sat from the model. The dp grad sync
    # is one psum of the param pytree; on one chip there is no comms
    # term and the prediction is the pure compute roofline.
    from ray_tpu.analysis.collectives import CollectiveUse
    from ray_tpu.analysis.shardcheck import MeshLayout
    from ray_tpu.observability import roofline

    param_bytes = int(sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params0)
        if hasattr(x, "size")))
    grad_sync = [CollectiveUse("psum", ("dp",), param_bytes)] \
        if n_chips > 1 else []
    predicted = roofline.predict_step_time(
        MeshLayout({"dp": n_chips}, name="bench_dp"), grad_sync,
        flops_per_token * tokens_per_step,
        _chip_peak(devices[0]) * n_chips,
        links=roofline.device_link_constants(devices[0]),
        name="bench_dp")
    measured_ms = device_summary["mean_ms"]
    oracle = {
        "predicted": {k: round(predicted[k], 4) for k in
                      ("device_step_ms", "ici_wait_ms", "dcn_wait_ms",
                       "predicted_step_ms")},
        "measured_device_step_ms": round(measured_ms, 3),
        "residual_ratio": round(
            measured_ms / predicted["predicted_step_ms"], 4)
        if predicted["predicted_step_ms"] > 0 else None,
    }

    print(json.dumps({
        "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n_chips},
        "value": round(tok_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_per_sec_per_chip
                             / REF_TOKENS_PER_SEC_PER_CHIP, 3),
        "implied_mfu": round(implied_mfu, 4),
        "per_chip_batch": per_chip_batch,
        "seq_len": seq,
        "remat": remat,
        "n_chips": n_chips,
        "fused_flash_bwd": fused_bwd,
        "flash_blocks": list(flash_blocks),
        # flight-recorder step breakdown (observability.StepTimer
        # schema) so the BENCH_*.json perf trajectory is self-describing;
        # data_wait is 0 by construction (the synthetic batch is
        # device-resident before the loop).
        "step_breakdown": {
            "data_wait_ms": 0.0,
            "compile_ms": round(compile_dt * 1e3, 1),
            "device_step_ms": round(device_summary["mean_ms"], 3),
            "device_step_p99_ms": round(device_summary["p99_ms"], 3),
            "mfu": round(implied_mfu, 6),
        },
        "oracle": oracle,
    }))


def _serve_main() -> None:
    """Serving benchmark child (`_BENCH_MODE=serve`): replay a
    Zipf-popularity workload of prompts sharing a block-aligned system
    prompt through ContinuousBatchingEngine and report tokens/s, TTFT
    p50/p99, and the paged-KV prefix hit rate. Its record rides INSIDE
    the headline JSON under "serve"."""
    platform = _start_on_chip()[0].platform
    import jax
    import threading

    import numpy as np

    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.models.llama import LlamaConfig, llama_init

    cfg = LlamaConfig.small()
    block = int(os.environ.get("RAY_TPU_KV_BLOCK_SIZE", "16"))
    params = llama_init(cfg, jax.random.PRNGKey(0))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "128"))
    n_distinct = 8
    max_new = 48
    rng = np.random.default_rng(0)
    # shared system prompt, block-aligned so prefix reuse can bite
    sys_len = 8 * block
    sys_prompt = rng.integers(1, cfg.vocab_size, sys_len).tolist()
    distinct = [sys_prompt + rng.integers(
        1, cfg.vocab_size, int(rng.integers(4, 2 * block))).tolist()
        for _ in range(n_distinct)]
    # Zipf popularity over the distinct prompts (rank^-1.1)
    pop = 1.0 / np.arange(1, n_distinct + 1) ** 1.1
    order = rng.choice(n_distinct, size=n_requests, p=pop / pop.sum())

    eng = ContinuousBatchingEngine(params, cfg, max_batch=8)
    try:
        list(eng.stream(distinct[0], 2))  # compile warmup, not measured
        ttfts, produced = [], [0] * n_requests
        lock = threading.Lock()

        def one(i: int, prompt) -> None:
            t0 = time.perf_counter()
            first = None
            n = 0
            for _ in eng.stream(prompt, max_new):
                if first is None:
                    first = time.perf_counter() - t0
                n += 1
            with lock:
                ttfts.append(first if first is not None else 0.0)
                produced[i] = n

        t_start = time.perf_counter()
        threads = [threading.Thread(target=one,
                                    args=(i, distinct[int(d)]))
                   for i, d in enumerate(order)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start
        stats = eng.kv_stats()
    finally:
        eng.stop()

    total_tokens = int(sum(produced))
    print(json.dumps({
        "metric": f"serve_decode_tokens_per_sec_{platform}",
        "value": round(total_tokens / wall, 1),
        "unit": "tokens/s",
        "platform": platform,
        "n_requests": n_requests,
        "max_new_tokens": max_new,
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
        "ttft_p99_ms": round(float(np.percentile(ttfts, 99)) * 1e3, 2),
        "prefix_hit_rate": round(stats.get("hit_rate", 0.0), 4),
        "token_reuse_rate": round(stats.get("token_reuse_rate", 0.0), 4),
        "reused_tokens": stats.get("reused_tokens", 0),
        "prefilled_tokens": stats.get("prefilled_tokens", 0),
        "kv_pool_utilization": round(stats.get("pool_utilization", 0.0),
                                     4),
    }))


def _attach_serve(rec: dict) -> dict:
    """Run the serve child and graft its record into the final headline
    JSON under "serve" (the driver keys on the LAST line, so the training
    headline metric stays the headline). A failed serve stage fails the
    run."""
    if os.environ.get("BENCH_SERVE", "1") != "1":
        return rec
    timeout = float(os.environ.get("BENCH_SERVE_TIMEOUT", "600"))
    srec, serr, _rc = _run_child({"_BENCH_MODE": "serve"}, timeout)
    if srec is None:
        sys.exit(f"bench: serve stage failed ({serr})")
    return dict(rec, serve=srec)


def _prior_bench_records(bench_dir: str = None):
    """(filename, record) pairs of prior BENCH_r*.json rounds beside
    this script, newest round first (by the driver wrapper's "n" round
    counter — lexical filename order misplaces r100 vs r99). The driver
    wraps each round's parsed record under "parsed"; bare records are
    accepted too."""
    base = bench_dir or os.path.dirname(os.path.abspath(__file__))
    rounds = []
    for path in glob.glob(os.path.join(base, "BENCH_r*.json")):
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(raw, dict):
            continue
        parsed = raw.get("parsed") if isinstance(raw.get("parsed"),
                                                 dict) else raw
        if isinstance(parsed, dict):
            n = raw.get("n") if isinstance(raw.get("n"), int) else -1
            rounds.append((n, os.path.basename(path), parsed))
    rounds.sort(key=lambda r: (r[0], r[1]), reverse=True)
    return [(fname, parsed) for _, fname, parsed in rounds]


def _attribute_regression(rec: dict, bench_dir: str = None) -> dict:
    """Perf-regression attribution: diff this run's step_breakdown
    against the most recent prior BENCH record and name the phase that
    moved. A record that is a failure or carries a different metric is
    never the baseline; a prior round without a step_breakdown is
    skipped the same way. regression is None when no phase got slower."""
    cur = rec.get("step_breakdown")
    if not isinstance(cur, dict):
        return rec
    for fname, prior in _prior_bench_records(bench_dir):
        if ("error" in prior or "tpu_error" in prior
                or not prior.get("value")
                or prior.get("metric") != rec.get("metric")):
            continue
        prev = prior.get("step_breakdown")
        if not isinstance(prev, dict):
            continue
        # phases only: the breakdown also carries summary keys
        # (device_step_p99_ms) that would double-count their phase and
        # attribute a "regression" to 2-sample noise
        phases = ("data_wait", "bubble_wait", "compile", "device_step",
                  "checkpoint", "report", "other")
        deltas = {
            p: float(cur[f"{p}_ms"]) - float(prev[f"{p}_ms"])
            for p in phases
            if isinstance(cur.get(f"{p}_ms"), (int, float))
            and isinstance(prev.get(f"{p}_ms"), (int, float))}
        if not deltas:
            continue
        phase, delta = max(deltas.items(), key=lambda kv: kv[1])
        rec = dict(rec)
        if delta <= 0:
            rec["regression"] = None  # explicitly: nothing got slower
            return rec
        base = float(prev.get(f"{phase}_ms") or 0.0)
        rec["regression"] = {
            "phase": phase,
            "delta_ms": round(delta, 3),
            "pct": round(100.0 * delta / base, 2) if base > 0 else None,
            "vs": fname,
        }
        return rec
    return rec


def _sweep_stale_shm() -> int:
    """Remove leaked rtpu arena slabs from earlier crashed runs: stale
    segments eat /dev/shm and have previously degraded or broken the
    measurement. Only this framework's prefix is touched."""
    n = 0
    for path in glob.glob("/dev/shm/rtpu_a_*"):
        try:
            os.unlink(path)
            n += 1
        except OSError:
            pass
    if n:
        print(f"bench: swept {n} stale /dev/shm/rtpu_a_* segment(s)",
              file=sys.stderr)
    return n


def _run_child(extra_env: dict, timeout: float):
    """Run this script as a child stage; return (json_dict | None,
    reason, returncode | None). The last stdout line must be the JSON
    record; stderr is passed through for diagnostics. The child has
    exited, and with it let go of the chip, before this returns."""
    env = dict(os.environ, _BENCH_CHILD="1", **extra_env)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            stdout, stderr = proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            stdout, stderr = proc.communicate()
        if stderr:
            sys.stderr.write(stderr)
        return None, f"timeout after {timeout:.0f}s", None
    if stderr:
        sys.stderr.write(stderr)
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    if proc.returncode != 0:
        # Tracebacks/SystemExit messages land on stderr; stdout is
        # usually empty on failure — diagnose from the stderr tail.
        err_lines = [ln for ln in (stderr or "").strip().splitlines()
                     if ln.strip()]
        tail = (err_lines[-1] if err_lines
                else lines[-1] if lines else "")[:300]
        return None, f"rc={proc.returncode}: {tail}", proc.returncode
    try:
        rec = json.loads(lines[-1])
        if "value" not in rec:
            raise ValueError("no 'value' key")
        return rec, "", 0
    except Exception:
        return (None, f"rc=0 but no JSON record in output: {stdout[-300:]}",
                proc.returncode)


def _supervise() -> int:
    """Parent entry: never initializes a jax backend in-process. Stages,
    strictly one child at a time: measure -> optional explore children
    (BENCH_EXPLORE=1) -> serve. Emits one parsed JSON line last; any
    failed stage is a non-zero exit."""
    tpu_timeout = float(os.environ.get("BENCH_TPU_TIMEOUT", "900"))

    _sweep_stale_shm()

    rec, err, rc = _run_child({}, tpu_timeout)
    if rec is None:
        # no chip, a failed run, or the child's own validity guard
        # (impossible MFU, unstable timing: INVALID_MEASUREMENT_RC)
        print(json.dumps({
            "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
            "value": 0.0, "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "error": ("measurement declared invalid by child: "
                      if rc == INVALID_MEASUREMENT_RC
                      else "measure child failed: ") + err,
        }))
        return 1
    if os.environ.get("BENCH_EXPLORE", "1") == "1":
        # headline first, THEN explore: the driver parses the LAST
        # complete JSON line, so a failed or timed-out exploration can
        # only fail to improve the record, never lose it
        print(json.dumps(rec), flush=True)
        rec = _explore(rec, tpu_timeout)
    # serve stage LAST (after the headline is safe on stdout): its record
    # rides inside the final line's "serve" key
    print(json.dumps(_attach_serve(_attribute_regression(rec))))
    return 0


def _explore(rec: dict, timeout: float) -> dict:
    """Kernel exploration, run only once a headline number is already in
    hand: fused-bwd probe child, then block-autotune child. Keeps
    whichever child's record is fastest; a failed child is reported and
    leaves the headline record untouched."""
    best = rec
    probe, perr, _ = _run_child({"RAY_TPU_FLASH_FUSED_BWD": "1"}, timeout)
    if probe is not None and probe.get("value", 0) > best.get("value", 0):
        best = probe
    elif probe is None:
        sys.stderr.write(f"bench: fused-bwd explore failed ({perr})\n")
    tuned, terr, _ = _run_child({"BENCH_AUTOTUNE": "1"}, timeout)
    if tuned is not None and tuned.get("value", 0) > best.get("value", 0):
        best = tuned
    elif tuned is None:
        sys.stderr.write(f"bench: autotune explore failed ({terr})\n")
    return best


if __name__ == "__main__":
    if os.environ.get("_BENCH_CHILD") == "1":
        if os.environ.get("_BENCH_MODE") == "serve":
            _serve_main()
        else:
            main()
    else:
        sys.exit(_supervise())
