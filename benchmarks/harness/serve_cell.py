"""A serving cell: HTTP -> GatewayServer -> DisaggRouter(colocated=
ContinuousBatchingEngine) in the process that holds the chip, driven by
the client process of `client.py` at the load the traffic file fixes.
Open-loop and closed-loop cells, and every model family the engine knows,
share every line of this file: they differ in their data files alone."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from . import common, lastline, reference, traffic as traffic_mod
from .configs import init_params, program_config


class Client:
    """The child process that sends the load. Its stdin and stdout are
    pipes to this process; its stderr is the run's stderr."""

    def __init__(self) -> None:
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=2,
            env=env, cwd=common.REPO_ROOT, text=True)
        lastline.register_child(self.proc)

    def ask(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        assert self.proc.stdin and self.proc.stdout
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"the client ended (code {self.proc.poll()}) during "
                f"{cmd['cmd']!r}")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise RuntimeError(f"client {cmd['cmd']!r} failed: "
                               f"{json.dumps(reply)[:600]}")
        return reply

    def close(self) -> None:
        """Ask it to leave, then make sure it has."""
        try:
            if self.proc.poll() is None and self.proc.stdin:
                self.proc.stdin.write('{"cmd": "exit"}\n')
                self.proc.stdin.flush()
                self.proc.stdin.close()
            self.proc.wait(timeout=10.0)
        except (OSError, ValueError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait(timeout=10.0)


def reference_check(run: Dict[str, Any], engine: Any, params: Any,
                    cfg: Any) -> Dict[str, Any]:
    """Two seeded prompts of the cell's shortest length through the
    engine (prefill, then decoding through the cache), each emitted token
    held against the float32 reference's full forward pass: the engine's
    own log-probability of the token against the reference's, and the
    reference's margin for it."""
    conf, traffic = run["conf"], run["traffic"]
    tol = traffic["tolerances"]
    n_new = int(traffic.get("reference_new_tokens", 8))
    length = traffic_mod.prompt_lengths(traffic)[0]
    worst_lp, worst_margin = 0.0, 0.0
    gaps: List[float] = []
    rows = []
    for k in range(2):
        prompt = traffic_mod.prompt_tokens(run["seed"], 20_000_000 + k,
                                           length, cfg.vocab_size)
        stream = engine.stream(prompt, n_new, timeout_s=600.0)
        emitted = [int(t) for t in stream]
        scores = [float(s) for s in stream.scores]
        ref = reference.score_emitted(conf, params, prompt, emitted)
        for tok, s, r in zip(emitted, scores, ref):
            gaps.append(abs(s - r["logprob"]))
            worst_lp = max(worst_lp, gaps[-1])
            worst_margin = max(worst_margin, r["margin"])
        rows.append({"emitted": emitted, "engine_logprob": scores,
                     "reference": ref})
    mean_lp = float(np.mean(gaps)) if gaps else float("nan")
    ok = (all(len(r["emitted"]) == n_new for r in rows)
          and math.isfinite(worst_lp)
          and worst_lp <= tol["logprob_abs"]
          and mean_lp <= tol["logprob_mean_abs"]
          and worst_margin <= tol["margin_abs"])
    return {"ok": ok, "worst_logprob_gap": worst_lp,
            "mean_logprob_gap": mean_lp,
            "worst_margin": worst_margin, "rows": rows}


def reduce_requests(records: List[Dict[str, Any]], planned: int,
                    seconds: float, loop: str, drain_s: float
                    ) -> Dict[str, Any]:
    """From the client's records to the end-to-end numbers. A tail is
    the tail of ALL requests: one that failed, was refused or shed, or
    never finished, counts as failed and as waiting to the end of the
    drain."""
    horizon = seconds + drain_s
    ttft, itl, per_token = [], [], []
    failed = 0
    tokens_in_window = 0
    wrong_length = 0
    for r in records:
        good = (r["status"] == 200 and r["done"] and not r["error"])
        if good and len(r["tokens"]) != r["max_tokens"]:
            wrong_length += 1
        if not good or not r["token_t"]:
            failed += 1
            ttft.append(1e3 * (horizon - r["due_t"]))
            per_token.append(ttft[-1] / r["max_tokens"])
            continue
        ttft.append(1e3 * (r["token_t"][0] - r["due_t"]))
        times = r["token_t"]
        per_token.append(1e3 * (times[-1] - r["due_t"]) / r["max_tokens"])
        itl.extend(1e3 * (b - a) for a, b in zip(times, times[1:]))
        if r.get("end_t", horizon) <= seconds:
            tokens_in_window += r["prompt_len"] + len(r["tokens"])
    attempted = len(records)
    if loop == "open":
        # a request the client never got to send missed too
        failed += planned - attempted
        attempted = planned
    out = {"attempted": attempted, "failed": failed,
           "wrong_length": wrong_length,
           "serve_tokens_per_s": tokens_in_window / seconds,
           "n_ttft": len(ttft), "n_itl": len(itl),
           # kept in the record, for statistics tried offline
           "rows": [[r["prompt_len"], r["max_tokens"], t,
                     n * r["max_tokens"]]
                    for r, t, n in zip(records, ttft, per_token)]}
    if ttft:
        out["ttft_p95_ms"] = float(np.percentile(ttft, 95))
        out["ttft_p75_ms"] = float(np.percentile(ttft, 75))
        out["ttft_p50_ms"] = float(np.percentile(ttft, 50))
        # from the due time to the last token, over the tokens asked
        # for, averaged over ALL requests: what a caller waits a token,
        # the first token's wait and any failure included
        out["request_ms_per_token"] = float(np.mean(per_token))
    if itl:
        out["itl_p95_ms"] = float(np.percentile(itl, 95))
        out["itl_p50_ms"] = float(np.percentile(itl, 50))
    return out


def ttft_by_third(records: List[Dict[str, Any]], seconds: float
                  ) -> List[float]:
    """Median time to first token of the requests due in each third of
    the window: a backlog that grows shows as a rising row."""
    out = []
    for k in range(3):
        lo, hi = k * seconds / 3.0, (k + 1) * seconds / 3.0
        vals = [1e3 * (r["token_t"][0] - r["due_t"]) for r in records
                if r.get("token_t") and lo <= r["due_t"] < hi]
        out.append(float(np.median(vals)) if vals else -1.0)
    return out


def phase_spans(traces: List[Dict[str, Any]]
                ) -> List[Tuple[str, float, float]]:
    """The flight recorder's kept traces as (phase, start, end) in
    seconds of `time.time()`. A trace's `ts` is taken when the gateway
    mints it, which is the end of its backdated `qos_admission`."""
    spans = []
    for tr in traces:
        phases = tr.get("phases") or []
        base = float(tr["ts"])
        qos = next((p for p in phases if p["phase"] == "qos_admission"),
                   None)
        if qos is not None:
            base -= (float(qos["t_ms"]) + float(qos["dur_ms"])) / 1e3
        for p in phases:
            start = base + float(p["t_ms"]) / 1e3
            spans.append((p["phase"], start,
                          start + float(p.get("dur_ms", 0.0)) / 1e3))
    return spans


def run_serve(run: Dict[str, Any]) -> Dict[str, Any]:
    client = Client()  # starts while this process reaches the chip
    try:
        return _run_serve(run, client)
    finally:
        client.close()


def _run_serve(run: Dict[str, Any], client: Client) -> Dict[str, Any]:
    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.observability import requests as reqtrace
    from ray_tpu.serve.disagg import DisaggRouter
    from ray_tpu.serve.gateway import GatewayServer
    from ray_tpu.util.compile_cache import (compile_cache_counts,
                                            enable_compile_cache)

    conf, traffic = run["conf"], run["traffic"]
    seconds = float(run["seconds"])
    device = common.require_devices(run)
    cache_dir = enable_compile_cache()
    cfg = program_config(conf, int(traffic["max_seq_len"]))
    lengths = traffic_mod.prompt_lengths(traffic)
    max_out = max(int(v) for v in traffic["output_tokens"]["values"])
    if lengths[-1] + max_out > cfg.max_seq_len:
        raise ValueError("the longest prompt plus the longest answer "
                         "does not fit max_seq_len")
    params = init_params(conf, cfg, run["seed"])
    common.mark(run, "params")
    model = run["cell"]["config"]
    timeout_s = float(traffic.get("request_timeout_s", 120.0))
    drain_s = float(traffic.get("drain_s", 30.0))

    engine = ContinuousBatchingEngine(params, cfg,
                                      max_batch=int(traffic["max_batch"]))
    gateway = None
    try:
        router = DisaggRouter(
            colocated=engine,
            max_queue_depth=int(traffic["max_queue_depth"]))
        gateway = GatewayServer(router, model=model,
                                vocab_size=cfg.vocab_size,
                                max_tokens_cap=max_out,
                                request_timeout_s=timeout_s)
        host, port = gateway.ready()
        base = {"host": host, "port": port, "model": model,
                "seed": run["seed"], "vocab": cfg.vocab_size,
                "timeout_s": timeout_s}

        # set-up: the correctness check (it also compiles the tick and
        # the shortest length's programs), then one streamed request of
        # every length of the set, so that the window compiles nothing
        common.mark(run, "gateway")
        check = reference_check(run, engine, params, cfg)
        common.mark(run, "reference_check")
        common.log(f"reference check: ok={check['ok']} worst logprob gap "
                   f"{check['worst_logprob_gap']:.5f} worst margin "
                   f"{check['worst_margin']:.5f}")
        warm = client.ask(dict(base, cmd="warmup", lengths=lengths,
                               max_tokens=2))
        common.mark(run, "warmup")
        cache_setup = compile_cache_counts()
        kv_before = engine.kv_stats()
        seq_before = reqtrace.store().seq()
        setup_s = time.perf_counter() - run["t_start"]

        window = common.TracedWindow(run) if run["trace"] else None
        trace_error: List[BaseException] = []
        tracer = None
        if window is not None:
            def sample() -> Dict[str, Any]:
                return {"prefilled_tokens":
                        engine.kv_stats().get("prefilled_tokens", 0)}

            def trace_later() -> None:
                try:
                    time.sleep(window.delay_s)
                    window.record(sample)
                except BaseException as e:  # noqa: BLE001 - re-raised
                    trace_error.append(e)

            tracer = threading.Thread(target=trace_later, daemon=True)
            tracer.start()
        reply = client.ask(dict(base, cmd="run", traffic=traffic,
                                seconds=seconds, drain_s=drain_s))
        cache_end = compile_cache_counts()
        kv_after = engine.kv_stats()
        if tracer is not None:
            tracer.join(timeout=120.0)
            if tracer.is_alive():
                raise RuntimeError("the profiler did not stop")
            if trace_error:
                raise trace_error[0]

        records = reply["records"]
        nums = reduce_requests(records, reply["planned"], seconds,
                               traffic["loop"], drain_s)
        rows = nums.pop("rows")
        common.log(f"client: {nums['attempted']} attempted, "
                   f"{nums['failed']} failed, ran late by at most "
                   f"{1e3 * reply['lateness_s']['max']:.2f} ms (mean "
                   f"{1e3 * reply['lateness_s']['mean']:.3f} ms), "
                   f"elapsed {reply['elapsed_s']:.2f} s")

        # after the window: non-streamed repeats of a few requests must
        # give the tokens their streams gave
        done = sorted((r for r in records if r["done"] and r["tokens"]),
                      key=lambda r: (r["prompt_len"], r["i"]))
        picks = [r["i"] for r in done[:int(traffic.get("replays", 2))]]
        by_i = {r["i"]: r for r in records}
        replay_ok = True
        if picks:
            # the replays must meet the programs their streams met: drop
            # the prefix cache, or each would be prefilled as a cached
            # prefix plus a suffix, by another program, and with random
            # weights a near-tie can then fall the other way
            if engine.kv_cache is not None:
                engine.kv_cache.invalidate()
            again = client.ask(dict(base, cmd="replay", indices=picks))
            for r in again["records"]:
                if r["status"] != 200 \
                        or r["tokens"] != by_i[r["i"]]["tokens"]:
                    replay_ok = False
                    common.log(f"replay of request {r['i']} differs: "
                               f"{r['tokens'][:8]} vs "
                               f"{by_i[r['i']]['tokens'][:8]} "
                               f"(status {r['status']}, {r['error']})")
        router_stats = router.stats()
    finally:
        if gateway is not None:
            gateway.stop()
        engine.stop()

    compiles_in_window = cache_end["compiles"] - cache_setup["compiles"]
    programs_in_window = (kv_after.get("prefill_programs", 0)
                          - kv_before.get("prefill_programs", 0))
    correct = (check["ok"] and warm["ok"] and replay_ok
               and compiles_in_window == 0 and programs_in_window == 0
               and nums["wrong_length"] == 0)
    summaries = reqtrace.store().summaries_since(seq_before)
    obs: Dict[str, Any] = {
        "trace": None, "host": [], "requests": records, "numbers": nums,
        "phases": [s.get("phase_ms") or {} for s in summaries],
        "counters": {
            "cache_misses_setup": cache_setup["misses"],
            "compiles_in_window": compiles_in_window,
            "prefill_programs_in_window": programs_in_window},
        "cell": {"conf": conf, "traffic": traffic, "peaks": run["peaks"],
                 "chips": run["cell"]["chips"], "seconds": seconds},
    }
    if window is not None:
        if len(window.samples) == 2:
            obs["counters"]["prefilled_tokens_in_trace"] = (
                window.samples[1]["prefilled_tokens"]
                - window.samples[0]["prefilled_tokens"])
        spans = phase_spans(reqtrace.store().slowest(10 ** 6))
        obs["trace"] = window.reduce(spans)
        obs["host"] = obs["trace"]["gaps"]
    common.write_record(run, {
        "workload": run["cell"]["name"], "seed": run["seed"],
        "setup_s": setup_s, "cache_dir": cache_dir,
        "cache_at_setup": cache_setup, "cache_at_end": cache_end,
        "marks": run.get("marks"),
        "numbers": nums, "lateness_s": reply["lateness_s"],
        "ttft_p50_by_third_ms": ttft_by_third(records, seconds),
        "tokens_out_per_s": sum(len(r["tokens"]) for r in records)
        / max(reply["elapsed_s"], 1e-9),
        "elapsed_s": reply["elapsed_s"],
        "reference": {k: check[k] for k in (
            "ok", "worst_logprob_gap", "mean_logprob_gap",
            "worst_margin")},
        "warmup": warm["records"], "replay_ok": replay_ok,
        "compiles_in_window": compiles_in_window,
        "router": {k: router_stats.get(k) for k in (
            "completed", "shed", "dispatched", "max_pending")},
        "kv": {k: kv_after.get(k) for k in (
            "prefill_programs", "prefilled_tokens", "reused_tokens",
            "prefill_calls", "admitted")},
        "correct": correct,
        "programs_in_trace": sorted((obs["trace"] or {}).get(
            "programs", {})),
        "rows_prompt_out_ttft_total_ms": rows,
    })
    # every statistic of the window (the counts are whole numbers);
    # BENCHMARK.json says which of them this cell is judged on
    end_to_end = {k: v for k, v in nums.items() if isinstance(v, float)}
    end_to_end["setup_s"] = setup_s
    return common.assemble(run, obs, end_to_end=end_to_end,
                           correct=correct, attempted=nums["attempted"],
                           failed=nums["failed"], device=device)
