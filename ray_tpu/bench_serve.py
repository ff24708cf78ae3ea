"""Open-loop serving load harness — the disaggregated-serving
acceptance benchmark the ROADMAP names.

Open loop means arrivals follow a SCHEDULE, not completions: requests
land at their appointed time whether or not the system has drained the
previous ones, which is what exposes head-of-line blocking, queue
growth, and the shed knee (a closed-loop client self-throttles and
hides all three). The workload shape:

- **Zipf prompt popularity** (``rank^-a``): a few hot prompts sharing a
  block-aligned system prefix dominate, so the prefill tier's prefix
  cache gets realistic reuse.
- **Arrival shapes**: ``uniform`` (constant rate), ``burst`` (groups
  arriving simultaneously — the TTFT-p99 killer), ``diurnal`` (a
  sinusoidal rate swing compressed into the run, peak ~2x the mean).
- **Slow clients**: a fraction of requests drain their token stream
  slowly (``token_sleep_s`` per token); decode must keep serving other
  requests while they linger.

Every request routes through a ``serve.disagg.DisaggRouter`` (disagg or
colocated mode — same admission control), so shedding engages before
queue depth is unbounded; sheds are counted, never retried (open loop).

The JSON record (last stdout line; ``--out`` also writes it) carries
TTFT p50/p99 ms, tokens/s, shed rate, and the KV-transfer accounting
(published vs fetched bytes, shm vs rpc split) — the one-set-of-numbers
evidence that no process materialized a full KV copy. Run tiny on CPU::

    python -m ray_tpu.bench_serve --requests 32 --arrival burst

``--cluster`` starts a local ray_tpu cluster and runs the prefill and
decode tiers as separate actor processes (real chunk-fabric transfers,
shm-accounted); without it everything runs in-process and the KV rides
the record inline (fetched_bytes 0 — the colocated-process shape).
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np


def make_prompts(config, *, n_distinct: int = 8, block_size: int = 16,
                 sys_blocks: int = 2, seed: int = 0) -> List[List[int]]:
    """Distinct prompts sharing a block-aligned system prefix (so the
    prefix cache can bite), each with a short distinct tail."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, config.vocab_size,
                              sys_blocks * block_size).tolist()
    return [sys_prompt + rng.integers(
        1, config.vocab_size,
        int(rng.integers(2, block_size + 1))).tolist()
        for _ in range(n_distinct)]


def arrival_offsets(n: int, rate_rps: float, shape: str,
                    burst_size: int = 8) -> List[float]:
    """Seconds-from-start arrival time of each request (open loop)."""
    if rate_rps <= 0:
        raise ValueError("rate_rps must be > 0")
    if shape == "uniform":
        return [i / rate_rps for i in range(n)]
    if shape == "burst":
        return [(i // burst_size) * (burst_size / rate_rps)
                for i in range(n)]
    if shape == "diurnal":
        # sinusoidal intensity over the run: rate(t) swings between
        # ~0.4x and ~2x the mean (one compressed "day"), integrated
        # stepwise so the schedule stays deterministic
        out, t = [], 0.0
        horizon = n / rate_rps
        for _ in range(n):
            phase = min(1.0, t / max(horizon, 1e-9))
            inst = rate_rps * (0.4 + 1.6 * np.sin(np.pi * phase) ** 2)
            out.append(t)
            t += 1.0 / inst
        return out
    raise ValueError(f"unknown arrival shape {shape!r} "
                     "(uniform|burst|diurnal)")


def run_load(router, prompts: Sequence[Sequence[int]], *,
             n_requests: int = 64, max_new_tokens: int = 8,
             rate_rps: float = 8.0, arrival: str = "uniform",
             burst_size: int = 8, zipf_a: float = 1.1,
             slow_client_frac: float = 0.0,
             token_sleep_s: float = 0.02,
             timeout_s: float = 120.0,
             deadline_s: Optional[float] = None,
             outputs: Optional[Dict[int, List[int]]] = None,
             tenants: Optional[Sequence[str]] = None,
             tenant_zipf: float = 1.1,
             samples: Optional[List[Dict[str, Any]]] = None,
             seed: int = 0) -> Dict[str, Any]:
    """Replay the open-loop schedule against `router` and return the
    benchmark record (no JSON printing — callers compose it).
    `deadline_s` propagates a per-request deadline (sheds past it carry
    cause "deadline" — slow clients exercise exactly that edge).
    `outputs`, when given, collects each completed request's token list
    by request index — the chaos harness diffs it against a clean run's
    to prove failed-over requests stayed bit-identical.
    `tenants` (multi-tenant LoRA): each request carries a tenant tag
    drawn Zipf(`tenant_zipf`) over the list — hot tenants dominate, the
    tail pages through the adapter pool. `samples`, when given,
    collects one per-request dict (index, tenant, arrival offset, ttft)
    — the publish-no-stall analysis slices these."""
    from ray_tpu.observability import requests as reqtrace
    from ray_tpu.serve.handle import RequestShedError

    # flight-recorder window start: the record embeds the p99
    # attribution and slowest-request phase breakdowns computed over
    # ONLY this run's traces (warm-up traffic is excluded by seq)
    trace_store = reqtrace.store() if reqtrace.enabled() else None
    trace_seq0 = trace_store.seq() if trace_store is not None else 0

    rng = np.random.default_rng(seed)
    pop = 1.0 / np.arange(1, len(prompts) + 1) ** zipf_a
    picks = rng.choice(len(prompts), size=n_requests, p=pop / pop.sum())
    if tenants:
        tpop = 1.0 / np.arange(1, len(tenants) + 1) ** tenant_zipf
        tpicks = rng.choice(len(tenants), size=n_requests,
                            p=tpop / tpop.sum())
    slow = rng.random(n_requests) < slow_client_frac
    offsets = arrival_offsets(n_requests, rate_rps, arrival, burst_size)

    lock = threading.Lock()
    ttfts: List[float] = []
    latencies: List[float] = []
    tokens = [0] * n_requests
    outcomes = {"ok": 0, "shed": 0, "error": 0}
    shed_causes: Dict[str, int] = {}
    errors: List[str] = []

    def one(i: int) -> None:
        t0 = time.perf_counter()
        first: List[float] = []
        tenant = tenants[int(tpicks[i])] if tenants else None
        try:
            toks = router.generate(
                prompts[int(picks[i])], max_new_tokens,
                timeout_s=timeout_s,
                deadline_s=deadline_s,
                on_first_token=lambda: first.append(
                    time.perf_counter() - t0),
                token_sleep_s=token_sleep_s if slow[i] else 0.0,
                tenant=tenant)
            wall = time.perf_counter() - t0
            with lock:
                outcomes["ok"] += 1
                tokens[i] = len(toks)
                latencies.append(wall)
                if first:
                    ttfts.append(first[0])
                if outputs is not None:
                    outputs[i] = list(toks)
                if samples is not None:
                    samples.append({
                        "i": i, "tenant": tenant,
                        "prompt": int(picks[i]),
                        "offset_s": offsets[i],
                        "ttft_ms": first[0] * 1e3 if first else None})
        except RequestShedError as e:
            # a shed WITHOUT a cause is a regression the chaos verdict
            # must catch — never default it to a legitimate cause
            cause = getattr(e, "cause", None) or "unattributed"
            with lock:
                outcomes["shed"] += 1
                shed_causes[cause] = shed_causes.get(cause, 0) + 1
        except Exception as e:  # noqa: BLE001 — recorded, not fatal
            with lock:
                outcomes["error"] += 1
                if len(errors) < 5:
                    errors.append(f"{type(e).__name__}: {str(e)[:120]}")

    t_start = time.perf_counter()
    threads: List[threading.Thread] = []
    for i in range(n_requests):
        delay = offsets[i] - (time.perf_counter() - t_start)
        if delay > 0:
            time.sleep(delay)  # open loop: fire on schedule, not drain
        th = threading.Thread(target=one, args=(i,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=timeout_s)
    wall = time.perf_counter() - t_start

    # ONE locked snapshot for the whole record: stuck request threads
    # outlive their join timeout (daemon) and may still be mutating the
    # outcome state while the record is built. hung is derived from the
    # same view — every request thread records exactly one outcome
    # before exiting, so completed+shed+errors+hung == n_requests holds
    # by construction and a smaller population can never go unreported.
    with lock:
        snap = dict(outcomes)
        total_tokens = int(sum(tokens))
        ttft_ms = sorted(t * 1e3 for t in ttfts)
        lat_ms = sorted(t * 1e3 for t in latencies)
        causes = dict(shed_causes)
        err_samples = list(errors)
    hung = n_requests - sum(snap.values())
    pct = (lambda p: round(float(np.percentile(ttft_ms, p)), 2)
           if ttft_ms else None)
    lpct = (lambda p: round(float(np.percentile(lat_ms, p)), 2)
            if lat_ms else None)
    rec: Dict[str, Any] = {
        "n_requests": n_requests,
        "arrival": arrival,
        "rate_rps": rate_rps,
        "zipf_a": zipf_a,
        **({"tenants": len(tenants), "tenant_zipf": tenant_zipf}
           if tenants else {}),
        "max_new_tokens": max_new_tokens,
        "slow_client_frac": slow_client_frac,
        "completed": snap["ok"],
        "shed": snap["shed"],
        "errors": snap["error"],
        "shed_rate": round(snap["shed"] / n_requests, 4),
        "shed_causes": causes,
        "ttft_p50_ms": pct(50),
        "ttft_p99_ms": pct(99),
        "latency_p50_ms": lpct(50),
        "latency_p99_ms": lpct(99),
        "tokens_total": total_tokens,
        "tokens_per_sec": round(total_tokens / wall, 1) if wall else 0.0,
        "wall_s": round(wall, 3),
    }
    if deadline_s is not None:
        rec["deadline_s"] = deadline_s
    if hung:
        rec["hung"] = hung
    if err_samples:
        rec["error_samples"] = err_samples
    if trace_store is not None:
        # per-request tail attribution over this run's traces: which
        # phase owns the p50->p99 gap, plus the five slowest requests'
        # full phase breakdowns — the BENCH_* record names the tail
        # owner instead of just reporting that a tail exists
        run_traces = trace_store.summaries_since(trace_seq0)
        if run_traces:
            slowest = sorted(run_traces,
                             key=lambda s: -s.get("total_ms", 0.0))[:5]
            rec["request_trace"] = {
                "n_traced": len(run_traces),
                "p99_attribution": reqtrace.p99_attribution(run_traces),
                "slowest": [
                    {"request_id": s.get("request_id"),
                     "total_ms": round(s.get("total_ms", 0.0), 2),
                     "outcome": s.get("outcome"),
                     "attempts": s.get("attempts", 1),
                     "phase_ms": {k: round(v, 2) for k, v in
                                  (s.get("phase_ms") or {}).items()}}
                    for s in slowest],
            }
    return rec


def collect_kv_accounting(prefill: Sequence[Any],
                          decode: Sequence[Any]) -> Dict[str, int]:
    """Sum the tiers' transfer counters (local objects or actors) —
    the record's no-full-copy evidence."""
    from ray_tpu.serve.disagg import _call

    out = {"transfers": 0, "published_transfers": 0,
           "published_bytes": 0, "fetched_bytes": 0,
           "shm_bytes": 0, "rpc_bytes": 0}
    for p in prefill:
        s = _call(p, "stats")
        out["published_transfers"] += int(s.get("published_transfers", 0))
        out["published_bytes"] += int(s.get("published_bytes", 0))
    for d in decode:
        s = _call(d, "stats")
        out["transfers"] += int(s.get("transfers", 0))
        out["fetched_bytes"] += int(s.get("kv_fetched_bytes", 0))
        out["shm_bytes"] += int(s.get("shm_bytes", 0))
        out["rpc_bytes"] += int(s.get("rpc_bytes", 0))
    return out


def _tier_factories(params, config, args, use_cluster: bool,
                    chaos_spec: Optional[str] = None):
    """(prefill_factory, decode_factory, kill) — one replica per call,
    in-process objects or actors. The autoscaled run grows tiers through
    exactly these, so a scaled-up replica pays the same real cold-start
    (engine init + first compile) a production scale-up would.
    `chaos_spec` scripts kill_replica faults into the replicas; each
    factory numbers its replicas per role (creation index) so the plan
    targets exactly one, and a self-healer replacement (a later index)
    never re-fires the same action."""
    import itertools as it

    from ray_tpu.serve.disagg import DecodeServer, PrefillServer

    # retention must cover every transfer that can be legitimately
    # in flight (held from publish until the router acks after decode):
    # decode_replicas * (capacity + queue depth), and affinity can
    # route ALL of them to ONE prefill server — a smaller window would
    # reap chunks a decode replica is about to fetch, failing requests
    # under exactly the burst load the harness measures. The router
    # re-pushes the live bound on every add_*, this only seeds it.
    retain = max(32, 2 * args.decode_replicas
                 * (args.max_batch + args.queue_depth))
    pf_seq, dec_seq = it.count(), it.count()
    speculate_k = int(getattr(args, "_speculate_k", 0) or 0)
    kv_int8 = bool(getattr(args, "_kv_int8", False))
    # multi-tenant LoRA tiers (--tenants): cluster replicas page
    # adapters from the weight fabric (lora=True -> subscriber-backed
    # source; the driver publishes the tenant set up front), inline
    # replicas from a local source seeded with the same adapters
    lora_kw: Dict[str, Any] = {}
    tenant_adapters = getattr(args, "_tenant_adapters", None)
    if tenant_adapters:
        lora_kw = dict(
            lora=True if use_cluster else dict(tenant_adapters),
            lora_pool_slots=args.lora_pool_slots,
            lora_rank_max=max(args.lora_rank, 1))
    # --pool-blocks unset (None) flows through to resolve_pool_config's
    # own sizing — which is what doubles the defaulted pool under int8.
    # The harness must NOT double anything itself: an explicit size is
    # honored as-is (a user pinned it to fit HBM), and the int8
    # capacity gain in the record has to come from the real mechanism.
    kw = dict(kv_block_size=args.block_size,
              kv_pool_blocks=args.pool_blocks, kv_int8=kv_int8,
              retain=retain, chaos=chaos_spec, **lora_kw)
    # --kvplane legs pin the tiered KV plane on/off per run (None =
    # leave the replica on its env-knob default); the arena bound makes
    # the tier-2 spill capacity an explicit part of the record
    kvplane = getattr(args, "_kvplane", None)
    if kvplane is not None:
        kw["kvplane"] = bool(kvplane)
        if kvplane and getattr(args, "kvplane_arena_mb", 0):
            kw["kvplane_arena_bytes"] = int(
                args.kvplane_arena_mb) * (1 << 20)
    if use_cluster:
        import ray_tpu

        def prefill_factory():
            a = ray_tpu.remote(PrefillServer).options(
                max_concurrency=8).remote(
                    params, config, chaos_replica=next(pf_seq), **kw)
            ray_tpu.get(a.stats.remote(), timeout=120.0)  # fail fast
            return a

        def decode_factory():
            a = ray_tpu.remote(DecodeServer).options(
                max_concurrency=args.max_batch + 4).remote(
                    params, config, max_batch=args.max_batch,
                    chaos=chaos_spec, chaos_replica=next(dec_seq),
                    speculate_k=speculate_k, **lora_kw)
            ray_tpu.get(a.stats.remote(), timeout=120.0)
            return a

        def kill(replica):
            try:
                ray_tpu.kill(replica)
            except Exception:  # noqa: BLE001 — already gone
                pass
    else:
        def prefill_factory():
            return PrefillServer(params, config,
                                 chaos_replica=next(pf_seq), **kw)

        def decode_factory():
            return DecodeServer(params, config,
                                max_batch=args.max_batch,
                                chaos=chaos_spec,
                                chaos_replica=next(dec_seq),
                                speculate_k=speculate_k, **lora_kw)

        def kill(replica):
            stop = getattr(replica, "stop", None)
            if callable(stop):
                try:
                    stop()
                except Exception:  # noqa: BLE001 — already stopped
                    pass

    return prefill_factory, decode_factory, kill


def _build_tiers(params, config, args, use_cluster: bool,
                 prefill_replicas: Optional[int] = None,
                 decode_replicas: Optional[int] = None):
    """(router, prefill_list, decode_list, cleanup) for one mode."""
    from ray_tpu.serve.disagg import DisaggRouter

    pf_n = (args.prefill_replicas if prefill_replicas is None
            else prefill_replicas)
    dec_n = (args.decode_replicas if decode_replicas is None
             else decode_replicas)
    prefill_factory, decode_factory, kill = _tier_factories(
        params, config, args, use_cluster)
    prefill = [prefill_factory() for _ in range(pf_n)]
    decode = [decode_factory() for _ in range(dec_n)]
    router = DisaggRouter(decode=decode, prefill=prefill,
                          max_queue_depth=args.queue_depth,
                          affinity_tokens=args.block_size)

    def cleanup():
        # the ROUTER's live view, not the construction-time lists: an
        # autoscaled run may have grown or drained either tier
        live = [r["target"] for t in ("prefill", "decode")
                for r in router.tier_replicas(t)]
        for a in live:
            kill(a)

    return router, prefill, decode, cleanup


def _warm(router, prompts) -> None:
    """Warm the compile caches off the clock: each distinct prompt
    shape costs one prefill compile on first sight."""
    for p in prompts:
        router.generate(p, 2)


def _static_run(params, config, args, use_cluster, prompts, load_kw,
                pf_n: int, dec_n: int) -> Dict[str, Any]:
    """One fixed-(P,D) provisioning replayed through the open-loop
    schedule; replica-hours are simply (P + D) x wall."""
    router, prefill, decode, cleanup = _build_tiers(
        params, config, args, use_cluster, prefill_replicas=pf_n,
        decode_replicas=dec_n)
    try:
        _warm(router, prompts)
        warm_rt = router.stats()  # counters cover ONLY the measured run
        rec = run_load(router, prompts, **load_kw)
        st = router.stats()
        rec["router"] = {k: st[k] - warm_rt[k] for k in
                         ("dispatched", "completed", "shed")}
        rec["router"]["max_pending"] = st["max_pending"]
    finally:
        cleanup()
    rec["config"] = f"{pf_n}x{dec_n}"
    rec["prefill_replicas"] = pf_n
    rec["decode_replicas"] = dec_n
    rec["replica_hours"] = round(
        (pf_n + dec_n) * rec["wall_s"] / 3600.0, 6)
    return rec


def _autoscaled_run(params, config, args, use_cluster, prompts,
                    load_kw, target_p99_ms: float) -> Dict[str, Any]:
    """The closed control loop under the same schedule: tiers start at
    the minimum, the serve/autoscale.py policy drives them, and
    replica-hours are the loop's measured integral of live replicas."""
    from ray_tpu.serve.autoscale import (DisaggAutoscaler, DisaggPolicy,
                                         TierSpec)

    prefill_factory, decode_factory, _kill = _tier_factories(
        params, config, args, use_cluster)
    router, prefill, decode, cleanup = _build_tiers(
        params, config, args, use_cluster,
        prefill_replicas=args.min_prefill,
        decode_replicas=args.min_decode)
    scaler = DisaggAutoscaler(
        router,
        prefill=TierSpec(prefill_factory,
                         min_replicas=args.min_prefill,
                         max_replicas=args.max_prefill,
                         up_delay_s=args.up_delay,
                         down_delay_s=args.down_delay,
                         cooldown_s=args.cooldown),
        decode=TierSpec(decode_factory,
                        min_replicas=args.min_decode,
                        max_replicas=args.max_decode,
                        up_delay_s=args.up_delay,
                        down_delay_s=args.down_delay,
                        cooldown_s=args.cooldown),
        interval_s=args.autoscale_interval,
        drain_grace_s=args.drain_grace)
    scaler.policy.target_p99_ms = target_p99_ms
    try:
        _warm(router, prompts)
        warm_rt = router.stats()  # counters cover ONLY the measured run
        # the warm phase's first-compile TTFTs must not read as an SLO
        # breach when the policy wakes up
        router.reset_signal_windows()
        scaler.start()
        rec = run_load(router, prompts, **load_kw)
        st = router.stats()
        rec["router"] = {k: st[k] - warm_rt[k] for k in
                         ("dispatched", "completed", "shed")}
        rec["router"]["max_pending"] = st["max_pending"]
    finally:
        scaler.stop()
        cleanup()
    st = scaler.status()
    rs = st["replica_seconds"]
    rec["config"] = "autoscale"
    rec["replica_hours"] = round(
        (rs["prefill"] + rs["decode"]) / 3600.0, 6)
    rec["autoscale"] = {
        "target_p99_ms": target_p99_ms,
        "bounds": {"prefill": st["prefill_bounds"],
                   "decode": st["decode_bounds"]},
        "scale_ups": st["scale_ups"],
        "scale_downs": st["scale_downs"],
        "drains_completed": st["drains_completed"],
        "drains_forced": st["drains_forced"],
        "replica_seconds": rs,
        "final_active": {"prefill": st["prefill_active"],
                         "decode": st["decode_active"]},
    }
    return rec


def _fault_run(params, config, args, prompts, load_kw,
               chaos_spec: Optional[str]):
    """One open-loop run with tier self-healing attached (actor
    replicas over the real chunk fabric): the chaos harness's unit of
    measurement. Returns (record, outputs-by-request-index). The
    self-healer WATCHES (event-driven death handling) without the
    scaling tick — recovery here is pure failover + replacement, never
    a load decision."""
    from ray_tpu.serve.autoscale import DisaggAutoscaler, TierSpec
    from ray_tpu.serve.disagg import DisaggRouter, _call

    pf_n = args.prefill_replicas
    dec_n = max(2, args.decode_replicas)  # failover needs a survivor
    prefill_factory, decode_factory, kill = _tier_factories(
        params, config, args, True, chaos_spec)
    prefill = [prefill_factory() for _ in range(pf_n)]
    decode = [decode_factory() for _ in range(dec_n)]
    router = DisaggRouter(decode=decode, prefill=prefill,
                          max_queue_depth=args.queue_depth,
                          affinity_tokens=args.block_size)
    # bounds sized so a replacement always fits; the huge delays make
    # the hysteresis machinery inert even if someone calls tick()
    scaler = DisaggAutoscaler(
        router,
        prefill=TierSpec(prefill_factory, min_replicas=pf_n,
                         max_replicas=pf_n + 1, up_delay_s=3600.0,
                         down_delay_s=3600.0),
        decode=TierSpec(decode_factory, min_replicas=dec_n,
                        max_replicas=dec_n + 1, up_delay_s=3600.0,
                        down_delay_s=3600.0),
        interval_s=3600.0, drain_grace_s=args.drain_grace)
    outputs: Dict[int, List[int]] = {}
    try:
        _warm(router, prompts)
        # measurement starts HERE: zero the chaos counters so a plan's
        # `at=request:N` / `at=token:K` means the Nth MEASURED request
        # (Kth measured token), not warm-up traffic (PR-12 known limit)
        for tier in ("prefill", "decode"):
            for r in router.tier_replicas(tier):
                try:
                    _call(r["target"], "reset_chaos_counts")  # shardlint: disable=unsupervised-actor-call
                except Exception:  # noqa: BLE001 — pre-reset replica
                    pass
        warm_rt = router.stats()
        router.reset_signal_windows()
        scaler.watch()
        rec = run_load(router, prompts, outputs=outputs, **load_kw)
        st = router.stats()
        rec["router"] = {k: st[k] - warm_rt[k] for k in
                         ("dispatched", "completed", "shed")}
        rec["router"]["max_pending"] = st["max_pending"]
        # give the event-driven heal a moment to finish registering a
        # replacement before the teardown sweeps the replica set
        deadline = time.perf_counter() + 10.0
        while time.perf_counter() < deadline:
            h = scaler.servefault_stats()
            if sum(h["deaths"].values()) == \
                    sum(h["replacements"].values()) \
                    + h["replacements_blocked"]:
                break
            time.sleep(0.25)
        rec["servefault"] = router.servefault_stats()
        rec["healer"] = scaler.servefault_stats()
        router.publish_servefault(force=True)
    finally:
        scaler.stop()
        for t in ("prefill", "decode"):
            for r in router.tier_replicas(t):
                kill(r["target"])
    return rec, outputs


def _chaos_record(params, config, args, prompts, load_kw
                  ) -> Dict[str, Any]:
    """The acceptance scenario: a clean replay vs the same replay with
    a scripted replica kill. Records the failover recovery impact and
    the zero-silently-dropped / bit-identical verdict."""
    # the decode pick's free-slot tie-break favors the LAST replica,
    # so that's the one whose token counter reliably reaches the kill
    # point; prefill affinity hashes, so replica 0 is as good as any
    victim = (max(2, args.decode_replicas) - 1
              if args.chaos_role == "decode" else 0)
    plan = [{"action": "kill_replica", "role": args.chaos_role,
             "at": args.chaos_at, "replica": victim}]
    spec = json.dumps(plan)
    clean, clean_out = _fault_run(params, config, args, prompts,
                                  load_kw, None)
    chaos, chaos_out = _fault_run(params, config, args, prompts,
                                  load_kw, spec)
    common = sorted(set(clean_out) & set(chaos_out))
    mismatched = [i for i in common if clean_out[i] != chaos_out[i]]
    n = load_kw["n_requests"]
    sf = chaos.get("servefault") or {}
    healer = chaos.get("healer") or {}
    deaths = sum((healer.get("deaths") or {}).values())
    causes = chaos.get("shed_causes") or {}
    verdict = {
        # every accepted request either completed or shed WITH a cause
        "zero_silently_dropped": (not chaos.get("hung")
                                  and chaos.get("errors", 0) == 0
                                  and chaos["completed"]
                                  + chaos["shed"] == n),
        # falsifiable: run_load buckets cause-less sheds under
        # "unattributed" instead of defaulting them to a real cause
        "all_sheds_attributed": ("unattributed" not in causes
                                 and sum(causes.values())
                                 == chaos["shed"]),
        # failed-over requests match the clean run token-for-token
        "bit_identical_completed": not mismatched,
        "compared_outputs": len(common),
        "mismatched_outputs": mismatched[:8],
        "kill_fired": deaths >= 1,
        "failovers": sum((sf.get("failovers") or {}).values()),
        "replaced": sum((healer.get("replacements") or {}).values()),
    }
    verdict["pass"] = bool(
        verdict["zero_silently_dropped"]
        and verdict["all_sheds_attributed"]
        and verdict["bit_identical_completed"]
        and verdict["kill_fired"])
    recovery = {
        "ttft_p99_ms_clean": clean.get("ttft_p99_ms"),
        "ttft_p99_ms_chaos": chaos.get("ttft_p99_ms"),
        "latency_p99_ms_clean": clean.get("latency_p99_ms"),
        "latency_p99_ms_chaos": chaos.get("latency_p99_ms"),
        "failover_recovery_ms":
            sf.get("recent_failover_recovery_ms"),
    }
    return {"chaos_plan": plan, "clean": clean, "chaos": chaos,
            "recovery": recovery, "verdict": verdict}


# --------------------------------------------------------- HTTP front door


def _http_sse_drain(resp, t0: float) -> Dict[str, Any]:
    """Drain one SSE completions stream off the real socket: returns
    {text, ttft_s, finish, frames}. The concatenated deltas ARE the
    response — the bit-identity check compares them against the
    engine oracle verbatim."""
    text = ""
    ttft: Optional[float] = None
    finish: Optional[str] = None
    frames = 0
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        payload = line[len(b"data: "):]
        if payload == b"[DONE]":
            break
        obj = json.loads(payload)
        if "error" in obj:
            raise RuntimeError(str(obj["error"].get("message",
                                                    "stream error")))
        frames += 1
        choice = obj["choices"][0]
        delta = choice.get("text") or ""
        if delta and ttft is None:
            ttft = time.perf_counter() - t0
        text += delta
        if choice.get("finish_reason"):
            finish = choice["finish_reason"]
    return {"text": text, "ttft_s": ttft, "finish": finish,
            "frames": frames}


def _http_record(params, config, args, prompts) -> Dict[str, Any]:
    """The front-door acceptance run: a mixed interactive+batch storm
    over REAL sockets against serve/gateway.py.

    Shape: `--http-max-batch` long batch decodes grab every engine slot
    at t=0 (slow clients — `token_sleep_s` pacing rides the request
    body); surplus batch arrivals land on the full system and shed with
    an attributed cause; interactive requests arrive mid-decode and
    must PREEMPT a batch slot (cancel + replay-with-history) to hold
    their TTFT SLO. Every completed response — including the preempted-
    then-resumed batch streams — must be bit-identical to a serial
    engine-oracle decode of the same prompt, which is what makes the
    preemption path oracle-checked rather than best-effort."""
    import dataclasses
    import http.client

    import jax

    from ray_tpu.models.engine import ContinuousBatchingEngine
    from ray_tpu.models.llama import llama_init
    from ray_tpu.serve.disagg import DisaggRouter
    from ray_tpu.serve.gateway import GatewayServer
    from ray_tpu.serve.qos import QosGate

    # The preemption window is the ENGINE's production time for a batch
    # stream, so the batch budget needs headroom past tiny()'s 128-token
    # horizon (llama has no learned positions — same seed, same weights)
    cfg = dataclasses.replace(
        config, max_seq_len=max(config.max_seq_len,
                                args.http_batch_new + 2 * args.block_size
                                + 32))
    params = llama_init(cfg, jax.random.PRNGKey(args.seed))
    prompts = make_prompts(cfg, n_distinct=args.distinct,
                           block_size=args.block_size, seed=args.seed)

    engine = ContinuousBatchingEngine(params, cfg,
                                      max_batch=args.http_max_batch)
    router = DisaggRouter(colocated=engine, max_queue_depth=0)
    gw = GatewayServer(router, model="bench",
                       vocab_size=cfg.vocab_size,
                       qos=QosGate(router=router),
                       max_tokens_cap=args.http_batch_new)
    host, port = gw.ready()

    n_fill = args.http_max_batch
    n_extra = max(0, args.http_batch - n_fill)
    n_inter = args.http_interactive
    rng = np.random.default_rng(args.seed)
    pop = 1.0 / np.arange(1, len(prompts) + 1) ** args.zipf_a
    picks = rng.choice(len(prompts), size=n_fill + n_extra + n_inter,
                       p=pop / pop.sum())

    # serial engine oracle BEFORE the storm: one uninterrupted greedy
    # decode per (prompt, budget) — doubles as compile warm-up, so the
    # measured TTFTs are steady-state
    oracle: Dict[Any, str] = {}
    for i in range(n_fill + n_extra + n_inter):
        budget = (args.http_interactive_new if i >= n_fill + n_extra
                  else args.http_batch_new)
        key = (int(picks[i]), budget)
        if key not in oracle:
            toks = engine.generate(prompts[int(picks[i])], budget)
            oracle[key] = " ".join(str(int(t)) for t in toks)

    plan: List[Dict[str, Any]] = []
    for i in range(n_fill):
        plan.append({"i": i, "cls": "batch", "offset": 0.0,
                     "budget": args.http_batch_new,
                     "pace": args.token_sleep})
    for i in range(n_extra):
        plan.append({"i": n_fill + i, "cls": "batch",
                     "offset": 0.4 + 0.05 * i,
                     "budget": args.http_batch_new, "pace": 0.0})
    for i in range(n_inter):
        plan.append({"i": n_fill + n_extra + i, "cls": "interactive",
                     "offset": 0.9 + 0.7 * i,
                     "budget": args.http_interactive_new, "pace": 0.0})

    lock = threading.Lock()
    results: List[Dict[str, Any]] = []

    def one(req: Dict[str, Any]) -> None:
        time.sleep(req["offset"])
        pidx = int(picks[req["i"]])
        body = json.dumps({
            "model": "bench", "prompt": prompts[pidx],
            "max_tokens": req["budget"], "stream": True,
            "priority": req["cls"],
            "token_sleep_s": req["pace"]})
        t0 = time.perf_counter()
        rec: Dict[str, Any] = {"i": req["i"], "class": req["cls"],
                               "prompt": pidx, "budget": req["budget"]}
        try:
            conn = http.client.HTTPConnection(host, port, timeout=180)
            conn.request("POST", "/v1/completions", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status == 200:
                out = _http_sse_drain(resp, t0)
                rec["outcome"] = "ok"
                rec["text"] = out["text"]
                rec["ttft_ms"] = (round(out["ttft_s"] * 1e3, 2)
                                  if out["ttft_s"] is not None else None)
                rec["finish"] = out["finish"]
            else:
                rec["outcome"] = "shed" if resp.status in (429, 503) \
                    else "error"
                rec["cause"] = (resp.headers.get("X-Shed-Cause")
                                or "unattributed")
                try:
                    err = json.loads(resp.read() or b"{}")
                    if rec["cause"] == "unattributed":
                        rec["cause"] = err.get("error", {}).get(
                            "code") or "unattributed"
                except Exception:  # noqa: BLE001 — cause is best-effort
                    pass
            conn.close()
        except Exception as e:  # noqa: BLE001 — recorded, not fatal
            rec["outcome"] = "error"
            rec["cause"] = f"{type(e).__name__}: {str(e)[:120]}"
        rec["latency_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        with lock:
            results.append(rec)

    t_start = time.perf_counter()
    threads = [threading.Thread(target=one, args=(r,), daemon=True)
               for r in plan]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=240)
    wall = time.perf_counter() - t_start

    router.publish_telemetry(force=True)
    gw.publish_telemetry(force=True)
    rt = router.stats()
    kv = engine.kv_stats()
    gw_stats = gw.stats()
    gw.stop()
    engine.stop()

    with lock:
        rows = list(results)
    by_class: Dict[str, Dict[str, Any]] = {}
    mismatches: List[Dict[str, Any]] = []
    for cls in ("interactive", "batch"):
        sub = [r for r in rows if r["class"] == cls]
        ttfts = sorted(r["ttft_ms"] for r in sub
                       if r.get("ttft_ms") is not None)
        lats = sorted(r["latency_ms"] for r in sub)
        pct = (lambda xs, p: round(float(np.percentile(xs, p)), 2)
               if xs else None)
        by_class[cls] = {
            "n": len(sub),
            "completed": sum(1 for r in sub if r.get("outcome") == "ok"),
            "shed": sum(1 for r in sub if r.get("outcome") == "shed"),
            "errors": sum(1 for r in sub
                          if r.get("outcome") == "error"),
            "shed_causes": {},
            "ttft_p50_ms": pct(ttfts, 50),
            "ttft_p99_ms": pct(ttfts, 99),
            "latency_p50_ms": pct(lats, 50),
            "latency_p99_ms": pct(lats, 99),
        }
        for r in sub:
            if r.get("outcome") == "shed":
                c = r.get("cause") or "unattributed"
                sc = by_class[cls]["shed_causes"]
                sc[c] = sc.get(c, 0) + 1
    for r in rows:
        if r.get("outcome") != "ok":
            continue
        want = oracle[(r["prompt"], r["budget"])]
        if r["text"] != want:
            mismatches.append({"i": r["i"], "class": r["class"],
                               "prompt": r["prompt"],
                               "got_len": len(r["text"]),
                               "want_len": len(want)})

    inter, batch = by_class["interactive"], by_class["batch"]
    total = len(rows)
    verdict: Dict[str, Any] = {
        "accounted": (sum(c["n"] for c in by_class.values())
                      == len(plan) == total),
        "bit_identity": not mismatches,
        "interactive_ttft_slo_ms": args.http_slo_ms,
        "interactive_ttft_slo": (
            inter["ttft_p99_ms"] is not None
            and inter["ttft_p99_ms"] <= args.http_slo_ms),
        "interactive_all_served": (
            inter["completed"] == inter["n"] and inter["shed"] == 0),
        "batch_absorbs": (
            batch["shed"] >= (1 if n_extra else 0)
            and "unattributed" not in batch["shed_causes"]
            and inter["shed"] == 0),
        "preemptions_observed": int(rt.get("preemptions", 0)) >= 1,
        "preempted_resumed": int(rt.get("preempted_requests", 0)) >= 1,
        "no_errors": all(c["errors"] == 0 for c in by_class.values()),
    }
    verdict["pass"] = all(
        verdict[k] for k in ("accounted", "bit_identity",
                             "interactive_ttft_slo",
                             "interactive_all_served", "batch_absorbs",
                             "preemptions_observed", "preempted_resumed",
                             "no_errors"))
    rec: Dict[str, Any] = {
        "n_requests": total,
        "wall_s": round(wall, 3),
        "by_class": by_class,
        "preemptions": int(rt.get("preemptions", 0)),
        "preempted_requests": int(rt.get("preempted_requests", 0)),
        "router_sheds_by_cause": dict(rt.get("sheds_by_cause") or {}),
        "engine_cancels_by_reason": dict(
            kv.get("cancelled_by_reason") or {}),
        "gateway": {k: gw_stats.get(k) for k in
                    ("accepted", "completed", "streamed", "tokens_out",
                     "rate_limited", "sheds", "disconnects", "errors",
                     "by_class", "by_code", "ttft_ms")},
        "requests": [{k: v for k, v in r.items() if k != "text"}
                     for r in sorted(rows, key=lambda r: r["i"])],
        "verdict": verdict,
    }
    if mismatches:
        rec["mismatches"] = mismatches[:5]
    return rec


def _collect_lora_pools(router) -> Dict[str, int]:
    """Sum the tier replicas' adapter-pool counters (local objects or
    actors) — the record's paging-amortization evidence."""
    from ray_tpu.serve.disagg import _call

    out = {k: 0 for k in ("acquires", "hits", "misses", "evictions",
                          "swaps", "page_in_bytes", "resident")}
    for tier in ("prefill", "decode"):
        for r in router.tier_replicas(tier):
            s = _call(r["target"], "stats").get("lora") or {}  # shardlint: disable=unsupervised-actor-call
            for k in out:
                out[k] += int(s.get(k, 0))
    return out


def _lora_record(params, config, args, prompts, load_kw,
                 use_cluster: bool) -> Dict[str, Any]:
    """The multi-tenant LoRA acceptance run (``--tenants N``): tenants
    drawn Zipf over N adapters against pools holding fewer, one
    mid-run adapter publish for the hottest tenant, and the four
    verdicts the ROADMAP item names — paging amortized (hit rate high,
    page-in bytes « per-request adapter bytes), per-tenant isolation
    of shed/SLO counters, mixed-batch outputs bit-identical to
    sequential per-tenant runs, and untouched tenants' TTFT flat
    across the publish."""
    from ray_tpu.serve.disagg import _call
    from ray_tpu.serve.lora import (adapter_nbytes, make_lora_adapter,
                                    publish_adapter)

    tenants = [f"t{i:03d}" for i in range(args.tenants)]
    adapters = {t: make_lora_adapter(config, args.lora_rank,
                                     seed=1000 + i)
                for i, t in enumerate(tenants)}
    warm_tenant = "warmup"  # compiles the lora programs off the clock
    adapters[warm_tenant] = make_lora_adapter(config, args.lora_rank,
                                              seed=9999)
    args._tenant_adapters = adapters
    if use_cluster:
        # the fabric is the paging source: publish the tenant set up
        # front, replicas fetch on demand (real page-in byte
        # accounting through the subscriber)
        for t, a in adapters.items():
            publish_adapter(t, a)
    router, prefill, decode, cleanup = _build_tiers(
        params, config, args, use_cluster)
    pub_tenant = tenants[0]  # Zipf rank 1: the hottest tenant
    try:
        for p in prompts:
            router.generate(p, 2)
            router.generate(p, 2, tenant=warm_tenant)
        warm_rt = router.stats()
        warm_pools = _collect_lora_pools(router)
        router.reset_signal_windows()
        samples: List[Dict[str, Any]] = []
        outputs: Dict[int, List[int]] = {}
        publish_at_s = 0.5 * load_kw["n_requests"] / load_kw["rate_rps"]
        pub_state: Dict[str, Any] = {}

        def publisher():
            time.sleep(publish_at_s)
            v2 = make_lora_adapter(config, args.lora_rank, seed=7777)
            t0 = time.perf_counter()
            try:
                if use_cluster:
                    pub_state["version"] = publish_adapter(pub_tenant,
                                                           v2)
                else:
                    for tier in ("prefill", "decode"):
                        for r in router.tier_replicas(tier):
                            pub_state["version"] = _call(  # shardlint: disable=unsupervised-actor-call
                                r["target"], "publish_adapter",
                                pub_tenant, v2)
                pub_state["publish_ms"] = (time.perf_counter() - t0) \
                    * 1e3
            except Exception as e:  # noqa: BLE001 — recorded
                pub_state["error"] = f"{type(e).__name__}: {e}"

        th = threading.Thread(target=publisher, daemon=True)
        th.start()
        rec = run_load(router, prompts, tenants=tenants,
                       tenant_zipf=args.tenant_zipf, samples=samples,
                       outputs=outputs, **load_kw)
        th.join(timeout=30.0)
        st = router.stats()
        rec["router"] = {k: st[k] - warm_rt[k] for k in
                         ("dispatched", "completed", "shed")}
        rec["router"]["max_pending"] = st["max_pending"]
        pools_end = _collect_lora_pools(router)
        pools = {k: pools_end[k] - warm_pools.get(k, 0)
                 for k in pools_end if k != "resident"}
        pools["resident"] = pools_end["resident"]
        acq = pools["acquires"]
        hit_rate = pools["hits"] / acq if acq else 0.0
        # paging-amortization denominator: the bytes a pool-less
        # design would move — every tenant-tagged request ships its
        # whole adapter to both tiers
        naive = 2 * sum(adapter_nbytes(adapters[s["tenant"]])
                        for s in samples if s.get("tenant"))
        # per-tenant isolation: the router's counters, straight off
        # the lora surface
        tstats = router.tenant_stats()
        tstats.pop(warm_tenant, None)
        per_tenant = {t: {k: v[k] for k in ("dispatched", "completed",
                                            "shed", "slo_misses")}
                      for t, v in tstats.items()}
        isolation_ok = all(
            v["completed"] <= v["dispatched"]
            for v in per_tenant.values()) and sum(
            v["dispatched"] for v in per_tenant.values()) == \
            rec["router"]["dispatched"]
        # mixed-batch bit-identity: re-run a sample of completed
        # requests SEQUENTIALLY (one at a time, same tenant + prompt)
        # and diff — greedy decode must not care about batch
        # composition. The hot-published tenant is excluded (its
        # adapter changed mid-run by design). The prefix caches are
        # flushed first so the re-runs prefill CACHE-COLD: the check
        # then independently covers the prefill path instead of
        # replaying whatever the mixed run cached.
        for r in router.tier_replicas("prefill"):
            try:
                _call(r["target"], "invalidate_prefix_cache")  # shardlint: disable=unsupervised-actor-call
            except Exception:  # noqa: BLE001 — older replica
                pass
        checked = mismatched = 0
        for s in samples:
            if checked >= 12:
                break
            if s["tenant"] == pub_tenant or s["i"] not in outputs:
                continue
            seq = router.generate(prompts[s["prompt"]],
                                  load_kw["max_new_tokens"],
                                  tenant=s["tenant"])
            checked += 1
            if list(seq) != outputs[s["i"]]:
                mismatched += 1
        # publish-no-stall: untouched tenants' TTFT before vs after
        # the publish instant
        untouched = [s for s in samples
                     if s["tenant"] not in (pub_tenant, None)
                     and s["ttft_ms"] is not None]
        before = sorted(s["ttft_ms"] for s in untouched
                        if s["offset_s"] < publish_at_s)
        after = sorted(s["ttft_ms"] for s in untouched
                       if s["offset_s"] >= publish_at_s)
        p99 = (lambda xs: round(float(np.percentile(xs, 99)), 2)
               if xs else None)
        p99_before, p99_after = p99(before), p99(after)
        ttft_flat = (p99_before is not None and p99_after is not None
                     and p99_after <= max(2.5 * p99_before,
                                          p99_before + 250.0))
        rec["lora"] = {
            "tenants": len(tenants),
            "tenant_zipf": args.tenant_zipf,
            "pool_slots": args.lora_pool_slots,
            "rank": args.lora_rank,
            "adapter_nbytes": adapter_nbytes(adapters[pub_tenant]),
            "pools": pools,
            "hit_rate": round(hit_rate, 4),
            "page_in_bytes": pools["page_in_bytes"],
            "naive_per_request_adapter_bytes": naive,
            "paging_ratio": round(pools["page_in_bytes"] / naive, 4)
            if naive else None,
            "per_tenant": per_tenant,
            "publish": {
                "tenant": pub_tenant, "at_s": publish_at_s,
                **pub_state,
                "untouched_ttft_p99_before_ms": p99_before,
                "untouched_ttft_p99_after_ms": p99_after,
            },
            "bit_identity": {"checked": checked,
                             "mismatched": mismatched},
        }
        rec["lora"]["verdict"] = {
            "paging_amortized": (hit_rate >= 0.5
                                 and naive > 0
                                 and pools["page_in_bytes"] < naive),
            "tenant_isolation": isolation_ok,
            "mixed_batch_bit_identical": (checked > 0
                                          and mismatched == 0),
            "publish_no_stall": ttft_flat and "error" not in pub_state,
        }
        rec["lora"]["verdict"]["pass"] = all(
            rec["lora"]["verdict"].values())
        for tier_reps in (prefill, decode):
            for rep in tier_reps:
                try:
                    _call(rep, "publish_telemetry", True)  # shardlint: disable=unsupervised-actor-call
                except Exception:  # noqa: BLE001 — telemetry only
                    pass
    finally:
        cleanup()
    return rec


def _spec_run(params, config, args, prompts, load_kw, use_cluster,
              speculate_k: int, kv_int8: bool):
    """One mode of the speculative-decoding comparison: build tiers
    with the given knobs, replay the SAME open-loop Zipf schedule, and
    return (record, per-request outputs). The transient `_speculate_k`
    / `_kv_int8` attrs parameterize `_tier_factories` without touching
    the user-visible flags (each mode overrides them)."""
    from ray_tpu.serve.disagg import _call

    args._speculate_k = speculate_k
    args._kv_int8 = kv_int8
    router, prefill, decode, cleanup = _build_tiers(
        params, config, args, use_cluster)
    try:
        _warm(router, prompts)
        if speculate_k:
            # the verify program (q = k+1) compiles on the first tick
            # that actually holds a draft — the repeat pass hits the
            # output memory, drafts, and pays that compile OFF the
            # measured clock (the plain _warm's 2-token budget never
            # drafts)
            for p in prompts[:2]:
                router.generate(p, 12)
                router.generate(p, 12)
        outputs: Dict[int, List[int]] = {}
        rec = run_load(router, prompts, outputs=outputs, **load_kw)
        # decode-tier speculation counters (acceptance, tokens/verify)
        spec = {"speculate_k": speculate_k, "spec_proposed": 0,
                "spec_accepted": 0, "spec_verify_ticks": 0,
                "spec_emitted_tokens": 0}
        for d in decode:
            s = _call(d, "stats").get("speculation") or {}
            for k in ("spec_proposed", "spec_accepted",
                      "spec_verify_ticks", "spec_emitted_tokens"):
                spec[k] += int(s.get(k, 0))
        if spec["spec_proposed"]:
            spec["acceptance_rate"] = round(
                spec["spec_accepted"] / spec["spec_proposed"], 4)
        if spec["spec_verify_ticks"]:
            spec["tokens_per_verify"] = round(
                spec["spec_emitted_tokens"] / spec["spec_verify_ticks"],
                3)
        rec["speculation"] = spec
        # prefill-tier pool capacity (the int8-doubling evidence)
        pool = {"effective_pool_blocks": 0, "capacity_factor": 1,
                "int8": kv_int8}
        for p in prefill:
            pc = _call(p, "stats").get("prefix_cache") or {}
            pool["effective_pool_blocks"] += int(pc.get("num_blocks", 0))
            pool["capacity_factor"] = max(pool["capacity_factor"],
                                          int(pc.get("capacity_factor",
                                                     1)))
        rec["kv_pool"] = pool
    finally:
        cleanup()
        args._speculate_k = 0
        args._kv_int8 = False
    return rec, outputs


def _int8_logit_probe(params, config, args,
                      prompts) -> Dict[str, Any]:
    """The int8 tolerance contract, measured directly: prefill the
    hottest prompt once from scratch (exact KV) and once through a hit
    on an int8 pool (quantize-on-commit -> dequant-on-gather), and
    compare the last-position logits. Token streams are ints, so
    'unchanged within rtol' is a statement about THESE — quantization
    may legitimately flip a near-tie greedy argmax, and the probe
    bounds how near the tie has to be."""
    import jax.numpy as jnp

    from ray_tpu.models.engine import _prefill_paged
    from ray_tpu.models.generate import _model_fns
    from ray_tpu.models.kvcache import PagedKVCache

    prompt = np.asarray(prompts[0], np.int32)[None]
    probe = _model_fns(config)[1](config, 1, max_len=1)
    empty = jnp.zeros((len(probe), 0) + probe[0]["k"].shape[2:],
                      probe[0]["k"].dtype)
    ref_logits, ck, cv, _ = _prefill_paged(params, prompt, config, empty,
                                           empty)
    kv = PagedKVCache(config, block_size=args.block_size,
                      num_blocks=max(args.pool_blocks or 32, 16),
                      int8=True)
    m = kv.lookup(prompt[0], max_tokens=prompt.shape[1] - 1)
    kv.commit(prompt[0], ck, cv, m)
    m2 = kv.lookup(prompt[0], max_tokens=prompt.shape[1] - 1)
    pk, pv = kv.gather(m2)
    q_logits, _, _, _ = _prefill_paged(params, prompt[:, m2.tokens:],
                                       config, pk, pv)
    ref = np.asarray(ref_logits[0, :config.vocab_size], np.float32)
    got = np.asarray(q_logits[0, :config.vocab_size], np.float32)
    rel = float(np.max(np.abs(got - ref))
                / (np.max(np.abs(ref)) + 1e-9))
    return {"reused_tokens": int(m2.tokens),
            "max_rel_err": round(rel, 5),
            "rtol_bound": 0.05,
            "within_rtol": rel <= 0.05}


def _outputs_identical(base: Dict[int, List[int]],
                       other: Dict[int, List[int]]) -> Dict[str, Any]:
    """Bit-identity evidence over the requests BOTH runs completed
    (sheds may differ between runs — admission timing is load-
    dependent — but any request served by both must match exactly)."""
    common = sorted(set(base) & set(other))
    mismatched = [i for i in common if base[i] != other[i]]
    return {"compared": len(common), "mismatched": len(mismatched),
            "identical": bool(common) and not mismatched}


def _spec_record(params, config, args, prompts, load_kw,
                 use_cluster) -> Dict[str, Any]:
    """The --speculate comparison: the SAME open-loop Zipf schedule
    replayed unspeculated (the PR-9-shaped baseline), speculated, and —
    with --kv-int8 — speculated over the int8 KV pool. The verdict
    gates on >= 2x tokens/s with bit-identical greedy outputs
    (speculation) and unchanged outputs over the quantized pool (int8;
    the pool's dequant rtol bound is tested in tests/test_speculate.py
    — token streams are ints, so "within rtol" at this level means
    unchanged)."""
    out: Dict[str, Any] = {}
    base_rec, base_out = _spec_run(params, config, args, prompts,
                                   load_kw, use_cluster, 0, False)
    out["baseline"] = base_rec
    spec_rec, spec_out = _spec_run(params, config, args, prompts,
                                   load_kw, use_cluster,
                                   args.speculate, False)
    spec_rec["vs_baseline"] = _outputs_identical(base_out, spec_out)
    out["speculate"] = spec_rec
    speedup = (spec_rec["tokens_per_sec"] / base_rec["tokens_per_sec"]
               if base_rec["tokens_per_sec"] else 0.0)
    verdict: Dict[str, Any] = {
        "speedup": round(speedup, 3),
        "bit_identical": spec_rec["vs_baseline"]["identical"],
        "acceptance_rate":
            spec_rec["speculation"].get("acceptance_rate", 0.0),
        "tokens_per_verify":
            spec_rec["speculation"].get("tokens_per_verify", 0.0),
    }
    int8_ok = True
    if args.kv_int8:
        int8_rec, int8_out = _spec_run(params, config, args, prompts,
                                       load_kw, use_cluster,
                                       args.speculate, True)
        int8_rec["vs_baseline"] = _outputs_identical(base_out, int8_out)
        int8_rec["logit_equivalence"] = _int8_logit_probe(
            params, config, args, prompts)
        out["int8"] = int8_rec
        verdict["int8_within_rtol"] = \
            int8_rec["logit_equivalence"]["within_rtol"]
        verdict["int8_output_match_rate"] = round(
            1.0 - int8_rec["vs_baseline"]["mismatched"]
            / max(1, int8_rec["vs_baseline"]["compared"]), 4)
        verdict["int8_pool_gain"] = round(
            int8_rec["kv_pool"]["effective_pool_blocks"]
            / max(1, base_rec["kv_pool"]["effective_pool_blocks"]), 3)
        int8_ok = (verdict["int8_within_rtol"]
                   and verdict["int8_pool_gain"] >= 2.0)
    verdict["pass"] = bool(
        speedup >= 2.0 and verdict["bit_identical"] and int8_ok)
    out["verdict"] = verdict
    return out


def _kvplane_prompts(config, *, n_distinct: int = 8,
                     block_size: int = 16, sys_blocks: int = 2,
                     tail_blocks: int = 4,
                     seed: int = 0) -> List[List[int]]:
    """make_prompts with DEEP distinct tails: each prompt carries
    `tail_blocks` full blocks of its own past the shared system prefix,
    so the distinct-block working set (sys_blocks + n_distinct *
    tail_blocks) can be sized past one replica's HBM pool — the
    pressure that makes the tiered plane's spill path load-bearing."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, config.vocab_size,
                              sys_blocks * block_size).tolist()
    return [sys_prompt + rng.integers(
        1, config.vocab_size,
        tail_blocks * block_size + int(rng.integers(2, block_size))
        ).tolist() for _ in range(n_distinct)]


# per-replica kvplane counters the record aggregates (monotone only —
# gauges like arena entries/bytes don't survive warm-up subtraction)
_KVP_COUNTERS = (
    "spills", "spill_bytes", "tier2_hits", "tier2_probes",
    "tier2_reused_tokens", "tier2_fetched_bytes", "arena_evictions",
    "tier3_publishes", "tier3_adopts", "tier3_adopted_blocks",
    "tier3_reused_tokens", "tier3_fetched_bytes", "evict_storms",
    "storm_evicted_blocks")


def _kvp_totals(prefill_targets) -> Dict[str, int]:
    """Tier counters summed over EVERY prefill replica the leg ever
    created (a cold-swapped replica leaves the router but its spill
    and publish history still belongs to the run's accounting), plus
    the engine-level reused_tokens total (tier-1 hits AND arena
    re-adopts AND tier-3 imports all land there — it is the
    cross-leg comparable 'prefill work the caches absorbed')."""
    from ray_tpu.serve.disagg import _call

    tot = {k: 0 for k in _KVP_COUNTERS}
    tot["reused_tokens"] = 0
    for t in prefill_targets:
        try:
            kvp = _call(t, "kvplane_stats")  # shardlint: disable=unsupervised-actor-call
            st = _call(t, "stats")  # shardlint: disable=unsupervised-actor-call
        except Exception:  # noqa: BLE001 — replica mid-teardown
            continue
        for k in _KVP_COUNTERS:
            tot[k] += int(kvp.get(k, 0))
        tot["reused_tokens"] += int(st.get("reused_tokens", 0))
    return tot


def _kvplane_reset_directory() -> None:
    """Reap every prefix-directory entry between legs (TTL 0 reaps
    unconditionally): a later leg's lookups must not ride the previous
    leg's publishes — its holders are gone, and a stale fallback hint
    would smear tier-3 traffic across the per-leg attribution."""
    import ray_tpu

    w = ray_tpu._private.worker.global_worker
    if w is None or getattr(w, "conductor", None) is None:
        return
    try:
        w.conductor.call("kvplane_reap", 0.0, timeout=5.0)
    except Exception:  # noqa: BLE001 — best-effort hygiene
        pass


def _kvplane_run(params, config, args, prompts, load_kw, *,
                 kvplane: bool, chaos_spec: Optional[str] = None,
                 cold_swap: bool = False,
                 pool_blocks: Optional[int] = None):
    """One leg of the --kvplane comparison: replay the SAME open-loop
    Zipf schedule with the tiered plane pinned on or off (and, for the
    HBM-reference leg, `pool_blocks` overriding the deliberately small
    pool). Returns (record, per-request outputs). With `cold_swap`,
    after the measured run the entire prefill tier is RETIRED from the
    router (replicas stay alive so their published tier-3 chunks do)
    and replaced with cold replicas, then every distinct prompt
    replays once: the directory's holders are gone, so each lookup
    degrades to a fallback hint and the cold replica re-adopts the
    prefix from the object store — the tier-3 persistence story,
    measured."""
    from ray_tpu.serve.disagg import DisaggRouter, _call

    pf_n = max(2, args.prefill_replicas)
    dec_n = args.decode_replicas
    prev_pool = args.pool_blocks
    if pool_blocks is not None:
        args.pool_blocks = pool_blocks
    args._kvplane = kvplane
    try:
        prefill_factory, decode_factory, kill = _tier_factories(
            params, config, args, True, chaos_spec)
        prefill = [prefill_factory() for _ in range(pf_n)]
        decode = [decode_factory() for _ in range(dec_n)]
        all_prefill = list(prefill)
        router = DisaggRouter(decode=decode, prefill=prefill,
                              max_queue_depth=args.queue_depth,
                              affinity_tokens=args.block_size)
        outputs: Dict[int, List[int]] = {}
        try:
            _warm(router, prompts)
            # measurement starts HERE (chaos `at=request:N` counts
            # measured traffic only, counters subtract the warm-up)
            for r in router.tier_replicas("prefill"):
                try:
                    _call(r["target"], "reset_chaos_counts")  # shardlint: disable=unsupervised-actor-call
                except Exception:  # noqa: BLE001 — pre-reset replica
                    pass
            warm_rt = router.stats()
            warm_kvp = _kvp_totals(all_prefill)
            rec = run_load(router, prompts, outputs=outputs, **load_kw)
            st = router.stats()
            rec["router"] = {k: st[k] - warm_rt[k] for k in
                             ("dispatched", "completed", "shed",
                              "directory_hits", "directory_misses",
                              "directory_fallbacks")}
            rec["router"]["max_pending"] = st["max_pending"]
            # tier counters cover exactly the measured run — the cold
            # replay below is extra work the baseline leg never does,
            # so it gets its OWN deltas, not a seat in these
            run_kvp = _kvp_totals(all_prefill)
            rec["kvplane"] = {k: run_kvp[k] - warm_kvp[k]
                              for k in run_kvp}
            rec["kvplane"]["enabled"] = bool(kvplane)
            rec["kvplane"]["directory"] = router.kvplane_stats()
            if cold_swap:
                ref = [router.generate(p, args.max_new)
                       for p in prompts]
                pre = _kvp_totals(all_prefill)
                pre_rt = router.stats()
                for r in router.tier_replicas("prefill"):
                    router.remove_dead("prefill", r["rid"])
                fresh = [prefill_factory() for _ in range(pf_n)]
                for a in fresh:
                    router.add_prefill(a)
                all_prefill.extend(fresh)
                got = [router.generate(p, args.max_new)
                       for p in prompts]
                post = _kvp_totals(all_prefill)
                post_rt = router.stats()
                rec["cold_replay"] = {
                    "prompts": len(prompts),
                    "bit_identical": got == ref,
                    "directory_fallbacks":
                        post_rt["directory_fallbacks"]
                        - pre_rt["directory_fallbacks"],
                }
                for k in ("tier3_adopts", "tier3_adopted_blocks",
                          "tier3_reused_tokens", "tier3_fetched_bytes"):
                    rec["cold_replay"][k] = post[k] - pre[k]
            router.publish_telemetry(force=True)
        finally:
            for t in all_prefill:
                kill(t)
            for r in router.tier_replicas("decode"):
                kill(r["target"])
    finally:
        args._kvplane = None
        args.pool_blocks = prev_pool
    return rec, outputs


def _kvplane_record(params, config, args, prompts,
                    load_kw) -> Dict[str, Any]:
    """The --kvplane acceptance scenario: a Zipf replay whose distinct-
    block working set exceeds one replica's HBM pool, run four ways on
    the SAME schedule — (1) `hbm_reference`: the plane off and a pool
    big enough to never evict, the engine an unlimited-HBM replica
    would be; (2) `baseline`: the plane off and the SMALL pool —
    single-tier, evictions simply lose the prefix; (3) `kvplane`: the
    small pool with the plane on — spills land in the host arena and
    come back, the directory routes repeats to holders, and a
    cold-swapped prefill tier re-adopts everything from the object
    store; (4) `storm`: the plane on under a scripted evict_storm.

    All legs run int8 pools: the spill/publish wire format IS the int8
    pool block, so tier-2 re-adopts and tier-3 imports round-trip
    byte-exactly and every full prefix match — resident, re-adopted,
    or imported — gathers the same bytes at the same split as the
    reference's resident hit. That is what lets the verdict demand
    BIT-IDENTICAL outputs from the tiered legs against the reference
    (fp pools would quantize on spill: rtol-close, not bit-equal).
    The verdict gates on strictly more reused tokens than the
    single-tier baseline absorbed, tier-2 AND tier-3 actually
    engaging, bit-identical outputs vs the reference everywhere, and
    zero wrong outputs through the storm."""
    out: Dict[str, Any] = {}
    bs = args.block_size
    blocks = set()
    for p in prompts:
        for i in range(len(p) // bs):
            blocks.add(tuple(p[:(i + 1) * bs]))
    out["working_set_blocks"] = len(blocks)
    out["pool_blocks"] = args.pool_blocks
    ref_pool = len(blocks) + 16  # whole working set + pinning slack
    out["reference_pool_blocks"] = ref_pool

    ref_rec, ref_out = _kvplane_run(params, config, args, prompts,
                                    load_kw, kvplane=False,
                                    pool_blocks=ref_pool)
    out["hbm_reference"] = ref_rec
    _kvplane_reset_directory()
    base_rec, base_out = _kvplane_run(params, config, args, prompts,
                                      load_kw, kvplane=False)
    out["baseline"] = base_rec
    _kvplane_reset_directory()
    kv_rec, kv_out = _kvplane_run(params, config, args, prompts,
                                  load_kw, kvplane=True,
                                  cold_swap=True)
    kv_rec["vs_reference"] = _outputs_identical(ref_out, kv_out)
    out["kvplane"] = kv_rec
    _kvplane_reset_directory()
    # storm every replica's whole pool early in the measured run —
    # the arena must hand every evicted block straight back
    plan = json.dumps([
        {"action": "evict_storm", "role": "prefill",
         "blocks": max(int(args.pool_blocks or 1), 1),
         "at": "request:2", "replica": r}
        for r in range(max(2, args.prefill_replicas))])
    storm_rec, storm_out = _kvplane_run(params, config, args, prompts,
                                        load_kw, kvplane=True,
                                        chaos_spec=plan)
    storm_rec["vs_reference"] = _outputs_identical(ref_out, storm_out)
    out["storm"] = storm_rec

    kvp = kv_rec["kvplane"]
    cold = kv_rec.get("cold_replay") or {}
    rtr = kv_rec["router"]
    probes = (rtr["directory_hits"] + rtr["directory_misses"]
              + rtr["directory_fallbacks"])
    verdict = {
        "working_set_exceeds_pool":
            out["working_set_blocks"] > int(args.pool_blocks or 0),
        "pool_pressure": kvp["spills"] > 0,
        "baseline_reused_tokens":
            base_rec["kvplane"]["reused_tokens"],
        "kvplane_reused_tokens": kvp["reused_tokens"],
        "multi_tier_reuse_gain":
            kvp["reused_tokens"]
            > base_rec["kvplane"]["reused_tokens"],
        "tier2_reused_tokens": kvp["tier2_reused_tokens"],
        "tier3_reused_tokens": cold.get("tier3_reused_tokens", 0),
        "directory_hits": rtr["directory_hits"],
        "directory_hit_rate": (round(rtr["directory_hits"] / probes, 4)
                               if probes else 0.0),
        "bit_identical_vs_reference":
            kv_rec["vs_reference"]["identical"],
        "cold_replay_bit_identical": bool(cold.get("bit_identical")),
        "storm_fired": storm_rec["kvplane"]["evict_storms"] >= 1,
        "storm_zero_wrong":
            (storm_rec["vs_reference"]["compared"] > 0
             and storm_rec["vs_reference"]["mismatched"] == 0),
    }
    verdict["pass"] = bool(
        all(_clean_run(r) for r in (ref_rec, base_rec, kv_rec,
                                    storm_rec))
        and verdict["working_set_exceeds_pool"]
        and verdict["pool_pressure"]
        and verdict["multi_tier_reuse_gain"]
        and verdict["tier2_reused_tokens"] > 0
        and verdict["tier3_reused_tokens"] > 0
        and verdict["directory_hits"] > 0
        and verdict["bit_identical_vs_reference"]
        and verdict["cold_replay_bit_identical"]
        and verdict["storm_fired"]
        and verdict["storm_zero_wrong"])
    out["verdict"] = verdict
    return out


def _clean_run(rec: Dict[str, Any]) -> bool:
    """A run may headline/verdict only when every request is accounted
    ok|shed — a hung or errored request silently shrinking the measured
    population is exactly the lie the r04/r05 rule exists to prevent."""
    return not rec.get("hung") and not rec.get("errors")


def compare_verdict(auto: Dict[str, Any], sweep: List[Dict[str, Any]],
                    target_p99_ms: float) -> Dict[str, Any]:
    """The acceptance comparison: the autoscaled run beats a static
    (P,D) either because the static config misses the SLO (TTFT p99
    over target, or it sheds more at the peak than the autoscaled run
    did), or — when the static config does meet it — because the
    autoscaler matched the SLO with strictly fewer replica-hours. Shed
    discipline is additionally checked against the BEST static config
    (lowest p99). Any hung/errored run voids the verdict entirely."""
    valid = _clean_run(auto) and all(_clean_run(s) for s in sweep)
    auto_p99 = auto.get("ttft_p99_ms")
    auto_ok = auto_p99 is not None and auto_p99 <= target_p99_ms
    per = []
    for s in sweep:
        p99 = s.get("ttft_p99_ms")
        slo_ok = (p99 is not None and p99 <= target_p99_ms
                  and s["shed_rate"] <= auto["shed_rate"] + 1e-9)
        if not slo_ok:
            beats, how = True, ("static misses the SLO (p99 over "
                                "target, or sheds more at the peak)")
        elif auto_ok and auto["replica_hours"] < s["replica_hours"]:
            beats, how = True, "met the SLO at fewer replica-hours"
        else:
            beats, how = False, "static config not dominated"
        per.append({"config": s["config"],
                    "ttft_p99_ms": p99,
                    "shed_rate": s["shed_rate"],
                    "replica_hours": s["replica_hours"],
                    "static_meets_slo": slo_ok,
                    "beats": beats, "how": how})
    # "best static" ranks shed rate BEFORE p99: a config shedding half
    # its traffic has a flattering p99 on what little it admitted
    best = min((s for s in sweep if s.get("ttft_p99_ms") is not None),
               key=lambda s: (s["shed_rate"], s["ttft_p99_ms"],
                              s["replica_hours"]),
               default=None)
    shed_ok = (best is not None
               and auto["shed_rate"] <= best["shed_rate"] + 1e-9)
    return {
        "valid": valid,
        "autoscale_meets_slo": auto_ok,
        "beats_all_static": valid and auto_ok and shed_ok
        and all(p["beats"] for p in per),
        "shed_at_peak_ok": shed_ok,
        "best_static": best["config"] if best else None,
        "per_config": per,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop disaggregated-serving load harness")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="mean arrival rate, requests/s")
    ap.add_argument("--arrival", default="burst",
                    choices=["uniform", "burst", "diurnal"])
    ap.add_argument("--burst-size", type=int, default=8)
    ap.add_argument("--zipf-a", type=float, default=1.1)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slow-frac", type=float, default=0.125,
                    help="fraction of slow clients (token-paced drain)")
    ap.add_argument("--token-sleep", type=float, default=0.02)
    ap.add_argument("--distinct", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="prefill KV pool blocks (default: "
                         "resolve_pool_config's sizing, which doubles "
                         "under --kv-int8; an explicit value is "
                         "honored as-is)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prefill-replicas", type=int, default=1)
    ap.add_argument("--decode-replicas", type=int, default=1)
    ap.add_argument("--queue-depth", type=int, default=8,
                    help="router backlog bound per decode replica")
    ap.add_argument("--cluster", action="store_true",
                    help="run the tiers as actors on a local cluster "
                         "(real chunk-fabric transfers)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline_s: requests past it "
                         "shed with cause 'deadline' (slow clients "
                         "exercise the edge)")
    ap.add_argument("--http", action="store_true",
                    help="mixed interactive+batch storm over real "
                         "sockets against the OpenAI-compatible "
                         "gateway (serve/gateway.py): batch decodes "
                         "fill every slot, surplus batch sheds with an "
                         "attributed cause, interactive arrivals "
                         "preempt and must hold the TTFT SLO; every "
                         "completed stream is checked bit-identical "
                         "against a serial engine oracle")
    ap.add_argument("--http-max-batch", type=int, default=3,
                    help="engine slots in --http mode (all of them "
                         "are seized by batch fillers at t=0)")
    ap.add_argument("--http-batch", type=int, default=5,
                    help="total batch requests in --http mode; the "
                         "surplus past --http-max-batch arrives on a "
                         "full system and must shed")
    ap.add_argument("--http-interactive", type=int, default=3,
                    help="interactive probes in --http mode, arriving "
                         "mid-decode so they must preempt")
    ap.add_argument("--http-batch-new", type=int, default=600,
                    help="batch decode budget in --http mode; sets "
                         "the engine-production window preemption "
                         "must land inside")
    ap.add_argument("--http-interactive-new", type=int, default=24,
                    help="interactive decode budget in --http mode")
    ap.add_argument("--http-slo-ms", type=float, default=2000.0,
                    help="interactive TTFT p99 SLO the --http verdict "
                         "enforces")
    ap.add_argument("--chaos", action="store_true",
                    help="serving-fault acceptance run (implies "
                         "--cluster): a clean replay vs the same "
                         "replay with a scripted replica kill; records "
                         "failover recovery impact + the zero-dropped/"
                         "bit-identical verdict")
    ap.add_argument("--chaos-role", default="decode",
                    choices=["prefill", "decode"],
                    help="which tier's replica 0 the chaos plan kills")
    ap.add_argument("--chaos-at", default="token:30",
                    help="kill point: 'token:K' (the replica's K-th "
                         "served token, mid-stream) or 'request:N' "
                         "(its N-th request); counters reset at "
                         "measurement start, so N/K count MEASURED "
                         "traffic only (warm-up excluded)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant LoRA acceptance run: N tenants "
                         "drawn Zipf over N adapters against pools "
                         "holding --lora-pool-slots (< N shows "
                         "paging), one mid-run adapter publish for "
                         "the hottest tenant; records hit rate, "
                         "page-in amortization, per-tenant isolation, "
                         "mixed-vs-sequential bit-identity, and the "
                         "publish-no-stall TTFT check")
    ap.add_argument("--tenant-zipf", type=float, default=1.1,
                    help="Zipf exponent of the tenant draw")
    ap.add_argument("--lora-pool-slots", type=int, default=8,
                    help="adapter-pool rows per replica (deliberately "
                         "< --tenants so cold tenants page)")
    ap.add_argument("--lora-rank", type=int, default=4)
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative-decoding comparison: replay the "
                         "same Zipf schedule unspeculated, then with "
                         "k-token prompt-lookup drafts verified per "
                         "tick; the verdict gates on >=2x tokens/s "
                         "with bit-identical greedy outputs")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV blocks (per-block-channel scales, "
                         "doubled default pool); with --speculate adds "
                         "the int8 comparison run to the record")
    ap.add_argument("--kvplane", action="store_true",
                    help="tiered-KV-plane acceptance run (implies "
                         "--cluster): a Zipf replay whose distinct-"
                         "block working set exceeds one replica's HBM "
                         "pool, replayed with the plane off (single-"
                         "tier baseline), on (host-arena spill/"
                         "re-adopt + prefix-directory routing + a "
                         "cold-swapped-tier tier-3 replay from the "
                         "object store), and on under a scripted "
                         "evict_storm; the verdict gates on strictly "
                         "more reused tokens than the baseline, "
                         "tier-2 AND tier-3 engagement, bit-identical "
                         "outputs everywhere, and zero wrong outputs "
                         "through the storm")
    ap.add_argument("--kvplane-arena-mb", type=int, default=64,
                    help="per-replica host-arena bound in --kvplane "
                         "mode")
    ap.add_argument("--kvplane-tail-blocks", type=int, default=4,
                    help="distinct full blocks per prompt tail in "
                         "--kvplane mode (sizes the working set past "
                         "--pool-blocks, default 16 there; the tiny "
                         "config's 128-token max_seq_len caps "
                         "sys + tail + --max-new)")
    ap.add_argument("--colocated-baseline", action="store_true",
                    help="also run the single-engine colocated path "
                         "for comparison")
    ap.add_argument("--autoscale", action="store_true",
                    help="run the SLO-driven autoscaler "
                         "(serve/autoscale.py) instead of a static "
                         "provisioning; tiers start at the minimum")
    ap.add_argument("--compare-static", default="",
                    help='static (P,D) sweep as comma "PxD" configs, '
                         'e.g. "1x1,2x1,1x2,2x2": run each, plus the '
                         "autoscaled run, and record the verdict "
                         "(implies --autoscale)")
    ap.add_argument("--target-p99-ms", type=float, default=None,
                    help="TTFT SLO for the policy AND the verdict "
                         "(default: RAY_TPU_AUTOSCALE_TARGET_P99_MS)")
    ap.add_argument("--min-prefill", type=int, default=1)
    ap.add_argument("--max-prefill", type=int, default=2)
    ap.add_argument("--min-decode", type=int, default=1)
    ap.add_argument("--max-decode", type=int, default=2)
    ap.add_argument("--up-delay", type=float, default=1.0)
    ap.add_argument("--down-delay", type=float, default=5.0)
    ap.add_argument("--cooldown", type=float, default=2.0)
    ap.add_argument("--autoscale-interval", type=float, default=0.25)
    ap.add_argument("--drain-grace", type=float, default=30.0)
    ap.add_argument("--window-s", type=float, default=None,
                    help="signal recency window (sets "
                         "RAY_TPU_AUTOSCALE_WINDOW_S for the run; a "
                         "compressed diurnal needs a window shorter "
                         "than its day)")
    ap.add_argument("--out", default="", help="also write JSON here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.window_s is not None:
        import os as os_mod

        os_mod.environ["RAY_TPU_AUTOSCALE_WINDOW_S"] = str(args.window_s)

    import jax

    from ray_tpu.models.llama import LlamaConfig, llama_init

    config = LlamaConfig.tiny()
    params = llama_init(config, jax.random.PRNGKey(args.seed))
    prompts = make_prompts(config, n_distinct=args.distinct,
                           block_size=args.block_size, seed=args.seed)

    use_cluster = args.cluster or args.chaos or args.kvplane
    if use_cluster:
        import ray_tpu

        # every mode's replica actors (default 1 CPU per lease) must
        # fit: the plain tiers, the autoscaler's max bounds, AND the
        # largest static config in the --compare-static sweep
        sweep_max = max(
            (int(p) + int(d) for p, _, d in
             (s.partition("x") for s in args.compare_static.split(",")
              if s)), default=0)
        # chaos mode runs >=2 decode replicas plus a self-heal
        # replacement beside the prefill tier
        chaos_need = (args.prefill_replicas + 1
                      + max(2, args.decode_replicas) + 1
                      if args.chaos else 0)
        # the cold-swap phase holds the retired prefill tier alive
        # (its tier-3 refs) BESIDE the fresh one
        kvplane_need = (2 * max(2, args.prefill_replicas)
                        + args.decode_replicas if args.kvplane else 0)
        ray_tpu.init(num_cpus=max(4, args.prefill_replicas
                                  + args.decode_replicas,
                                  args.max_prefill + args.max_decode,
                                  sweep_max, chaos_need,
                                  kvplane_need) + 2,
                     _system_config={"log_to_driver": 0},
                     ignore_reinit_error=True)
    record: Dict[str, Any] = {
        "metric": "disagg_serve_load",
        "platform": jax.devices()[0].platform,
        "cluster": use_cluster,
        "prefill_replicas": args.prefill_replicas,
        "decode_replicas": args.decode_replicas,
        "max_batch": args.max_batch,
        "queue_depth": args.queue_depth,
    }
    load_kw = dict(n_requests=args.requests, max_new_tokens=args.max_new,
                   rate_rps=args.rate, arrival=args.arrival,
                   burst_size=args.burst_size, zipf_a=args.zipf_a,
                   slow_client_frac=args.slow_frac,
                   token_sleep_s=args.token_sleep,
                   deadline_s=args.deadline, seed=args.seed)
    # --kv-int8 without --speculate: int8 tiers for whatever mode runs
    args._speculate_k = 0
    args._kv_int8 = bool(args.kv_int8 and not args.speculate)
    args._kvplane = None
    if args.pool_blocks is None and not (args.speculate
                                         or args.kv_int8
                                         or args.kvplane):
        # pre-existing modes keep their historical 64-block pool so
        # reruns stay comparable with the recorded BENCH_* baselines;
        # the spec/int8 modes flow None through to resolve_pool_config
        # so the int8 doubling is the real mechanism, not the harness
        args.pool_blocks = 64
    if args.kvplane:
        # deep distinct tails + a deliberately small pool: the
        # working set (sys + n_distinct * tail blocks) must exceed
        # one replica's HBM pool or no tier below it ever engages
        # enough distinct tails that each replica's SHARE of the
        # working set (directory affinity partitions prompts across
        # holders) still outruns its pool
        prompts = _kvplane_prompts(
            config, n_distinct=max(args.distinct, 10),
            block_size=args.block_size,
            tail_blocks=args.kvplane_tail_blocks, seed=args.seed)
        if args.pool_blocks is None:
            args.pool_blocks = 16
        # int8 pools: the spill/publish wire format is the raw int8
        # pool block, so tier-2/tier-3 round trips are byte-exact and
        # the bit-identical-vs-reference verdict is a hard gate (fp
        # pools quantize on spill — rtol-close only)
        args._kv_int8 = True
        # identity harness, not a tail-latency storm: uniform modest
        # arrivals bound concurrent prefills per replica, so an arena
        # re-adopt never loses the pin race for pool blocks (an
        # alloc-starved re-adopt would shorten the match and change
        # the split vs the reference)
        load_kw = dict(load_kw, arrival="uniform",
                       rate_rps=min(args.rate, 4.0),
                       slow_client_frac=0.0, token_sleep_s=0.0)
        record.update(metric="kvplane_tiered_load",
                      prefill_replicas=max(2, args.prefill_replicas),
                      pool_blocks=args.pool_blocks,
                      arena_mb=args.kvplane_arena_mb,
                      kv_int8=True, rate_rps=load_kw["rate_rps"],
                      arrival="uniform")
        try:
            record.update(_kvplane_record(params, config, args,
                                          prompts, load_kw))
            top = record["kvplane"]
            record.update(value=top["tokens_per_sec"],
                          unit="tokens/s",
                          ttft_p50_ms=top["ttft_p50_ms"],
                          ttft_p99_ms=top["ttft_p99_ms"],
                          shed_rate=top["shed_rate"],
                          directory_hit_rate=record["verdict"][
                              "directory_hit_rate"])
        finally:
            import ray_tpu

            ray_tpu.shutdown()
        line = json.dumps(record)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(record, f, indent=1)
        print(line)
        return 0 if record.get("verdict", {}).get("pass") else 1
    if args.http:
        record.update(metric="gateway_http_load",
                      max_batch=args.http_max_batch,
                      queue_depth=0,
                      slo_ms=args.http_slo_ms)
        try:
            record.update(_http_record(params, config, args, prompts))
            inter = record["by_class"]["interactive"]
            record.update(value=inter["ttft_p99_ms"], unit="ms",
                          ttft_p50_ms=inter["ttft_p50_ms"],
                          ttft_p99_ms=inter["ttft_p99_ms"],
                          shed_rate=(record["by_class"]["batch"]["shed"]
                                     / max(1, record["n_requests"])))
        finally:
            if use_cluster:
                import ray_tpu

                ray_tpu.shutdown()
        line = json.dumps(record)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(record, f, indent=1)
        print(line)
        return 0 if record.get("verdict", {}).get("pass") else 1
    if args.speculate:
        record.update(metric="speculative_decode_load",
                      speculate_k=args.speculate,
                      kv_int8=bool(args.kv_int8))
        try:
            record.update(_spec_record(params, config, args, prompts,
                                       load_kw, use_cluster))
            top = record["speculate"]
            record.update(value=top["tokens_per_sec"], unit="tokens/s",
                          ttft_p50_ms=top["ttft_p50_ms"],
                          ttft_p99_ms=top["ttft_p99_ms"],
                          shed_rate=top["shed_rate"],
                          speedup=record["verdict"]["speedup"],
                          acceptance_rate=record["verdict"][
                              "acceptance_rate"])
        finally:
            if use_cluster:
                import ray_tpu

                ray_tpu.shutdown()
        line = json.dumps(record)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(record, f, indent=1)
        print(line)
        return 0 if record.get("verdict", {}).get("pass") else 1
    if args.chaos:
        record.update(metric="servefault_chaos",
                      decode_replicas=max(2, args.decode_replicas))
        try:
            record.update(_chaos_record(params, config, args, prompts,
                                        load_kw))
            top = record["chaos"]
            record.update(value=top["tokens_per_sec"], unit="tokens/s",
                          ttft_p50_ms=top["ttft_p50_ms"],
                          ttft_p99_ms=top["ttft_p99_ms"],
                          shed_rate=top["shed_rate"])
        finally:
            import ray_tpu

            ray_tpu.shutdown()
        line = json.dumps(record)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(record, f, indent=1)
        print(line)
        return 0 if record.get("verdict", {}).get("pass") else 1
    if args.tenants:
        record.update(metric="lora_serve_load", tenants=args.tenants,
                      tenant_zipf=args.tenant_zipf,
                      lora_pool_slots=args.lora_pool_slots,
                      lora_rank=args.lora_rank)
        try:
            top = _lora_record(params, config, args, prompts, load_kw,
                               use_cluster)
            record["lora_run"] = top
            record.update(value=top["tokens_per_sec"],
                          unit="tokens/s",
                          ttft_p50_ms=top["ttft_p50_ms"],
                          ttft_p99_ms=top["ttft_p99_ms"],
                          shed_rate=top["shed_rate"],
                          lora_hit_rate=top["lora"]["hit_rate"])
        finally:
            if use_cluster:
                import ray_tpu

                ray_tpu.shutdown()
        line = json.dumps(record)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(record, f, indent=1)
        print(line)
        return 0 if record.get("lora_run", {}).get(
            "lora", {}).get("verdict", {}).get("pass") else 1
    if args.compare_static or args.autoscale:
        from ray_tpu.serve.autoscale import default_target_p99_ms

        target = (args.target_p99_ms if args.target_p99_ms is not None
                  else default_target_p99_ms())
        record.update(metric="autoscale_serve_load",
                      target_p99_ms=target)
        try:
            sweep: List[Dict[str, Any]] = []
            for spec in [s for s in args.compare_static.split(",") if s]:
                pf_n, _, dec_n = spec.partition("x")
                sweep.append(_static_run(
                    params, config, args, use_cluster, prompts,
                    load_kw, int(pf_n), int(dec_n)))
            record["autoscale_run"] = _autoscaled_run(
                params, config, args, use_cluster, prompts, load_kw,
                target)
            if sweep:
                record["sweep"] = sweep
                record["verdict"] = compare_verdict(
                    record["autoscale_run"], sweep, target)
            top = record["autoscale_run"]
            record.update(value=top["tokens_per_sec"], unit="tokens/s",
                          ttft_p50_ms=top["ttft_p50_ms"],
                          ttft_p99_ms=top["ttft_p99_ms"],
                          shed_rate=top["shed_rate"],
                          replica_hours=top["replica_hours"])
        finally:
            if use_cluster:
                import ray_tpu

                ray_tpu.shutdown()
        line = json.dumps(record)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(record, f, indent=1)
        print(line)
        return 0

    try:
        router, prefill, decode, cleanup = _build_tiers(
            params, config, args, use_cluster)
        try:
            # warm the compile caches off the clock: each distinct
            # prompt shape costs one prefill compile on first sight.
            # Snapshot the counters after warm-up so the recorded
            # accounting covers exactly the measured open-loop run —
            # published==fetched must cross-check against n_requests'
            # expected KV bytes, not n_requests + warm-up traffic.
            for p in prompts:
                router.generate(p, 2)
            warm_kv = collect_kv_accounting(prefill, decode)
            warm_rt = router.stats()
            record["disagg"] = run_load(router, prompts, **load_kw)
            kv = collect_kv_accounting(prefill, decode)
            record["disagg"]["kv_transfer"] = {
                k: v - warm_kv.get(k, 0) for k, v in kv.items()}
            record["disagg"]["router"] = {
                k: (v - warm_rt[k]
                    if k in ("dispatched", "completed", "shed") else v)
                for k, v in router.stats().items()}
            router.publish_telemetry(force=True)
        finally:
            cleanup()
        if args.colocated_baseline:
            from ray_tpu.models.engine import ContinuousBatchingEngine
            from ray_tpu.serve.disagg import DisaggRouter

            eng = ContinuousBatchingEngine(
                params, config, max_batch=args.max_batch,
                kv_block_size=args.block_size,
                kv_pool_blocks=args.pool_blocks)
            try:
                colo = DisaggRouter(colocated=eng,
                                    max_queue_depth=args.queue_depth)
                for p in prompts:
                    colo.generate(p, 2)
                warm_rt = colo.stats()
                record["colocated"] = run_load(colo, prompts, **load_kw)
                record["colocated"]["kv_transfer"] = {
                    "transfers": 0, "published_bytes": 0,
                    "fetched_bytes": 0, "shm_bytes": 0, "rpc_bytes": 0}
                record["colocated"]["router"] = {
                    k: (v - warm_rt[k]
                        if k in ("dispatched", "completed", "shed")
                        else v)
                    for k, v in colo.stats().items()}
            finally:
                eng.stop()
        # the headline numbers are the disagg run's
        top = record["disagg"]
        record.update(value=top["tokens_per_sec"], unit="tokens/s",
                      ttft_p50_ms=top["ttft_p50_ms"],
                      ttft_p99_ms=top["ttft_p99_ms"],
                      shed_rate=top["shed_rate"])
    finally:
        if use_cluster:
            import ray_tpu

            ray_tpu.shutdown()
    line = json.dumps(record)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
