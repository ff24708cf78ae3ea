"""CLI — analog of the reference's python/ray/scripts/scripts.py
(`ray start` :568, `stop` :1044, `submit` :1578, plus status/memory/
timeline/logs) and util/state/state_cli.py (`ray list ...`).

Run as ``python -m ray_tpu <command>``."""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Optional

_ADDR_FILE = os.path.join(tempfile.gettempdir(), "ray_tpu",
                          "head_address.txt")


def _resolve_address(explicit: Optional[str]) -> str:
    if explicit:
        return explicit
    env = os.environ.get("RAY_TPU_ADDRESS")
    if env:
        return env
    try:
        with open(_ADDR_FILE) as f:
            return f.read().strip()
    except FileNotFoundError:
        raise SystemExit(
            "no cluster address: pass --address, set RAY_TPU_ADDRESS, or "
            "start a head on this machine with "
            "`python -m ray_tpu start --head`")


def _connect(args) -> None:
    import ray_tpu

    ray_tpu.init(address=_resolve_address(getattr(args, "address", None)),
                 ignore_reinit_error=True)


def cmd_start(args) -> None:
    """Foreground head or worker-host process — reference `ray start`
    (scripts.py:568: --head starts GCS+raylet; --address joins an
    existing cluster as a worker node via the per-host NodeAgent)."""
    if not args.head:
        if not getattr(args, "address", None):
            raise SystemExit("pass --head to start a cluster or "
                             "--address host:port to join one")
        from ray_tpu._private.node_agent import main as agent_main

        argv = ["--address", args.address, "--num-cpus",
                str(args.num_cpus)]
        if args.resources:
            argv += ["--resources", args.resources]
        if getattr(args, "node_id", None):
            argv += ["--node-id", args.node_id]
        agent_main(argv)
        return
    from ray_tpu._private.conductor import Conductor

    resources = {"CPU": float(args.num_cpus)}
    if args.resources:
        resources.update(json.loads(args.resources))
    session_dir = os.path.join(
        tempfile.gettempdir(), "ray_tpu",
        f"session_{time.strftime('%Y%m%d-%H%M%S')}_{os.getpid()}")
    os.makedirs(session_dir, exist_ok=True)
    c = Conductor(resources, session_dir, host=args.host,
                  port=args.port).start()
    host, port = c.address
    dash = None
    if not args.no_dashboard:
        try:
            from ray_tpu.dashboard import DashboardServer

            dash = DashboardServer((host, port), host=args.host,
                                   port=args.dashboard_port).start()
            print(f"dashboard at {dash.url}", flush=True)
        except Exception as e:  # noqa: BLE001 — aiohttp/port problems
            print(f"dashboard not started: {e}", flush=True)
    os.makedirs(os.path.dirname(_ADDR_FILE), exist_ok=True)
    with open(_ADDR_FILE, "w") as f:
        f.write(f"{host}:{port}")
    print(f"ray_tpu head started at {host}:{port}\n"
          f"  session dir: {session_dir}\n"
          f"  connect with ray_tpu.init(address=\"{host}:{port}\") "
          f"or RAY_TPU_ADDRESS={host}:{port}", flush=True)
    # The head lives in this process either way (use `&`/systemd to
    # background it); --block is accepted for reference-CLI compatibility.
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        if dash is not None:
            dash.stop()
        c.stop()


def cmd_stop(args) -> None:
    from ray_tpu._private.rpc import RpcClient

    addr = _resolve_address(args.address)
    host, _, port = addr.rpartition(":")
    try:
        RpcClient((host, int(port))).call("shutdown_cluster", timeout=10.0)
        print(f"head at {addr} stopped")
    except Exception as e:  # noqa: BLE001
        # keep the address file: the head may still be alive and reachable
        raise SystemExit(f"could not reach head at {addr}: {e}")
    try:
        os.unlink(_ADDR_FILE)
    except OSError:
        pass
    import glob

    from ray_tpu._private.object_store import cleanup_leaked_segments

    # The head tears down asynchronously (SIGTERM grace then SIGKILL can
    # take >3s): poll-sweep until the segments' owners are gone.
    removed, deadline = 0, time.monotonic() + 6.0
    while True:
        removed += cleanup_leaked_segments()
        if not glob.glob("/dev/shm/rtpu_a_*") \
                or time.monotonic() >= deadline:
            break
        time.sleep(0.5)
    if removed:
        print(f"removed {removed} leaked shm segment(s)")


def cmd_status(args) -> None:
    _connect(args)
    from ray_tpu.util import state

    print(json.dumps(state.cluster_summary(), indent=2, default=str))


def cmd_list(args) -> None:
    _connect(args)
    from ray_tpu.util import state

    fns = {"nodes": state.list_nodes, "workers": state.list_workers,
           "actors": state.list_actors, "tasks": state.list_tasks,
           "objects": state.list_objects,
           "placement-groups": state.list_placement_groups}
    print(json.dumps(fns[args.kind](), indent=2, default=str))


def cmd_summary(args) -> None:
    _connect(args)
    from ray_tpu.util import state

    print(json.dumps(state.summarize_tasks(), indent=2, default=str))


def cmd_memory(args) -> None:
    _connect(args)
    from ray_tpu.util import state

    print(json.dumps(state.list_objects(), indent=2, default=str))


def cmd_timeline(args) -> None:
    _connect(args)
    from ray_tpu.util import state

    n = len(state.timeline(args.output, merged=args.merged))
    what = "merged (tasks+spans+train steps)" if args.merged else "task"
    print(f"wrote {n} {what} events to {args.output} "
          f"(load in chrome://tracing or Perfetto)")


def cmd_train_status(args) -> None:
    """Flight-recorder view of running/recent training gangs: per-rank
    step stats, the latest step's time breakdown, skew, stragglers."""
    _connect(args)
    from ray_tpu.util import state

    progress = state.train_progress(getattr(args, "run", None))
    if args.json:
        print(json.dumps(progress, indent=2, default=str))
        return
    if not progress:
        print("no training telemetry recorded "
              "(is the gang using ray_tpu.train.report()?)")
        return
    for run_id, run in progress.items():
        print(f"run {run_id}: world={run['world']} "
              f"last_step={run['last_step']} "
              f"steps_buffered={run['steps_buffered']}")
        bd = run.get("last_step_breakdown") or {}
        if bd:
            parts = " ".join(f"{k[:-3]}={v:.1f}ms" for k, v in bd.items())
            print(f"  last step: {parts}")
        skew = run.get("last_step_skew") or {}
        if skew:
            print(f"  skew: min={skew['min_ms']:.1f}ms "
                  f"median={skew['median_ms']:.1f}ms "
                  f"p99={skew['p99_ms']:.1f}ms "
                  f"max/median={skew['max_over_median']:.2f}")
        for rank, st in sorted(run["per_rank"].items()):
            extra = ""
            if st.get("tokens_per_sec"):
                extra += f" tok/s={st['tokens_per_sec']:.0f}"
            if st.get("mfu") is not None:
                extra += f" mfu={100 * st['mfu']:.2f}%"
            mark = " <- STRAGGLER" if rank in run["stragglers"] else ""
            print(f"  rank {rank}: steps={st['steps']} "
                  f"mean={st['mean_ms']:.1f}ms p99={st['p99_ms']:.1f}ms"
                  f"{extra}{mark}")


def _print_event_tail(events, n: int) -> None:
    """Shared `[HH:MM:SS] kind k=v ...` tail rendering for the event
    logs (resilience / kvcache / pipeline)."""
    for ev in events[-n:]:
        when = time.strftime("%H:%M:%S", time.localtime(ev.get("ts", 0)))
        extra = {k: v for k, v in ev.items()
                 if k not in ("kind", "ts") and v is not None}
        print(f"  [{when}] {ev.get('kind')} "
              + " ".join(f"{k}={v}" for k, v in extra.items()))


def cmd_resilience_status(args) -> None:
    """Recovery-subsystem view: quarantined/draining hosts with their
    decayed failure scores, event counters, and recent events."""
    _connect(args)
    from ray_tpu.util import state

    st = state.resilience_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    excluded = set(st.get("excluded") or [])
    print(f"quarantine threshold: {st['threshold']:g} "
          f"(half-life {st['half_life_s']:g}s)")
    domains = st.get("domains") or {}
    if not domains:
        print("no failure history recorded")
    for node_id, d in sorted(domains.items()):
        flags = []
        if d.get("quarantined"):
            flags.append("QUARANTINED" + (" (manual)" if d.get("manual")
                                          else ""))
        if d.get("draining"):
            flags.append(f"DRAINING {d['drain_remaining_s']:.0f}s left "
                         f"({d.get('drain_reason')})")
        if d.get("exempt"):
            flags.append("exempt")
        mark = " <- EXCLUDED" if node_id in excluded else ""
        print(f"  {node_id[:16]}: score={d['score']:.2f} "
              f"failures={d['failures']}"
              + (f" last={d['last_kind']}" if d.get("last_kind") else "")
              + (f" [{', '.join(flags)}]" if flags else "") + mark)
    counters = st.get("counters") or {}
    if counters:
        print("counters: " + " ".join(f"{k}={v}" for k, v
                                      in sorted(counters.items())))
    if st.get("last_ttr_s") is not None:
        print(f"last time-to-recovery: {st['last_ttr_s']:.2f}s")
    _print_event_tail(st.get("recent_events") or [], args.events)


def cmd_weights(args) -> None:
    """`ray_tpu weights list|inspect|gc` — the live weight fabric's
    registry view (ray_tpu.weights): committed versions per name with
    sizes and host counts, one version's full manifest (minus chunk
    payloads), or an operator keep-last-K GC."""
    _connect(args)
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.util import state

    w = worker_mod.global_worker
    if args.weights_cmd == "list":
        listing = state.weight_versions(getattr(args, "name", None))
        if args.json:
            print(json.dumps(listing, indent=2, default=str))
            return
        names = listing.get("names") or {}
        if not names and not listing.get("pending"):
            print("no weight versions published")
        for name, rec in sorted(names.items()):
            print(f"{name}: latest=v{rec['latest']} "
                  f"({len(rec['versions'])} kept)")
            for v in rec["versions"]:
                when = time.strftime("%H:%M:%S",
                                     time.localtime(v.get("ts", 0)))
                print(f"  v{v['version']}: step={v.get('step')} "
                      f"bytes={v['total_bytes']} hosts={v['num_hosts']} "
                      f"leaves={v['n_leaves']} chunks={v['n_chunks']} "
                      f"[{when}]"
                      + (f" run={v['run_id']}" if v.get("run_id") else ""))
        for p in listing.get("pending") or []:
            print(f"  PENDING {p['name']} v{p['version']}: "
                  f"{len(p['hosts_committed'])}/{p['num_hosts']} hosts, "
                  f"age {p['age_s']:.1f}s")
    elif args.weights_cmd == "inspect":
        m = w.conductor.call("weights_get_manifest", args.name,
                             args.version, timeout=10.0)
        if m is None:
            raise SystemExit(
                f"no committed version "
                f"{'(latest)' if args.version is None else args.version} "
                f"of {args.name!r}")
        m = dict(m)
        m.pop("treedef", None)  # pickled bytes, not printable
        print(json.dumps(m, indent=2, default=str))
    elif args.weights_cmd == "gc":
        dropped = w.conductor.call("weights_gc", args.name, args.keep,
                                   timeout=10.0)
        print(f"dropped {dropped} version(s) of {args.name!r}")


def cmd_kvcache(args) -> None:
    """`ray_tpu kvcache` — paged-KV prefix-cache view (models/kvcache):
    per-engine hit/miss/eviction counters and pool utilization plus the
    cluster totals every other surface (state API, /api/kvcache,
    Prometheus, timeline markers) reports from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.kv_cache_stats(getattr(args, "engine", None))
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    engines = st.get("engines") or {}
    totals = st.get("totals") or {}
    if not engines:
        print("no kv-cache telemetry recorded (is a "
              "ContinuousBatchingEngine with the prefix cache enabled "
              "running?)")
        return
    print(f"totals: lookups={totals.get('lookups', 0)} "
          f"hit_rate={totals.get('hit_rate', 0.0):.2%} "
          f"token_reuse={totals.get('token_reuse_rate', 0.0):.2%} "
          f"evictions={totals.get('evictions', 0)} "
          f"cow={totals.get('cow_copies', 0)}")
    for key, s in sorted(engines.items()):
        if not s.get("enabled", False):
            # decode replicas under disaggregation run cache-disabled:
            # they adopt prefilled KV, they never prefill
            print(f"  {key}: prefix cache DISABLED "
                  f"(admitted={s.get('admitted', 0)} "
                  f"prefill={s.get('prefill_admitted', 0)} "
                  f"adopted={s.get('adopted', 0)})")
            continue
        print(f"  {key}: hits={s.get('hits', 0)} "
              f"partial={s.get('partial_hits', 0)} "
              f"misses={s.get('misses', 0)} "
              f"reused_tok={s.get('reused_tokens', 0)} "
              f"prefilled_tok={s.get('prefilled_tokens', 0)} "
              f"pool={s.get('pool_utilization', 0.0):.0%} "
              f"({s.get('cached_blocks', 0)} cached / "
              f"{s.get('pinned_blocks', 0)} pinned / "
              f"{s.get('num_blocks', 0)} blocks) "
              f"evictions={s.get('evictions', 0)} "
              f"cow={s.get('cow_copies', 0)} "
              f"invalidations={s.get('invalidations', 0)}")
    if args.events:
        _print_event_tail(state.events("kvcache", args.events),
                          args.events)


def cmd_speculate(args) -> None:
    """`ray_tpu speculate` — speculative-decoding view (models/engine):
    per-engine draft proposal/acceptance counters, tokens-per-verify
    and acceptance rate plus the cluster totals every other surface
    (state API, /api/speculation, Prometheus, the kvcache timeline
    lane's spec markers) reports from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.speculation_stats(getattr(args, "engine", None))
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    engines = st.get("engines") or {}
    totals = st.get("totals") or {}
    if not engines:
        print("no speculation telemetry recorded (is an engine running "
              "with speculate_k > 0 / RAY_TPU_SPECULATE_K set?)")
        return
    print(f"totals: proposed={totals.get('spec_proposed', 0)} "
          f"accepted={totals.get('spec_accepted', 0)} "
          f"acceptance={totals.get('acceptance_rate', 0.0):.2%} "
          f"verify_ticks={totals.get('spec_verify_ticks', 0)} "
          f"tokens/verify={totals.get('tokens_per_verify', 0.0):.2f}")
    for key, s in sorted(engines.items()):
        print(f"  {key}: k={s.get('speculate_k', 0)} "
              f"proposed={s.get('spec_proposed', 0)} "
              f"accepted={s.get('spec_accepted', 0)} "
              f"acceptance={s.get('acceptance_rate', 0.0):.2%} "
              f"tokens/verify={s.get('tokens_per_verify', 0.0):.2f} "
              f"int8_kv={'on' if s.get('kv_int8') else 'off'}")
    if args.events:
        _print_event_tail(state.events("speculation", args.events),
                          args.events)


def cmd_pipeline(args) -> None:
    """`ray_tpu pipeline` — MPMD pipeline view (ray_tpu.mpmd): per-
    pipeline stage registry + per-stage run stats (bubble fraction,
    channel bytes) plus the cluster totals every other surface (state
    API, /api/pipeline, Prometheus, timeline markers) reports from the
    same registry."""
    _connect(args)
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.util import state

    st = state.pipeline_status(getattr(args, "name", None))
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    pipelines = st.get("pipelines") or {}
    if not pipelines:
        print("no MPMD pipelines registered (is a PipelineConductor/"
              "PipelineTrainer running?)")
        return
    for name, rec in sorted(pipelines.items()):
        status = "closed" if rec.get("closed") else (
            "formed" if rec.get("formed") else
            f"forming {len(rec.get('stages') or {})}/"
            f"{rec['num_stages']}")
        est = rec.get("bubble_estimate")
        print(f"{name}: stages={rec['num_stages']} "
              f"schedule={rec.get('schedule')} "
              f"microbatches={rec.get('num_microbatches')} [{status}]"
              + (f" est_bubble={est:.1%}" if est is not None else ""))
        totals = rec.get("totals") or {}
        if totals.get("steps"):
            mean = totals.get("bubble_fraction_mean")
            print(f"  totals: steps={totals['steps']} "
                  f"activation_bytes={totals['activation_bytes']}"
                  + (f" bubble_mean={mean:.1%}"
                     if mean is not None else ""))
        stages = rec.get("stages") or {}
        stats = rec.get("stats") or {}
        for s in sorted(stages, key=int):
            reg = stages[s]
            st_s = stats.get(s) or stats.get(str(s)) or {}
            line = (f"  stage {s}: slice={reg.get('slice_id')} "
                    f"worker={str(reg.get('worker_id'))[:12]}")
            if st_s:
                line += (f" steps={st_s.get('steps')} "
                         f"bubble={st_s.get('bubble_fraction', 0.0):.1%}"
                         f" sent={st_s.get('sent_bytes', 0)}B "
                         f"recv={st_s.get('recv_bytes', 0)}B")
            print(line)
    if args.events:
        w = worker_mod.global_worker
        events = w.conductor.call("get_pipeline_events", args.events,
                                  timeout=10.0)
        _print_event_tail(events, args.events)


def cmd_online(args) -> None:
    """`ray_tpu online` — online learning loop view (ray_tpu.online):
    per-sampler rollout/staleness stats, buffer occupancy and
    backpressure, learner ingest progress, plus the cluster totals
    every other surface (state API, /api/online, Prometheus, timeline
    markers) reports from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.online_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    totals = st.get("totals") or {}
    if not (st.get("samplers") or st.get("buffers")
            or st.get("learners")):
        print("no online-loop telemetry recorded (is an "
              "OnlineTrainer / RolloutSampler running?)")
        return
    stale = totals.get("max_staleness_versions")
    print(f"totals: samplers={totals.get('samplers', 0)} "
          f"rollouts={totals.get('rollouts', 0)} "
          f"rollout_tokens={totals.get('rollout_tokens', 0)} "
          f"ingested={totals.get('ingested_rollouts', 0)} "
          f"buffer={totals.get('buffer_occupancy', 0)}"
          f"/{totals.get('buffer_capacity', 0)} "
          f"max_staleness={stale if stale is not None else '-'}")
    for key, s in sorted((st.get("samplers") or {}).items()):
        print(f"  {key}: rollouts={s.get('rollouts', 0)} "
              f"tokens={s.get('rollout_tokens', 0)} "
              f"serving=v{s.get('serving_version')} "
              f"latest=v{s.get('latest_version')} "
              f"staleness={s.get('staleness_versions')} "
              f"(max {s.get('max_staleness_versions')}) "
              f"swaps={s.get('swap_count', 0)}"
              + ("" if s.get("registry_reachable", True)
                 else " [REGISTRY UNREACHABLE]"))
    for key, b in sorted((st.get("buffers") or {}).items()):
        print(f"  {key}: occupancy={b.get('occupancy', 0)}"
              f"/{b.get('capacity', 0)} in={b.get('total_in', 0)} "
              f"out={b.get('total_out', 0)} "
              f"rejected={b.get('rejected', 0)}")
    for key, l in sorted((st.get("learners") or {}).items()):
        print(f"  {key}: steps={l.get('steps', 0)} "
              f"ingested={l.get('ingested_rollouts', 0)} "
              f"last_loss={l.get('last_loss')} "
              f"published=v{l.get('published_version')}")
    if args.events:
        _print_event_tail(state.events("online", args.events),
                          args.events)


def cmd_disagg(args) -> None:
    """`ray_tpu disagg` — disaggregated prefill/decode serving view
    (serve/disagg.py): prefill-tier reuse + published KV, decode-tier
    transfer accounting (shm vs rpc — the no-full-copy evidence),
    router dispatch/shed/queue-depth, plus the cluster totals every
    other surface (state API, /api/disagg, Prometheus, timeline
    markers) reports from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.disagg_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    totals = st.get("totals") or {}
    if not (st.get("prefill") or st.get("decode") or st.get("routers")):
        print("no disagg telemetry recorded (is a PrefillServer/"
              "DecodeServer/DisaggRouter running?)")
        return
    print(f"totals: transfers={totals.get('transfers', 0)} "
          f"kv_bytes={totals.get('kv_fetched_bytes', 0)} "
          f"(shm={totals.get('shm_bytes', 0)} "
          f"rpc={totals.get('rpc_bytes', 0)}) "
          f"adopted={totals.get('adopted', 0)} "
          f"dispatched={totals.get('dispatched', 0)} "
          f"shed={totals.get('shed', 0)} "
          f"queue_depth={totals.get('queue_depth', 0)} "
          f"(max {totals.get('max_queue_depth_seen', 0)})")
    for key, p in sorted((st.get("prefill") or {}).items()):
        pc = p.get("prefix_cache") or {}
        print(f"  {key}: prefills={p.get('prefills', 0)} "
              f"prefilled_tok={p.get('prefilled_tokens', 0)} "
              f"reused_tok={p.get('reused_tokens', 0)} "
              f"published={p.get('published_transfers', 0)} "
              f"({p.get('published_bytes', 0)}B) "
              f"held={p.get('held_transfers', 0)} "
              f"acked={p.get('acked', 0)}"
              + (f" hit_rate={pc.get('hit_rate', 0.0):.2%}"
                 if pc else ""))
    for key, d in sorted((st.get("decode") or {}).items()):
        print(f"  {key}: transfers={d.get('transfers', 0)} "
              f"fetched={d.get('kv_fetched_bytes', 0)}B "
              f"(shm={d.get('shm_bytes', 0)} rpc={d.get('rpc_bytes', 0)}) "
              f"adopted={d.get('adopted', 0)} "
              f"slots={d.get('free_slots', 0)}/{d.get('capacity', 0)} "
              f"prefill_programs={d.get('prefill_programs', 0)}")
    for key, r in sorted((st.get("routers") or {}).items()):
        print(f"  {key}: mode={r.get('mode')} "
              f"dispatched={r.get('dispatched', 0)} "
              f"completed={r.get('completed', 0)} "
              f"shed={r.get('shed', 0)} "
              f"pending={r.get('pending', 0)} "
              f"(max {r.get('max_pending', 0)}, "
              f"depth_knob={r.get('max_queue_depth')})")
    if args.events:
        _print_event_tail(state.events("disagg", args.events),
                          args.events)


def cmd_kvplane(args) -> None:
    """`ray_tpu kvplane` — global KV plane view (serve/kvplane.py):
    per-replica host arenas (tier-2 entries/bytes, spills absorbed,
    re-adopted tokens), tier-3 publish/adopt traffic through the chunk
    fabric, router directory routing outcomes (hit/fallback/miss), the
    conductor's prefix-directory summary, plus the cluster totals every
    other surface (state API, /api/kvplane, Prometheus, timeline
    markers) reports from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.kvplane_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    comps = st.get("components") or {}
    if not comps:
        print("no kvplane telemetry recorded (is a kvplane-enabled "
              "PrefillServer/DisaggRouter running?)")
        return
    t = st.get("totals") or {}
    print(f"totals: spills={t.get('spills', 0)} "
          f"({t.get('spill_bytes', 0)}B) "
          f"tier2_hits={t.get('tier2_hits', 0)}"
          f"/{t.get('tier2_probes', 0)} "
          f"({t.get('tier2_hit_rate', 0.0):.2%}) "
          f"t2_reused_tok={t.get('tier2_reused_tokens', 0)} "
          f"t3_publishes={t.get('tier3_publishes', 0)} "
          f"t3_adopts={t.get('tier3_adopts', 0)} "
          f"t3_reused_tok={t.get('tier3_reused_tokens', 0)} "
          f"directory_hit_rate={t.get('directory_hit_rate', 0.0):.2%} "
          f"arena={t.get('arena_entries', 0)} entries "
          f"({t.get('arena_bytes', 0)}B)")
    d = st.get("directory") or {}
    ns = d.get("namespaces") or {}
    ctr = d.get("counters") or {}
    print(f"directory: entries={d.get('entries', 0)} "
          f"({d.get('nbytes', 0)}B) namespaces={len(ns)} "
          f"publishes={ctr.get('publishes', 0)} "
          f"lookups={ctr.get('lookups', 0)} "
          f"reaped={ctr.get('reaped', 0)} "
          f"gced={ctr.get('gced', 0)} "
          f"unpublished={ctr.get('unpublished', 0)}")
    for key, c in sorted(comps.items()):
        if c.get("role") == "router":
            print(f"  {key}: directory hits={c.get('directory_hits', 0)} "
                  f"fallbacks={c.get('directory_fallbacks', 0)} "
                  f"misses={c.get('directory_misses', 0)}"
                  + (f" hit_rate={c['directory_hit_rate']:.2%}"
                     if c.get("directory_hit_rate") is not None else ""))
        else:
            print(f"  {key}: arena={c.get('entries', 0)} entries "
                  f"({c.get('bytes', 0)}B/{c.get('max_bytes', 0)}B) "
                  f"spills={c.get('spills', 0)} "
                  f"t2_hits={c.get('tier2_hits', 0)} "
                  f"t2_reused_tok={c.get('tier2_reused_tokens', 0)} "
                  f"t3_pub={c.get('tier3_publishes', 0)} "
                  f"t3_adopt={c.get('tier3_adopts', 0)} "
                  f"storms={c.get('evict_storms', 0)}")
    if args.events:
        _print_event_tail(state.events("kvplane", args.events),
                          args.events)


def cmd_servefault(args) -> None:
    """`ray_tpu servefault` — serving-plane fault-tolerance view
    (serve/disagg.py failover + serve/autoscale.py self-healing):
    per-router failovers by phase and sheds by attributed cause,
    per-healer deaths/replacements/breaker state, plus the cluster
    totals every other surface (state API, /api/servefault,
    Prometheus, resilience-lane timeline markers) reports from the
    same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.servefault_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    if not (st.get("routers") or st.get("healers")):
        print("no servefault telemetry recorded (is a DisaggRouter/"
              "DisaggAutoscaler running?)")
        return
    totals = st.get("totals") or {}
    fo = totals.get("failovers") or {}
    sheds = totals.get("sheds_by_cause") or {}
    repl = totals.get("replacements") or {}
    shed_txt = " ".join(f"{k}:{v}"
                        for k, v in sorted(sheds.items())) or "none"
    print(f"totals: failovers=prefill:{fo.get('prefill', 0)}"
          f"/decode:{fo.get('decode', 0)} "
          f"failed_over_requests={totals.get('failover_requests', 0)} "
          f"sheds={sum(sheds.values())} ({shed_txt}) "
          f"replacements=prefill:{repl.get('prefill', 0)}"
          f"/decode:{repl.get('decode', 0)} "
          f"breaker_trips={totals.get('breaker_trips', 0)} "
          f"drains_reaped={totals.get('drains_reaped', 0)}")
    for key, r in sorted((st.get("routers") or {}).items()):
        rec = (r.get("recent_failover_recovery_ms") or {})
        rfo = r.get("failovers") or {}
        rsh = r.get("sheds_by_cause") or {}
        rsh_txt = ", ".join(f"{k}:{v}" for k, v in sorted(rsh.items()))
        print(f"  {key}: failovers=pf:{rfo.get('prefill', 0)}"
              f"/dec:{rfo.get('decode', 0)} "
              f"failed_over_reqs={r.get('failover_requests', 0)} "
              "sheds={" + rsh_txt + "}"
              + (f" recovery_p50={rec.get('p50', 0.0):.0f}ms"
                 if rec.get("n") else ""))
    for key, h in sorted((st.get("healers") or {}).items()):
        d = h.get("deaths") or {}
        rp = h.get("replacements") or {}
        print(f"  {key}: deaths=pf:{d.get('prefill', 0)}"
              f"/dec:{d.get('decode', 0)} "
              f"replacements=pf:{rp.get('prefill', 0)}"
              f"/dec:{rp.get('decode', 0)} "
              f"blocked={h.get('replacements_blocked', 0)} "
              f"breaker_trips={h.get('breaker_trips', 0)} "
              f"breaker_open={h.get('breaker_open') or []} "
              f"drains_reaped={h.get('drains_reaped', 0)}")
    if args.events:
        _print_event_tail(state.events("servefault", args.events),
                          args.events)


def cmd_gateway(args) -> None:
    """`ray_tpu gateway` — HTTP front-door view (serve/gateway.py):
    per-replica request counters split by priority class and status
    code, recent TTFT per class, QoS admission/rejection, batch-slot
    preemptions, plus the cluster totals every other surface (state
    API, /api/gateway, Prometheus, `gateway` timeline lane) reports
    from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.gateway_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    if not st.get("gateways"):
        print("no gateway telemetry recorded (is a GatewayServer "
              "running?)")
        return
    totals = st.get("totals") or {}
    code_txt = " ".join(
        f"{k}:{v}"
        for k, v in sorted((totals.get("by_code") or {}).items())) \
        or "none"
    print(f"totals: gateways={totals.get('gateways', 0)} "
          f"accepted={totals.get('accepted', 0)} "
          f"completed={totals.get('completed', 0)} "
          f"(streamed {totals.get('streamed', 0)}) "
          f"tokens_out={totals.get('tokens_out', 0)} "
          f"rate_limited={totals.get('rate_limited', 0)} "
          f"sheds={totals.get('sheds', 0)} "
          f"disconnects={totals.get('disconnects', 0)} "
          f"preemptions={totals.get('preemptions', 0)} "
          f"codes=({code_txt})")
    for cls, row in sorted((totals.get("by_class") or {}).items()):
        print(f"  class {cls}: accepted={row.get('accepted', 0)} "
              f"completed={row.get('completed', 0)} "
              f"shed={row.get('shed', 0)} "
              f"disconnects={row.get('disconnects', 0)}")
    for key, g in sorted((st.get("gateways") or {}).items()):
        ttft = g.get("ttft_ms") or {}
        ttft_txt = " ".join(
            f"{c}_p99={w.get('p99', 0.0):.0f}ms"
            for c, w in sorted(ttft.items()) if w.get("n"))
        print(f"  {key}: {g.get('host')}:{g.get('port')} "
              f"models={','.join(g.get('models') or [])} "
              f"accepted={g.get('accepted', 0)} "
              f"completed={g.get('completed', 0)} "
              f"disconnects={g.get('disconnects', 0)} "
              f"sheds={g.get('sheds', 0)} "
              f"rate_limited={g.get('rate_limited', 0)} "
              f"preemptions={g.get('preemptions', 0)}"
              + (f" {ttft_txt}" if ttft_txt else ""))
    if args.events:
        _print_event_tail(state.events("gateway", args.events),
                          args.events)


def cmd_requests(args) -> None:
    """`ray_tpu requests` — per-request flight-recorder view
    (observability/requests.py): retention totals, the cluster-wide
    slowest requests with their per-phase latency breakdowns, and the
    p99-attribution report naming the phase that owns the tail —
    from the same aggregate every other surface (state API,
    /api/requesttrace, Prometheus, `requests` timeline lane) reads.
    `--trace <id>` replays one kept request's full span log."""
    _connect(args)
    from ray_tpu.util import state

    if args.trace:
        trc = state.request_trace(args.trace)
        if trc is None:
            print(f"no kept trace for request {args.trace!r} "
                  f"(sampled out, aged out, or never recorded)")
            return
        if args.json:
            print(json.dumps(trc, indent=2, default=str))
            return
        print(f"{trc.get('request_id')}: outcome={trc.get('outcome')} "
              f"total={trc.get('total_ms', 0.0):.1f}ms "
              f"attempts={trc.get('attempts', 1)} "
              f"preempts={trc.get('preempts', 0)} "
              f"source={trc.get('source')} "
              f"class={trc.get('class', '-')} "
              f"tenant={trc.get('tenant', '-')}")
        for ph in trc.get("phases") or []:
            extra = " ".join(
                f"{k}={v}" for k, v in sorted(ph.items())
                if k not in ("phase", "t_ms", "dur_ms", "attempt")
                and v is not None)
            print(f"  [a{ph.get('attempt', 1)}] "
                  f"{ph.get('phase'):<18} +{ph.get('t_ms', 0.0):9.1f}ms "
                  f"dur={ph.get('dur_ms', 0.0):9.2f}ms"
                  + (f"  {extra}" if extra else ""))
        for ph in trc.get("remote_phases") or []:
            print(f"  [a{ph.get('attempt', 1)}] "
                  f"{ph.get('phase'):<18} (remote) "
                  f"dur={ph.get('dur_ms', 0.0):9.2f}ms "
                  f"server={ph.get('server', '-')}")
        return
    st = state.requesttrace_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    if not st.get("stores"):
        print("no request-trace telemetry recorded (serve traffic "
              "with RAY_TPU_REQTRACE=1 — the default — first)")
        return
    totals = st.get("totals") or {}
    out_txt = " ".join(
        f"{k}:{v}"
        for k, v in sorted((totals.get("outcomes") or {}).items())) \
        or "none"
    print(f"totals: stores={totals.get('stores', 0)} "
          f"completed={totals.get('completed', 0)} "
          f"kept={totals.get('kept', 0)} "
          f"dropped={totals.get('dropped', 0)} "
          f"replayed={totals.get('replayed_requests', 0)} "
          f"preempted={totals.get('preempted_requests', 0)} "
          f"slowest={totals.get('slowest_ms', 0.0):.1f}ms "
          f"outcomes=({out_txt})")
    attr = st.get("attribution") or {}
    if attr.get("n"):
        owner = attr.get("tail_owner")
        share = attr.get("tail_share")
        print(f"p99 attribution over {attr['n']} requests: "
              f"p50={attr.get('p50_total_ms', 0.0):.1f}ms "
              f"p99={attr.get('p99_total_ms', 0.0):.1f}ms tail_owner="
              + (f"{owner} ({share:.0%} of the gap)"
                 if owner else "none"))
        for ph, row in sorted((attr.get("phases") or {}).items(),
                              key=lambda kv: -kv[1]["delta_ms"]):
            print(f"    {ph:<18} p50={row['p50_ms']:9.2f}ms "
                  f"p99={row['p99_ms']:9.2f}ms "
                  f"delta={row['delta_ms']:+9.2f}ms")
    k = max(1, int(args.slowest))
    for rec in (st.get("slowest") or [])[:k]:
        pm = rec.get("phase_ms") or {}
        ph_txt = " ".join(f"{p}={pm[p]:.1f}" for p in sorted(
            pm, key=lambda p: -pm[p]))
        print(f"  {rec.get('request_id')}: "
              f"{rec.get('total_ms', 0.0):.1f}ms "
              f"outcome={rec.get('outcome')} "
              f"attempts={rec.get('attempts', 1)}"
              + (f"  [{ph_txt}]" if ph_txt else ""))
    if args.events:
        _print_event_tail(state.events("requesttrace", args.events),
                          args.events)


def cmd_lora(args) -> None:
    """`ray_tpu lora` — multi-tenant LoRA serving view
    (serve/lora.py): per-pool adapter-paging counters and residents,
    per-tenant request counters, plus the cluster totals every other
    surface (state API, /api/lora, Prometheus, `lora` timeline lane)
    reports from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.lora_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    if not (st.get("pools") or st.get("routers")):
        print("no lora telemetry recorded (is an AdapterPool-backed "
              "replica running?)")
        return
    totals = st.get("totals") or {}
    print(f"totals: pools={totals.get('pools', 0)} "
          f"slots={totals.get('slots', 0)} "
          f"resident={totals.get('resident', 0)} "
          f"pinned={totals.get('pinned', 0)} "
          f"acquires={totals.get('acquires', 0)} "
          f"hit_rate={totals.get('hit_rate', 0.0):.2%} "
          f"misses={totals.get('misses', 0)} "
          f"evictions={totals.get('evictions', 0)} "
          f"swaps={totals.get('swaps', 0)} "
          f"page_in={totals.get('page_in_bytes', 0)}B "
          f"tenants={totals.get('tenants', 0)}")
    for key, p in sorted((st.get("pools") or {}).items()):
        print(f"  pool {key}: slots={p.get('slots')} "
              f"resident={p.get('resident')} "
              f"pinned={p.get('pinned')} "
              f"hits={p.get('hits')} misses={p.get('misses')} "
              f"evictions={p.get('evictions')} "
              f"swaps={p.get('swaps')} "
              f"rank_max={p.get('rank_max')}")
    tenants = st.get("tenants") or {}
    for t, ts in sorted(tenants.items()):
        print(f"  tenant {t}: dispatched={ts.get('dispatched', 0)} "
              f"completed={ts.get('completed', 0)} "
              f"shed={ts.get('shed', 0)} "
              f"slo_misses={ts.get('slo_misses', 0)} "
              f"pool_hits={ts.get('hits', 0)}/"
              f"misses={ts.get('misses', 0)} "
              f"swaps={ts.get('swaps', 0)}")
    if args.events:
        _print_event_tail(state.events("lora", args.events),
                          args.events)


def cmd_autoscale(args) -> None:
    """`ray_tpu autoscale` — serving-autoscaler view
    (serve/autoscale.py): per-loop tier targets, decision counts,
    drain outcomes, and replica-seconds, plus the cluster totals every
    other surface (state API, /api/autoscale, Prometheus, timeline
    markers) reports from the same snapshots."""
    _connect(args)
    from ray_tpu.util import state

    st = state.autoscaler_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    loops = st.get("autoscalers") or {}
    if not loops:
        print("no autoscaler telemetry recorded (is a "
              "serve.autoscale.DisaggAutoscaler running?)")
        return
    totals = st.get("totals") or {}
    rs = totals.get("replica_seconds") or {}
    print(f"totals: scale_ups={totals.get('scale_ups', 0)} "
          f"scale_downs={totals.get('scale_downs', 0)} "
          f"drains={totals.get('drains_completed', 0)} "
          f"(forced {totals.get('drains_forced', 0)}) "
          f"replica_s=prefill:{rs.get('prefill', 0.0):.1f}"
          f"/decode:{rs.get('decode', 0.0):.1f}")
    for key, s in sorted(loops.items()):
        print(f"  {key}: router={s.get('router')} "
              f"target_p99={s.get('target_p99_ms')}ms")
        for tier in ("prefill", "decode"):
            bounds = s.get(f"{tier}_bounds") or ["?", "?"]
            print(f"    {tier}: active={s.get(f'{tier}_active', 0)}"
                  f"/{s.get(f'{tier}_replicas', 0)} "
                  f"bounds=[{bounds[0]},{bounds[1]}] "
                  f"ups={(s.get('scale_ups') or {}).get(tier, 0)} "
                  f"downs={(s.get('scale_downs') or {}).get(tier, 0)} "
                  f"last={(s.get('last_reason') or {}).get(tier, '')!r}")
        if s.get("draining"):
            for d in s["draining"]:
                print(f"    DRAINING {d.get('tier')}:{d.get('rid')}")
    if args.events:
        _print_event_tail(state.events("autoscale", args.events),
                          args.events)


def cmd_oracle(args) -> None:
    """`ray_tpu oracle` — step-time oracle view (observability.roofline):
    the latest roofline prediction per layout, the predicted-vs-measured
    validation tail (per-phase residuals, fitted calibration), plus the
    totals every other surface (state API, /api/oracle, Prometheus,
    timeline counter track) reports from the same aggregate."""
    _connect(args)
    from ray_tpu.util import state

    st = state.oracle_status()
    if args.json:
        print(json.dumps(st, indent=2, default=str))
        return
    preds = st.get("predictions") or {}
    vals = st.get("validations") or []
    if not preds and not vals:
        print("no oracle telemetry recorded (run `ray_tpu analyze "
              "--predict-step-time` or roofline.record_prediction / "
              "validate_run)")
        return
    totals = st.get("totals") or {}
    cal = totals.get("last_calibration")
    worst = totals.get("worst_residual_ratio")
    print(f"totals: layouts={totals.get('layouts', 0)} "
          f"validations={totals.get('validations', 0)}"
          + (f" last_calibration={cal:.3f}" if cal is not None else "")
          + (f" worst_residual={worst:.2f}x" if worst is not None
             else ""))
    for layout, p in sorted(preds.items()):
        print(f"  {layout}: predicted="
              f"{p.get('predicted_step_ms', 0.0):.3f}ms "
              f"(device={p.get('device_step_ms', 0.0):.3f} "
              f"ici={p.get('ici_wait_ms', 0.0):.3f} "
              f"dcn={p.get('dcn_wait_ms', 0.0):.3f}) "
              f"dcn_bytes={p.get('dcn_bytes', 0):.0f}"
              + (" UNMODELED:" + ",".join(p["unmodeled_collectives"])
                 if p.get("unmodeled_collectives") else ""))
    for v in vals[-5:]:
        res = " ".join(f"{k}={r:.2f}x" for k, r
                       in (v.get("residuals") or {}).items())
        print(f"  validation run={v.get('run_id')} "
              f"layout={v.get('layout')} steps={v.get('n_steps')} "
              f"calibration={v.get('calibration', 1.0):.3f} {res}")
    if args.events:
        _print_event_tail(state.events("oracle", args.events),
                          args.events)


def cmd_metrics(args) -> None:
    _connect(args)
    from ray_tpu.util import state

    sys.stdout.write(state.prometheus_metrics())


def cmd_dashboard(args) -> None:
    from ray_tpu.dashboard import main as dash_main

    dash_main(["--address", _resolve_address(args.address),
               "--host", args.host, "--port", str(args.port)])


def cmd_config(args) -> None:
    """Print the flag table (ray_config_def.h analog) with live values."""
    from ray_tpu._private.config import config

    rows = config.describe()
    w = max(len(r["env_var"]) for r in rows)
    for r in rows:
        mark = "*" if r["source"] == "env" else " "
        print(f"{mark} {r['env_var']:<{w}}  {r['type']:<5} "
              f"= {r['value']!r:<14} {r['doc']}")
    print("\n(* = overridden via environment / _system_config)")


def cmd_microbench(args) -> None:
    from ray_tpu._private import perf

    perf.run(scale=args.scale, out=args.out)


def cmd_job(args) -> None:
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(_resolve_address(args.address))
    if args.job_cmd == "submit":
        import shlex

        tokens = args.entrypoint
        if tokens and tokens[0] == "--":  # REMAINDER keeps the separator
            tokens = tokens[1:]
        job_id = client.submit_job(
            entrypoint=" ".join(shlex.quote(t) for t in tokens))
        print(job_id)
        if args.wait:
            status = client.wait_until_finished(job_id, timeout=args.timeout)
            sys.stdout.write(client.get_job_logs(job_id))
            print(f"job {job_id}: {status}")
            if status != "SUCCEEDED":
                raise SystemExit(1)
    elif args.job_cmd == "status":
        print(client.get_job_status(args.job_id))
    elif args.job_cmd == "logs":
        sys.stdout.write(client.get_job_logs(args.job_id))
    elif args.job_cmd == "stop":
        print("stopped" if client.stop_job(args.job_id) else "not running")
    elif args.job_cmd == "list":
        print(json.dumps(client.list_jobs(), indent=2, default=str))


def cmd_analyze(args) -> None:
    """`ray_tpu analyze` — shardlint static analysis: AST lint over
    Python sources (blocking-in-async, host-sync-in-jit) plus, with
    --layouts, the shard/collective/DCN-cost checks over the built-in
    dryrun mesh layouts. Fully deviceless: jax is pinned to cpu and no
    backend device is ever enumerated, so the lint neither needs nor
    takes a chip."""
    # Force the cpu platform BEFORE anything imports jax: the layout
    # checks trace against AbstractMesh and never need silicon. Restored
    # on exit so programmatic main([...]) callers (and their subprocess
    # children) are not pinned to cpu afterwards.
    prev_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        _run_analyze(args)
    finally:
        if prev_platform is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_platform


def _format_predictions(preds: dict) -> str:
    lines = ["predicted step time per layout (roofline, "
             "compile-excluded; observability.roofline):"]
    for name, p in sorted(preds.items()):
        extra = ""
        if p.get("unmodeled_collectives"):
            extra = (" [unmodeled: "
                     + ", ".join(p["unmodeled_collectives"]) + "]")
        lines.append(
            f"  {name:<14} {p['predicted_step_ms']:>10.4f} ms  "
            f"(device {p['device_step_ms']:.4f} + "
            f"ici {p['ici_wait_ms']:.4f} + "
            f"dcn {p['dcn_wait_ms']:.4f})  "
            f"dcn {p['dcn_bytes'] / 2 ** 20:.2f} MiB/step{extra}")
    return "\n".join(lines)


def _nearest_readme(root: str) -> "str | None":
    """README.md beside the analyzed tree or up to two levels above it
    (the package dir's README lives at the repo root) — feeds the
    env-knob-undocumented check; None skips that rule."""
    d = os.path.abspath(root)
    for _ in range(3):
        cand = os.path.join(d, "README.md")
        if os.path.exists(cand):
            try:
                with open(cand) as f:
                    return f.read()
            except OSError:
                return None
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return None


def _run_analyze(args) -> None:
    from ray_tpu import analysis

    findings = []
    paths = args.paths
    if not paths:
        # --layouts is additive ("also analyze ..."): the source lint of
        # the installed package always runs unless explicit paths narrow
        # it.
        import ray_tpu

        paths = [os.path.dirname(os.path.abspath(ray_tpu.__file__))]
    for p in paths:
        if not os.path.exists(p):
            raise SystemExit(f"no such file or directory: {p}")
        findings.extend(analysis.lint_path(p))
    want_knobs = getattr(args, "knob_table", False)
    knob_rows = None
    if getattr(args, "invariants", False) or want_knobs:
        for p in paths:
            root = p if os.path.isdir(p) else (os.path.dirname(p) or ".")
            if getattr(args, "invariants", False):
                findings.extend(analysis.analyze_invariants(
                    root, readme_text=_nearest_readme(root)))
            if want_knobs:
                rows = analysis.knob_table(
                    analysis.collect_env_reads(root))
                knob_rows = (knob_rows or []) + rows
    predict = getattr(args, "predict_step_time", False)
    predictions = None
    if args.layouts or predict:
        # If jax first loads HERE, it initializes under our forced
        # JAX_PLATFORMS=cpu — its config value is our pin, not the
        # caller's, so restore to None (auto-detect), not to `prev`.
        jax_preloaded = "jax" in sys.modules
        import jax

        # config (not just env) pin: a jax imported before this call
        # read JAX_PLATFORMS then and no longer looks at the env.
        # Restored so a programmatic main([...]) caller is not left
        # cpu-pinned.
        prev = jax.config.jax_platforms if jax_preloaded else None
        jax.config.update("jax_platforms", "cpu")
        try:
            if args.layouts:
                for name, fs in \
                        analysis.analyze_builtin_layouts().items():
                    findings.extend(fs)
            if predict:
                from ray_tpu.observability import roofline

                predictions = roofline.predict_builtin_layouts()
        finally:
            jax.config.update("jax_platforms", prev)
    sorted_findings = [f.to_dict() for f in
                       analysis.sort_findings(findings)]
    if args.json:
        # plain --json keeps the historical bare findings list; the
        # predictions / knob table ride in a wrapper object only when
        # asked for
        if predictions is not None or knob_rows is not None:
            payload = {"findings": sorted_findings}
            if predictions is not None:
                payload["predicted_step_time"] = predictions
            if knob_rows is not None:
                payload["env_knobs"] = knob_rows
            print(json.dumps(payload, indent=2))
        else:
            print(json.dumps(sorted_findings, indent=2))
    else:
        print(analysis.format_report(findings))
        if predictions is not None:
            print(_format_predictions(predictions))
        if knob_rows is not None:
            print(analysis.format_knob_table(knob_rows))
    worst = analysis.max_severity(findings)
    order = list(analysis.SEVERITIES)
    if findings and order.index(worst) <= order.index(args.fail_on):
        raise SystemExit(1)


def cmd_serve(args) -> None:
    """`serve run|deploy|status|config|shutdown|delete` — reference
    python/ray/serve/scripts.py:147-746 (run/deploy/config/status) over
    the declarative YAML schema (serve/schema.py)."""
    _connect(args)
    from ray_tpu import serve
    from ray_tpu.serve.schema import (ServeDeploySchema, deploy_config,
                                      get_deployed_config)

    if args.serve_cmd in ("run", "deploy"):
        if args.config_or_import.endswith((".yaml", ".yml")):
            schema = ServeDeploySchema.from_yaml_file(args.config_or_import)
        else:
            # bare import path: one app with defaults
            schema = ServeDeploySchema.from_dict({"applications": [
                {"import_path": args.config_or_import}]})
        names = deploy_config(schema)
        print(f"deployed application(s): {', '.join(names)}")
        addr = serve.proxy_address()
        if addr:
            print(f"HTTP ingress at http://{addr[0]}:{addr[1]}")
        if args.serve_cmd == "run":
            # reference `serve run` stays attached and tears down on ^C
            import time as _t

            try:
                while True:
                    _t.sleep(3600)
            except KeyboardInterrupt:
                for name in names:
                    serve.delete(name)
                print("applications deleted")
    elif args.serve_cmd == "status":
        try:
            print(json.dumps(serve.status(), indent=2, default=str))
        except RuntimeError as e:
            print(json.dumps({"applications": {}, "error": str(e)}))
    elif args.serve_cmd == "config":
        cfg = get_deployed_config()
        if cfg is None:
            print("no config deployed (code-deployed apps have no "
                  "declarative config)")
        else:
            import yaml

            sys.stdout.write(yaml.safe_dump(cfg, sort_keys=False))
    elif args.serve_cmd == "delete":
        serve.delete(args.name)
        print(f"application {args.name!r} deleted")
    elif args.serve_cmd == "shutdown":
        serve.shutdown()
        print("serve shut down")


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        prog="ray_tpu", description="ray_tpu cluster CLI")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start",
                        help="start a head node or join as worker host")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address", help="head host:port to join (worker host)")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=0)
    sp.add_argument("--num-cpus", type=float,
                    default=float(os.cpu_count() or 1))
    sp.add_argument("--resources", help='extra resources as JSON, e.g. '
                    '\'{"TPU": 4}\'')
    sp.add_argument("--node-id", help="pre-assigned node id (worker-host "
                                      "joins launched by a provider)")
    sp.add_argument("--block", action="store_true")
    sp.add_argument("--dashboard-port", type=int, default=8265)
    sp.add_argument("--no-dashboard", action="store_true")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("dashboard", help="serve the web dashboard for a "
                        "running cluster")
    sp.add_argument("--address", help="conductor host:port")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=8265)
    sp.set_defaults(fn=cmd_dashboard)

    for name, fn in [("stop", cmd_stop), ("status", cmd_status),
                     ("summary", cmd_summary), ("memory", cmd_memory),
                     ("metrics", cmd_metrics)]:
        sp = sub.add_parser(name)
        sp.add_argument("--address")
        sp.set_defaults(fn=fn)

    sp = sub.add_parser("config", help="show the runtime flag table")
    sp.set_defaults(fn=cmd_config)

    sp = sub.add_parser("list", help="list cluster entities")
    sp.add_argument("kind", choices=["nodes", "workers", "actors", "tasks",
                                     "objects", "placement-groups"])
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("timeline", help="export chrome trace")
    sp.add_argument("--output", default="ray_tpu_timeline.json")
    sp.add_argument("--merged", action="store_true",
                    help="one unified trace: task events + tracing spans "
                         "+ training step markers (flight recorder)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser("train-status",
                        help="gang training telemetry: per-rank step "
                             "stats, MFU, skew, stragglers")
    sp.add_argument("--run", help="filter to one run id")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_train_status)

    sp = sub.add_parser("resilience-status",
                        help="recovery subsystem: quarantined/draining "
                             "hosts, failure scores, restart/preemption "
                             "counters, recent events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=10,
                    help="recent events to print (default 10)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_resilience_status)

    sp = sub.add_parser("weights",
                        help="live weight fabric: published versions, "
                             "manifests, keep-last-K GC")
    # --address lives on the LEAF parsers only: a mid-level flag would
    # be clobbered by the leaf's default (None) and silently ignored
    wsub = sp.add_subparsers(dest="weights_cmd", required=True)
    ws = wsub.add_parser("list", help="versions per weight-set name")
    ws.add_argument("--name", help="filter to one weight set")
    ws.add_argument("--json", action="store_true")
    ws.add_argument("--address")
    ws = wsub.add_parser("inspect",
                         help="one version's manifest (metadata only)")
    ws.add_argument("name")
    ws.add_argument("--version", type=int,
                    help="default: latest committed")
    ws.add_argument("--address")
    ws = wsub.add_parser("gc", help="keep only the newest K versions")
    ws.add_argument("name")
    ws.add_argument("--keep", type=int, required=True)
    ws.add_argument("--address")
    sp.set_defaults(fn=cmd_weights)

    sp = sub.add_parser("kvcache",
                        help="paged KV prefix cache: per-engine "
                             "hit/miss/eviction stats, pool "
                             "utilization, recent events")
    sp.add_argument("--engine", help="filter to one engine id")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N cache events")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_kvcache)

    sp = sub.add_parser("speculate",
                        help="speculative decoding: per-engine draft "
                             "proposal/acceptance counters, "
                             "tokens-per-verify, int8-KV flag")
    sp.add_argument("--engine", help="filter to one engine id")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N spec_accept/"
                         "spec_reject markers")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_speculate)

    sp = sub.add_parser("pipeline",
                        help="MPMD pipelines: stage registry, per-stage "
                             "bubble fraction and channel bytes, "
                             "recent events")
    sp.add_argument("--name", help="filter to one pipeline")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N pipeline events")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_pipeline)

    sp = sub.add_parser("online",
                        help="online learning loop: per-sampler "
                             "rollout/staleness stats, buffer "
                             "occupancy, learner ingest, recent "
                             "events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N online-loop events")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_online)

    sp = sub.add_parser("disagg",
                        help="disaggregated prefill/decode serving: "
                             "KV-transfer accounting (shm vs rpc), "
                             "router shed/queue depth, recent events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N disagg events")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_disagg)

    sp = sub.add_parser("kvplane",
                        help="global KV plane: tiered prefix cache "
                             "(HBM -> host arena -> object store), "
                             "spill/re-adopt accounting, prefix "
                             "directory routing, recent events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N kvplane events "
                         "(spill/tier2_hit/tier3_publish/tier3_adopt/"
                         "directory_hit markers)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_kvplane)

    sp = sub.add_parser("servefault",
                        help="serving-plane fault tolerance: request "
                             "failovers by phase, sheds by cause, "
                             "replica deaths/replacements, breaker "
                             "state, recent events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N servefault events "
                         "(the resilience lane's failover/replace/"
                         "breaker_trip slice)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_servefault)

    sp = sub.add_parser("gateway",
                        help="HTTP front door: per-replica request "
                             "counters by priority class and status "
                             "code, recent TTFT, QoS admissions, "
                             "batch-slot preemptions, recent events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N gateway events "
                         "(accept/first_byte/preempt/rate_limit/"
                         "disconnect markers)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_gateway)

    sp = sub.add_parser("requests",
                        help="per-request flight recorder: slowest "
                             "requests with per-phase breakdowns, "
                             "p99 tail attribution, single-trace "
                             "replay by request id")
    sp.add_argument("--slowest", type=int, default=10,
                    help="print the K slowest kept requests "
                         "(default 10)")
    sp.add_argument("--trace",
                    help="replay ONE kept request's phase spans by "
                         "request id")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N request-trace events "
                         "(kept-trace + remote-phase records)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_requests)

    sp = sub.add_parser("lora",
                        help="multi-tenant LoRA serving: adapter-pool "
                             "paging (hits/misses/evictions/swaps, "
                             "residents), per-tenant request counters, "
                             "recent page_in/evict/swap events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N lora events")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_lora)

    sp = sub.add_parser("autoscale",
                        help="serving autoscaler: per-tier targets and "
                             "decision counts, drain outcomes, "
                             "replica-seconds, recent events")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N autoscale events")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_autoscale)

    sp = sub.add_parser("oracle",
                        help="step-time oracle: roofline predictions "
                             "per layout, predicted-vs-measured "
                             "residuals, fitted calibration")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--events", type=int, default=0,
                    help="also print the last N oracle events")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_oracle)

    sp = sub.add_parser("microbench",
                        help="core-runtime micro benchmarks (ray_perf "
                             "analog): task/actor/put-get/queue/churn")
    sp.add_argument("--scale", type=float, default=1.0)
    sp.add_argument("--out", default="")
    sp.set_defaults(fn=cmd_microbench)

    sp = sub.add_parser("analyze",
                        help="shardlint static analysis: AST lint over "
                             "sources, --layouts for mesh/DCN checks")
    sp.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: the "
                         "installed ray_tpu package)")
    sp.add_argument("--layouts", action="store_true",
                    help="also analyze the built-in dryrun mesh layouts "
                         "(sharding specs, collectives over DCN)")
    sp.add_argument("--predict-step-time", action="store_true",
                    help="also print the step-time oracle's roofline "
                         "prediction (device/ici/dcn breakdown) per "
                         "built-in dryrun layout")
    sp.add_argument("--invariants", action="store_true",
                    help="also run the cross-module invariant engine "
                         "(lock discipline, env-knob registry, "
                         "donation audit)")
    sp.add_argument("--knob-table", action="store_true",
                    help="print the canonical RAY_TPU_* env-knob table "
                         "from the registry (markdown; rides the JSON "
                         "wrapper as env_knobs with --json)")
    sp.add_argument("--json", action="store_true",
                    help="machine-readable findings")
    sp.add_argument("--fail-on", choices=["error", "warning", "info"],
                    default="error",
                    help="exit 1 when a finding at this severity or "
                         "worse exists (default: error)")
    sp.set_defaults(fn=cmd_analyze)

    sp = sub.add_parser("serve", help="Serve applications: run/deploy from "
                                      "YAML config, status, shutdown")
    sp.add_argument("--address")
    ssub = sp.add_subparsers(dest="serve_cmd", required=True)
    for sc in ("run", "deploy"):
        s = ssub.add_parser(sc, help="deploy apps from a YAML config or a "
                                     "module:attr import path"
                                     + (" and stay attached"
                                        if sc == "run" else ""))
        s.add_argument("config_or_import",
                       help="path/to/config.yaml or module:application")
    ssub.add_parser("status")
    ssub.add_parser("config", help="echo the last deployed YAML config")
    s = ssub.add_parser("delete")
    s.add_argument("name")
    ssub.add_parser("shutdown")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("job", help="job submission")
    sp.add_argument("--address")
    jsub = sp.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--wait", action="store_true")
    j.add_argument("--timeout", type=float, default=600.0)
    j.add_argument("entrypoint", nargs=argparse.REMAINDER)
    for jc in ["status", "logs", "stop"]:
        j = jsub.add_parser(jc)
        j.add_argument("job_id")
    jsub.add_parser("list")
    sp.set_defaults(fn=cmd_job)

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
