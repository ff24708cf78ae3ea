"""Cluster state API — analog of the reference's python/ray/util/state/
(api.py: list_actors :788, list_tasks :1020, list_objects :1066,
summarize_tasks :1382; backed by the dashboard StateHead + GCS
GcsTaskManager). Here the conductor IS the state authority; workers answer
store-stats probes directly."""
from __future__ import annotations

import json
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional

from ray_tpu._private.telemetry import speculation_totals  # noqa: F401


def _conductor():
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.global_worker
    if w is None:
        raise RuntimeError("ray_tpu.init() must be called first")
    return w


def list_nodes() -> List[Dict[str, Any]]:
    return _conductor().conductor.call("nodes", timeout=10.0)


def list_workers() -> List[Dict[str, Any]]:
    return _conductor().conductor.call("list_workers", timeout=10.0)


def list_actors(state: Optional[str] = None) -> List[Dict[str, Any]]:
    actors = _conductor().conductor.call("list_actors", timeout=10.0)
    if state is not None:
        actors = [a for a in actors if a.get("state") == state]
    return actors


def list_placement_groups() -> List[Dict[str, Any]]:
    return _conductor().conductor.call("list_placement_groups", timeout=10.0)


def list_tasks(limit: int = 10_000,
               name: Optional[str] = None) -> List[Dict[str, Any]]:
    w = _conductor()
    events = w.conductor.call("get_task_events", limit, timeout=30.0)
    with w._task_events_lock:  # include this process's unflushed batch
        events = events + list(w._task_events)
    if name is not None:
        events = [e for e in events if e.get("name") == name]
    return events


def list_objects() -> List[Dict[str, Any]]:
    """Per-process object-store stats (reference `ray memory` summary)."""
    w = _conductor()
    out = [dict(w.store.stats(), worker_id=w.worker_id, is_driver=True)]
    for rec in list_workers():
        addr = rec.get("address")
        if not addr:
            continue
        try:
            out.append(w.clients.get(tuple(addr)).call("store_stats",
                                                       timeout=5.0))
        except Exception:  # noqa: BLE001 — worker mid-restart
            pass
    return out


def slice_topology(group: Optional[str] = None) -> Dict[str, Any]:
    """Slice maps of jax.distributed gangs that ran a multi-slice
    rendezvous (parallel.distributed.initialize_jax_distributed with a
    slice id): {group_key: {"slices": {slice_id: [ranks]},
    "process_ids": {rank: process_id}, "world": n}}. Rank 0 of each
    gang publishes its map into the conductor KV; this reads it back —
    the state-API analog of `list_placement_groups` for DCN topology."""
    w = _conductor()
    suffix = "/slice_map"
    keys = w.conductor.call("kv_keys", b"", "_jax_distributed",
                            timeout=10.0)
    out: Dict[str, Any] = {}
    for key in keys:
        name = key.decode() if isinstance(key, bytes) else str(key)
        if not name.endswith(suffix):
            continue
        g = name[:-len(suffix)]
        if group is not None and g != group:
            continue
        raw = w.conductor.call("kv_get", key, "_jax_distributed",
                               timeout=10.0)
        if not raw:
            continue
        rec = json.loads(raw.decode())
        out[g] = {
            "slices": {int(s): rs
                       for s, rs in rec.get("slices", {}).items()},
            "process_ids": {int(r): p for r, p
                            in rec.get("process_ids", {}).items()},
            "world": rec.get("world"),
        }
    return out


def train_progress(run: Optional[str] = None) -> Dict[str, Any]:
    """Gang-wide training telemetry (the flight recorder's state-API
    surface): {run_id: {world, last_step, per_rank: {rank: {mean_ms,
    p50_ms, p99_ms, tokens_per_sec, mfu, ...}}, last_step_skew,
    last_step_breakdown, stragglers}}. Ranks ship per-step records with
    their metric/span batches; the conductor aggregates (see
    ray_tpu.observability.gang). `run` filters to one run id."""
    out = _conductor().conductor.call("get_train_progress", timeout=30.0)
    if run is not None:
        out = {k: v for k, v in out.items() if k == run}
    return out


def weight_versions(name: Optional[str] = None) -> Dict[str, Any]:
    """Live weight fabric registry state (ray_tpu.weights): per name the
    latest committed version and the kept manifests' summaries
    (version, step, run_id, bytes, host/leaf/chunk counts), plus any
    in-flight (pending) publishes. The CLI analog is
    `python -m ray_tpu weights list`; the dashboard serves it at
    /api/weights. `name` filters to one weight set."""
    out = _conductor().conductor.call("get_weight_versions", timeout=10.0)
    if name is not None:
        out = {"names": {k: v for k, v in out.get("names", {}).items()
                         if k == name},
               "pending": [p for p in out.get("pending", [])
                           if p.get("name") == name]}
    return out


def status(subsystem: str) -> Dict[str, Any]:
    """One telemetry subsystem's aggregate (a row of
    ray_tpu._private.telemetry.SUBSYSTEMS): the dict `ray_tpu
    <subsystem>` prints and the dashboard serves at /api/<subsystem>."""
    return _conductor().conductor.call("get_status", subsystem,
                                       timeout=10.0)


def events(subsystem: str, limit: int = 10_000) -> List[Dict[str, Any]]:
    """The newest `limit` instant markers of one telemetry subsystem
    (its lane of the merged timeline)."""
    return _conductor().conductor.call("get_events", subsystem, limit,
                                       timeout=10.0)


def kv_cache_stats(engine: Optional[str] = None) -> Dict[str, Any]:
    """Paged-KV prefix-cache view (models/kvcache.py): per-engine stat
    snapshots (hits/misses/evictions, pool utilization, reused vs
    prefilled tokens) plus cluster totals with hit/token-reuse rates.
    The CLI analog is `python -m ray_tpu kvcache`; the dashboard serves
    it at /api/kvcache. `engine` filters to one engine id."""
    out = status("kvcache")
    if engine is not None:
        out = {"engines": {k: v for k, v in out.get("engines",
                                                    {}).items()
                           if v.get("engine_id") == engine},
               "totals": out.get("totals", {})}
    return out


def speculation_stats(engine: Optional[str] = None) -> Dict[str, Any]:
    """Speculative-decoding view (models/engine.py): per-engine draft
    counters (proposed/accepted, verify ticks, tokens-per-verify,
    acceptance rate, the int8-KV flag) plus cluster totals. Rides the
    SAME conductor snapshots as kv_cache_stats() — one report channel,
    one set of numbers. The CLI analog is `python -m ray_tpu
    speculate`; the dashboard serves it at /api/speculation;
    spec_accept/spec_reject markers ride the merged timeline's kvcache
    lane. `engine` filters to one engine id."""
    out = status("speculation")
    if engine is not None:
        engines = {k: v for k, v in out.get("engines", {}).items()
                   if v.get("engine_id") == engine}
        # totals must describe the FILTERED view, or the one engine
        # shown disagrees with the summary printed beside it
        out = {"engines": engines,
               "totals": speculation_totals(engines)}
    return out


def pipeline_status(name: Optional[str] = None) -> Dict[str, Any]:
    """MPMD pipeline view (ray_tpu.mpmd): per-pipeline stage registry
    (formed flag, per-stage slice/worker identity), per-stage run stats
    (steps, bubble_fraction, channel bytes), cross-stage totals, and the
    channel-mailbox depth. The CLI analog is `python -m ray_tpu
    pipeline`; the dashboard serves it at /api/pipeline. `name` filters
    to one pipeline."""
    out = _conductor().conductor.call("get_pipeline_status", timeout=10.0)
    if name is not None:
        out = {"pipelines": {k: v for k, v
                             in out.get("pipelines", {}).items()
                             if k == name},
               "mailbox_depth": out.get("mailbox_depth")}
    return out


def online_status() -> Dict[str, Any]:
    """Online learning loop view (ray_tpu.online): per-component stat
    snapshots grouped by role — samplers (rollouts, tokens, serving/
    latest version, staleness incl. its high-water mark), the rollout
    buffer (occupancy, capacity, backpressured puts), the learner
    (steps, ingested rollouts/tokens, last published version) — plus
    cluster totals. The CLI analog is `python -m ray_tpu online`; the
    dashboard serves it at /api/online."""
    return status("online")


def disagg_status() -> Dict[str, Any]:
    """Disaggregated-serving view (serve/disagg.py): per-component stat
    snapshots grouped by role — prefill servers (prefills, prefix
    reuse, published transfers/bytes), decode servers (transfers, KV
    bytes split shm/rpc, adoptions, free slots, prefill-program count —
    flat on a pure decode replica), routers (dispatched, shed, live and
    high-water queue depth) — plus cluster totals. The CLI analog is
    `python -m ray_tpu disagg`; the dashboard serves it at
    /api/disagg."""
    return status("disagg")


def kvplane_status() -> Dict[str, Any]:
    """Global KV plane view (serve/kvplane.py): per-component
    snapshots — prefill arenas (tier-2 entries/bytes, spills, hits,
    re-adopted tokens), tier-3 publish/adopt counters, routers'
    directory routing outcomes (hit/fallback/miss) — plus cluster
    totals with tier-2 hit rate and directory hit rate, and the
    conductor-side prefix directory summary (entries, bytes, per-
    namespace counts, commit/reap/GC counters). The CLI analog is
    `python -m ray_tpu kvplane`; the dashboard serves it at
    /api/kvplane; spill/tier2_hit/tier3_publish/tier3_adopt/
    directory_hit markers ride the merged timeline's `kvplane`
    lane."""
    return status("kvplane")


def lora_status() -> Dict[str, Any]:
    """Multi-tenant LoRA serving view (serve/lora.py): per-pool
    adapter-paging snapshots (slots, residents, hits/misses/evictions/
    hot-swaps, page-in bytes), per-router tenant request counters
    (dispatched/completed/shed/SLO misses with recent TTFT/latency
    windows), a per-tenant rollup, and cluster totals. The CLI analog
    is `python -m ray_tpu lora`; the dashboard serves it at
    /api/lora; page_in/evict/swap markers ride the merged timeline's
    `lora` lane."""
    return status("lora")


def gateway_status() -> Dict[str, Any]:
    """HTTP front-door view (serve/gateway.py): per-replica request
    counters split by priority class (interactive/batch accepted/
    completed/shed/disconnects) and status code, recent TTFT windows
    per class, QoS gate admission/rejection stats, batch-slot
    preemptions — plus cluster totals. The CLI analog is `python -m
    ray_tpu gateway`; the dashboard serves it at /api/gateway; the
    accept/first_byte/preempt/rate_limit/disconnect markers ride the
    merged timeline's `gateway` lane."""
    return status("gateway")


def requesttrace_status() -> Dict[str, Any]:
    """Per-request flight-recorder view (observability/requests.py):
    per-store retention counters (completed/kept/dropped, outcomes,
    replayed + preempted requests), the cluster-wide slowest-request
    list with per-phase breakdowns, and the p99-attribution report
    that diffs per-phase time between the p50 and p99 cohorts and
    names the phase that owns the tail. The CLI analog is `python -m
    ray_tpu requests`; the dashboard serves it at /api/requesttrace;
    kept traces render as real spans in the merged timeline's
    `requests` lane."""
    return status("requesttrace")


def request_trace(request_id: str) -> Optional[Dict[str, Any]]:
    """One request's full kept trace by id (None when it was sampled
    out or has aged past the retention budget): outcome, attempts,
    per-phase spans tagged with their attempt number — failover and
    preemption replays read as child spans under the same id — plus
    any remote child phases actor-mode tiers pushed."""
    return _conductor().conductor.call("get_request_trace",
                                       str(request_id), timeout=10.0)


def servefault_status() -> Dict[str, Any]:
    """Serving-plane fault-tolerance view (serve/disagg.py failover +
    serve/autoscale.py self-healing): per-router failover counts by
    phase, sheds by attributed cause (capacity/deadline/failover/
    draining), corpses removed, recent failover-recovery latency;
    per-healer replica deaths, replacements, breaker trips and open
    hosts — plus cluster totals. The failover/replace/breaker_trip
    instant markers live in the merged timeline's RESILIENCE lane. The
    CLI analog is `python -m ray_tpu servefault`; the dashboard serves
    it at /api/servefault."""
    return status("servefault")


def autoscaler_status() -> Dict[str, Any]:
    """Serving-autoscaler view (serve/autoscale.py): per-loop status
    snapshots (per-tier targets and bounds, scale-up/down decision
    counts, drain outcomes, replica-seconds — the provisioning cost the
    policy minimizes, last decision reason) plus cluster totals. The
    CLI analog is `python -m ray_tpu autoscale`; the dashboard serves
    it at /api/autoscale. (The NODE-level autoscaler —
    ray_tpu.autoscaler, which launches/terminates hosts — mirrors its
    status separately at /api/autoscaler.)"""
    return status("autoscale")


def oracle_status() -> Dict[str, Any]:
    """Step-time oracle view (observability.roofline): the latest
    roofline prediction per layout ({device_step, ici_wait, dcn_wait}
    breakdown + predicted total), the predicted-vs-measured validation
    tail (per-phase residuals, fitted calibration), and totals. The CLI
    analog is `python -m ray_tpu oracle`; the dashboard serves it at
    /api/oracle."""
    return status("oracle")


def resilience_status() -> Dict[str, Any]:
    """Recovery-subsystem view (ray_tpu.resilience): per-host failure
    scores with quarantine/drain flags, the excluded host list, event
    counters (preemption/restart/quarantine/grace_checkpoint/...),
    last time-to-recovery, and the most recent events. The CLI analog
    is `python -m ray_tpu resilience-status`; the dashboard serves it
    at /api/resilience."""
    return _conductor().conductor.call("get_resilience_status",
                                       timeout=10.0)


def summarize_tasks() -> Dict[str, Any]:
    """Group task events by name — reference api.py summarize_tasks :1382."""
    groups: Dict[str, Dict[str, Any]] = defaultdict(
        lambda: {"count": 0, "failed": 0, "total_s": 0.0,
                 "min_s": float("inf"), "max_s": 0.0})
    for ev in list_tasks():
        g = groups[ev["name"]]
        dur = max(0.0, ev["end"] - ev["start"])
        g["count"] += 1
        g["failed"] += 1 if ev.get("status") == "FAILED" else 0
        g["total_s"] += dur
        g["min_s"] = min(g["min_s"], dur)
        g["max_s"] = max(g["max_s"], dur)
    for g in groups.values():
        g["mean_s"] = g["total_s"] / max(1, g["count"])
        if g["min_s"] == float("inf"):
            g["min_s"] = 0.0
    return dict(groups)


def timeline(filename: Optional[str] = None,
             merged: bool = False) -> List[Dict[str, Any]]:
    """Chrome-trace export of task events — reference `ray timeline`
    (scripts.py; ProfileEvents via GcsTaskManager). Load the output in
    chrome://tracing or Perfetto.

    merged=True produces the unified flight-recorder timeline instead:
    task events + tracing spans + training step markers in one trace
    (`python -m ray_tpu timeline --merged`)."""
    if merged:
        from ray_tpu.observability.timeline import merged_timeline

        return merged_timeline(filename)
    from ray_tpu.observability.timeline import task_trace_events

    trace = task_trace_events(list_tasks())
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


# ---------------------------------------------------------------- metrics

def _prom_escape(s: str) -> str:
    return s.replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def prometheus_metrics() -> str:
    """Render all pushed metric snapshots in Prometheus text exposition
    format — reference python/ray/_private/prometheus_exporter.py. Samples
    are grouped per metric family (HELP/TYPE once, then ALL of the family's
    series contiguously, across workers) as strict parsers require."""
    per_worker = _conductor().conductor.call("get_metrics", timeout=10.0)
    return _render_prometheus(per_worker)


def _render_prometheus(per_worker: Dict[str, Any]) -> str:
    """Pure renderer over the conductor's per-worker snapshots (shared
    with the dashboard, which has no global_worker)."""
    # family name -> list of (worker_id, snapshot dict)
    families: Dict[str, List[Any]] = {}
    for worker_id, snapshot in sorted(per_worker.items()):
        for m in snapshot:
            families.setdefault(m["name"], []).append((worker_id, m))

    def labels(keys, tag_json: str, worker_id: str, extra: str = "") -> str:
        vals = json.loads(tag_json) if tag_json else []
        parts = [f'{k}="{_prom_escape(v)}"' for k, v in zip(keys, vals)]
        parts.append(f'WorkerId="{worker_id[:12]}"')
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}"

    lines: List[str] = []
    for name, members in families.items():
        first = members[0][1]
        if first.get("description"):
            lines.append(f"# HELP {name} "
                         f"{_prom_escape(first['description'])}")
        mtype = first["type"] if first["type"] != "untyped" else "gauge"
        lines.append(f"# TYPE {name} {mtype}")
        for worker_id, m in members:
            keys = list(m.get("tag_keys") or ())
            if m["type"] == "histogram":
                for tag_json, buckets in m.get("buckets", {}).items():
                    acc = 0
                    for bound, n in zip(m["boundaries"], buckets):
                        acc += n
                        le = f'le="{bound}"'
                        lines.append(
                            f"{name}_bucket"
                            f"{labels(keys, tag_json, worker_id, le)}"
                            f" {acc}")
                    acc += buckets[-1]
                    inf = 'le="+Inf"'
                    lines.append(
                        f"{name}_bucket"
                        f"{labels(keys, tag_json, worker_id, inf)}"
                        f" {acc}")
                    lines.append(f"{name}_sum"
                                 f"{labels(keys, tag_json, worker_id)} "
                                 f"{m['sums'][tag_json]}")
                    lines.append(f"{name}_count"
                                 f"{labels(keys, tag_json, worker_id)} "
                                 f"{m['counts'][tag_json]}")
            else:
                for tag_json, v in m.get("values", {}).items():
                    lines.append(
                        f"{name}{labels(keys, tag_json, worker_id)} {v}")
    return "\n".join(lines) + ("\n" if lines else "")


def rpc_stats() -> Dict[str, Dict[str, float]]:
    """Control-plane dispatch latency by RPC method (count, mean/max
    queue and handler ms) — see ConductorHandler.get_rpc_stats."""
    return _conductor().conductor.call("get_rpc_stats", timeout=10.0)


def cluster_summary() -> Dict[str, Any]:
    """One-call overview — reference `ray status`."""
    w = _conductor()
    return {
        "timestamp": time.time(),
        "nodes": list_nodes(),
        "resources_total": w.conductor.call("cluster_resources",
                                            timeout=10.0),
        "resources_available": w.conductor.call("available_resources",
                                                timeout=10.0),
        "num_actors": len(list_actors()),
        "num_workers": len(list_workers()),
        "placement_groups": list_placement_groups(),
    }
