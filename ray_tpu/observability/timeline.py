"""One unified timeline: driver/worker spans + conductor task events +
training step markers merged into a single chrome-trace file, so one
Perfetto load shows driver, gang, and step structure together
(``python -m ray_tpu timeline --merged``).

The three sources already exist separately — ``util.state.timeline``
(task events), ``util.tracing.to_chrome_trace`` (spans), and the flight
recorder's step records (``report_train_steps``) — this module only
merges and labels them:

- task events:   pid = job id,            tid = executing worker
- spans:         pid = recording process, tid = trace id prefix
- step markers:  pid = "train:<run_id>",  tid = "rank <r>", one X event
                 per step carrying the phase breakdown in args, plus a
                 counter event series for tokens/sec and MFU.
- resilience:    pid = "resilience",      tid = event kind — instant
                 markers for preemptions, restarts, quarantines, grace
                 checkpoints, and chaos injections (ray_tpu.resilience),
                 plus the serving plane's recovery markers: request
                 `failover` (serve/disagg.py replaying a request off a
                 dead tier replica), replica `replace` and
                 `breaker_trip` (serve/autoscale.py self-healing) —
                 recovery events share one lane whether they heal a
                 training gang or a serving tier.
- weights:       pid = "weights",         tid = event kind — instant
                 markers for weight publishes, fetches, hot swaps, GC
                 and reaps (ray_tpu.weights), so a serving replica's
                 swap lines up against the training steps that
                 produced the version.
- kvcache:       pid = "kvcache",         tid = event kind — instant
                 markers for paged-KV prefix hits, evictions, and
                 swap invalidations (models/kvcache.py), so serving
                 cache behavior lines up against request traffic and
                 weight swaps.
- pipeline:      pid = "pipeline",        tid = "stage <s>" (or event
                 kind) — one lane per MPMD stage-gang (ray_tpu.mpmd):
                 formation, per-stage run reports (bubble fraction,
                 channel bytes), stage deaths — beside the per-stage
                 train-step markers whose args carry bubble_wait_ms.
- online:        pid = "online",          tid = the sampler id (or
                 event kind) — instant markers of the online learning
                 loop (ray_tpu.online): rollouts completing, learner
                 ingests, weight publishes and sampler hot swaps, so
                 the sampler/learner cadence reads directly against the
                 weights lane's fabric-side publish/fetch/swap markers.
- disagg:        pid = "disagg",          tid = event kind — instant
                 markers of disaggregated serving (serve/disagg.py):
                 KV publishes on the prefill tier, prefill->decode
                 KV transfers with their shm/rpc byte split, and
                 router sheds, so cross-replica KV traffic lines up
                 against request latency and the kvcache lane.
- lora:          pid = "lora",            tid = event kind — instant
                 markers of multi-tenant LoRA serving (serve/lora.py):
                 adapter page_in / evict / swap per tenant, so adapter
                 paging lines up against the disagg lane's requests
                 and the weights lane's publishes.
- gateway:       pid = "gateway",         tid = event kind — instant
                 markers of the HTTP front door (serve/gateway.py +
                 serve/qos.py): request accepts, first bytes (TTFT),
                 batch-slot preemptions, rate-limit rejections, and
                 client disconnects per priority class, so ingress
                 pressure reads against the disagg lane's shed markers
                 and the lora lane's tenant paging.
- speculation:   pid = "speculation",     tid = event kind — instant
                 markers for speculative-decoding verify outcomes
                 (models/engine.py): spec_accept / spec_reject with the
                 accepted/proposed split per verify tick. The engine
                 pushes them through the kvcache event channel (ONE
                 report path), and the merge splits the spec_* slice
                 into its own lane so acceptance reads against the
                 kvcache and gateway tracks.
- autoscale:     pid = "autoscale",       tid = event kind — instant
                 markers of the serving autoscaler (serve/autoscale.py):
                 scale_up / drain / scale_down per tier, so replica-set
                 changes line up against the disagg lane's shed markers
                 and the request traffic they react to.
- oracle:        pid = "oracle" — a predicted-step-time COUNTER track
                 (one "C" series per layout, observability.roofline)
                 that draws the analytic roofline under the measured
                 train-step markers, plus instant validation markers
                 carrying the fitted calibration and residuals.
- kvplane:       pid = "kvplane",        tid = event kind — instant
                 markers of the global KV plane (serve/kvplane.py):
                 HBM->host-arena spills, tier-2 re-adoptions, tier-3
                 prefix publishes/adoptions through the chunk fabric,
                 directory-routed requests, eviction storms, and
                 directory reaps, so cross-tier prefix movement reads
                 against the kvcache lane's block-level hits and the
                 disagg lane's transfers.
- requests:      pid = "requests",       tid = the request id prefix —
                 one REAL "X" span per recorded phase of a kept request
                 trace (observability.requests): qos_admission ->
                 queue_reserve -> prefill -> kv_transfer ->
                 decode_first_token -> decode_steady -> sse_flush, with
                 failover/preempt replay attempts suffixed " a<n>" so a
                 replayed request reads as child spans under one id,
                 plus one enclosing span carrying the outcome and total
                 — a sampled request's whole lifecycle rendered against
                 the disagg/gateway lanes that produced it.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple


def step_trace_events(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Chrome-trace events for flattened step records (each record
    carries run_id/rank — see ConductorHandler.get_train_steps)."""
    out: List[Dict[str, Any]] = []
    for rec in records:
        t0, t1 = rec.get("t_start"), rec.get("t_end")
        if t0 is None or t1 is None:
            continue
        pid = f"train:{rec.get('run_id', 'default')}"
        tid = f"rank {rec.get('rank', 0)}"
        args = {k: round(v, 3) for k, v in rec.items()
                if k.endswith("_ms") and isinstance(v, (int, float))}
        for key in ("tokens", "tokens_per_sec", "mfu"):
            if key in rec:
                args[key] = rec[key]
        out.append({
            "name": f"step {rec.get('step', '?')}", "cat": "train_step",
            "ph": "X", "ts": t0 * 1e6,
            "dur": max(0.0, t1 - t0) * 1e6,
            "pid": pid, "tid": tid, "args": args,
        })
        # counter tracks: throughput/MFU trend lines under the steps
        counters = {}
        if rec.get("tokens_per_sec") is not None:
            counters["tokens_per_sec"] = round(rec["tokens_per_sec"], 1)
        if rec.get("mfu") is not None:
            counters["mfu_pct"] = round(100.0 * rec["mfu"], 3)
        if counters:
            out.append({"name": "throughput", "cat": "train_step",
                        "ph": "C", "ts": t1 * 1e6, "pid": pid,
                        "args": counters})
    return out


def resilience_trace_events(events: List[Dict[str, Any]]
                            ) -> List[Dict[str, Any]]:
    """Instant markers for resilience events (preemption, restart,
    quarantine, grace checkpoint, chaos injection, recovery) — one
    global-scope "i" event per entry so failures and recoveries line up
    against the task/span/step tracks they interrupted."""
    out: List[Dict[str, Any]] = []
    for ev in events:
        ts = ev.get("ts")
        if ts is None:
            continue
        kind = str(ev.get("kind", "event"))
        # serving-plane recovery markers name their replica/router/host
        # the same way training markers name their node/run (explicit
        # None checks: a chaos kill's replica index 0 is a real label)
        where = next((ev[k] for k in ("node_id", "run_id", "name",
                                      "replica", "router", "host")
                      if ev.get(k) is not None), None)
        out.append({
            "name": f"{kind}:{where}" if where is not None else kind,
            "cat": "resilience", "ph": "i", "s": "g", "ts": ts * 1e6,
            "pid": "resilience", "tid": kind,
            "args": {k: v for k, v in ev.items()
                     if k != "ts" and v is not None},
        })
    return out


# lane -> (the key a marker's track is named by, or None for its kind;
# the parts of its label after the kind: a format and the keys it may
# take its value from, the first that carries one)
LANES: Dict[str, Tuple[Optional[str], Tuple[Tuple[str, ...], ...]]] = {
    "weights": (None, ((":{}", "name"), ("@v{}", "version"))),
    "kvcache": (None, ((":{}", "outcome"), (" +{}tok", "reused_tokens"))),
    "online": ("sampler", ((":{}", "sampler"),
                           ("@v{}", "weights_version", "version"))),
    "disagg": (None, ((":{}", "server", "router"), (" {}B", "bytes"))),
    "lora": (None, ((":{}", "tenant"), ("@v{}", "version"),
                    (" {}B", "bytes"))),
    "kvplane": (None, ((":{}", "replica", "holder", "router"),
                       (" {}blk", "blocks"), (" {}B", "nbytes"))),
    "gateway": (None, ((":{}", "class"), ("@{}", "tenant"),
                       (" {}ms", "ttft_ms"))),
    "autoscale": (None, ((":{}", "tier"), ("->{}", "to"))),
}


def instant_lane(subsystem: str, events: List[Dict[str, Any]]
                 ) -> List[Dict[str, Any]]:
    """Instant markers of one subsystem under pid `subsystem`: one
    global-scope "i" event per entry, named by its kind and the parts
    of the lane's label (its row of LANES; without one, the bare kind)
    whose keys the event carries, with the whole event in args."""
    track, parts = LANES.get(subsystem, (None, ()))
    out: List[Dict[str, Any]] = []
    for ev in events:
        ts = ev.get("ts")
        if ts is None:
            continue
        kind = str(ev.get("kind", "event"))
        name = kind
        for fmt, *keys in parts:
            value = next((ev[k] for k in keys
                          if ev.get(k) not in (None, "")), None)
            if value is not None:
                name += fmt.format(value)
        out.append({
            "name": name, "cat": subsystem, "ph": "i", "s": "g",
            "ts": ts * 1e6, "pid": subsystem,
            "tid": str(ev.get(track) or kind) if track else kind,
            "args": {k: v for k, v in ev.items()
                     if k != "ts" and v is not None},
        })
    return out


def gateway_trace_events(events: List[Dict[str, Any]]
                         ) -> List[Dict[str, Any]]:
    """The HTTP front door's lane (accept, first_byte, preempt,
    rate_limit, disconnect)."""
    return instant_lane("gateway", events)


def _kvcache_lanes(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The kvcache ring feeds two lanes: the spec_* markers render on
    the speculation lane, the rest on the prefix cache's own."""
    own = [ev for ev in events
           if not str(ev.get("kind", "")).startswith("spec_")]
    return instant_lane("kvcache", own) + speculation_trace_events(events)


def speculation_trace_events(events: List[Dict[str, Any]]
                             ) -> List[Dict[str, Any]]:
    """Instant markers for speculative-decoding verify outcomes — the
    spec_* slice of the kvcache event channel (engines push spec_accept
    / spec_reject through the kvcache row's event ring), rendered
    under its own pid "speculation" so acceptance reads as a lane
    instead of noise in the prefix-cache track."""
    out: List[Dict[str, Any]] = []
    for ev in events:
        ts = ev.get("ts")
        kind = str(ev.get("kind", "event"))
        if ts is None or not kind.startswith("spec_"):
            continue
        label = kind
        if ev.get("proposed") is not None:
            label += f" {ev.get('accepted', 0)}/{ev['proposed']}"
        out.append({
            "name": label, "cat": "speculation", "ph": "i", "s": "g",
            "ts": ts * 1e6, "pid": "speculation", "tid": kind,
            "args": {k: v for k, v in ev.items()
                     if k != "ts" and v is not None},
        })
    return out


def pipeline_trace_events(events: List[Dict[str, Any]]
                          ) -> List[Dict[str, Any]]:
    """Instant markers for MPMD pipeline events (open, stage_registered,
    formed, stage_report, stage_death, closed) — one lane per stage
    under pid "pipeline" so each stage-gang's lifecycle reads as its own
    track."""
    out: List[Dict[str, Any]] = []
    for ev in events:
        ts = ev.get("ts")
        if ts is None:
            continue
        kind = str(ev.get("kind", "event"))
        name = ev.get("pipeline")
        stage = ev.get("stage")
        label = f"{kind}:{name}" if name else kind
        if stage is not None:
            label += f"/stage{stage}"
        out.append({
            "name": label, "cat": "pipeline", "ph": "i", "s": "g",
            "ts": ts * 1e6, "pid": "pipeline",
            "tid": f"stage {stage}" if stage is not None else kind,
            "args": {k: v for k, v in ev.items()
                     if k != "ts" and v is not None},
        })
    return out


def oracle_trace_events(events: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
    """The step-time oracle's track (observability.roofline): every
    prediction event becomes a point on a per-layout ``predicted_step_ms``
    counter series under pid "oracle" (the analytic roofline drawn under
    the measured train-step markers); validation events become instant
    markers carrying calibration + residuals."""
    out: List[Dict[str, Any]] = []
    for ev in events:
        ts = ev.get("ts")
        if ts is None:
            continue
        kind = str(ev.get("kind", "event"))
        layout = ev.get("layout")
        if kind == "prediction":
            pred = ev.get("predicted_step_ms")
            if pred is None:
                continue
            out.append({
                "name": f"predicted_step_ms:{layout}" if layout
                else "predicted_step_ms",
                "cat": "oracle", "ph": "C", "ts": ts * 1e6,
                "pid": "oracle",
                "args": {"predicted_step_ms": round(float(pred), 3)},
            })
            continue
        label = kind
        if layout:
            label += f":{layout}"
        cal = ev.get("calibration")
        if cal is not None:
            label += f" cal={float(cal):.2f}"
        out.append({
            "name": label, "cat": "oracle", "ph": "i", "s": "g",
            "ts": ts * 1e6, "pid": "oracle", "tid": kind,
            "args": {k: v for k, v in ev.items()
                     if k != "ts" and v is not None},
        })
    return out


def requests_trace_events(events: List[Dict[str, Any]]
                          ) -> List[Dict[str, Any]]:
    """Real spans for kept request traces (observability.requests):
    each ``kind == "trace"`` event carries its phase list with offsets
    from the request's start, so every phase renders as an "X" span on
    the request's own track — replay attempts (failover/preempt) get an
    " a<n>" suffix so they read as child spans under the one request id.
    One enclosing span per request carries the outcome and totals."""
    out: List[Dict[str, Any]] = []
    for ev in events:
        if ev.get("kind") != "trace":
            continue
        ts = float(ev.get("ts", 0.0))
        rid = str(ev.get("request_id", "?"))
        tid = rid[:12]
        total_ms = float(ev.get("total_ms", 0.0) or 0.0)
        # the conductor stamps ts at completion; phases carry offsets
        # from the request's start, so anchor the lane at ts - total
        t_start = ts - total_ms / 1e3
        out.append({
            "name": f"request {ev.get('outcome', '?')}",
            "cat": "request", "ph": "X", "ts": t_start * 1e6,
            "dur": max(0.0, total_ms) * 1e3,
            "pid": "requests", "tid": tid,
            "args": {"request_id": rid,
                     "outcome": ev.get("outcome"),
                     "attempts": ev.get("attempts", 1),
                     "preempts": ev.get("preempts", 0),
                     "total_ms": round(total_ms, 3)},
        })
        for ph in ev.get("phases", []) or []:
            name = str(ph.get("phase", "phase"))
            attempt = int(ph.get("attempt", 1) or 1)
            if attempt > 1:
                name += f" a{attempt}"
            dur_ms = float(ph.get("dur_ms", 0.0) or 0.0)
            t_ms = ph.get("t_ms")
            t0 = t_start + (float(t_ms) / 1e3 if t_ms is not None
                            else 0.0)
            args = {k: v for k, v in ph.items()
                    if k not in ("phase", "t_ms") and v is not None}
            out.append({
                "name": name, "cat": "request_phase", "ph": "X",
                "ts": t0 * 1e6, "dur": max(0.0, dur_ms) * 1e3,
                "pid": "requests", "tid": tid, "args": args,
            })
    return out


def task_trace_events(task_events: List[Dict[str, Any]]
                      ) -> List[Dict[str, Any]]:
    """Chrome-trace events for conductor task events — the ONE rendering
    of the task-event schema, shared by the plain `util.state.timeline`
    export and the merged flight-recorder trace."""
    out: List[Dict[str, Any]] = []
    for ev in task_events:
        worker = ev.get("worker")
        out.append({
            "name": ev["name"], "cat": "task", "ph": "X",
            "ts": ev["start"] * 1e6,
            "dur": max(0.0, ev["end"] - ev["start"]) * 1e6,
            "pid": ev.get("job_id", "job"),
            "tid": f"{worker[0]}:{worker[1]}" if worker else "driver",
            "args": {"task_id": ev["task_id"],
                     "status": ev.get("status", "FINISHED")},
        })
    return out


# telemetry rings whose lane has a structure of its own; the others are
# instant markers
_OWN_LANES = {
    "kvcache": _kvcache_lanes,
    "oracle": oracle_trace_events,
    "requesttrace": requests_trace_events,
}


def merged_chrome_trace(task_events: List[Dict[str, Any]],
                        spans: List[Dict[str, Any]],
                        step_records: List[Dict[str, Any]],
                        resilience_events: Optional[
                            List[Dict[str, Any]]] = None,
                        weight_events: Optional[
                            List[Dict[str, Any]]] = None,
                        pipeline_events: Optional[
                            List[Dict[str, Any]]] = None,
                        telemetry_events: Optional[
                            Dict[str, List[Dict[str, Any]]]] = None
                        ) -> List[Dict[str, Any]]:
    """Merge the sources into one sorted event list.
    `telemetry_events` holds the event ring of each telemetry subsystem
    by its name."""
    from ray_tpu.util import tracing

    trace = task_trace_events(task_events)
    trace.extend(tracing.to_chrome_trace(spans))
    trace.extend(step_trace_events(step_records))
    if resilience_events:
        trace.extend(resilience_trace_events(resilience_events))
    if weight_events:
        trace.extend(instant_lane("weights", weight_events))
    if pipeline_events:
        trace.extend(pipeline_trace_events(pipeline_events))
    for name, events in (telemetry_events or {}).items():
        lane = _OWN_LANES.get(name)
        trace.extend(lane(events) if lane
                     else instant_lane(name, events))
    trace.sort(key=lambda e: e.get("ts", 0.0))
    return trace


def merged_timeline(filename: Optional[str] = None,
                    limit: int = 10_000) -> List[Dict[str, Any]]:
    """Pull all sources from the live cluster and merge (the
    ``timeline --merged`` backend). Flushes this process's pending task
    events and spans first so a short driver's trace is complete."""
    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.telemetry import SUBSYSTEMS

    w = worker_mod.global_worker
    if w is None:
        raise RuntimeError("ray_tpu.init() must be called first")
    w._flush_task_events()  # spans ride the same flush (tracing.drain)
    events = w.conductor.call("get_task_events", limit, timeout=30.0)
    spans = w.conductor.call("get_spans", limit, timeout=30.0)

    def tail(method: str, *args: Any) -> List[Dict[str, Any]]:
        try:
            return w.conductor.call(method, *args, limit, timeout=30.0)
        except Exception:  # noqa: BLE001 — a lane less, not no trace
            return []

    # a row that keeps no ring of its own has its markers in another
    # lane (speculation's in kvcache's, servefault's in resilience's)
    telemetry = {name: tail("get_events", name)
                 for name, row in SUBSYSTEMS.items()
                 if row.view_of is None and row.events_kept}
    trace = merged_chrome_trace(
        events, spans, tail("get_train_steps"),
        tail("get_resilience_events"), tail("get_weight_events"),
        tail("get_pipeline_events"), telemetry)
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace
