"""Telemetry: one store and one table for every subsystem that only
carries numbers from a component to a reader.

A subsystem is a row of ``SUBSYSTEMS``: its caps and its ``aggregate``,
a pure function from the newest snapshot a component to the dict every
surface shows (``util.state``, `ray_tpu <x>`, the dashboard's
``/api/<x>``). Components push through ``ray_tpu.util.telemetry.Pusher``
into the conductor's four methods (``report_stats``, ``report_event``,
``get_events``, ``get_status``), which are this module's
``TelemetryStore`` under a lock of its own. What the conductor ACTS on
(the resilience tracker, the weight and stage registries, the kvplane
prefix directory) is not telemetry and stays with the conductor.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional

Snapshots = Dict[str, Dict[str, Any]]

# Live gauges (a router's queue depth, an arena's bytes) only count
# snapshots at most this old: components re-push at the pusher's
# interval, so anything older is a dead component's frozen last word.
# Monotonic counters tolerate stale snapshots.
GAUGE_FRESH_S = 15.0


def sum_keys(snapshots: Snapshots, keys: Iterable[str]) -> Dict[str, Any]:
    """Per-key sums of the numeric values the snapshots carry."""
    totals: Dict[str, Any] = {k: 0 for k in keys}
    for st in snapshots.values():
        for k in totals:
            v = st.get(k)
            if isinstance(v, (int, float)):
                totals[k] += v
    return totals


def _by_role(snapshots: Snapshots, role: str) -> Snapshots:
    return {k: v for k, v in snapshots.items() if v.get("role") == role}


def _sum(snapshots: Snapshots, key: str) -> int:
    return sum(int(s.get(key, 0)) for s in snapshots.values())


def _fresh(snapshots: Snapshots, now: float) -> Snapshots:
    return {k: s for k, s in snapshots.items()
            if now - float(s.get("ts", 0.0)) <= GAUGE_FRESH_S}


# ------------------------------------------------------------ aggregates

_KVCACHE_TOTAL_KEYS = (
    "lookups", "hits", "partial_hits", "misses", "reused_tokens",
    "prefilled_tokens", "spliced_tokens", "inserted_blocks",
    "evictions", "cow_copies", "invalidations", "admitted",
    "prefill_admitted", "adopted", "prefill_calls",
    "spec_proposed", "spec_accepted", "spec_verify_ticks",
    "spec_emitted_tokens")


def _kvcache(engines: Snapshots, now: float) -> Dict[str, Any]:
    totals = sum_keys(engines, _KVCACHE_TOTAL_KEYS)
    looked = totals["lookups"]
    totals["hit_rate"] = ((totals["hits"] + totals["partial_hits"])
                          / looked if looked else 0.0)
    seen = totals["reused_tokens"] + totals["prefilled_tokens"]
    totals["token_reuse_rate"] = (totals["reused_tokens"] / seen
                                  if seen else 0.0)
    return {"engines": engines, "totals": totals}


def speculation_totals(engines: Dict[str, Dict[str, Any]]
                       ) -> Dict[str, Any]:
    """The ONE speculation rollup (counter sums + acceptance rate +
    tokens-per-verify) — shared by the speculation view below and
    util.state's engine filter so a new counter can never make the
    filtered view disagree with the cluster-wide one."""
    totals: Dict[str, Any] = {
        k: sum(int(e.get(k, 0)) for e in engines.values())
        for k in ("spec_proposed", "spec_accepted",
                  "spec_verify_ticks", "spec_emitted_tokens")}
    totals["acceptance_rate"] = (
        totals["spec_accepted"] / totals["spec_proposed"]
        if totals["spec_proposed"] else 0.0)
    totals["tokens_per_verify"] = (
        totals["spec_emitted_tokens"] / totals["spec_verify_ticks"]
        if totals["spec_verify_ticks"] else 0.0)
    totals["engines"] = len(engines)
    return totals


def _speculation(snaps: Snapshots, now: float) -> Dict[str, Any]:
    """The speculative-decoding slice of the kvcache snapshots (engines
    embed their spec counters in the same kv_stats push — ONE report
    channel, so no surface can disagree with the kvcache one). Engines
    that never enabled speculation are filtered out of `engines` but an
    all-zero totals dict is still returned."""
    engines = {k: {
        "engine_id": v.get("engine_id"),
        "speculate_k": v.get("speculate_k", 0),
        "spec_proposed": v.get("spec_proposed", 0),
        "spec_accepted": v.get("spec_accepted", 0),
        "spec_verify_ticks": v.get("spec_verify_ticks", 0),
        "spec_emitted_tokens": v.get("spec_emitted_tokens", 0),
        "acceptance_rate": v.get("acceptance_rate", 0.0),
        "tokens_per_verify": v.get("tokens_per_verify", 0.0),
        "kv_int8": v.get("kv_int8", False),
        "ts": v.get("ts"),
    } for k, v in snaps.items() if v.get("speculate_k")}
    return {"engines": engines, "totals": speculation_totals(engines)}


def _online(comps: Snapshots, now: float) -> Dict[str, Any]:
    """Components grouped by role (sampler / buffer / learner) plus
    cluster totals (rollouts, rollout tokens, buffer occupancy, learner
    ingest, worst sampler staleness)."""
    samplers = _by_role(comps, "sampler")
    buffers = _by_role(comps, "buffer")
    learners = _by_role(comps, "learner")
    totals: Dict[str, Any] = {
        "samplers": len(samplers),
        "rollouts": _sum(samplers, "rollouts"),
        "rollout_tokens": _sum(samplers, "rollout_tokens"),
        "swaps": _sum(samplers, "swap_count"),
        "buffer_occupancy": _sum(buffers, "occupancy"),
        "buffer_capacity": _sum(buffers, "capacity"),
        "buffer_rejected": _sum(buffers, "rejected"),
        "ingested_rollouts": _sum(learners, "ingested_rollouts"),
        "ingested_tokens": _sum(learners, "ingested_tokens"),
        "learner_steps": max((int(l.get("steps", 0))
                              for l in learners.values()),
                             default=0),
        "published_versions": max((int(l.get("published_version", 0))
                                   for l in learners.values()),
                                  default=0),
    }
    stale = [s.get("staleness_versions") for s in samplers.values()
             if s.get("staleness_versions") is not None]
    totals["staleness_versions"] = max(stale) if stale else None
    high = [s.get("max_staleness_versions")
            for s in samplers.values()
            if s.get("max_staleness_versions") is not None]
    totals["max_staleness_versions"] = max(high + stale) \
        if (high or stale) else None
    return {"samplers": samplers, "buffers": buffers,
            "learners": learners, "totals": totals}


def _disagg(comps: Snapshots, now: float) -> Dict[str, Any]:
    """Components grouped by role (prefill / decode / router) plus
    cluster totals (transfers, KV bytes split shm/rpc, adoptions,
    sheds, live queue depth)."""
    prefill = _by_role(comps, "prefill")
    decode = _by_role(comps, "decode")
    routers = _by_role(comps, "router")
    totals: Dict[str, Any] = {
        "prefill_replicas": len(prefill),
        "decode_replicas": len(decode),
        "prefills": _sum(prefill, "prefills"),
        "prefilled_tokens": _sum(prefill, "prefilled_tokens"),
        "reused_tokens": _sum(prefill, "reused_tokens"),
        "published_transfers": _sum(prefill, "published_transfers"),
        "published_bytes": _sum(prefill, "published_bytes"),
        "transfers": _sum(decode, "transfers"),
        "kv_fetched_bytes": _sum(decode, "kv_fetched_bytes"),
        "shm_bytes": _sum(decode, "shm_bytes"),
        "rpc_bytes": _sum(decode, "rpc_bytes"),
        "adopted": _sum(decode, "adopted"),
        "decoded_tokens": _sum(decode, "decoded_tokens"),
        "dispatched": _sum(routers, "dispatched"),
        "shed": _sum(routers, "shed"),
        # live gauge, not a counter: a crashed router's final snapshot
        # (which never expires from the roster) must not contribute
        # phantom queue depth forever. This is the input signal of the
        # SLO autoscaler.
        "queue_depth": _sum(_fresh(routers, now), "pending"),
        "max_queue_depth_seen": max(
            (int(r.get("max_pending", 0))
             for r in routers.values()), default=0),
    }
    return {"prefill": prefill, "decode": decode,
            "routers": routers, "totals": totals}


_KVPLANE_TOTAL_KEYS = (
    "spills", "spill_bytes", "tier2_hits", "tier2_probes",
    "tier2_reused_tokens", "tier2_fetched_bytes",
    "arena_evictions", "tier3_publishes", "tier3_adopts",
    "tier3_adopted_blocks", "tier3_reused_tokens",
    "tier3_fetched_bytes", "directory_hits", "directory_misses",
    "directory_fallbacks")


def _kvplane(comps: Snapshots, now: float) -> Dict[str, Any]:
    """Per-component snapshots + cluster totals. The conductor adds the
    prefix directory's summary, which is its own state."""
    totals = sum_keys(comps, _KVPLANE_TOTAL_KEYS)
    live = _fresh(comps, now)
    totals["arena_entries"] = _sum(live, "entries")
    totals["arena_bytes"] = _sum(live, "bytes")
    probes = totals["tier2_probes"]
    totals["tier2_hit_rate"] = (totals["tier2_hits"] / probes
                                if probes else 0.0)
    looks = totals["directory_hits"] + totals["directory_misses"]
    totals["directory_hit_rate"] = (totals["directory_hits"] / looks
                                    if looks else 0.0)
    return {"components": comps, "totals": totals}


def _gateway(gateways: Snapshots, now: float) -> Dict[str, Any]:
    """Per-replica snapshots plus cluster totals (requests by outcome,
    per-class accept/complete/shed/disconnect split, status-code
    histogram, preemptions)."""
    by_class: Dict[str, Dict[str, int]] = {}
    by_code: Dict[str, int] = {}
    for g in gateways.values():
        for cls, row in (g.get("by_class") or {}).items():
            agg = by_class.setdefault(cls, {})
            for k, v in row.items():
                agg[k] = agg.get(k, 0) + int(v)
        for code, n in (g.get("by_code") or {}).items():
            by_code[code] = by_code.get(code, 0) + int(n)
    totals: Dict[str, Any] = {
        "gateways": len(gateways),
        "by_class": by_class,
        "by_code": by_code,
    }
    totals.update(sum_keys(gateways, (
        "accepted", "completed", "streamed", "tokens_out",
        "rate_limited", "sheds", "disconnects", "errors",
        "preemptions")))
    return {"gateways": gateways, "totals": totals}


def _requesttrace(stores: Snapshots, now: float) -> Dict[str, Any]:
    """Per-store snapshots, cluster totals (completed/kept/dropped,
    outcome tally, replay + preempt counts), the cluster-wide slowest
    list, and a p99-attribution report recomputed over the merged
    per-component summary windows so the tail owner is named from the
    whole population, not one process's slice."""
    totals: Dict[str, Any] = {"stores": len(stores)}
    totals.update(sum_keys(stores, (
        "completed", "kept", "dropped", "replayed_requests",
        "preempted_requests")))
    outcomes: Dict[str, int] = {}
    slowest: List[Dict[str, Any]] = []
    merged_recent: List[Dict[str, Any]] = []
    for s in stores.values():
        for k, v in (s.get("outcomes") or {}).items():
            outcomes[k] = outcomes.get(k, 0) + int(v)
        slowest.extend(s.get("slowest") or [])
        merged_recent.extend(s.get("recent") or [])
    totals["outcomes"] = outcomes
    totals["slowest_ms"] = max(
        [float(s.get("slowest_ms", 0.0)) for s in stores.values()],
        default=0.0)
    slowest.sort(key=lambda r: float(r.get("total_ms") or 0.0),
                 reverse=True)
    from ray_tpu.observability.requests import p99_attribution

    return {"stores": stores, "totals": totals,
            "slowest": slowest[:32],
            "attribution": p99_attribution(merged_recent)}


def _servefault(comps: Snapshots, now: float) -> Dict[str, Any]:
    """Router snapshots (failovers by phase, sheds by cause, corpses
    removed) + healer snapshots (deaths, replacements, breaker) +
    cluster totals."""
    routers = _by_role(comps, "router")
    healers = _by_role(comps, "healer")
    tiers = ("prefill", "decode")

    def _sum_tiered(snaps, key):
        return {t: sum(int((s.get(key) or {}).get(t, 0))
                       for s in snaps.values()) for t in tiers}

    sheds_by_cause: Dict[str, int] = {}
    for r in routers.values():
        for cause, n in (r.get("sheds_by_cause") or {}).items():
            sheds_by_cause[cause] = \
                sheds_by_cause.get(cause, 0) + int(n)
    totals: Dict[str, Any] = {
        "routers": len(routers),
        "healers": len(healers),
        "failovers": _sum_tiered(routers, "failovers"),
        "failovers_total": sum(
            sum((r.get("failovers") or {}).values())
            for r in routers.values()),
        "failover_requests": _sum(routers, "failover_requests"),
        "sheds_by_cause": sheds_by_cause,
        "removed_dead": _sum_tiered(routers, "removed_dead"),
        "deaths": _sum_tiered(healers, "deaths"),
        "replacements": _sum_tiered(healers, "replacements"),
        "replacements_total": sum(
            sum((h.get("replacements") or {}).values())
            for h in healers.values()),
        "replacements_blocked": _sum(healers, "replacements_blocked"),
        "breaker_trips": _sum(healers, "breaker_trips"),
        "drains_reaped": _sum(healers, "drains_reaped"),
    }
    return {"routers": routers, "healers": healers, "totals": totals}


def _lora(comps: Snapshots, now: float) -> Dict[str, Any]:
    """Pool snapshots (paging counters + residents), router tenant
    counters, plus cluster totals (acquires/hits/misses/evictions/
    swaps/page-in bytes, per-tenant request rollup)."""
    pools = _by_role(comps, "pool")
    routers = _by_role(comps, "router")
    tenants: Dict[str, Dict[str, Any]] = {}
    for snaps, keys in ((pools, ("hits", "misses", "evictions", "swaps")),
                        (routers, ("dispatched", "completed", "shed",
                                   "slo_misses"))):
        for snap in snaps.values():
            for t, ts in (snap.get("tenants") or {}).items():
                agg = tenants.setdefault(
                    t, {"hits": 0, "misses": 0, "evictions": 0,
                        "swaps": 0, "dispatched": 0, "completed": 0,
                        "shed": 0, "slo_misses": 0})
                for key in keys:
                    agg[key] += int(ts.get(key, 0))
    acquires = _sum(pools, "acquires")
    hits = _sum(pools, "hits")
    totals: Dict[str, Any] = {
        "pools": len(pools),
        "routers": len(routers),
        "slots": _sum(pools, "slots"),
        "resident": _sum(pools, "resident"),
        "pinned": _sum(pools, "pinned"),
        "acquires": acquires,
        "hits": hits,
        "misses": _sum(pools, "misses"),
        "evictions": _sum(pools, "evictions"),
        "swaps": _sum(pools, "swaps"),
        "page_in_bytes": _sum(pools, "page_in_bytes"),
        "hit_rate": hits / acquires if acquires else 0.0,
        "tenants": len(tenants),
    }
    return {"pools": pools, "routers": routers,
            "tenants": tenants, "totals": totals}


def _autoscale(loops: Snapshots, now: float) -> Dict[str, Any]:
    """Per-loop status snapshots plus cluster totals (decisions by
    direction, drains, replica-seconds per tier, current targets)."""
    totals: Dict[str, Any] = {
        "autoscalers": len(loops),
        "scale_ups": sum(sum(s.get("scale_ups", {}).values())
                         for s in loops.values()),
        "scale_downs": sum(sum(s.get("scale_downs", {}).values())
                           for s in loops.values()),
        "drains_completed": _sum(loops, "drains_completed"),
        "drains_forced": _sum(loops, "drains_forced"),
        "replica_seconds": {
            tier: round(sum(
                float(s.get("replica_seconds", {}).get(tier, 0.0))
                for s in loops.values()), 3)
            for tier in ("prefill", "decode")},
        "active_replicas": {
            tier: sum(int(s.get(f"{tier}_active", 0))
                      for s in loops.values())
            for tier in ("prefill", "decode")},
    }
    return {"autoscalers": loops, "totals": totals}


# observability.roofline reports under two kinds of component id, and
# the row's cap is the sum of what each kind keeps
ORACLE_PREDICTION = "prediction/"   # + layout: the newest a layout
ORACLE_VALIDATION = "validation/"   # + a serial: a log of records
_ORACLE_PREDICTIONS_KEPT = 256
_ORACLE_VALIDATIONS_KEPT = 1024


def _oracle(comps: Snapshots, now: float) -> Dict[str, Any]:
    """The latest prediction per layout, the validation tail, and
    totals (counts + the last fitted calibration and its worst phase
    residual)."""
    def records(kind):
        return {k[len(kind):]: {f: v for f, v in rec.items()
                                if f != "component_id"}
                for k, rec in comps.items() if k.startswith(kind)}

    preds = records(ORACLE_PREDICTION)
    logged = list(records(ORACLE_VALIDATION).values())  # as they came
    vals = logged[-100:]
    last = vals[-1] if vals else {}
    residuals = last.get("residuals") or {}
    totals: Dict[str, Any] = {
        "layouts": len(preds),
        "validations": len(logged),
        "last_calibration": last.get("calibration"),
        "worst_residual_ratio": max(
            (float(r) for r in residuals.values()), default=None,
            key=lambda r: abs(r - 1.0)),
    }
    return {"predictions": preds, "validations": vals,
            "totals": totals}


# ------------------------------------------------------------- the table

def _component(worker_id: str, component_id: str) -> str:
    return component_id


@dataclass(frozen=True)
class Subsystem:
    """One row: what a subsystem keeps and how it reads."""
    aggregate: Callable[[Snapshots, float], Dict[str, Any]]
    stats_kept: int = 256       # snapshots; at the cap the oldest goes
    events_kept: int = 10_000   # the ring of instant markers
    # the name a snapshot's component id is stamped under, and the key
    # it is kept under
    id_field: str = "component_id"
    key: Callable[[str, str], str] = _component
    # a second VIEW over another row's snapshots and events (it keeps
    # none of its own), and which of that row's events are its own
    view_of: Optional[str] = None
    own_event: Optional[Callable[[Dict[str, Any]], bool]] = None


SUBSYSTEMS: Dict[str, Subsystem] = {
    # serving engines' prefix caches (models/engine.py); two engines of
    # two workers may share an engine id, so the worker is in the key
    "kvcache": Subsystem(
        _kvcache, id_field="engine_id",
        key=lambda worker_id, engine_id:
            f"{str(worker_id)[:12]}:{engine_id}"),
    "speculation": Subsystem(
        _speculation, view_of="kvcache",
        own_event=lambda e: str(e.get("kind", "")).startswith("spec_")),
    # samplers, the rollout buffer and the learner (ray_tpu.online);
    # learner snapshots are keyed by unique run ids, hence the cap
    "online": Subsystem(_online),
    # prefill/decode servers and routers (serve/disagg.py)
    "disagg": Subsystem(_disagg),
    # host arenas, tier-3 adoption and directory routing
    # (serve/kvplane.py)
    "kvplane": Subsystem(_kvplane),
    # gateway replicas and the QoS gate (serve/gateway.py, serve/qos.py)
    "gateway": Subsystem(_gateway, stats_kept=64),
    # the per-request flight recorder (observability/requests.py): a
    # KEPT trace rides the ring as a kind="trace" event, a remote tier
    # hop as a kind="phase" event under the same request id
    "requesttrace": Subsystem(_requesttrace, stats_kept=64),
    # routers' failover accounting and the self-healers' counters; the
    # markers are recovery events and live in the conductor's
    # resilience log, so this row keeps no ring
    "servefault": Subsystem(_servefault, stats_kept=128, events_kept=0),
    # adapter pools and routers' tenant counters (serve/lora.py)
    "lora": Subsystem(_lora),
    # the serving autoscaler's policy loops (serve/autoscale.py)
    "autoscale": Subsystem(_autoscale, stats_kept=64,
                           id_field="autoscaler_id"),
    # the step-time oracle (observability.roofline)
    "oracle": Subsystem(
        _oracle,
        stats_kept=_ORACLE_PREDICTIONS_KEPT + _ORACLE_VALIDATIONS_KEPT),
}


# ------------------------------------------------------------- the store

class TelemetryStore:
    """Per subsystem a bounded dict of the newest snapshot a component
    and a bounded ring of events. What arrives comes from outside the
    process: a payload that is no dict, and a subsystem that has no row
    to keep it in, are dropped."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        own = [name for name, row in SUBSYSTEMS.items()
               if row.view_of is None]
        self._snapshots: Dict[str, Snapshots] = {n: {} for n in own}
        self._events: Dict[str, List[Dict[str, Any]]] = {
            n: [] for n in own}

    def report_stats(self, subsystem: str, worker_id: str,
                     component_id: str, stats: Dict[str, Any]) -> None:
        kept = self._snapshots.get(subsystem)
        if kept is None or not isinstance(stats, dict):
            return
        row = SUBSYSTEMS[subsystem]
        component_id = str(component_id)
        snapshot = dict(stats, worker_id=worker_id, ts=time.time())
        snapshot[row.id_field] = component_id
        with self._lock:
            kept[row.key(worker_id, component_id)] = snapshot
            while len(kept) > row.stats_kept:
                del kept[min(kept, key=lambda k: kept[k].get("ts", 0.0))]

    def report_event(self, subsystem: str, event: Dict[str, Any]) -> None:
        ring = self._events.get(subsystem)
        if ring is None or not isinstance(event, dict):
            return
        kept = SUBSYSTEMS[subsystem].events_kept
        event = dict(event)
        event.setdefault("ts", time.time())
        with self._lock:
            ring.append(event)
            if len(ring) > kept:
                del ring[:len(ring) - kept]

    def events(self, subsystem: str,
               limit: int = 10_000) -> List[Dict[str, Any]]:
        row = _row(subsystem)
        with self._lock:
            if row.view_of is None:
                return self._events[subsystem][-limit:]
            ring = list(self._events[row.view_of])
        return [e for e in ring if row.own_event(e)][-limit:]

    def snapshots(self, subsystem: str) -> Snapshots:
        """A copy of what the row's aggregate reads."""
        row = _row(subsystem)
        with self._lock:
            return {k: dict(v) for k, v in self._snapshots[
                row.view_of or subsystem].items()}

    def status(self, subsystem: str) -> Dict[str, Any]:
        return _row(subsystem).aggregate(self.snapshots(subsystem),
                                         time.time())


def _row(subsystem: str) -> Subsystem:
    row = SUBSYSTEMS.get(subsystem)
    if row is None:
        raise ValueError(f"no telemetry subsystem {subsystem!r} "
                         f"(have {sorted(SUBSYSTEMS)})")
    return row
