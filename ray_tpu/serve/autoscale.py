"""SLO-driven autoscaler for (disaggregated) serving: close the
control loop.

Every input the loop needs already exists — per-replica TTFT telemetry
(PR 3/6), bounded-queue admission with shed counters and live queue
depths (PR 9), and the preemption grace/drain flow (PR 4) — this module
adds the POLICY that turns them into replica counts. The Gemma-on-TPU
serving envelope (PAPERS.md: arXiv 2605.25645) frames what "enough
replicas" means; the TPU concurrency-limits roofline (arXiv 2011.03641)
is why prefill and decode saturate on DIFFERENT signals and must scale
independently:

- **prefill** is compute-bound burst work: its saturation shows up as
  queueing delay ahead of the first token — recent p99 TTFT against the
  target SLO — discounted by the prefix-cache hit rate (a hit-heavy
  window prefills only suffixes and needs fewer prefill chips).
- **decode** is memory-bound steady work: its saturation is free-slot
  exhaustion — when the tier's decode slots run out, admission control
  starts queueing and then shedding, long before prefill notices.

Pieces (each independently testable, no cluster required):

- ``SlidingWindow``: trailing-window samples -> recent p50/p99 summary
  (the shared ``step_timer.percentile``), so the policy reads *recent*
  percentiles, not lifetime-cumulative ones that lag load shifts.
- ``ScalingPolicy``: the hysteresis + cooldown core — desired-vs-current
  persistence gates (scale up only after the pressure held for
  ``up_delay_s``, down after ``down_delay_s``, nothing within
  ``cooldown_s`` of the last change) — shared by the disagg loop AND the
  generic Serve controller's reconcile tick (serve/controller.py), so
  one engine owns "don't flap" everywhere.
- ``DisaggPolicy``: maps a signals snapshot to desired per-tier counts.
- ``DisaggAutoscaler``: the loop. Scale-up builds a replica via the
  tier's factory and registers it with the router — new replicas admit
  immediately. Scale-down REUSES the graceful-drain flow: the router
  stops dispatching to the victim (``begin_drain``) while its in-flight
  requests finish and its KV transfers are acked, then
  ``prepare_for_shutdown`` (the replica-side grace drain, the same
  shape as serve/replica.py and the preemption grace window) runs
  before the actor dies — an in-flight request is NEVER dropped by a
  scale-down.

The loop also owns **tier self-healing** (the serving-plane complement
of PR 4's gang supervision): once started it subscribes to the
conductor's actor-death pubsub for its managed replicas. A death is NOT
load — it bypasses the hysteresis/cooldown machinery entirely: the
corpse is removed from the router immediately (distinct from a drain —
no grace, its in-flight requests already failed over at the router) and
a replacement is spawned through the tier's ``TierSpec.factory``. A
per-host circuit breaker (the existing
``resilience.domains.FailureDomainTracker``, threshold
``RAY_TPU_SERVE_BREAKER_THRESHOLD`` deaths decaying over
``RAY_TPU_SERVE_BREAKER_WINDOW_S``) stops replacing replicas that die
repeatedly on the same host — replacing into a bad host only
manufactures failures — and a replica that dies MID-DRAIN is reaped
and its drain record finalized instead of leaking a ``draining`` entry
forever. ``replace`` / ``breaker_trip`` markers land in the merged
timeline's resilience lane beside the router's ``failover`` markers,
and per-tier ``replacements_total`` counters feed the servefault
surface.

Surfaces (the full treatment): ``util.state.autoscaler_status()``,
``ray_tpu autoscale`` CLI, dashboard ``/api/autoscale`` + SPA tab, lazy
Prometheus (``ray_tpu_autoscale_target_replicas{tier}``,
``ray_tpu_autoscale_decisions_total{tier,direction}``,
``ray_tpu_autoscale_replica_seconds_total{tier}``), and scale_up /
scale_down / drain instant markers in the merged timeline (drains are
mirrored into the resilience lane — they ARE the grace flow).

Knobs (env, all overridable per-instance): RAY_TPU_AUTOSCALE_TARGET_P99_MS
(the SLO), RAY_TPU_AUTOSCALE_UP_DELAY_S / _DOWN_DELAY_S / _COOLDOWN_S
(hysteresis), RAY_TPU_AUTOSCALE_INTERVAL_S (tick), _DRAIN_GRACE_S (the
drain window), _WINDOW_S (signal recency). The zero-dropped drain is
held by ``tests/test_autoscale.py::test_scale_down_drains_zero_dropped_inflight``.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.observability.step_timer import percentile
from ray_tpu.util.telemetry import Pusher, emit

_SEQ = itertools.count()

TIERS = ("prefill", "decode")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def default_target_p99_ms() -> float:
    """The serving SLO the loop closes on (recent p99 TTFT, ms)."""
    return _env_float("RAY_TPU_AUTOSCALE_TARGET_P99_MS", 1500.0)


# ----------------------------------------------------- prometheus (lazy)

_metrics: Optional[Dict[str, Any]] = None
_metrics_lock = threading.Lock()


def autoscale_metrics() -> Dict[str, Any]:
    global _metrics
    m = _metrics
    if m is not None:
        return m
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            _metrics = dict(
                target=Gauge(
                    "ray_tpu_autoscale_target_replicas",
                    "replica count the autoscaler is currently driving "
                    "a tier toward",
                    tag_keys=("tier",)),
                decisions=Counter(
                    "ray_tpu_autoscale_decisions_total",
                    "scale decisions taken (direction=up|down)",
                    tag_keys=("tier", "direction")),
                replica_seconds=Counter(
                    "ray_tpu_autoscale_replica_seconds_total",
                    "cumulative live replica-seconds per tier (the "
                    "provisioning cost the policy is minimizing)",
                    tag_keys=("tier",)))
    return _metrics


# --------------------------------------------------------- sliding window

class SlidingWindow:
    """Trailing-window scalar samples -> recent summary.

    The policy (and `serve status` / router stats) must read RECENT
    percentiles: a lifetime-cumulative histogram still remembers the
    morning's quiet hours at the evening peak. Samples older than
    ``window_s`` age out; ``max_samples`` bounds memory under a flood.
    Percentiles come from the shared ``step_timer.percentile`` so every
    recent-p99 in the system is the same derivation."""

    def __init__(self, window_s: Optional[float] = None,
                 max_samples: int = 2048):
        if window_s is None:
            window_s = _env_float("RAY_TPU_AUTOSCALE_WINDOW_S", 30.0)
        self.window_s = float(window_s)
        self.max_samples = int(max_samples)
        self._lock = threading.Lock()
        self._samples: List[Tuple[float, float]] = []  # (ts, value)

    def add(self, value: float, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        with self._lock:
            self._samples.append((now, float(value)))
            if len(self._samples) > self.max_samples:
                del self._samples[:len(self._samples) - self.max_samples]

    def _values(self, now: Optional[float]) -> List[float]:
        now = time.monotonic() if now is None else now
        horizon = now - self.window_s
        with self._lock:
            # prune in place so a long-lived idle window frees its tail
            i = 0
            while i < len(self._samples) and self._samples[i][0] < horizon:
                i += 1
            if i:
                del self._samples[:i]
            return [v for _, v in self._samples]

    def summary(self, now: Optional[float] = None) -> Dict[str, Any]:
        """{"n", "mean", "p50", "p99", "last"} over the live window
        ({"n": 0} when empty — callers treat missing signals as
        no-evidence, never as zero)."""
        vals = self._values(now)
        if not vals:
            return {"n": 0}
        ordered = sorted(vals)
        return {"n": len(vals),
                "mean": sum(vals) / len(vals),
                "p50": percentile(ordered, 0.5),
                "p99": percentile(ordered, 0.99),
                "last": vals[-1]}


# --------------------------------------------------------- policy engine

class ScalingPolicy:
    """Hysteresis + cooldown around a desired-replicas signal.

    Semantics (lifted from serve/controller.py's reconcile tick, now THE
    shared engine): the clock toward scaling up runs only while
    desired > current — any tick at-or-below resets it (and vice versa
    for down) — so a transient burst never scales and an oscillating
    signal never flaps; ``cooldown_s`` additionally freezes the tier
    after any change so back-to-back moves can't chase noise."""

    def __init__(self, min_replicas: int = 1, max_replicas: int = 4,
                 up_delay_s: Optional[float] = None,
                 down_delay_s: Optional[float] = None,
                 cooldown_s: Optional[float] = None):
        if up_delay_s is None:
            up_delay_s = _env_float("RAY_TPU_AUTOSCALE_UP_DELAY_S", 2.0)
        if down_delay_s is None:
            down_delay_s = _env_float("RAY_TPU_AUTOSCALE_DOWN_DELAY_S",
                                      10.0)
        if cooldown_s is None:
            cooldown_s = _env_float("RAY_TPU_AUTOSCALE_COOLDOWN_S", 5.0)
        if max_replicas < max(1, min_replicas):
            raise ValueError(
                f"invalid replica bounds [{min_replicas}, {max_replicas}]")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.up_delay_s = float(up_delay_s)
        self.down_delay_s = float(down_delay_s)
        self.cooldown_s = float(cooldown_s)
        # last instant the tier was NOT under up/down pressure — the
        # persistence gate measures from here (None until the first
        # decide() so injected clocks and the real one never mix)
        self._calm_up: Optional[float] = None
        self._calm_down: Optional[float] = None
        self._last_change: Optional[float] = None

    def clamp(self, n: int) -> int:
        return min(max(int(n), self.min_replicas), self.max_replicas)

    def decide(self, desired: int, current: int,
               now: Optional[float] = None) -> int:
        """The new target (== current when the gates hold it back)."""
        now = time.monotonic() if now is None else now
        desired = self.clamp(desired)
        if self._calm_up is None:
            self._calm_up = self._calm_down = now
        if desired <= current:
            self._calm_up = now       # not under scale-up pressure
        if desired >= current:
            self._calm_down = now     # not over-provisioned
        in_cooldown = (self._last_change is not None
                       and now - self._last_change < self.cooldown_s)
        if desired > current and not in_cooldown \
                and now - self._calm_up >= self.up_delay_s:
            self._last_change = now
            self._calm_up = self._calm_down = now
            return desired
        if desired < current and not in_cooldown \
                and now - self._calm_down >= self.down_delay_s:
            self._last_change = now
            self._calm_up = self._calm_down = now
            return desired
        return current


class DisaggPolicy:
    """Signals -> desired replica counts, one tier at a time.

    The signals snapshot (``DisaggRouter.signals()`` + per-tick
    free-slot probes; every key optional — missing evidence never
    scales):

    - ``ttft_p99_ms``: recent p99 TTFT (router sliding window). Under
      disaggregation TTFT ends when prefill returns the first token, so
      this IS the prefill queueing-delay signal.
    - ``cache_hit_rate``: recent fraction of prefills served fully or
      partially from the prefix cache. A hit-heavy window prefills only
      suffixes — scale-down of the prefill tier is gated on it (or on
      the tier being outright idle).
    - ``prefill_inflight_p99``: recent concurrent prefills — the
      does-it-fit-in-one-fewer check for prefill scale-down.
    - ``decode_free_p50`` / ``decode_busy_p99``: recent free and busy
      decode slots across the tier; ``decode_cap_per_replica`` sizes
      what one fewer replica could still hold.
    - ``queue_depth_p99``: recent router pending — backlog past the
      decode tier's capacity also reads as slot exhaustion (sheds live
      at that same bound).
    - ``spec_tokens_per_verify``: measured speculative-decoding
      acceptance factor (mean tokens emitted per verify step across the
      decode tier, from the engines' speculation_stats). A tier whose
      engines emit ~f tokens per step drains a BACKLOG f× faster, so
      queued demand is discounted by it before the policy sizes the
      tier — busy slots are not (speculation shortens a stream, it
      does not free the slot it occupies). Absent (or <= 1) means no
      discount: behavior is bit-identical to a non-speculative tier.
    """

    # scale down only when the recent p99 fits inside one-fewer replicas
    # at this utilization — the headroom that makes drain safe
    low_util = 0.7
    # prefill scale-down additionally wants the SLO comfortably met
    down_ratio = 0.5
    # ...and a hit-heavy cache (or an idle tier): hit windows need fewer
    # prefill chips even at the same request rate
    hit_floor = 0.5

    def __init__(self, target_p99_ms: Optional[float] = None,
                 prefill_policy: Optional[ScalingPolicy] = None,
                 decode_policy: Optional[ScalingPolicy] = None):
        self.target_p99_ms = (default_target_p99_ms()
                              if target_p99_ms is None
                              else float(target_p99_ms))
        self.policies = {"prefill": prefill_policy or ScalingPolicy(),
                         "decode": decode_policy or ScalingPolicy()}

    # -- desired (pure; no hysteresis — ScalingPolicy applies that) ------

    def desired_decode(self, signals: Dict[str, Any],
                       current: int) -> Tuple[int, str]:
        free_p50 = signals.get("decode_free_p50")
        busy_p99 = signals.get("decode_busy_p99")
        depth_p99 = signals.get("queue_depth_p99")
        cap = max(1, int(signals.get("decode_cap_per_replica", 1)))
        capacity = current * cap
        # speculation-aware demand: f tokens emitted per verify step
        # means each slot drains its queued successor f× sooner, so a
        # backlog of N requests is N/f slot-windows of work. Only the
        # QUEUE is discounted — an occupied slot is occupied whatever
        # its token rate. f <= 1 (or no signal) leaves every number
        # untouched, so a non-speculative tier is bit-identical.
        spec = signals.get("spec_tokens_per_verify")
        factor = max(1.0, float(spec or 0.0))
        eff_depth = (depth_p99 / factor
                     if depth_p99 is not None else None)
        if eff_depth is not None and eff_depth > capacity:
            # PROPORTIONAL scale step for deep backlogs (the PR-11
            # follow-on): ±1 per decision chases a burst one cooldown
            # at a time — when the backlog exceeds 2x one replica's
            # capacity, jump straight to the replica count that holds
            # it (ceil(backlog / capacity_per_replica); TierSpec
            # bounds clamp at apply time, hysteresis still gates)
            desired = current + 1
            if eff_depth > 2 * cap:
                desired = max(desired, -(-int(eff_depth) // cap))
            return desired, (
                f"backlog p99 {depth_p99:.0f}"
                + (f" (/{factor:.2f} speculation -> {eff_depth:.0f})"
                   if factor > 1.0 else "")
                + f" past tier capacity {capacity}"
                + (f" (proportional step -> {desired})"
                   if desired > current + 1 else ""))
        if free_p50 is not None and free_p50 <= 0:
            return current + 1, "decode slots exhausted (free p50 = 0)"
        # slot DEMAND, not just engine-busy slots: a slow client drains
        # its stream long after the engine slot freed, but it still
        # occupies the router's admission bound — the thing a removed
        # replica would shrink. Take the worse of the two recent views.
        # current > 0 (not > 1): at current == 1 the condition reduces
        # to demand == 0, i.e. a truly idle tier may drain to ZERO —
        # the ScalingPolicy's min_replicas floor (1 everywhere except
        # an explicit scale-to-zero tier) clamps it back otherwise.
        demand = max((v for v in (busy_p99, eff_depth)
                      if v is not None), default=None)
        if current > 0 and demand is not None \
                and demand <= self.low_util * (current - 1) * cap:
            return current - 1, (
                f"slot demand p99 {demand:.1f} fits in {current - 1} "
                f"replica(s) at {self.low_util:.0%} utilization")
        return current, "steady"

    def desired_prefill(self, signals: Dict[str, Any],
                        current: int) -> Tuple[int, str]:
        ttft_p99 = signals.get("ttft_p99_ms")
        hit_rate = signals.get("cache_hit_rate")
        inflight_p99 = signals.get("prefill_inflight_p99")
        if current > 0 and ttft_p99 is None and inflight_p99 is None:
            # missing evidence never scales UP — but for a tier above
            # its floor, a request window with no samples at all IS the
            # evidence: nothing has needed prefill for a whole window
            # (current > 0 so a scale-to-zero tier drains its last
            # replica on the same evidence; min_replicas clamps
            # everyone else at 1)
            return current - 1, "tier idle (no requests in the window)"
        if ttft_p99 is not None and ttft_p99 > self.target_p99_ms:
            return current + 1, (
                f"TTFT p99 {ttft_p99:.0f}ms over target "
                f"{self.target_p99_ms:.0f}ms (queueing delay)")
        if current > 1 and ttft_p99 is not None \
                and ttft_p99 < self.down_ratio * self.target_p99_ms:
            hit_heavy = hit_rate is not None and hit_rate >= self.hit_floor
            idle = inflight_p99 is not None and \
                inflight_p99 <= self.low_util * (current - 1)
            # a hit-heavy window needs fewer prefill chips; an idle tier
            # trivially does — either way the SLO is comfortably met
            if hit_heavy or idle:
                why = (f"hit rate {hit_rate:.0%} — suffix-only prefills"
                       if hit_heavy else
                       f"inflight p99 {inflight_p99:.1f} fits in "
                       f"{current - 1}")
                return current - 1, (
                    f"TTFT p99 {ttft_p99:.0f}ms well under target; {why}")
        return current, "steady"

    def decide(self, signals: Dict[str, Any], current: Dict[str, int],
               now: Optional[float] = None
               ) -> Dict[str, Tuple[int, str]]:
        """{tier: (target, reason)} after hysteresis; target == current
        means hold."""
        out: Dict[str, Tuple[int, str]] = {}
        for tier, fn in (("prefill", self.desired_prefill),
                         ("decode", self.desired_decode)):
            cur = int(current[tier])
            desired, reason = fn(signals, cur)
            target = self.policies[tier].decide(desired, cur, now)
            out[tier] = (target, reason if target != cur else "hold")
        return out


# ----------------------------------------------------------- the loop

def _worker():
    from ray_tpu._private import worker as worker_mod

    return worker_mod.global_worker


def _notify_resilience(event: Dict[str, Any]) -> None:
    """Drains ride the resilience grace flow — mirror them into its
    event log/counters too (the PR-4 lane preemptions already use)."""
    w = _worker()
    if w is None:
        return
    try:
        w.conductor.notify("report_resilience_event", dict(event))
    except Exception:  # noqa: BLE001 — cluster shutting down
        pass


class TierSpec:
    """How one tier scales: bounds plus the factory that builds a fresh
    replica (in-process object or actor handle — the router accepts
    either; the autoscaler tears actors down with kill after the grace
    drain)."""

    def __init__(self, factory: Callable[[], Any], *,
                 min_replicas: int = 1, max_replicas: int = 4,
                 up_delay_s: Optional[float] = None,
                 down_delay_s: Optional[float] = None,
                 cooldown_s: Optional[float] = None):
        self.factory = factory
        self.policy = ScalingPolicy(min_replicas, max_replicas,
                                    up_delay_s, down_delay_s, cooldown_s)


class _Draining:
    __slots__ = ("tier", "rid", "since", "grace_deadline")

    def __init__(self, tier: str, rid: str, since: float, grace_s: float):
        self.tier = tier
        self.rid = rid
        self.since = since
        self.grace_deadline = since + grace_s


class DisaggAutoscaler:
    """Drives a ``DisaggRouter``'s prefill/decode replica sets toward
    the TTFT SLO. One ``tick()`` = read signals, decide, apply; the
    background thread just calls tick on ``interval_s``. Fully
    synchronous and injectable (``now`` flows through) so tests replay
    load shapes without sleeping."""

    def __init__(self, router: Any, *,
                 prefill: TierSpec, decode: TierSpec,
                 policy: Optional[DisaggPolicy] = None,
                 interval_s: Optional[float] = None,
                 drain_grace_s: Optional[float] = None,
                 autoscaler_id: Optional[str] = None):
        if not router.tier_replicas("prefill") \
                or not router.tier_replicas("decode"):
            raise ValueError("the autoscaler drives disagg routers "
                             "(a prefill AND a decode tier); colocated "
                             "deployments autoscale via the Serve "
                             "controller's AutoscalingConfig")
        self.router = router
        self.specs = {"prefill": prefill, "decode": decode}
        self.policy = policy or DisaggPolicy(
            prefill_policy=prefill.policy, decode_policy=decode.policy)
        self.interval_s = (interval_s if interval_s is not None else
                           _env_float("RAY_TPU_AUTOSCALE_INTERVAL_S", 1.0))
        self.drain_grace_s = (
            drain_grace_s if drain_grace_s is not None else
            _env_float("RAY_TPU_AUTOSCALE_DRAIN_GRACE_S", 30.0))
        self.autoscaler_id = autoscaler_id or \
            f"autoscale-{os.getpid()}-{next(_SEQ)}"
        self._free_win = SlidingWindow()
        self._busy_win = SlidingWindow()
        self._lock = threading.Lock()
        self._draining: List[_Draining] = []
        self._stats: Dict[str, Any] = {
            "scale_ups": {t: 0 for t in TIERS},
            "scale_downs": {t: 0 for t in TIERS},
            "drains_completed": 0,
            "drains_forced": 0,
            "drains_reaped": 0,
            "replica_seconds": {t: 0.0 for t in TIERS},
            "last_reason": {t: "" for t in TIERS},
            "deaths": {t: 0 for t in TIERS},
            "replacements": {t: 0 for t in TIERS},
            "replacements_blocked": 0,
            "breaker_trips": 0,
            "wakeups": {t: 0 for t in TIERS},
        }
        # scale-to-zero (min_replicas=0 on a TierSpec): an idle tier
        # drains to ZERO replicas, and the router calls the waker on
        # the first arrival — an immediate factory scale-up OUTSIDE
        # hysteresis (absence is not load), single-flight per tier
        self._waking: Dict[str, bool] = {t: False for t in TIERS}
        if any(self.specs[t].policy.min_replicas == 0 for t in TIERS):
            router.set_tier_waker(self._wake_tier)
        # the replacement circuit breaker: the existing failure-domain
        # tracker keyed by the replicas' HOST (machine id) — a host
        # whose replicas die repeatedly trips the latch and stops
        # getting replacements until the decayed score releases it
        from ray_tpu.resilience.domains import FailureDomainTracker

        self._breaker = FailureDomainTracker(
            threshold=_env_float("RAY_TPU_SERVE_BREAKER_THRESHOLD", 3.0),
            half_life_s=_env_float("RAY_TPU_SERVE_BREAKER_WINDOW_S",
                                   60.0))
        self._watching = False
        # actor_id -> (tier, {"rid", "machine"}) for every ACTOR
        # replica under management. Kept eagerly (watch/tick/add): by
        # the time a death event arrives, the router's failover wrapper
        # may already have removed the corpse from the replica set, so
        # the death must resolve against what we KNEW, not what's left.
        self._managed: Dict[str, Tuple[str, Dict[str, Any]]] = {}
        self._heals: List[threading.Thread] = []
        self._last_tick: Optional[float] = None
        self._pusher = Pusher("autoscale", self.autoscaler_id)
        self._sf_pusher = Pusher("servefault", self.autoscaler_id)
        self._teardowns: List[threading.Thread] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        autoscale_metrics()  # lazy registration before the first event

    # ------------------------------------------------------------ signals

    def probe_signals(self, now: Optional[float] = None) -> Dict[str, Any]:
        """Router windows + a live free-slot probe of the active decode
        replicas (folded into this loop's own sliding windows so one
        slow probe doesn't blind the policy)."""
        from .disagg import _call

        sig = self.router.signals()
        reps = [r for r in self.router.tier_replicas("decode")
                if not r["draining"]]
        free = cap = 0
        ok = False
        # issue every probe BEFORE resolving any (the _admit_or_shed
        # pattern): N actor replicas answer concurrently instead of
        # serializing N round-trips into every control-loop tick
        probes = []
        for r in reps:
            try:
                # read-only probe, supervised by the except below
                probes.append((r, _call(r["target"], "free_slots",  # shardlint: disable=unsupervised-actor-call
                                        block=False)))
            except Exception:  # noqa: BLE001 — replica mid-restart
                pass
        for r, v in probes:
            try:
                from ray_tpu._private.object_store import ObjectRef

                if isinstance(v, ObjectRef):
                    import ray_tpu

                    v = ray_tpu.get(v)
                free += int(v)
                cap += int(r["cap"])
                ok = True
            except Exception:  # noqa: BLE001 — replica mid-restart
                pass
        if ok:
            self._free_win.add(free, now)
            self._busy_win.add(cap - free, now)
        free_sum = self._free_win.summary(now)
        busy_sum = self._busy_win.summary(now)
        if free_sum["n"]:
            sig["decode_free_p50"] = free_sum["p50"]
            sig["decode_busy_p99"] = busy_sum["p99"]
        if reps:
            sig["decode_cap_per_replica"] = max(
                1, int(sum(r["cap"] for r in reps) / len(reps)))
        # measured speculation acceptance factor: best-effort stats
        # probe of the same live replicas; replicas without speculation
        # (or test doubles without a stats surface) simply contribute
        # nothing and the policy sees no discount
        stat_probes = []
        for r in reps:
            try:
                stat_probes.append(_call(r["target"], "stats",  # shardlint: disable=unsupervised-actor-call
                                         block=False))
            except Exception:  # noqa: BLE001 — replica mid-restart
                pass
        tpv: List[float] = []
        for v in stat_probes:
            try:
                from ray_tpu._private.object_store import ObjectRef

                if isinstance(v, ObjectRef):
                    import ray_tpu

                    v = ray_tpu.get(v)
                sp = (v or {}).get("speculation") or {}
                if int(sp.get("spec_verify_ticks", 0)) > 0:
                    tpv.append(float(sp.get("tokens_per_verify", 0.0)))
            except Exception:  # noqa: BLE001 — replica mid-restart
                pass
        if tpv:
            sig["spec_tokens_per_verify"] = sum(tpv) / len(tpv)
        return sig

    # --------------------------------------------------------------- tick

    def tick(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        """One control-loop pass; returns the actions taken."""
        now = time.monotonic() if now is None else now
        actions: List[Dict[str, Any]] = []
        self._account_replica_seconds(now)
        self._advance_drains(now, actions)
        signals = self.probe_signals(now)
        current = {t: self._active_count(t) for t in TIERS}
        decisions = self.policy.decide(signals, current, now)
        m = autoscale_metrics()
        for tier in TIERS:
            target, reason = decisions[tier]
            # the TierSpec bounds are the authoritative capacity limits
            # — a caller-supplied policy (its own clamps, or a test
            # stand-in) must not scale past what the tier may hold
            target = self.specs[tier].policy.clamp(target)
            with self._lock:  # _stats is shared with the wake/death threads
                self._stats["last_reason"][tier] = reason
            m["target"].set(target, tags={"tier": tier})
            if target > current[tier]:
                actions.extend(self._scale_up(
                    tier, target - current[tier], target, reason))
            elif target < current[tier]:
                actions.extend(self._scale_down(
                    tier, current[tier] - target, target, reason, now))
        self.publish_telemetry(force=bool(actions))
        return actions

    def _active_count(self, tier: str) -> int:
        return sum(1 for r in self.router.tier_replicas(tier)
                   if not r["draining"])

    def _account_replica_seconds(self, now: float) -> None:
        if self._last_tick is not None:
            dt = max(0.0, now - self._last_tick)
            m = autoscale_metrics()
            for tier in TIERS:
                live = len(self.router.tier_replicas(tier))
                with self._lock:
                    self._stats["replica_seconds"][tier] += live * dt
                if live:
                    m["replica_seconds"].inc(live * dt,
                                             tags={"tier": tier})
        self._last_tick = now

    # ----------------------------------------------------------- scale up

    def _scale_up(self, tier: str, n: int, target: int,
                  reason: str) -> List[Dict[str, Any]]:
        actions = []
        for _ in range(n):
            try:
                replica = self.specs[tier].factory()
            except Exception as e:  # noqa: BLE001 — no capacity yet:
                # hold the target; the next tick retries
                with self._lock:
                    self._stats["last_reason"][tier] = (
                        f"scale-up blocked: {type(e).__name__}: {e}")
                break
            rid = (self.router.add_prefill(replica) if tier == "prefill"
                   else self.router.add_decode(replica))
            if self._watching:
                self._refresh_managed()
            with self._lock:
                self._stats["scale_ups"][tier] += 1
            autoscale_metrics()["decisions"].inc(
                tags={"tier": tier, "direction": "up"})
            ev = {"kind": "scale_up", "tier": tier, "replica": rid,
                  "to": target, "reason": reason,
                  "autoscaler": self.autoscaler_id}
            emit("autoscale", ev)
            actions.append(ev)
        return actions

    # ------------------------------------------------------ scale to zero

    def _wake_tier(self, tier: str) -> bool:
        """The router's first-arrival-to-an-empty-tier hook: spawn one
        replica through the tier factory NOW (no hysteresis, no
        cooldown — the request is already waiting on it), off the
        arrival's thread, single-flight per tier. Returns whether a
        wake is coming — the router only WAITS on a True answer; a
        False keeps the pre-existing empty-tier behavior (immediate
        shed / self-healer wait). ONLY a min_replicas=0 tier wakes
        this way: a tier with a floor is empty because its replicas
        DIED, and respawning it from the traffic path would bypass the
        self-healer's per-host circuit breaker — exactly the
        repeatedly-dying-host churn the breaker exists to stop."""
        if tier not in self.specs \
                or self.specs[tier].policy.min_replicas != 0:
            return False
        with self._lock:
            if self._waking.get(tier):
                return True  # a wake is already in flight
            self._waking[tier] = True

        def run() -> None:
            try:
                if self._active_count(tier) > 0:
                    return  # raced another wake / a tick scale-up
                try:
                    replica = self.specs[tier].factory()
                except Exception as e:  # noqa: BLE001 — no capacity
                    with self._lock:
                        self._stats["last_reason"][tier] = (
                            f"wake blocked: {type(e).__name__}: {e}")
                    return
                rid = (self.router.add_prefill(replica)
                       if tier == "prefill"
                       else self.router.add_decode(replica))
                if self._watching:
                    self._refresh_managed()
                with self._lock:
                    self._stats["wakeups"][tier] += 1
                autoscale_metrics()["decisions"].inc(
                    tags={"tier": tier, "direction": "up"})
                emit("autoscale", {"kind": "scale_from_zero", "tier": tier,
                                   "replica": rid,
                                   "autoscaler": self.autoscaler_id})
                self.publish_telemetry(force=True)
            finally:
                with self._lock:
                    self._waking[tier] = False

        threading.Thread(target=run, daemon=True,
                         name=f"autoscale-wake-{tier}").start()
        return True

    # --------------------------------------------------------- scale down

    def _scale_down(self, tier: str, n: int, target: int, reason: str,
                    now: float) -> List[Dict[str, Any]]:
        """Begin draining the newest active replicas (never below the
        initial set's oldest — newest-first mirrors the Serve
        controller's pending-first scale-down). A min_replicas=0 tier
        may drain its LAST replica (allow_empty): the attached waker
        makes the empty tier serveable again on the next arrival."""
        actions = []
        allow_empty = self.specs[tier].policy.min_replicas == 0
        active = [r for r in self.router.tier_replicas(tier)
                  if not r["draining"]]
        for r in list(reversed(active))[:n]:
            if not self.router.begin_drain(tier, r["rid"],
                                           allow_empty=allow_empty):
                continue
            with self._lock:
                self._draining.append(
                    _Draining(tier, r["rid"], now, self.drain_grace_s))
                self._stats["scale_downs"][tier] += 1
            autoscale_metrics()["decisions"].inc(
                tags={"tier": tier, "direction": "down"})
            ev = {"kind": "drain", "tier": tier, "replica": r["rid"],
                  "to": target, "inflight": r["inflight"],
                  "grace_s": self.drain_grace_s, "reason": reason,
                  "autoscaler": self.autoscaler_id}
            emit("autoscale", ev)
            _notify_resilience({"kind": "serve_drain", "name": r["rid"],
                                "tier": tier,
                                "grace_s": self.drain_grace_s})
            actions.append(ev)
        return actions

    def _replica_drained(self, d: _Draining) -> bool:
        """The zero-drop condition: no in-flight left at the router AND
        — for a prefill replica — no unacked KV transfer still held. A
        prefill call returns long before the decode side fetches its
        KV, so router in-flight alone would let a drain kill chunks a
        decode replica is about to read."""
        from .disagg import _call

        if not self.router.drained(d.tier, d.rid):
            return False
        if d.tier != "prefill":
            return True
        rep = next((r for r in self.router.tier_replicas("prefill")
                    if r["rid"] == d.rid), None)
        if rep is None:
            return True
        try:
            # drain probe on a possibly-dead replica, supervised below
            return int(_call(rep["target"], "stats")  # shardlint: disable=unsupervised-actor-call
                       .get("held_transfers", 0)) == 0
        except Exception:  # noqa: BLE001 — replica already dead
            return True

    def _advance_drains(self, now: float,
                        actions: List[Dict[str, Any]]) -> None:
        """Finalize drains whose replica has nothing left in flight (or
        whose grace window expired — the replica-side
        prepare_for_shutdown still runs, off the tick thread, so even
        the forced path waits out stragglers up to its own timeout
        before the actor dies)."""
        with self._lock:
            pending = list(self._draining)
        still: List[_Draining] = []
        for d in pending:
            drained = self._replica_drained(d)
            if not drained and now < d.grace_deadline:
                still.append(d)
                continue
            self._finalize_drain(d, drained)
            ev = {"kind": "scale_down", "tier": d.tier,
                  "replica": d.rid, "drained": bool(drained),
                  "waited_s": round(now - d.since, 3),
                  "autoscaler": self.autoscaler_id}
            emit("autoscale", ev)
            actions.append(ev)
        finalized = [d for d in pending if d not in still]
        with self._lock:
            # drop only what this pass finalized: the death watcher may
            # have reaped records (and _scale_down added new ones) while
            # the drain probes above ran off-lock
            self._draining = [d for d in self._draining
                              if d not in finalized]

    def _finalize_drain(self, d: _Draining, drained: bool) -> None:
        replica = self.router.remove(d.tier, d.rid)
        with self._lock:
            # the teardown below kills the actor ON PURPOSE — its DEAD
            # event must not read as a death to heal
            self._managed = {aid: v for aid, v in self._managed.items()
                             if v[1]["rid"] != d.rid}
            self._stats["drains_completed" if drained
                        else "drains_forced"] += 1
        if replica is None:
            return
        # replica-side teardown runs OFF the tick thread: a forced
        # drain's shutdown window must not stall the control loop
        # during exactly the load spike that may follow a scale-down
        t = threading.Thread(
            target=self._shutdown_replica, args=(replica, drained),
            daemon=True, name=f"autoscale-teardown-{d.rid}")
        t.start()
        self._teardowns.append(t)
        self._teardowns = [x for x in self._teardowns if x.is_alive()]

    def _shutdown_replica(self, replica: Any, drained: bool) -> None:
        """The replica-side grace drain (serve/replica.py shape): wait
        out in-flight work / unacked transfers, stop the engine, then
        release the actor. Drained replicas return from the wait
        immediately; the FORCED path (router-side grace expired with
        requests still running) gets one final bounded window so a
        straggling stream isn't cut mid-token the instant the deadline
        passes."""
        from .disagg import _call

        grace = 5.0 if drained else min(self.drain_grace_s, 10.0)
        try:
            _call(replica, "prepare_for_shutdown", grace)
        except Exception:  # noqa: BLE001 — replica already dead
            pass
        remote = getattr(getattr(replica, "stats", None), "remote", None)
        if remote is not None:  # actor handle: release the process
            try:
                import ray_tpu

                ray_tpu.kill(replica)
            except Exception:  # noqa: BLE001 — already gone
                pass

    # ------------------------------------------------------- self-healing

    def watch(self) -> "DisaggAutoscaler":
        """Subscribe to the conductor's actor-death pubsub for the
        managed replicas (idempotent; ``start()`` calls it). Death
        handling is fully event-driven — it never waits for a tick."""
        if self._watching:
            return self
        self._refresh_managed()
        w = _worker()
        if w is not None:
            w.subscribe_channel("actor_state", self._on_actor_state)
            self._watching = True
        return self

    def _refresh_managed(self) -> None:
        """Snapshot actor_id -> replica identity for every managed
        ACTOR replica currently registered with the router."""
        seen = []
        for tier in TIERS:
            for r in self.router.tier_replicas(tier):
                aid = getattr(r.get("target"), "actor_id", None)
                if aid:
                    seen.append((aid, (tier, {
                        "rid": r["rid"],
                        "machine": r.get("machine")})))
        with self._lock:
            self._managed.update(seen)

    def unwatch(self) -> None:
        if not self._watching:
            return
        w = _worker()
        if w is not None:
            try:
                w.unsubscribe_channel("actor_state",
                                      self._on_actor_state)
            except Exception:  # noqa: BLE001 — worker shutting down
                pass
        self._watching = False

    def _on_actor_state(self, msg: Any) -> None:
        if not isinstance(msg, dict) or msg.get("state") != "DEAD":
            return
        with self._lock:
            found = self._managed.pop(msg.get("actor_id"), None)
        if found is None:
            return  # not one of ours (or a scale-down teardown we did)
        # handle OFF the pubsub dispatch thread: replacement runs the
        # factory (actor spawn + engine init + first compile)
        t = threading.Thread(
            target=self._handle_replica_death,
            args=(found[0], found[1]), daemon=True,
            name=f"autoscale-heal-{found[1]['rid']}")
        t.start()
        self._heals.append(t)
        self._heals = [x for x in self._heals if x.is_alive()]

    def _handle_replica_death(self, tier: str,
                              rep: Dict[str, Any]) -> None:
        """One dead managed replica: reap the corpse (and any drain
        record it dies holding), charge the breaker, replace through
        the tier factory unless the breaker is open. Death is NOT load
        — none of this goes through hysteresis or cooldown."""
        rid = rep["rid"]
        machine = rep.get("machine") or "unknown-host"
        self.router.remove_dead(tier, rid)
        was_draining = False
        with self._lock:
            self._stats["deaths"][tier] += 1
            still = [d for d in self._draining if d.rid != rid]
            was_draining = len(still) != len(self._draining)
            self._draining = still
            if was_draining:
                # the drain/death race: a replica that dies mid-drain
                # must finalize its drain record, not stay "draining"
                # forever
                self._stats["drains_reaped"] += 1
        death_ev = {"kind": "replica_death", "tier": tier,
                    "replica": rid, "machine": machine,
                    "was_draining": was_draining,
                    "autoscaler": self.autoscaler_id}
        emit("autoscale", death_ev)        # the autoscale lane
        _notify_resilience(dict(death_ev))  # the servefault event slice
        if was_draining:
            emit("autoscale", {"kind": "scale_down", "tier": tier,
                               "replica": rid, "drained": False,
                               "reaped": True,
                               "autoscaler": self.autoscaler_id})
        # breaker: decayed per-host death score through the existing
        # failure-domain tracker. The OPEN edge comes from the
        # tracker's own trip counter (incremented under ITS lock
        # exactly once per transition); our lock serializes concurrent
        # heal threads so two same-instant deaths can't both read the
        # pre-trip count and double-report one edge.
        from .disagg import servefault_metrics

        with self._lock:
            before = self._breaker.trip_count(machine)
            self._breaker.record(machine, "replica_death",
                                 detail=f"{tier}:{rid}")
            tripped = self._breaker.trip_count(machine) > before
            if tripped:
                self._stats["breaker_trips"] += 1
        if tripped:
            servefault_metrics()["breaker_trips"].inc()
            _notify_resilience({"kind": "breaker_trip", "host": machine,
                                "tier": tier, "replica": rid,
                                "score": round(
                                    self._breaker.score(machine), 3),
                                "autoscaler": self.autoscaler_id})
        if was_draining:
            # it was being removed anyway — reap, don't replace
            self.publish_servefault(force=True)
            self.publish_telemetry(force=True)
            return
        if self._breaker.is_quarantined(machine):
            with self._lock:
                self._stats["replacements_blocked"] += 1
                self._stats["last_reason"][tier] = (
                    f"replacement blocked: breaker open for {machine} "
                    f"({self._breaker.score(machine):.1f} deaths in "
                    f"window)")
            self.publish_servefault(force=True)
            return
        self._replace(tier, rid)

    def _replace(self, tier: str, dead_rid: str) -> None:
        """Spawn a 1-for-1 replacement through the tier factory —
        OUTSIDE the hysteresis/cooldown machinery (death is not load;
        the tier must return to strength now, not after up_delay_s)."""
        from .disagg import servefault_metrics

        try:
            replica = self.specs[tier].factory()
        except Exception as e:  # noqa: BLE001 — no capacity right now
            with self._lock:
                self._stats["last_reason"][tier] = (
                    f"replacement blocked: {type(e).__name__}: {e}")
            self.publish_servefault(force=True)
            return
        rid = (self.router.add_prefill(replica) if tier == "prefill"
               else self.router.add_decode(replica))
        self._refresh_managed()
        with self._lock:
            self._stats["replacements"][tier] += 1
        servefault_metrics()["replacements"].inc(tags={"tier": tier})
        ev = {"kind": "replace", "tier": tier, "replica": rid,
              "for": dead_rid, "autoscaler": self.autoscaler_id}
        emit("autoscale", ev)
        _notify_resilience(dict(ev))
        self.publish_servefault(force=True)
        self.publish_telemetry(force=True)

    def servefault_stats(self) -> Dict[str, Any]:
        """The self-healer's contribution to the servefault surface."""
        with self._lock:
            sf: Dict[str, Any] = {
                "deaths": dict(self._stats["deaths"]),
                "replacements": dict(self._stats["replacements"]),
                "replacements_blocked":
                    self._stats["replacements_blocked"],
                "breaker_trips": self._stats["breaker_trips"],
                "drains_reaped": self._stats["drains_reaped"],
            }
        sf.update(role="healer", autoscaler_id=self.autoscaler_id,
                  router=self.router.router_id,
                  breaker_open=self._breaker.excluded(),
                  breaker_threshold=self._breaker.threshold,
                  watching=self._watching)
        return sf

    def publish_servefault(self, force: bool = False) -> None:
        self._sf_pusher.push(self.servefault_stats, force=force)

    # ------------------------------------------------------------ status

    def status(self) -> Dict[str, Any]:
        with self._lock:
            s = {
                "autoscaler_id": self.autoscaler_id,
                "router": self.router.router_id,
                "target_p99_ms": self.policy.target_p99_ms,
                "interval_s": self.interval_s,
                "drain_grace_s": self.drain_grace_s,
                "scale_ups": dict(self._stats["scale_ups"]),
                "scale_downs": dict(self._stats["scale_downs"]),
                "drains_completed": self._stats["drains_completed"],
                "drains_forced": self._stats["drains_forced"],
                "drains_reaped": self._stats["drains_reaped"],
                "deaths": dict(self._stats["deaths"]),
                "replacements": dict(self._stats["replacements"]),
                "replacements_blocked":
                    self._stats["replacements_blocked"],
                "breaker_trips": self._stats["breaker_trips"],
                "replica_seconds": {
                    t: round(v, 3) for t, v
                    in self._stats["replica_seconds"].items()},
                "wakeups": dict(self._stats["wakeups"]),
                "last_reason": dict(self._stats["last_reason"]),
                "draining": [{"tier": d.tier, "rid": d.rid}
                             for d in self._draining],
            }
        s["breaker_open"] = self._breaker.excluded()
        s["watching"] = self._watching
        for tier in TIERS:
            reps = self.router.tier_replicas(tier)
            s[f"{tier}_replicas"] = len(reps)
            s[f"{tier}_active"] = sum(1 for r in reps
                                      if not r["draining"])
            s[f"{tier}_bounds"] = [self.specs[tier].policy.min_replicas,
                                   self.specs[tier].policy.max_replicas]
        return s

    def publish_telemetry(self, force: bool = False) -> None:
        self._pusher.push(self.status, force=force)

    # -------------------------------------------------------------- loop

    def start(self) -> "DisaggAutoscaler":
        self.watch()  # self-healing is event-driven, not tick-driven

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.tick()
                except Exception:  # noqa: BLE001 — keep the loop alive
                    import traceback

                    traceback.print_exc()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serve-autoscale")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self.unwatch()
        for t in self._heals:
            t.join(timeout=30.0)
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        # finalize in-progress drains NOW: an abandoned draining
        # replica would stay registered (and its engine running)
        # forever — the replica-side grace still runs in the teardown
        # threads, which we wait out below
        if self._draining:
            past_every_deadline = max(
                [time.monotonic()]
                + [d.grace_deadline for d in self._draining])
            self._advance_drains(past_every_deadline, [])
        for t in self._teardowns:
            t.join(timeout=self.drain_grace_s + 15.0)
        self.publish_telemetry(force=True)
        self.publish_servefault(force=True)


__all__ = ["DisaggAutoscaler", "DisaggPolicy", "ScalingPolicy",
           "SlidingWindow", "TierSpec", "autoscale_metrics",
           "default_target_p99_ms"]
