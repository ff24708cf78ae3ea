"""Per-request flight recorder: distributed request tracing with
tail-latency attribution across the serving plane.

Every serving surface built so far is an *aggregate* — histograms,
counters, sliding windows. None of them can answer "where did THIS
request's 2 s go" or "which phase grows between p50 and p99". This
module is the per-request answer, the serving-plane sibling of the
training StepTimer:

- a :class:`RequestTrace` is minted at the gateway (or by the router
  for direct calls) under one request id, bridged to any incoming W3C
  ``traceparent`` (util/tracing.py wire format), and threaded through
  the serving path via a thread-local so every hop can stamp a phase
  without plumbing an argument through ten signatures;
- hops append **phase** records — ``qos_admission`` (gateway auth +
  QoS gate), ``queue_reserve`` (router admission/reservation),
  ``prefill``, ``kv_transfer`` (start_decode: ChunkFetcher pulls +
  adoption), ``decode_first_token``, ``decode_steady``, and the
  gateway's ``sse_flush`` (concurrent with decode, so excluded from
  the phase-sum-vs-wall invariant) — failover/preemption replays
  re-stamp the same phases tagged with their attempt number, child
  spans under the same request id;
- a phase may carry **parts**: child spans measured by the layer below
  on its own clock and handed over with the phase. ``decode_first_token``
  has two, from the engine's thread (models/engine.py): ``engine_queue``
  (``submit()`` to the pop in ``_admit``: the wait for a tick boundary
  and for the prefills ahead) and ``engine_prefill`` (that pop to the
  first token's ``_emit``: this request's own lookup, prefill, pool
  commit and splice). Parts stay inside their phase's record — the flat
  ``phases`` list gains no entry, so the phase-sum invariant never sees
  them — and surface in ``phase_ms`` as ``<phase>.<part>``; their sum
  never exceeds the phase (what is left of ``decode_first_token`` is
  the wake-up of the router's thread on the stream's queue);
- the store also holds the **engine loop ring**: one record per
  iteration of every ``ContinuousBatchingEngine`` in the process
  (``record_loop`` / ``loop_records``; fields in models/engine.py's
  docstring), bounded at ``LOOP_RING_CAP`` records, under the same
  switch and the same lock as the request summaries;
- a completed trace lands in the process-local
  :class:`RequestTraceStore` under **tail-based retention**: every
  anomalous outcome (shed/error/deadline/disconnect/preempt/failover)
  is always kept, the slowest N are always kept, the boring majority
  is probabilistically sampled under the ``RAY_TPU_REQTRACE_*``
  budget;
- :func:`p99_attribution` diffs per-phase time between the p50 and
  p99 cohorts and names the phase that owns the tail.

One set of numbers: the store pushes stats + kept traces to the
conductor (the ``requesttrace`` row of the telemetry channel), and
``util.state.requesttrace_status()``, ``ray_tpu requests``,
``/api/requesttrace``, the lazy ``ray_tpu_reqtrace_*`` Prometheus
family, and the merged timeline's ``requests`` lane all read the same
aggregate.

Knobs (all live-retunable through util/envknobs.py):

- ``RAY_TPU_REQTRACE`` (default ``1``) — master switch; ``0`` makes
  every hook a no-op.
- ``RAY_TPU_REQTRACE_SLOWEST`` (default ``32``) — the slowest-N set
  retention always protects.
- ``RAY_TPU_REQTRACE_SAMPLE`` (default ``0.05``) — keep probability
  for ok-outcome, not-slowest traces.
- ``RAY_TPU_REQTRACE_KEPT`` (default ``512``) — hard cap on kept
  full traces per process (FIFO eviction that never evicts the
  current slowest-N).
- ``RAY_TPU_REQTRACE_WINDOW`` (default ``2048``) — compact per-request
  summaries retained for p99 attribution (every completion lands here
  regardless of full-trace retention, so the cohorts are unbiased).
"""
from __future__ import annotations

import collections
import random
import threading
import time
import uuid
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

from ray_tpu.util.telemetry import Pusher, emit

# The canonical phase order (rendering + report ordering). ``sse_flush``
# overlaps decode on the gateway's event loop, so it is excluded from
# the phase-sum ≈ wall-time invariant.
PHASES = ("qos_admission", "queue_reserve", "prefill", "kv_transfer",
          "decode_first_token", "decode_steady", "sse_flush")
CONCURRENT_PHASES = frozenset({"sse_flush"})

# Engine loop records kept per process (oldest dropped first): two
# minutes of an 8 ms tick, half an hour of a 55 ms one.
LOOP_RING_CAP = 32768

# Outcomes whose traces tail-based retention always keeps.
ANOMALOUS_OUTCOMES = frozenset({"shed", "error", "deadline",
                                "disconnect", "preempt"})

def enabled() -> bool:
    """Master switch (RAY_TPU_REQTRACE, default on)."""
    from ray_tpu.util import envknobs

    return envknobs.get_bool("RAY_TPU_REQTRACE", True)


def _mint_trace_id() -> str:
    return uuid.uuid4().hex  # 32 lowercase hex — W3C trace-id width


def _mint_span_id() -> str:
    return uuid.uuid4().hex[:16]


# ---------------------------------------------------------------- trace

class RequestTrace:
    """One request's mutable phase log. Thread-safe: the gateway's
    event loop (sse_flush) and its executor thread (router phases)
    append concurrently."""

    def __init__(self, request_id: str, *, source: str = "router",
                 trace_id: Optional[str] = None,
                 tenant: Optional[str] = None,
                 cls: Optional[str] = None,
                 store: Optional["RequestTraceStore"] = None,
                 t0: Optional[float] = None):
        self.request_id = str(request_id)
        self.trace_id = trace_id or _mint_trace_id()
        self.span_id = _mint_span_id()
        self.source = source
        self.tenant = tenant
        self.cls = cls
        self.start_ts = time.time()
        self._t0 = time.perf_counter() if t0 is None else t0
        self._store = store
        self._lock = threading.Lock()
        self._phases: List[Dict[str, Any]] = []
        self._open: List[Dict[str, Any]] = []  # innermost last
        self._attempt = 1
        self._preempts = 0
        self._finished: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------- identity

    def traceparent(self) -> str:
        """W3C header value carrying this trace downstream (same wire
        format as util/tracing.py Span.traceparent)."""
        return f"00-{self.trace_id}-{self.span_id}-01"

    # --------------------------------------------------------- phases

    def _now_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3

    @contextmanager
    def phase(self, name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
        """Record ``name`` spanning the with-block (exceptions still
        record the elapsed time — a failed prefill is exactly the span
        a failover breakdown needs)."""
        rec: Dict[str, Any] = {"phase": str(name),
                               "t_ms": round(self._now_ms(), 3)}
        if attrs:
            rec.update(attrs)
        with self._lock:
            rec["attempt"] = self._attempt
            self._open.append(rec)
        t0 = time.perf_counter()
        try:
            yield rec
        except BaseException as e:
            rec["error"] = type(e).__name__
            raise
        finally:
            rec["dur_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            with self._lock:
                if rec in self._open:
                    self._open.remove(rec)
                self._phases.append(rec)

    def add_phase(self, name: str, dur_ms: float, *,
                  t_ms: Optional[float] = None,
                  concurrent: bool = False,
                  parts: Optional[Dict[str, Optional[float]]] = None,
                  **attrs: Any) -> None:
        """Append an already-measured phase (the gateway's accumulated
        sse_flush; retroactive qos_admission). ``concurrent`` marks
        phases that overlap others and are excluded from the
        phase-sum invariant. ``parts`` ({name: ms}, None values
        skipped) are child spans of this phase, each clipped to what
        the ones before it left of ``dur_ms``: give the part that ends
        with the phase first."""
        dur_ms = float(dur_ms)
        rec: Dict[str, Any] = {
            "phase": str(name),
            "t_ms": round(self._now_ms() - dur_ms
                          if t_ms is None else t_ms, 3),
            "dur_ms": round(dur_ms, 3)}
        if concurrent or name in CONCURRENT_PHASES:
            rec["concurrent"] = True
        if parts:
            left = max(0.0, rec["dur_ms"])
            kept: Dict[str, float] = {}
            for part, ms in parts.items():
                if ms is None:
                    continue
                ms = min(round(max(0.0, float(ms)), 3), left)
                kept[str(part)] = ms
                left = round(left - ms, 3)
            if kept:
                rec["parts"] = kept
        if attrs:
            rec.update(attrs)
        with self._lock:
            rec["attempt"] = self._attempt
            self._phases.append(rec)

    def annotate(self, **attrs: Any) -> None:
        """Merge attrs into the innermost OPEN phase (ChunkFetcher
        refining the router's kv_transfer span from inside it); numeric
        values accumulate so per-pull calls sum instead of clobber."""
        with self._lock:
            if not self._open:
                return
            top = self._open[-1]
            for k, v in attrs.items():
                if isinstance(v, (int, float)) \
                        and isinstance(top.get(k), (int, float)):
                    top[k] = top[k] + v
                else:
                    top[k] = v

    def begin_attempt(self) -> int:
        """A failover replay starts: subsequent phases are child spans
        tagged with the new attempt number under the same id."""
        with self._lock:
            self._attempt += 1
            return self._attempt

    def mark_preempt(self) -> None:
        """A QoS preemption fired against this request; its replay is
        attempt-tagged like a failover but accounted separately."""
        with self._lock:
            self._preempts += 1
            self._attempt += 1

    # --------------------------------------------------------- finish

    def finish(self, outcome: str, *, cause: Optional[str] = None,
               **attrs: Any) -> Optional[Dict[str, Any]]:
        """Seal the trace and hand it to the store. Idempotent — the
        first finish wins (the gateway finishes on disconnect while the
        router thread may still be unwinding)."""
        with self._lock:
            if self._finished is not None:
                return self._finished
            total_ms = round(self._now_ms(), 3)
            phases = [dict(p) for p in self._phases]
            attempts = self._attempt
            preempts = self._preempts
            phase_ms: Dict[str, float] = {}
            for p in phases:
                phase_ms[p["phase"]] = round(
                    phase_ms.get(p["phase"], 0.0)
                    + float(p.get("dur_ms", 0.0)), 3)
                for part, ms in (p.get("parts") or {}).items():
                    key = f"{p['phase']}.{part}"
                    phase_ms[key] = round(phase_ms.get(key, 0.0) + ms, 3)
            rec: Dict[str, Any] = {
                "kind": "trace",
                "request_id": self.request_id,
                "trace_id": self.trace_id,
                "source": self.source,
                "ts": self.start_ts,
                "total_ms": total_ms,
                "outcome": str(outcome),
                "attempts": attempts,
                "replayed": attempts > 1,
                "preempts": preempts,
                "phases": phases,
                "phase_ms": phase_ms,
            }
            if cause is not None:
                rec["cause"] = str(cause)
            if self.tenant is not None:
                rec["tenant"] = self.tenant
            if self.cls is not None:
                rec["class"] = self.cls
            if attrs:
                rec.update({k: v for k, v in attrs.items()
                            if v is not None})
            self._finished = rec
        if self._store is not None:
            self._store.record(rec)
        return rec

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"request_id": self.request_id,
                    "trace_id": self.trace_id,
                    "attempt": self._attempt,
                    "phases": [dict(p) for p in self._phases]}


# ------------------------------------------------------- thread-local

_tls = threading.local()


def current_trace() -> Optional[RequestTrace]:
    return getattr(_tls, "trace", None)


@contextmanager
def activate(trace: Optional[RequestTrace]) -> Iterator[None]:
    """Bind ``trace`` as the thread's current trace for the block
    (None is a no-op so call sites need no branches). The gateway
    activates inside its executor work() so the router's generate —
    and every in-process tier hop under it — sees the trace."""
    if trace is None:
        yield
        return
    prev = getattr(_tls, "trace", None)
    _tls.trace = trace
    try:
        yield
    finally:
        _tls.trace = prev


@contextmanager
def phase(name: str, **attrs: Any) -> Iterator[None]:
    """Stamp a phase on the current trace; no-op without one. The ONE
    hook instrumented code calls — it never needs to know whether a
    gateway, a direct caller, or nobody is recording."""
    tr = current_trace()
    if tr is None:
        yield
        return
    with tr.phase(name, **attrs):
        yield


def annotate(**attrs: Any) -> None:
    """Merge attrs into the current trace's innermost open phase
    (no-op without a trace — the ChunkFetcher hot path pays one
    thread-local probe)."""
    tr = current_trace()
    if tr is not None:
        tr.annotate(**attrs)


def start_trace(request_id: Optional[str] = None, *,
                source: str = "router",
                traceparent: Optional[str] = None,
                tenant: Optional[str] = None,
                cls: Optional[str] = None,
                t0: Optional[float] = None) -> Optional[RequestTrace]:
    """Mint a trace bound to the process store, bridging an incoming
    W3C traceparent's trace id when one is supplied. Returns None when
    RAY_TPU_REQTRACE=0 — every downstream hook tolerates None."""
    if not enabled():
        return None
    trace_id = None
    if traceparent:
        from ray_tpu.util import tracing

        parsed = tracing._parse_traceparent(traceparent)
        if parsed:
            trace_id = parsed["trace_id"]
    return RequestTrace(request_id or f"req-{uuid.uuid4().hex[:24]}",
                        source=source, trace_id=trace_id, tenant=tenant,
                        cls=cls, store=store(), t0=t0)


# -------------------------------------------------------- attribution

def p99_attribution(summaries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Diff per-phase mean time between the p50 cohort (total latency
    at or below the median) and the p99 cohort (at or above the 99th
    percentile; always at least the slowest request) and name the
    phase that owns the tail. Pure over compact summaries
    ({total_ms, phase_ms}) so the conductor can run it over merged
    per-component windows."""
    rows = [s for s in summaries
            if isinstance(s.get("total_ms"), (int, float))]
    if not rows:
        return {"n": 0, "phases": {}, "tail_owner": None}
    rows = sorted(rows, key=lambda s: s["total_ms"])
    n = len(rows)
    p50_cut = rows[(n - 1) // 2]["total_ms"]
    p99_cut = rows[min(n - 1, max(0, int(0.99 * n)))]["total_ms"]
    p50 = [s for s in rows if s["total_ms"] <= p50_cut]
    p99 = [s for s in rows if s["total_ms"] >= p99_cut] or [rows[-1]]

    def _mean(cohort: List[Dict[str, Any]], ph: str) -> float:
        return sum(float((s.get("phase_ms") or {}).get(ph, 0.0))
                   for s in cohort) / len(cohort)

    names: List[str] = list(PHASES)
    for s in rows:
        for ph in (s.get("phase_ms") or {}):
            if ph not in names:
                names.append(ph)
    # "<phase>.<part>" is a child of <phase> (RequestTrace.add_phase's
    # parts): listed under its parent, never beside it
    top = [ph for ph in names
           if "." not in ph or ph.split(".", 1)[0] not in names]

    def _row(ph: str) -> Optional[Dict[str, Any]]:
        lo, hi = _mean(p50, ph), _mean(p99, ph)
        if lo == 0.0 and hi == 0.0:
            return None
        return {"p50_ms": round(lo, 3), "p99_ms": round(hi, 3),
                "delta_ms": round(hi - lo, 3)}

    phases: Dict[str, Dict[str, Any]] = {
        ph: row for ph in top if (row := _row(ph)) is not None}
    deltas = {ph: row["delta_ms"] for ph, row in phases.items()}
    part_deltas: Dict[str, float] = {}
    for ph in names:
        parent, _, part = ph.partition(".")
        if ph in top or parent not in phases:
            continue
        row = _row(ph)
        if row is not None:
            phases[parent].setdefault("parts", {})[part] = row
            part_deltas[ph] = row["delta_ms"]
    tail_owner = None
    if deltas:
        tail_owner = max(deltas, key=lambda ph: deltas[ph])
        if deltas[tail_owner] <= 0.0:
            tail_owner = None
    owner_delta = deltas.get(tail_owner, 0.0)
    if tail_owner is not None:
        # a part owns the tail only where it holds more of the growth
        # than every OTHER top-level phase; else its parent does
        others = max([d for ph, d in deltas.items() if ph != tail_owner],
                     default=0.0)
        mine = {ph: d for ph, d in part_deltas.items()
                if ph.split(".", 1)[0] == tail_owner}
        if mine:
            best = max(mine, key=lambda ph: mine[ph])
            if mine[best] > max(others, 0.0):
                tail_owner, owner_delta = best, mine[best]
    out: Dict[str, Any] = {
        "n": n,
        "p50_cohort": len(p50),
        "p99_cohort": len(p99),
        "p50_total_ms": round(float(p50_cut), 3),
        "p99_total_ms": round(float(p99_cut), 3),
        "phases": phases,
        "tail_owner": tail_owner,
    }
    if tail_owner is not None:
        gap = sum(d for d in deltas.values() if d > 0)
        out["tail_share"] = round(owner_delta / gap, 4) \
            if gap > 0 else 0.0
    return out


# ------------------------------------------------------------ metrics

_metrics: Optional[Dict[str, Any]] = None
_metrics_lock = threading.Lock()


def reqtrace_metrics() -> Dict[str, Any]:
    """Lazy ray_tpu_reqtrace_* family (the repo's lazy-Prometheus
    pattern: built on first touch, rebound once fully constructed)."""
    global _metrics
    if _metrics is not None:
        return _metrics
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge, Histogram

            m = {
                "phase_ms": Histogram(
                    "ray_tpu_reqtrace_phase_ms",
                    "Per-request phase latency by phase name (ms)",
                    boundaries=[1.0, 5.0, 10.0, 25.0, 50.0, 100.0,
                                250.0, 500.0, 1000.0, 2500.0, 5000.0,
                                10000.0],
                    tag_keys=("phase",)),
                "requests": Counter(
                    "ray_tpu_reqtrace_requests_total",
                    "Traced requests by outcome",
                    tag_keys=("outcome",)),
                "kept": Counter(
                    "ray_tpu_reqtrace_kept_total",
                    "Traces retained, by retention reason",
                    tag_keys=("reason",)),
                "dropped": Counter(
                    "ray_tpu_reqtrace_dropped_total",
                    "Completed traces not retained (sampled out)"),
                # the slowest-request exemplar: one series per CHAMPION
                # id, written only when the slowest request changes —
                # bounded by champion turnover, not request volume
                # (util/metrics.py has no series removal)
                "slowest_ms": Gauge(
                    "ray_tpu_reqtrace_slowest_ms",
                    "Slowest traced request (exemplar id in the "
                    "request_id label)",
                    tag_keys=("request_id",)),
            }
            _metrics = m
    return _metrics


# -------------------------------------------------------- conductor IO

def push_remote_phase(request_id: str, phase_name: str,
                      dur_ms: float, *, attempt: int = 1,
                      **attrs: Any) -> None:
    """A tier hop running in ANOTHER process (actor-mode prefill or
    decode replica) records a child phase under the originating
    request id by pushing it to the conductor; ``get_request_trace``
    merges these into the kept trace's breakdown."""
    if not enabled():
        return
    ev: Dict[str, Any] = {"kind": "phase", "request_id": str(request_id),
                          "phase": str(phase_name),
                          "dur_ms": round(float(dur_ms), 3),
                          "attempt": int(attempt)}
    if attrs:
        ev.update(attrs)
    emit("requesttrace", ev)


# -------------------------------------------------------------- store

class RequestTraceStore:
    """Process-local retention + aggregation of finished traces.

    Retention ("tail-based sampling"): every anomalous outcome is kept
    at admission; the slowest N (RAY_TPU_REQTRACE_SLOWEST) are never
    evicted while they hold the title; everything else is kept with
    probability RAY_TPU_REQTRACE_SAMPLE. The kept set is hard-capped
    (RAY_TPU_REQTRACE_KEPT) with oldest-first eviction that skips the
    current slowest-N — so anomalies age out under pressure but the
    tail exemplars survive. Compact summaries of EVERY completion land
    in a separate window (RAY_TPU_REQTRACE_WINDOW) so p99 attribution
    sees the unbiased population, not just the kept traces."""

    def __init__(self, component_id: Optional[str] = None):
        self.component_id = component_id \
            or f"reqtrace-{uuid.uuid4().hex[:8]}"
        self._lock = threading.Lock()
        self._kept: Dict[str, Dict[str, Any]] = {}  # insertion-ordered
        self._summaries: List[Dict[str, Any]] = []
        self._loop_ring: "collections.deque[Dict[str, Any]]" = \
            collections.deque(maxlen=LOOP_RING_CAP)
        self._seq = 0
        self._completed = 0
        self._dropped = 0
        self._outcomes: Dict[str, int] = {}
        self._replayed = 0
        self._preempted = 0
        self._slowest_ms = 0.0
        self._pusher = Pusher("requesttrace", self.component_id)
        self._rng = random.Random()

    # ------------------------------------------------------- knobs

    @staticmethod
    def _knobs() -> Dict[str, Any]:
        from ray_tpu.util import envknobs

        return {
            "slowest": max(1, envknobs.get_int(
                "RAY_TPU_REQTRACE_SLOWEST", 32)),
            "sample": envknobs.get_float(
                "RAY_TPU_REQTRACE_SAMPLE", 0.05),
            "kept": max(1, envknobs.get_int(
                "RAY_TPU_REQTRACE_KEPT", 512)),
            "window": max(16, envknobs.get_int(
                "RAY_TPU_REQTRACE_WINDOW", 2048)),
        }

    # ------------------------------------------------------ recording

    def record(self, rec: Dict[str, Any]) -> None:
        """Ingest one finished trace record (RequestTrace.finish)."""
        knobs = self._knobs()
        outcome = str(rec.get("outcome", "ok"))
        total_ms = float(rec.get("total_ms", 0.0))
        anomalous = (outcome in ANOMALOUS_OUTCOMES
                     or bool(rec.get("replayed"))
                     or int(rec.get("preempts", 0)) > 0)
        new_champion = False
        with self._lock:
            self._seq += 1
            seq = self._seq
            self._completed += 1
            self._outcomes[outcome] = self._outcomes.get(outcome, 0) + 1
            if rec.get("replayed"):
                self._replayed += 1
            if int(rec.get("preempts", 0)) > 0:
                self._preempted += 1
            summary = {"seq": seq,
                       "request_id": rec.get("request_id"),
                       "ts": rec.get("ts"),
                       "total_ms": total_ms,
                       "outcome": outcome,
                       "phase_ms": dict(rec.get("phase_ms") or {})}
            self._summaries.append(summary)
            if len(self._summaries) > knobs["window"]:
                del self._summaries[
                    :len(self._summaries) - knobs["window"]]
            slow_bar = self._slow_bar_locked(knobs["slowest"])
            reason = None
            if anomalous:
                reason = "anomaly"
            elif len(self._kept) < knobs["slowest"] \
                    or total_ms >= slow_bar:
                reason = "slowest"
            elif self._rng.random() < knobs["sample"]:
                reason = "sampled"
            if reason is None:
                self._dropped += 1
            else:
                self._kept[str(rec.get("request_id"))] = dict(rec)
                self._evict_locked(knobs)
            if total_ms > self._slowest_ms:
                self._slowest_ms = total_ms
                new_champion = True
        m = reqtrace_metrics()
        m["requests"].inc(tags={"outcome": outcome})
        for ph, ms in (rec.get("phase_ms") or {}).items():
            m["phase_ms"].observe(float(ms), tags={"phase": ph})
        if reason is None:
            m["dropped"].inc()
        else:
            m["kept"].inc(tags={"reason": reason})
            # kept traces ride the conductor event log: the timeline's
            # `requests` lane and get_request_trace read them back
            emit("requesttrace", rec)
        if new_champion:
            m["slowest_ms"].set(
                total_ms,
                tags={"request_id": str(rec.get("request_id"))})
        self.publish_telemetry()

    def _slow_bar_locked(self, n: int) -> float:
        """Caller holds self._lock. The Nth-slowest kept total — a new
        trace at or past it earns slowest-N retention."""
        totals = sorted((float(r.get("total_ms", 0.0))
                         for r in self._kept.values()), reverse=True)
        return totals[n - 1] if len(totals) >= n else 0.0

    def _evict_locked(self, knobs: Dict[str, Any]) -> None:
        """Caller holds self._lock. FIFO eviction protecting the
        current slowest-N."""
        cap = knobs["kept"]
        if len(self._kept) <= cap:
            return
        protect = set(
            sorted(self._kept,
                   key=lambda rid: float(
                       self._kept[rid].get("total_ms", 0.0)),
                   reverse=True)[:knobs["slowest"]])
        for rid in list(self._kept):
            if len(self._kept) <= cap:
                break
            if rid in protect:
                continue
            del self._kept[rid]

    def record_loop(self, rec: Dict[str, Any]) -> None:
        """One iteration of an engine's loop (models/engine.py builds
        the record on its own thread and never touches it again)."""
        with self._lock:
            self._loop_ring.append(rec)

    # -------------------------------------------------------- reading

    def loop_records(self) -> List[Dict[str, Any]]:
        """The engine loop ring, oldest first."""
        with self._lock:
            return list(self._loop_ring)

    def seq(self) -> int:
        with self._lock:
            return self._seq

    def summaries_since(self, seq: int = 0) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(s) for s in self._summaries
                    if s["seq"] > seq]

    def trace(self, request_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._kept.get(str(request_id))
            return dict(rec) if rec else None

    def slowest(self, k: Optional[int] = None) -> List[Dict[str, Any]]:
        knobs = self._knobs()
        k = knobs["slowest"] if k is None else int(k)
        with self._lock:
            recs = sorted(self._kept.values(),
                          key=lambda r: float(r.get("total_ms", 0.0)),
                          reverse=True)[:k]
            return [dict(r) for r in recs]

    def stats(self) -> Dict[str, Any]:
        knobs = self._knobs()
        with self._lock:
            summaries = [dict(s) for s in self._summaries]
            kept = len(self._kept)
            out: Dict[str, Any] = {
                "component_id": self.component_id,
                "completed": self._completed,
                "kept": kept,
                "dropped": self._dropped,
                "outcomes": dict(self._outcomes),
                "replayed_requests": self._replayed,
                "preempted_requests": self._preempted,
                "slowest_ms": round(self._slowest_ms, 3),
                "window": len(summaries),
            }
        out["slowest"] = [
            {"request_id": r.get("request_id"),
             "total_ms": r.get("total_ms"),
             "outcome": r.get("outcome"),
             "attempts": r.get("attempts"),
             "phase_ms": dict(r.get("phase_ms") or {})}
            for r in self.slowest(knobs["slowest"])]
        out["attribution"] = p99_attribution(summaries)
        # the compact window tail rides the stats push so the conductor
        # can attribute cluster-wide over every component's population
        out["recent"] = [
            {k: v for k, v in s.items() if k != "seq"}
            for s in summaries[-256:]]
        return out

    # ------------------------------------------------------ publishing

    def publish_telemetry(self, force: bool = False) -> None:
        self._pusher.push(self.stats, force=force)


# ----------------------------------------------------- global store

_store: Optional[RequestTraceStore] = None
_store_lock = threading.Lock()


def store() -> RequestTraceStore:
    """The process's shared store (gateway + router + bench record into
    one retention budget)."""
    global _store
    if _store is None:
        with _store_lock:
            if _store is None:
                _store = RequestTraceStore()
    return _store


def _reset_store_for_tests() -> None:
    global _store
    with _store_lock:
        _store = None


__all__ = ["ANOMALOUS_OUTCOMES", "CONCURRENT_PHASES", "PHASES",
           "RequestTrace", "RequestTraceStore", "activate", "annotate",
           "current_trace", "enabled", "p99_attribution", "phase",
           "push_remote_phase", "reqtrace_metrics", "start_trace",
           "store"]
