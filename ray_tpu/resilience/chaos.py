"""Chaos harness: deterministic, scriptable fault injection.

The recovery machinery in this package is only trustworthy if exact
failure scenarios can be replayed in tests — "kill rank 2 at step 5",
"preempt host H with 3 seconds of grace mid-run", "delay heartbeats by
500ms". A chaos *plan* is a JSON list of such actions, carried in the
``RAY_TPU_CHAOS_PLAN`` env var (inline JSON, or ``@/path/plan.json``)
or handed to the trainer programmatically; a :class:`ChaosMonkey` built
from the plan is consulted at every training step boundary
(``ray_tpu.train.report``) and fires each matching action exactly once.

Actions (all fields beyond ``action`` optional unless noted):

- ``{"action": "raise", "rank": R, "at_step": S}`` — raise
  :class:`ChaosError` inside the training loop (survivable failure; the
  trainer's retry path catches it).
- ``{"action": "kill", "rank": R, "at_step": S}`` — hard ``os._exit``
  of the rank's process (worker death; exercises death-pub detection).
- ``{"action": "preempt", "node": N, "grace_s": G, "at_step": S}`` —
  report a preemption for node N (a node id, ``"head"``, or ``"self"``
  = the firing rank's host) to the conductor, which broadcasts the
  checkpoint-now signal and starts draining the host.
- ``{"action": "delay_heartbeats", "ms": M}`` — node agents stretch
  their heartbeat period by M ms (consulted each beat, not stepwise).
- ``{"action": "bounce_conductor", "at_step": S}`` — matched by
  :meth:`ChaosPlan.external_actions`; executed by the test harness
  (only it owns the conductor's lifecycle), not by the monkey.

Serving-plane actions (consulted by the disagg tier replicas through a
:class:`ServeChaosMonkey`, exactly-once per replica process like the
training ops; see serve/disagg.py):

- ``{"action": "kill_replica", "role": "prefill"|"decode",
  "at": "token:K"|"request:N", "replica": R}`` — hard ``os._exit`` of
  the matching tier replica's process. ``at=token:K`` fires when the
  replica has served its K-th decoded token (mid-stream death — the
  request-failover path); ``at=request:N`` fires at the start of its
  N-th request (prefill death before the KV transfer is acked).
  ``replica`` (default 0) is the replica's creation index within its
  role, so one plan kills exactly one replica and the self-healer's
  replacement (a higher index) does not re-fire.
- ``{"action": "drop_connection", "at": "token:K"|"request:N",
  "replica": R}`` — the HTTP gateway (serve/gateway.py) hard-aborts
  the CLIENT socket of the request that crosses the K-th served token
  (or at admission of the N-th request): a deterministic mid-stream
  client disconnect, proving the disconnect-reap path (decode
  cancelled, ``shed cause=disconnect``). ``role`` defaults to
  ``gateway``; the gateway replica dies with nothing — only the
  connection does (its monkey gets a flag-latching exit_fn).
- ``{"action": "delay_chunk_fetch", "ms": M}`` — every ChunkFetcher
  pull sleeps M ms first (consulted out-of-band per fetch, like
  delay_heartbeats), stretching KV-transfer and weight-fetch latency.
- ``{"action": "evict_storm", "role": "prefill", "blocks": B,
  "at": "request:N", "replica": R}`` — force-evict B blocks from the
  matching prefill replica's HBM prefix pool at the start of its N-th
  request (deterministic cache-pressure injection: with the KV plane
  attached the storm spills into the tier-2 host arena instead of
  destroying the prefixes — serve/kvplane.py's chaos test asserts
  zero wrong outputs). Non-lethal: the replica consults
  ``take_storm()`` and applies the eviction itself.

``at_step`` compares against the step number being reported (the
``step`` metric when present, else the session's report count, both
1-based for the first report). ``attempt`` (default 0) scopes an action
to one restart generation so a resumed run replaying the same step
numbers does not re-fire it; ``"attempt": "any"`` fires every time the
step matches. ``rank`` defaults to 0 for cluster-wide actions
(``preempt``) and is required for ``raise``/``kill``.
"""
from __future__ import annotations

import json
import os
import re
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

ENV_VAR = "RAY_TPU_CHAOS_PLAN"

_IN_PROCESS = ("raise", "kill", "preempt")
_EXTERNAL = ("bounce_conductor",)
_PASSIVE = ("delay_heartbeats", "delay_chunk_fetch")
_SERVE = ("kill_replica", "drop_connection", "evict_storm")

_AT_RE = re.compile(r"^(token|request):(\d+)$")


class ChaosError(RuntimeError):
    """A scripted, survivable failure injected by the chaos harness."""


@dataclass
class ChaosAction:
    action: str
    at_step: int = 0
    rank: Optional[int] = None
    attempt: Any = 0            # int generation, or "any"
    node: Optional[str] = None  # preempt: node id | "head" | "self"
    grace_s: Optional[float] = None
    ms: float = 0.0             # delay_heartbeats / delay_chunk_fetch
    role: Optional[str] = None  # kill_replica: prefill | decode
    at: Optional[str] = None    # kill_replica: "token:K" | "request:N"
    replica: int = 0            # kill_replica: creation index in role
    blocks: int = 0             # evict_storm: HBM blocks to force-evict

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ChaosAction":
        action = str(d.get("action", ""))
        known = _IN_PROCESS + _EXTERNAL + _PASSIVE + _SERVE
        if action not in known:
            raise ValueError(f"unknown chaos action {action!r}; "
                             f"known: {sorted(known)}")
        if action in ("raise", "kill") and d.get("rank") is None:
            raise ValueError(f"chaos action {action!r} requires a rank")
        if action == "kill_replica":
            if d.get("role") not in ("prefill", "decode"):
                raise ValueError(
                    "chaos action 'kill_replica' requires "
                    "role=prefill|decode")
            if not _AT_RE.match(str(d.get("at", ""))):
                raise ValueError(
                    "chaos action 'kill_replica' requires "
                    "at='token:K'|'request:N'")
        if action == "evict_storm":
            if d.get("role") not in (None, "prefill"):
                raise ValueError(
                    "chaos action 'evict_storm' fires at a prefill "
                    "replica's prefix pool (role=prefill or omitted)")
            d = dict(d, role="prefill")
            if int(d.get("blocks", 0)) < 1:
                raise ValueError(
                    "chaos action 'evict_storm' requires blocks>=1")
            if not _AT_RE.match(str(d.get("at", ""))):
                raise ValueError(
                    "chaos action 'evict_storm' requires "
                    "at='token:K'|'request:N'")
        if action == "drop_connection":
            if d.get("role") not in (None, "gateway"):
                raise ValueError(
                    "chaos action 'drop_connection' fires at the "
                    "gateway (role=gateway or omitted)")
            d = dict(d, role="gateway")
            if not _AT_RE.match(str(d.get("at", ""))):
                raise ValueError(
                    "chaos action 'drop_connection' requires "
                    "at='token:K'|'request:N'")
        return cls(action=action,
                   at_step=int(d.get("at_step", 0)),
                   rank=(None if d.get("rank") is None
                         else int(d["rank"])),
                   attempt=d.get("attempt", 0),
                   node=d.get("node"),
                   grace_s=(None if d.get("grace_s") is None
                            else float(d["grace_s"])),
                   ms=float(d.get("ms", 0.0)),
                   role=d.get("role"),
                   at=(None if d.get("at") is None else str(d["at"])),
                   replica=int(d.get("replica", 0)),
                   blocks=int(d.get("blocks", 0)))

    def at_spec(self) -> Optional[tuple]:
        """("token"|"request", N) for a kill_replica action."""
        if not self.at:
            return None
        m = _AT_RE.match(self.at)
        return (m.group(1), int(m.group(2))) if m else None

    def matches(self, step: int, rank: int, attempt: int) -> bool:
        if self.action in _PASSIVE or self.action in _SERVE:
            return False  # consulted out-of-band, not stepwise
        if self.attempt != "any" and int(self.attempt) != attempt:
            return False
        if self.at_step != step:
            return False
        want = 0 if self.rank is None else self.rank
        return want == rank


class ChaosPlan:
    """An ordered list of actions, parsed from JSON."""

    def __init__(self, actions: List[ChaosAction], spec: str = ""):
        self.actions = list(actions)
        self.spec = spec

    def __bool__(self) -> bool:
        return bool(self.actions)

    @classmethod
    def from_spec(cls, spec: Optional[str]) -> "ChaosPlan":
        """Parse inline JSON or ``@/path/to/plan.json``; None/"" is the
        empty plan. A malformed plan raises — silently dropping scripted
        faults would make a chaos test vacuously green."""
        if not spec:
            return cls([], "")
        text = spec
        if spec.startswith("@"):
            with open(spec[1:]) as f:
                text = f.read()
        data = json.loads(text)
        if isinstance(data, dict):
            data = data.get("actions", [])
        return cls([ChaosAction.from_dict(d) for d in data], spec)

    @classmethod
    def from_env(cls) -> "ChaosPlan":
        return cls.from_spec(os.environ.get(ENV_VAR))

    def heartbeat_delay_s(self) -> float:
        """Extra node-agent heartbeat delay scripted by the plan."""
        return sum(a.ms for a in self.actions
                   if a.action == "delay_heartbeats") / 1000.0

    def chunk_fetch_delay_s(self) -> float:
        """Extra per-pull ChunkFetcher delay scripted by the plan."""
        return sum(a.ms for a in self.actions
                   if a.action == "delay_chunk_fetch") / 1000.0

    def serve_actions(self, role: str, replica: int
                      ) -> List[ChaosAction]:
        """The serving-plane actions (kill_replica / drop_connection)
        scoped to one tier or gateway replica."""
        return [a for a in self.actions
                if a.action in _SERVE and a.role == role
                and a.replica == int(replica)]

    def external_actions(self, step: int, attempt: int = 0
                         ) -> List[ChaosAction]:
        """Actions the harness itself must execute at this step (e.g.
        bounce_conductor) — the monkey cannot, it lives inside the run."""
        return [a for a in self.actions
                if a.action in _EXTERNAL
                and a.matches(step, a.rank or 0, attempt)]


_HB_DELAY_CACHE: Optional[tuple] = None  # (env spec, parsed delay)
_CF_DELAY_CACHE: Optional[tuple] = None  # (env spec, parsed delay)


def heartbeat_delay_s() -> float:
    """Env-plan heartbeat stretch, for the node agent's beat loop.
    Cached per env value (the agent consults this every beat — no
    point re-parsing an @file plan each second); parse failures count
    as no delay here — the agent must keep heartbeating no matter what
    is in the env."""
    global _HB_DELAY_CACHE
    spec = os.environ.get(ENV_VAR)
    if _HB_DELAY_CACHE is not None and _HB_DELAY_CACHE[0] == spec:
        return _HB_DELAY_CACHE[1]
    try:
        delay = ChaosPlan.from_spec(spec).heartbeat_delay_s()
    except Exception:  # noqa: BLE001
        delay = 0.0
    _HB_DELAY_CACHE = (spec, delay)
    return delay


def chunk_fetch_delay_s() -> float:
    """Env-plan chunk-fetch stretch, for util.chunks.ChunkFetcher
    (consulted once per pull — same cache discipline as the heartbeat
    delay; parse failures count as no delay, a fetch must proceed no
    matter what is in the env)."""
    global _CF_DELAY_CACHE
    spec = os.environ.get(ENV_VAR)
    if _CF_DELAY_CACHE is not None and _CF_DELAY_CACHE[0] == spec:
        return _CF_DELAY_CACHE[1]
    try:
        delay = ChaosPlan.from_spec(spec).chunk_fetch_delay_s()
    except Exception:  # noqa: BLE001
        delay = 0.0
    _CF_DELAY_CACHE = (spec, delay)
    return delay


class ChaosMonkey:
    """Per-process executor of a plan's in-process actions.

    Created by the trainer for each fit attempt and consulted from
    ``ray_tpu.train.report`` at every step boundary. Each action fires
    at most once per monkey; the ``attempt`` field on actions provides
    cross-restart determinism (a restarted run is a new monkey with a
    new attempt number).
    """

    def __init__(self, plan: ChaosPlan, rank: int = 0, attempt: int = 0,
                 conductor_call: Optional[Callable[..., Any]] = None):
        self.plan = plan
        self.rank = int(rank)
        self.attempt = int(attempt)
        self._conductor_call = conductor_call
        self._fired: set = set()
        self._lock = threading.Lock()

    # ------------------------------------------------------------- firing

    def on_step(self, step: int) -> None:
        """Fire every in-process action matching (step, rank, attempt).
        May raise ChaosError or terminate the process — by design."""
        for idx, a in enumerate(self.plan.actions):
            if a.action not in _IN_PROCESS:
                continue
            with self._lock:
                if idx in self._fired:
                    continue
                if not a.matches(step, self.rank, self.attempt):
                    continue
                self._fired.add(idx)
            self._execute(a, step)

    def _execute(self, a: ChaosAction, step: int) -> None:
        self._report_event(a, step)
        if a.action == "raise":
            raise ChaosError(
                f"chaos: injected failure at rank {self.rank} "
                f"step {step} (attempt {self.attempt})")
        if a.action == "kill":
            os._exit(137)
        if a.action == "preempt":
            self._preempt(a)

    def _call(self, method: str, *args, **kwargs) -> Any:
        if self._conductor_call is not None:
            return self._conductor_call(method, *args, **kwargs)
        from ray_tpu._private import worker as worker_mod

        w = worker_mod.global_worker
        if w is None:
            return None
        return w.conductor.call(method, *args, timeout=10.0, **kwargs)

    def _preempt(self, a: ChaosAction) -> None:
        node_id, worker_id = a.node, None
        if a.node in (None, "self"):
            node_id = None
            from ray_tpu._private import worker as worker_mod

            w = worker_mod.global_worker
            worker_id = w.worker_id if w is not None else None
        elif a.node == "head":
            node_id = None  # conductor defaults to its head node
        try:
            self._call("report_preemption", node_id, worker_id,
                       a.grace_s, "chaos")
        except Exception:  # noqa: BLE001 — conductor mid-bounce: the
            pass           # preempt injection is lost, the plan is not

    def _report_event(self, a: ChaosAction, step: int) -> None:
        try:
            self._call("report_resilience_event", {
                "kind": "chaos", "action": a.action, "rank": self.rank,
                "step": step, "attempt": self.attempt, "node": a.node})
        except Exception:  # noqa: BLE001 — telemetry only
            pass


def monkey_from_spec(spec: Optional[str], rank: int = 0,
                     attempt: int = 0) -> Optional[ChaosMonkey]:
    """Build a monkey when `spec` (or, if None, the env) carries a
    plan; None when there is no chaos configured."""
    plan = (ChaosPlan.from_env() if spec is None
            else ChaosPlan.from_spec(spec))
    if not plan:
        return None
    return ChaosMonkey(plan, rank=rank, attempt=attempt)


class ServeChaosMonkey:
    """Per-replica-process executor of a plan's kill_replica actions.

    Created by a disagg tier replica (serve/disagg.py PrefillServer /
    DecodeServer) with its role and creation index; consulted at every
    request admission (``on_request``) and every served token
    (``on_tokens``). Each action fires at most once — the process dies
    with it, but the latch also guards the in-process test doubles.
    ``at`` counts are cumulative per replica (the K-th token / N-th
    request THIS replica serves), which is what makes a mid-stream
    decode death deterministic under concurrent traffic."""

    def __init__(self, plan: ChaosPlan, role: str, replica: int = 0,
                 exit_fn: Callable[[int], Any] = os._exit):
        self.role = str(role)
        self.replica = int(replica)
        self.actions = plan.serve_actions(self.role, self.replica)
        self._exit = exit_fn
        self._lock = threading.Lock()
        self._fired: set = set()
        self._tokens = 0
        self._requests = 0
        # evict_storm is non-lethal: firing latches the block count
        # here and the replica applies the eviction itself via
        # take_storm() (the monkey has no handle on the prefix pool)
        self._pending_storm = 0

    def __bool__(self) -> bool:
        return bool(self.actions)

    def take_storm(self) -> int:
        """Pop the pending evict_storm block count (0 when none is
        due). The prefill replica consults this right after
        ``on_request`` and force-evicts that many HBM blocks."""
        with self._lock:
            n, self._pending_storm = self._pending_storm, 0
        return n

    def reset_counts(self) -> None:
        """Zero the cumulative request/token counters, so a plan's
        ``at=request:N`` / ``at=token:K`` counts the Nth MEASURED
        request / Kth measured token instead of including warm-up
        traffic (the PR-12 known limit). LETHAL latches persist — a
        fired kill already took its process, the latch only guards
        in-process test doubles — but non-lethal evict_storm latches
        re-arm (and any warm-up-fired pending count is dropped): a
        storm that tripped during warm-up must still fire at the Nth
        measured request, or the measured run storms nothing."""
        with self._lock:
            self._tokens = 0
            self._requests = 0
            self._fired -= {i for i, a in enumerate(self.actions)
                            if a.action == "evict_storm"}
            self._pending_storm = 0

    # ------------------------------------------------------------- firing

    def on_request(self) -> None:
        """One request admitted (prefill call / decode adoption)."""
        with self._lock:
            self._requests += 1
            fire = self._due_locked("request", self._requests)
        if fire is not None:
            self._fire(fire)

    def on_tokens(self, n: int = 1) -> None:
        """`n` more tokens served by this replica."""
        with self._lock:
            self._tokens += int(n)
            fire = self._due_locked("token", self._tokens)
        if fire is not None:
            self._fire(fire)

    def _due_locked(self, kind: str, count: int) -> Optional[ChaosAction]:
        for idx, a in enumerate(self.actions):
            if idx in self._fired:
                continue
            spec = a.at_spec()
            if spec is not None and spec[0] == kind and count >= spec[1]:
                self._fired.add(idx)
                return a
        return None

    def _fire(self, a: ChaosAction) -> None:
        try:
            from ray_tpu._private import worker as worker_mod

            w = worker_mod.global_worker
            if w is not None:
                ev = {"kind": "chaos", "action": a.action,
                      "role": self.role, "replica": self.replica,
                      "at": a.at, "tokens": self._tokens,
                      "requests": self._requests}
                if a.action == "evict_storm":
                    ev["blocks"] = a.blocks
                w.conductor.notify("report_resilience_event", ev)
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        if a.action == "evict_storm":
            # non-lethal: the replica pops the count via take_storm()
            with self._lock:
                self._pending_storm += max(0, int(a.blocks))
            return
        self._exit(137)


def serve_monkey_from_spec(spec: Optional[str], role: str,
                           replica: int = 0,
                           exit_fn: Callable[[int], Any] = os._exit
                           ) -> Optional[ServeChaosMonkey]:
    """Build a serving monkey when `spec` (or, if None, the env)
    carries serving actions for this (role, replica); None when no
    serving chaos is configured — the hot path then pays a single
    None check per token batch. `exit_fn` is what firing does: tier
    replicas keep the default hard exit; the gateway passes a
    flag-latching fn so a drop_connection kills one SOCKET, not the
    ingress process."""
    try:
        plan = (ChaosPlan.from_env() if spec is None
                else ChaosPlan.from_spec(spec))
    except Exception:
        if spec is not None:
            raise  # an explicit plan must not be silently dropped
        return None  # malformed env plan: serving keeps running
    if not plan:
        return None
    monkey = ServeChaosMonkey(plan, role, replica, exit_fn)
    return monkey if monkey else None
