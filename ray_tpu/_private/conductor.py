"""Conductor: the cluster control plane.

TPU-native consolidation of the reference's GCS server + raylet scheduling
(/root/reference/src/ray/gcs/gcs_server/gcs_server.h:78 composition — node /
actor / job / placement-group / KV / health managers — and
src/ray/raylet/scheduling/cluster_task_manager.cc). Per SURVEY.md §7 we merge
the two: TPU slices are homogeneous and topology-known, so a single authority
holds the resource view and grants worker leases directly; there is no
spillback protocol. Workers are leased to submitters which then push tasks
*directly* worker-to-worker (the reference's direct task transport design,
direct_task_transport.h:75 — kept, because it is the right hot path).

Responsibilities:
- worker pool per node: pre-start/spawn Python worker processes, lease/return
  (reference worker_pool.h:156 / PopWorker :343)
- actor management: creation (conductor-mediated like gcs_actor_manager.cc:255),
  named actors, restart-on-death with max_restarts
- internal KV + simple pubsub (gcs_kv_manager.cc)
- placement groups: atomic bundle reservation (PACK/SPREAD/STRICT_*)
- health: reap dead worker processes, publish deaths, restart actors
- task-event buffer for the state API (gcs_task_manager.cc)
"""
from __future__ import annotations

import collections
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .. import exceptions as exc
from . import serialization
from .ids import ActorID, NodeID, PlacementGroupID, WorkerID
from .rpc import ClientPool, RpcServer
from .telemetry import TelemetryStore

def _worker_start_timeout() -> float:
    from .config import config

    return config.worker_start_timeout


_RESILIENCE_COUNTER = None
_TTR_GAUGE = None


def _resilience_metrics():
    """Lazy Prometheus-surface twins of the conductor's resilience
    counters (created on first event so importing the conductor never
    spawns a metrics pusher)."""
    global _RESILIENCE_COUNTER, _TTR_GAUGE
    if _RESILIENCE_COUNTER is None:
        from ray_tpu.util.metrics import Counter, Gauge

        _RESILIENCE_COUNTER = Counter(
            "ray_tpu_resilience_events_total",
            "resilience events by kind (preemption/restart/quarantine/"
            "grace_checkpoint/chaos/recovery)", tag_keys=("kind",))
        _TTR_GAUGE = Gauge(
            "ray_tpu_time_to_recovery_seconds",
            "first failure -> successful fit, most recent recovery")
    return _RESILIENCE_COUNTER, _TTR_GAUGE


def _chips_needed(resources: Dict[str, float]) -> int:
    """Whole-chip count a lease pins to the worker via TPU_VISIBLE_CHIPS
    (reference accelerators/tpu.py:30). Fractional TPU shares only
    resource-count — chip binding is per-process (libtpu is single-client
    per chip), so there is nothing meaningful to pin below one chip."""
    for k, v in resources.items():
        if k == "TPU" or k.endswith("_TPU"):
            if v >= 1 and float(v).is_integer():
                return int(v)
    return 0


# libtpu process bounds for a worker bound to k of a host's chips. Without
# them a second process on the host cannot initialize the TPU at all: it
# claims the whole host topology and fails on libtpu's multi-process
# lockfile (four-chip v5e host, PR 21). Other counts leave libtpu to its
# defaults.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def chip_worker_env(chips: Tuple[int, ...]) -> Dict[str, str]:
    """Environment of a worker process that owns exactly `chips`
    (reference accelerators/tpu.py:147,161
    set_current_process_visible_accelerator_ids): the chips it may open,
    the process bounds that make them its whole topology, and the TPU
    platform, so a worker that cannot reach its chips fails instead of
    computing on the CPU. Inside the process the chips are renumbered
    from 0; TPU_VISIBLE_CHIPS is what tells two workers apart."""
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "JAX_PLATFORMS": "tpu",
        "RAY_TPU_WORKER_FULL_SITE": "1",
    }
    bounds = _CHIP_BOUNDS.get(len(chips))
    if bounds:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


@dataclass
class WorkerRecord:
    worker_id: str
    node_id: str
    address: Optional[Tuple[str, int]] = None
    pid: Optional[int] = None
    state: str = "STARTING"  # STARTING | IDLE | BUSY | ACTOR | DEAD
    proc: Optional[subprocess.Popen] = None
    resources: Dict[str, float] = field(default_factory=dict)  # held while leased
    # node whose resources the current lease took (an autoscaled accounting
    # node may differ from the spawn node on this single-host runtime)
    lease_node_id: Optional[str] = None
    # lease resources parked while the worker blocks in get()/wait()
    # (reference: raylet releases blocked workers' resources)
    blocked_resources: Optional[Dict[str, float]] = None
    # TPU chips this process was bound to at spawn (TPU_VISIBLE_CHIPS);
    # chips stay bound for the process lifetime — its TPU runtime owns the
    # devices — and return to the node pool only on death
    chip_ids: Optional[Tuple[int, ...]] = None
    # set on records rebuilt from a persistence snapshot: liveness is
    # unknown until the worker re-registers (fills pid) or a grace period
    # expires (presumed dead with the old conductor)
    restored_at: Optional[float] = None
    # why the worker died, when the runtime knows (e.g. "oom: ..." from
    # the memory monitor) — submitters query this to raise a typed error
    death_cause: Optional[str] = None
    # set before a deliberate kill (ray_tpu.kill, node deregistration,
    # gang teardown): the death must not charge the failure-domain
    # tracker — only UNEXPECTED deaths count toward quarantine
    expected_death: bool = False


@dataclass
class ActorRecord:
    actor_id: str
    name: Optional[str]
    namespace: str
    state: str = "PENDING"  # PENDING | ALIVE | RESTARTING | DEAD
    worker_id: Optional[str] = None
    address: Optional[Tuple[str, int]] = None
    spec: Optional[bytes] = None  # pickled (cls, args, kwargs, options)
    restarts_remaining: int = 0
    max_task_retries: int = 0
    resources: Dict[str, float] = field(default_factory=dict)
    death_cause: Optional[str] = None
    num_restarts: int = 0
    placement_group_id: Optional[str] = None
    # "DEFAULT" | "SPREAD" | ("NODE_AFFINITY", node_id, soft)
    scheduling_strategy: Any = "DEFAULT"


@dataclass
class PlacementGroupRecord:
    pg_id: str
    bundles: List[Dict[str, float]]
    strategy: str
    state: str = "CREATED"  # CREATED | REMOVED
    name: Optional[str] = None
    # node_id per bundle (parallel to `bundles`) — the scheduler's
    # placement decision (reference bundle_scheduling_policy.h)
    assignments: List[str] = field(default_factory=list)


@dataclass
class NodeRecord:
    node_id: str
    total: Dict[str, float]
    available: Dict[str, float]
    address: Optional[Tuple[str, int]] = None  # node agent RPC (None = inline)
    alive: bool = True
    last_heartbeat: float = 0.0  # agent nodes only (address is not None)
    # physical TPU chip ids not bound to any live worker process
    # (reference: accelerators/tpu.py:30 TPU_VISIBLE_CHIPS partitioning)
    free_chips: List[int] = field(default_factory=list)

    @property
    def has_agent(self) -> bool:
        return self.address is not None


class ConductorHandler:
    """RPC handler — every public method is remotely callable."""

    # block-by-design handlers (waiting IS their job): exempt from the
    # RPC server's slow-handler warning — see RpcServer warn_slow.
    # create_actor blocks on the same capacity wait via _place_actor.
    _slow_ok_methods = frozenset({"lease_worker", "create_actor"})

    def __init__(self, resources: Dict[str, float], session_dir: str,
                 worker_env: Optional[Dict[str, str]] = None):
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._nodes: Dict[str, NodeRecord] = {}
        self._workers: Dict[str, WorkerRecord] = {}
        self._actors: Dict[str, ActorRecord] = {}
        self._named_actors: Dict[Tuple[str, str], str] = {}  # (ns, name) -> id
        self._pgs: Dict[str, PlacementGroupRecord] = {}
        self._kv: Dict[str, Dict[bytes, bytes]] = {}
        self._subs: Dict[str, List[Tuple[str, int]]] = {}  # channel -> addrs
        self._task_events: List[Dict[str, Any]] = []
        self._spans: List[Dict[str, Any]] = []  # tracing span table
        # flight recorder: run_id -> {"steps": {step -> {rank -> record}},
        # "updated": ts} ring buffers fed by StepTimer.flush batches
        self._train_runs: Dict[str, Dict[str, Any]] = {}
        self._session_dir = session_dir
        self._worker_env = dict(worker_env or {})
        self._clients = ClientPool()
        self._stopped = False
        self._waiting_leases = 0
        # Parked lease_worker calls, each on its OWN condition sharing
        # self._lock. Capacity events wake exactly ONE waiter (rotating
        # for fairness) and a successful grant cascades to the next —
        # notify_all here caused a measured 6x throughput collapse once
        # waiters outnumbered workers (every return_worker woke every
        # parked waiter into a full rescan). The 0.1s wait timeout in
        # _lease_locked remains the liveness net for any missed wakeup.
        self._lease_waiter_cvs: "collections.deque" = collections.deque()
        # resource shapes of leases currently blocked (autoscaler signal)
        self._pending_demand: List[Tuple[float, Dict[str, float]]] = []
        self.address: Optional[Tuple[str, int]] = None  # set by Conductor

        head = NodeRecord(node_id=NodeID().hex(), total=dict(resources),
                          available=dict(resources),
                          free_chips=list(range(int(resources.get("TPU", 0)))))
        self._nodes[head.node_id] = head
        self._head_node_id = head.node_id

        # Failure-domain quarantine + resilience event log
        # (ray_tpu.resilience): unexpected worker deaths charge their
        # host's decayed score; hosts over the threshold are excluded
        # from lease grants and bundle assignment. The head is exempt
        # from AUTO-quarantine (it is the control plane's own pool —
        # excluding it on a single-host runtime would deadlock every
        # lease), though an operator quarantine_node still pins it.
        from ray_tpu.resilience.domains import FailureDomainTracker
        from .config import config as _config

        self._fd_tracker = FailureDomainTracker(
            threshold=_config.quarantine_threshold,
            half_life_s=_config.quarantine_halflife_s,
            exempt=(head.node_id,))
        self._resilience_events: List[Dict[str, Any]] = []
        self._resilience_counters: Dict[str, int] = {}
        self._last_ttr_s: Optional[float] = None

        # Live weight fabric (ray_tpu.weights): versioned manifests of
        # sharded in-memory weight publications. Chunks stay in their
        # producers' object stores (ownership model — no bytes here);
        # the registry holds only metadata and is the single commit
        # authority: a version becomes visible to subscribers atomically
        # when its LAST host fragment lands.
        # committed: name -> {version -> manifest}; pending: (name,
        # version) -> in-flight publish (reaped after weights_publish_ttl_s)
        self._weights_committed: Dict[str, Dict[int, Dict[str, Any]]] = {}
        self._weights_pending: Dict[Tuple[str, int], Dict[str, Any]] = {}
        self._weight_events: List[Dict[str, Any]] = []

        # Telemetry (kvcache, online, disagg, ...): the newest snapshot
        # a component and a ring of instant markers a subsystem, one
        # row of _private/telemetry.py each, under the store's own lock.
        self._telemetry = TelemetryStore()

        # Global KV plane (serve/kvplane.py): the PREFIX DIRECTORY —
        # (namespace, digest-chain) -> holder + chunk descriptor,
        # metadata only (the weight-fabric registry pattern: atomic
        # commit, TTL reap, keep-last-K GC). KV payload bytes never
        # land here; they ride the chunk fabric between replicas.
        self._kvplane_dir: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._kvplane_dir_counters: Dict[str, int] = {
            k: 0 for k in ("publishes", "republishes", "lookups",
                           "directory_hits", "directory_misses",
                           "reaped", "gced", "unpublished")}

        # MPMD pipelines (ray_tpu.mpmd): stage registry (a pipeline
        # flips "formed" atomically when its LAST stage registers —
        # the weights-fragment commit pattern) + the channel mailbox.
        # The mailbox holds metadata-only descriptors of activation
        # chunks living in the SENDER's object store; payload bytes
        # never land here.
        self._pipelines: Dict[str, Dict[str, Any]] = {}
        self._pipeline_mailbox: Dict[str, Dict[str, Any]] = {}
        self._pipeline_events: List[Dict[str, Any]] = []

        # Durable control-plane tables (reference: GCS Redis-persisted
        # tables, gcs_server.h:103-110 / gcs_table_storage.cc). A snapshot
        # in the session dir lets a restarted conductor recover KV, named
        # actors, placement groups, and job metadata; live workers/agents
        # re-register themselves on their next periodic announce.
        self._persist_path = os.path.join(session_dir, "conductor_state.pkl")
        self._dirty = False
        self._jobs: Dict[str, Dict[str, Any]] = {}
        self._restore_state()

        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="conductor-monitor", daemon=True)

    # ------------------------------------------------------------------ nodes

    def register_node(self, node_id: str, resources: Dict[str, float],
                      address: Optional[Tuple[str, int]] = None) -> None:
        """address is the node's agent RPC endpoint; None registers an
        accounting-only node served by the head's worker pool (autoscaler
        test double, reference FakeMultiNodeProvider)."""
        with self._cv:
            # chips already announced by surviving workers of this node
            # (conductor-restart path: a worker's heartbeat may precede
            # its node agent's re-register) must not return to the pool
            bound = {c for w in self._workers.values()
                     if w.node_id == node_id and w.state != "DEAD"
                     for c in (w.chip_ids or ())}
            self._nodes[node_id] = NodeRecord(
                node_id=node_id, total=dict(resources),
                available=dict(resources),
                address=tuple(address) if address else None,
                last_heartbeat=time.monotonic(),
                free_chips=[c for c in range(int(resources.get("TPU", 0)))
                            if c not in bound])
            self._reapply_pg_reservations(node_id)
            self._notify_all_locked()

    def _reapply_pg_reservations(self, node_id: str) -> None:
        """A (re-)registered node's record starts with full availability;
        re-reserve any live placement-group bundles assigned to it (the
        conductor-restart path — PGs are persisted, nodes are not). Must
        hold the lock."""
        node = self._nodes[node_id]
        for pg in self._pgs.values():
            if pg.state != "CREATED":
                continue
            mine = [b for b, nid in zip(pg.bundles, pg.assignments or ())
                    if nid == node_id]
            if not mine:
                continue
            pk0 = f"_pg_{pg.pg_id}_"
            if any(k.startswith(pk0) for k in node.total):
                continue  # already applied (plain re-register)
            for b in mine:
                self._acquire_resources(node, b)
                for k, v in b.items():
                    pk = pk0 + k
                    node.total[pk] = node.total.get(pk, 0) + v
                    node.available[pk] = node.available.get(pk, 0) + v

    def node_heartbeat(self, node_id: str,
                       dead_worker_ids: Optional[List[str]] = None,
                       death_causes: Optional[Dict[str, str]] = None
                       ) -> bool:
        """Agent liveness + push-reported worker deaths (the conductor
        cannot poll pids on remote hosts). death_causes carries typed
        causes (e.g. the agent's memory monitor OOM kills)."""
        dead_recs: List[WorkerRecord] = []
        with self._cv:
            n = self._nodes.get(node_id)
            if n is None:
                return False  # unknown (e.g. after conductor restart)
            n.last_heartbeat = time.monotonic()
            n.alive = True
            for wid, cause in (death_causes or {}).items():
                w = self._workers.get(wid)
                if w is not None and w.death_cause is None:
                    w.death_cause = cause
            for wid in dead_worker_ids or []:
                w = self._workers.get(wid)
                if w is not None and w.state != "DEAD":
                    w.state = "DEAD"
                    self._release_resources(self._lease_release_node(w),
                                            w.resources)
                    w.resources = {}
                    self._free_worker_chips(w)
                    dead_recs.append(w)
                    if w.address:
                        self._clients.invalidate(w.address)
            self._notify_all_locked()
        for w in dead_recs:
            self._on_worker_death(w)
        return True

    def deregister_node(self, node_id: str, force: bool = False) -> bool:
        """Remove a non-head node. Without force (autoscaler scale-down)
        only an idle node may leave; with force (NodeAgent.stop — the host
        is going away regardless) its workers are declared dead, their
        leases freed, and their actors sent through the restart path."""
        dead: List[WorkerRecord] = []
        with self._cv:
            if node_id == self._head_node_id:
                return False
            n = self._nodes.get(node_id)
            if n is None:
                return False
            if not force and any(n.available.get(k, 0.0) < v
                                 for k, v in n.total.items()):
                return False  # leases still hold its resources
            for w in self._workers.values():
                if w.node_id == node_id and w.state != "DEAD":
                    w.state = "DEAD"
                    w.expected_death = True  # host is leaving on purpose
                    self._release_resources(self._lease_release_node(w),
                                            w.resources)
                    w.resources = {}
                    self._free_worker_chips(w)
                    dead.append(w)
                    if w.address:
                        self._clients.invalidate(w.address)
            del self._nodes[node_id]
            self._notify_all_locked()
        for w in dead:
            self._on_worker_death(w)
        return True

    def _free_worker_chips(self, w: WorkerRecord) -> None:
        """Return a dead worker's bound chips to its node's pool. Must
        hold the lock."""
        if not w.chip_ids:
            return
        n = self._nodes.get(w.node_id)
        if n is not None:
            n.free_chips.extend(w.chip_ids)
        w.chip_ids = None

    def _reclaim_chips_after_exit(self, w: WorkerRecord) -> None:
        """Terminate `w` and return its chips to the node pool only once
        the process is confirmed gone (reaped locally, or its RPC port
        stopped answering remotely). Immediate _free_worker_chips here
        would let a successor bind the same TPU_VISIBLE_CHIPS while the
        old owner's TPU runtime still holds the devices."""
        def confirmed_gone() -> bool:
            if w.proc is not None:
                try:
                    if w.proc.poll() is None:
                        w.proc.terminate()
                        try:
                            w.proc.wait(timeout=8.0)
                        except subprocess.TimeoutExpired:
                            w.proc.kill()
                            w.proc.wait(timeout=8.0)
                except (OSError, subprocess.TimeoutExpired):
                    pass
                return w.proc.poll() is not None
            if w.address:
                addr = tuple(w.address)
                try:
                    self._clients.get(addr).call("shutdown_worker",
                                                 timeout=5.0)
                except Exception:  # noqa: BLE001 — may already be gone
                    pass
                deadline = time.monotonic() + 15.0
                while time.monotonic() < deadline:
                    try:
                        self._clients.get(addr).call("ping", timeout=1.0)
                    except Exception:  # noqa: BLE001 — port closed
                        return True
                    time.sleep(0.2)
                return False
            return True  # no process handle and no address: nothing runs

        def reap():
            # Free the chips ONLY once the owner is verifiably gone. A
            # hung worker (e.g. stuck in a native call) keeps its chips
            # parked — leaked capacity beats a libtpu double-bind. Keep
            # retrying with backoff; most stragglers exit eventually.
            backoff = 1.0
            while not self._stopped:
                if confirmed_gone():
                    with self._cv:
                        self._free_worker_chips(w)
                        self._notify_all_locked()
                    return
                time.sleep(backoff)
                backoff = min(backoff * 2, 30.0)

        threading.Thread(target=reap, daemon=True,
                         name="chip-reaper").start()

    def cluster_resources(self) -> Dict[str, float]:
        with self._lock:
            out: Dict[str, float] = {}
            for n in self._nodes.values():
                if not n.alive:
                    continue
                for k, v in n.total.items():
                    out[k] = out.get(k, 0) + v
            return out

    def available_resources(self) -> Dict[str, float]:
        with self._lock:
            out: Dict[str, float] = {}
            for n in self._nodes.values():
                if not n.alive:
                    continue
                for k, v in n.available.items():
                    out[k] = out.get(k, 0) + v
            return out

    def nodes(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{"node_id": n.node_id, "alive": n.alive, "total": n.total,
                     "available": n.available,
                     "head": n.node_id == self._head_node_id,
                     "address": list(n.address) if n.address else None}
                    for n in self._nodes.values()]

    # ---------------------------------------------------------------- workers

    def register_worker(self, worker_id: str, address: Tuple[str, int],
                        pid: int, node_id: Optional[str] = None,
                        chip_ids: Optional[Tuple[int, ...]] = None) -> bool:
        """Returns False to tell the worker to shut itself down (its chip
        binding conflicts with the conductor's post-restart view)."""
        with self._cv:
            w = self._workers.get(worker_id)
            if w is not None and w.state == "DEAD":
                # a worker we already wrote off (e.g. chips reclaimed)
                # re-announcing after a partition: it must not run — its
                # chips may already be bound elsewhere
                return False
            if w is None:
                w = WorkerRecord(worker_id=worker_id,
                                 node_id=node_id or self._head_node_id)
                self._workers[worker_id] = w
            if node_id:
                w.node_id = node_id
            w.address = tuple(address)
            w.pid = pid
            w.restored_at = None  # liveness confirmed
            if chip_ids and not w.chip_ids:
                # A surviving chip worker re-announcing to a restarted
                # conductor (which reinitialized free_chips to the full
                # range): its TPU runtime still owns those devices, so
                # subtract them from the pool. If another live worker was
                # already bound to any of them meanwhile, the survivor
                # must die — libtpu is single-client per chip.
                chips = tuple(int(c) for c in chip_ids)
                n = self._nodes.get(w.node_id)
                taken = {c for rec in self._workers.values()
                         if rec is not w and rec.state != "DEAD"
                         for c in (rec.chip_ids or ())}
                if taken & set(chips):
                    w.state = "DEAD"
                    self._notify_all_locked()
                    return False
                if n is not None:
                    n.free_chips = [c for c in n.free_chips
                                    if c not in chips]
                w.chip_ids = chips
            if w.state == "STARTING":
                w.state = "IDLE"
            self._notify_all_locked()
            return True

    def _spawn_worker(self, env_extra: Optional[Dict[str, str]] = None,
                      node: Optional[NodeRecord] = None) -> WorkerRecord:
        """Start a worker (reference: WorkerPool PopWorker spawn path,
        worker_pool.h:343). Head/accounting nodes spawn locally; agent
        nodes get an RPC to their NodeAgent (the raylet-equivalent).
        Caller holds self._lock — the lease loop cv-waits for the new
        worker to register back."""
        from .worker_spawn import spawn_worker_process

        worker_id = WorkerID().hex()
        if node is not None and node.has_agent:
            w = WorkerRecord(worker_id=worker_id, node_id=node.node_id)
            self._workers[worker_id] = w
            agent_addr, env = node.address, dict(env_extra or {})

            def ask_agent():
                try:
                    self._clients.get(agent_addr).call(
                        "spawn_worker", worker_id, env or None,
                        timeout=30.0)
                except Exception:
                    with self._cv:
                        w.state = "DEAD"
                        self._free_worker_chips(w)
                        self._notify_all_locked()

            # RPC outside the conductor lock; the lease loop cv-waits for
            # the worker to register back.
            threading.Thread(target=ask_agent, daemon=True).start()
            return w
        proc = spawn_worker_process(
            worker_id, self.address, self._session_dir,
            worker_env=self._worker_env, env_extra=env_extra,
            node_id=self._head_node_id)
        w = WorkerRecord(worker_id=worker_id, node_id=self._head_node_id,
                         proc=proc)
        self._workers[worker_id] = w
        return w

    def _acquire_resources(self, node: NodeRecord, req: Dict[str, float]) -> bool:
        for k, v in req.items():
            if k.startswith("_pg_") and k not in node.available:
                # bundle pool lives elsewhere: even a ZERO-resource PG
                # lease (0-CPU actors) must bind to the bundle's node —
                # gang placement and failure-domain accounting both
                # depend on the lease landing where the bundle was
                # reserved
                return False
            if node.available.get(k, 0.0) + 1e-9 < v:
                return False
        for k, v in req.items():
            node.available[k] = node.available.get(k, 0.0) - v
        return True

    def _release_resources(self, node: Optional[NodeRecord],
                           req: Dict[str, float]) -> None:
        if node is None:
            return
        for k, v in req.items():
            node.available[k] = node.available.get(k, 0.0) + v

    def lease_worker(self, resources: Dict[str, float],
                     placement_group_id: Optional[str] = None,
                     timeout: Optional[float] = None,
                     strategy: str = "DEFAULT",
                     arg_locations=None) -> Tuple[str, Tuple[str, int]]:
        """Grant an idle worker (spawning if below capacity), holding
        `resources` against the node until return_worker. strategy
        DEFAULT packs (head-first, biased toward the node holding the
        most argument bytes — reference lease_policy.cc); SPREAD prefers
        the emptiest node; ("NODE_AFFINITY", node_id, soft) pins
        (reference node_affinity_scheduling_policy.cc).

        `arg_locations`: [(holder_address, nbytes), ...] locality hints
        from the submitter; addresses not belonging to a registered
        worker (e.g. a driver) are ignored."""
        deadline = time.monotonic() + (timeout if timeout is not None
                                       else _worker_start_timeout())
        resources = dict(resources or {})
        resources.setdefault("CPU", 1.0)
        if placement_group_id is not None:
            # resources come out of the PG's pre-reserved bundle pool
            resources = {f"_pg_{placement_group_id}_{k}": v
                         for k, v in resources.items()}
        demand_token = (time.time(), dict(resources))
        with self._cv:
            self._waiting_leases += 1
            self._pending_demand.append(demand_token)
            try:
                return self._lease_locked(resources, deadline, strategy,
                                          arg_locations)
            finally:
                self._waiting_leases -= 1
                self._pending_demand.remove(demand_token)

    def get_rpc_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-method dispatch latency of the conductor's RPC server —
        the control plane's instrumented_io_context analog (reference
        src/ray/common/asio/instrumented_io_context.h stats)."""
        srv = getattr(self, "_rpc_server", None)
        return srv.handler_stats() if srv is not None else {}

    def get_pending_demand(self) -> List[Dict[str, Any]]:
        """Resource shapes of leases currently waiting, with wait age —
        the autoscaler's scale-up signal (reference LoadMetrics /
        gcs_autoscaler_state_manager.cc)."""
        now = time.time()
        with self._lock:
            return [{"resources": dict(res), "age_s": now - t0}
                    for t0, res in self._pending_demand]

    def _lease_release_node(self, w: WorkerRecord) -> Optional[NodeRecord]:
        """The node to credit a worker's held resources back to, or None
        when the node was deregistered mid-lease (its resources died with
        it — crediting another node would inflate the pool)."""
        return self._nodes.get(w.lease_node_id or w.node_id) \
            or self._nodes.get(w.node_id)

    def _wake_lease_waiter_locked(self, skip=None) -> None:
        """Wake ONE parked lease waiter (rotating so consecutive events
        spread across waiters). `skip` excludes the granting thread's own
        cv during the grant cascade — notifying it would be a wasted
        wakeup (it is leaving) and the remaining waiters would sit out
        the full 0.1s poll. Must hold self._lock."""
        for _ in range(len(self._lease_waiter_cvs)):
            cv = self._lease_waiter_cvs[0]
            self._lease_waiter_cvs.rotate(-1)
            if cv is not skip:
                cv.notify()
                return

    def _notify_all_locked(self) -> None:
        """State-change fanout: wake shared-cv waiters (actor state, PG,
        spawn waits) plus one parked lease waiter. Must hold self._lock."""
        self._cv.notify_all()
        self._wake_lease_waiter_locked()

    def _lease_locked(self, resources, deadline,
                      strategy: str = "DEFAULT", arg_locations=None):
            affinity = None
            if isinstance(strategy, (tuple, list)) and strategy \
                    and strategy[0] == "NODE_AFFINITY":
                affinity = (str(strategy[1]), bool(strategy[2]))
            my_cv = threading.Condition(self._lock)
            self._lease_waiter_cvs.append(my_cv)
            try:
                return self._lease_wait_locked(resources, deadline, strategy,
                                               arg_locations, affinity, my_cv)
            finally:
                try:
                    self._lease_waiter_cvs.remove(my_cv)
                except ValueError:
                    pass

    def _lease_wait_locked(self, resources, deadline, strategy,
                           arg_locations, affinity, my_cv):
            while True:
                if self._stopped:
                    raise RuntimeError("conductor stopped")
                # head first, then any registered (e.g. autoscaled) node —
                # workers run on this host either way; remote nodes are
                # resource-accounting entries (single-host runtime).
                head = self._nodes[self._head_node_id]
                nodes = [head] + [n for nid, n in self._nodes.items()
                                  if nid != self._head_node_id and n.alive]
                pinned = None
                if affinity is not None:
                    pinned = self._affinity_nodes_locked(
                        affinity, resources)
                if pinned is None:
                    # failure-domain quarantine + preemption drain: a
                    # host that keeps killing gangs, or one about to be
                    # reclaimed, must not receive new leases. When EVERY
                    # node is excluded the filter yields — a degraded
                    # grant beats a cluster-wide deadlock. An explicit
                    # NODE_AFFINITY pin (pinned) beats quarantine.
                    kept = [n for n in nodes
                            if not self._fd_tracker.is_excluded(n.node_id)]
                    if kept:
                        nodes = kept
                if pinned is not None:
                    nodes = pinned
                elif strategy == "SPREAD":
                    # emptiest node first (reference SPREAD policy,
                    # scheduling/policy/spread_scheduling_policy.cc) —
                    # the DEFAULT order above is pack/head-first
                    def busy(n: NodeRecord) -> int:
                        return sum(1 for w in self._workers.values()
                                   if w.state in ("BUSY", "ACTOR")
                                   and (w.lease_node_id or w.node_id)
                                   == n.node_id)

                    nodes.sort(key=busy)
                elif arg_locations:
                    # data locality: stable-sort candidates by argument
                    # bytes resident on each node, most first (reference
                    # core_worker/lease_policy.cc LocalityAwareLeasePolicy)
                    score = self._locality_scores_locked(arg_locations)
                    if score:
                        nodes.sort(
                            key=lambda n: -score.get(n.node_id, 0.0))
                acquired = None
                for node in nodes:
                    if self._acquire_resources(node, resources):
                        acquired = node
                        break
                if acquired is not None:
                    w = self._take_idle_or_spawn(deadline, acquired,
                                                 _chips_needed(resources))
                    if w is not None:
                        w.state = "BUSY"
                        w.resources = resources
                        w.lease_node_id = acquired.node_id
                        # grant cascade: capacity may remain (coalesced
                        # frees) — hand the baton to the next waiter
                        self._wake_lease_waiter_locked(skip=my_cv)
                        return w.worker_id, w.address
                    self._release_resources(acquired, resources)
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"no worker available for {resources} within timeout; "
                        f"available={head.available}")
                my_cv.wait(min(remaining, 0.1))

    def _affinity_nodes_locked(self, affinity, resources):
        """Candidate list under ("NODE_AFFINITY", node_id, soft):
        [target] while the node is alive and can ever fit the request
        (merely-busy waits, reference node_affinity semantics); soft
        degrades to None (caller keeps the default order); hard raises
        SchedulingError — failing the task beats waiting forever."""
        node_id, soft = affinity
        target = self._nodes.get(node_id)
        feasible = target is not None and target.alive and all(
            target.total.get(k, 0.0) + 1e-9 >= v
            for k, v in resources.items() if not k.startswith("_pg_"))
        if feasible:
            # _pg_-prefixed keys exist only in `available` on the node(s)
            # holding the reservation: a pin to a node without the bundle
            # can never succeed and must not wait out the lease timeout
            feasible = all(k in target.available for k in resources
                           if k.startswith("_pg_"))
        if feasible:
            return [target]
        if soft:
            return None
        raise exc.SchedulingError(
            f"NodeAffinity(node_id={node_id!r}, soft=False) cannot be "
            "satisfied: node is "
            + ("dead or unknown" if target is None or not target.alive
               else f"too small for {resources}"))

    def _locality_scores_locked(self, arg_locations) -> Dict[str, float]:
        """node_id -> argument bytes held there, from (address, nbytes)
        hints. Unknown addresses (drivers, departed workers) score 0."""
        addr_to_node = {tuple(w.address): (w.lease_node_id or w.node_id)
                        for w in self._workers.values()
                        if w.address is not None}
        score: Dict[str, float] = {}
        for addr, nbytes in arg_locations:
            nid = addr_to_node.get(tuple(addr))
            if nid is not None:
                # unknown size still signals presence
                score[nid] = score.get(nid, 0.0) + max(float(nbytes), 1.0)
        return score

    def _spawn_node_id(self, node: NodeRecord) -> str:
        """The node whose worker pool serves a lease on `node`: agent
        nodes run their own workers; accounting nodes (autoscaler fakes,
        address=None) are served by the head's pool."""
        return node.node_id if node.has_agent else self._head_node_id

    def _take_idle_or_spawn(self, deadline: float, node: NodeRecord,
                            n_chips: int = 0) -> Optional[WorkerRecord]:
        """Must hold lock. Returns a registered IDLE worker on `node`'s
        serving pool, or None.

        n_chips > 0 requests a TPU-bound worker: its process was spawned
        with TPU_VISIBLE_CHIPS naming exactly that many chips (reference
        accelerators/tpu.py:147,161 set_current_process_visible_accelerator_ids).
        Chip workers are only reused for leases of the same chip count;
        idle chip workers with the wrong count are torn down to reclaim
        their chips when the pool runs dry."""
        pool_id = self._spawn_node_id(node)
        pool = self._nodes[pool_id]

        def idle():
            for w in self._workers.values():
                if w.state == "IDLE" and w.node_id == pool_id and \
                        len(w.chip_ids or ()) == n_chips:
                    return w
            return None

        def try_spawn_chip_worker() -> bool:
            if len(pool.free_chips) < n_chips:
                # Reclaim chips bound to idle workers of other counts.
                # Chips return to the pool only AFTER the old process has
                # verifiably exited (_reclaim_chips_after_exit): libtpu is
                # single-client per chip, so a successor spawned while the
                # old owner is still dying fails TPU init. The lease loop
                # cv-waits; the reaper's notify retries the spawn.
                prospective = len(pool.free_chips) + sum(
                    len(w.chip_ids or ()) for w in self._workers.values()
                    if w.state == "DEAD" and w.node_id == pool_id
                    and w.chip_ids)  # reclaims already in flight
                for w in list(self._workers.values()):
                    if prospective >= n_chips:
                        break
                    if w.state == "IDLE" and w.node_id == pool_id and \
                            w.chip_ids and len(w.chip_ids) != n_chips:
                        w.state = "DEAD"
                        prospective += len(w.chip_ids)
                        self._reclaim_chips_after_exit(w)
            if len(pool.free_chips) < n_chips:
                return False
            chips = tuple(sorted(pool.free_chips)[:n_chips])
            for c in chips:
                pool.free_chips.remove(c)
            w = self._spawn_worker(node=node,
                                   env_extra=chip_worker_env(chips))
            w.chip_ids = chips
            return True

        w = idle()
        if w is not None:
            return w
        if n_chips > 0:
            spawned = try_spawn_chip_worker()
            while time.monotonic() < deadline and not self._stopped:
                w = idle()
                if w is not None:
                    return w
                if not spawned:
                    spawned = try_spawn_chip_worker()
                self._cv.wait(0.05)
            return None
        n_starting = sum(1 for w in self._workers.values()
                         if w.state == "STARTING"
                         and w.node_id == pool_id and not w.chip_ids)
        # spawn enough for every lease currently waiting (parallel cold-start)
        want = max(1, self._waiting_leases)
        for _ in range(max(0, want - n_starting)):
            self._spawn_worker(node=node)
        while time.monotonic() < deadline and not self._stopped:
            w = idle()
            if w is not None:
                return w
            self._cv.wait(0.05)
        return None

    def return_worker(self, worker_id: str) -> None:
        with self._cv:
            w = self._workers.get(worker_id)
            if w is None or w.state == "DEAD":
                return
            self._release_resources(self._lease_release_node(w), w.resources)
            w.resources = {}
            w.blocked_resources = None  # a parked lease dies with the task
            if w.state == "BUSY":
                w.state = "IDLE"
            self._notify_all_locked()

    def worker_blocked(self, worker_id: str) -> None:
        """A worker's executor thread entered a blocking get()/wait():
        its lease resources return to the pool so the tasks it is
        waiting ON can schedule — without this, dependent tasks each
        get()ing their dep deadlock the moment tasks outnumber CPUs
        (reference: raylet releases resources of workers blocked in
        ray.get, node_manager.cc HandleWorkerBlocked)."""
        with self._cv:
            w = self._workers.get(worker_id)
            if w is None or w.state != "BUSY" or not w.resources \
                    or w.blocked_resources:
                return
            self._release_resources(self._lease_release_node(w),
                                    w.resources)
            w.blocked_resources = w.resources
            w.resources = {}
            self._notify_all_locked()

    def worker_unblocked(self, worker_id: str) -> None:
        """Re-take the parked lease on wake. Transient oversubscription
        is allowed (availability may go negative, stalling new leases
        until it recovers) — the reference's resume semantics."""
        with self._cv:
            w = self._workers.get(worker_id)
            if w is None or not w.blocked_resources or w.state != "BUSY":
                return
            node = self._lease_release_node(w)
            if node is not None:
                for k, v in w.blocked_resources.items():
                    node.available[k] = node.available.get(k, 0.0) - v
            w.resources = w.blocked_resources
            w.blocked_resources = None
            self._notify_all_locked()

    def prestart_workers(self, n: int) -> None:
        with self._cv:
            for _ in range(n):
                self._spawn_worker()

    def list_workers(self) -> List[Dict[str, Any]]:
        with self._lock:
            # lease_node_id: the node whose resources (possibly zero —
            # 0-CPU actor leases) the current lease took; the node
            # autoscaler's idle check needs it because zero-resource
            # leases don't show up in available-vs-total accounting
            return [{"worker_id": w.worker_id, "state": w.state, "pid": w.pid,
                     "address": w.address, "node_id": w.node_id,
                     "lease_node_id": w.lease_node_id}
                    for w in self._workers.values()]

    # ----------------------------------------------------------------- actors

    def create_actor(self, spec_bytes: bytes, name: Optional[str],
                     namespace: str, resources: Dict[str, float],
                     max_restarts: int, max_task_retries: int,
                     placement_group_id: Optional[str] = None,
                     get_if_exists: bool = False,
                     scheduling_strategy: Any = "DEFAULT") -> Dict[str, Any]:
        """GCS-mediated actor creation (reference gcs_actor_manager.cc:255,280)."""
        with self._cv:
            if name is not None:
                existing = self._named_actors.get((namespace, name))
                if existing is not None:
                    rec = self._actors[existing]
                    if rec.state != "DEAD":
                        if get_if_exists:
                            return self._actor_info_locked(rec)
                        raise ValueError(
                            f"actor name {name!r} already taken in namespace "
                            f"{namespace!r}")
            actor_id = ActorID().hex()
            rec = ActorRecord(actor_id=actor_id, name=name, namespace=namespace,
                              spec=spec_bytes,
                              restarts_remaining=max_restarts,
                              max_task_retries=max_task_retries,
                              resources=dict(resources or {}),
                              placement_group_id=placement_group_id,
                              scheduling_strategy=scheduling_strategy)
            self._actors[actor_id] = rec
            self._dirty = True
            if name is not None:
                self._named_actors[(namespace, name)] = actor_id
        self._place_actor(actor_id)
        with self._lock:
            return self._actor_info_locked(self._actors[actor_id])

    def _place_actor(self, actor_id: str) -> None:
        """Lease a dedicated worker and instantiate the actor on it."""
        with self._lock:
            rec = self._actors[actor_id]
            spec, res, pg = rec.spec, rec.resources, rec.placement_group_id
            # getattr: records restored from a pre-upgrade snapshot were
            # pickled without the field (pickle bypasses dataclass defaults)
            strat = getattr(rec, "scheduling_strategy", "DEFAULT")
        try:
            worker_id, address = self.lease_worker(
                res, placement_group_id=pg, strategy=strat)
        except (TimeoutError, RuntimeError, exc.SchedulingError) as e:
            with self._cv:
                rec.state = "DEAD"
                rec.death_cause = f"scheduling failed: {e}"
                self._notify_all_locked()
            return
        client = self._clients.get(address)
        try:
            client.call("become_actor", actor_id, spec,
                        timeout=_worker_start_timeout())
        except Exception as e:  # creation failed on the worker
            self.return_worker(worker_id)
            with self._cv:
                rec.state = "DEAD"
                rec.death_cause = f"__init__ failed: {e}"
                self._notify_all_locked()
            return
        with self._cv:
            w = self._workers.get(worker_id)
            if w is not None:
                w.state = "ACTOR"
            rec.worker_id = worker_id
            rec.address = address
            rec.state = "ALIVE"
            self._dirty = True
            self._notify_all_locked()
        self.publish("actor_state", {"actor_id": actor_id, "state": "ALIVE"})

    def get_actor_info(self, actor_id: Optional[str] = None,
                       name: Optional[str] = None,
                       namespace: str = "default",
                       wait_alive_timeout: float = 0.0) -> Dict[str, Any]:
        deadline = time.monotonic() + wait_alive_timeout
        with self._cv:
            while True:
                if actor_id is None:
                    aid = self._named_actors.get((namespace, name))
                    if aid is None:
                        raise ValueError(
                            f"no actor named {name!r} in namespace {namespace!r}")
                else:
                    aid = actor_id
                rec = self._actors.get(aid)
                if rec is None:
                    raise ValueError(f"unknown actor {aid}")
                if rec.state == "ALIVE" or rec.state == "DEAD" \
                        or time.monotonic() >= deadline:
                    return self._actor_info_locked(rec)
                self._cv.wait(min(0.1, max(0.0, deadline - time.monotonic())))

    def _actor_info_locked(self, rec: ActorRecord) -> Dict[str, Any]:
        return {"actor_id": rec.actor_id, "state": rec.state,
                "address": rec.address, "name": rec.name,
                "namespace": rec.namespace, "death_cause": rec.death_cause,
                "max_task_retries": rec.max_task_retries,
                "num_restarts": rec.num_restarts}

    def list_actors(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [self._actor_info_locked(r) for r in self._actors.values()]

    def kill_actor(self, actor_id: str, no_restart: bool = True) -> None:
        with self._cv:
            rec = self._actors.get(actor_id)
            if rec is None:
                return
            if no_restart:
                rec.restarts_remaining = 0
            worker_id = rec.worker_id
            w = self._workers.get(worker_id) if worker_id else None
            if w is not None and w.state != "DEAD" and \
                    not (w.proc is not None and w.proc.poll() is not None):
                # deliberate kill of a LIVE worker: don't charge the
                # failure tracker. A worker that already exited on its
                # own (crash racing this kill — e.g. a gang teardown
                # sweeping over the rank whose death triggered it) died
                # organically and must still count toward quarantine.
                w.expected_death = True
        if w is not None and w.proc is not None:
            try:
                w.proc.kill()
            except OSError:
                pass
        elif w is not None and w.pid is not None:
            try:
                os.kill(w.pid, 9)
            except OSError:
                pass
        # monitor loop will observe the death and finalize state

    def report_actor_exit(self, actor_id: str, cause: str) -> None:
        """Graceful exit (__ray_terminate__)."""
        with self._cv:
            rec = self._actors.get(actor_id)
            if rec is None:
                return
            rec.state = "DEAD"
            rec.death_cause = cause
            rec.restarts_remaining = 0
            self._dirty = True
            if rec.worker_id:
                w = self._workers.get(rec.worker_id)
                if w is not None and w.state == "ACTOR":
                    w.state = "DEAD"
                    # monitor skips DEAD workers, so release the lease here
                    self._release_resources(self._lease_release_node(w),
                                            w.resources)
                    w.resources = {}
                    self._free_worker_chips(w)
            self._notify_all_locked()
        self.publish("actor_state", {"actor_id": actor_id, "state": "DEAD"})

    # ------------------------------------------------------------------- KV

    def kv_put(self, key: bytes, value: bytes, overwrite: bool = True,
               namespace: str = "default") -> bool:
        with self._lock:
            ns = self._kv.setdefault(namespace, {})
            if not overwrite and key in ns:
                return False
            ns[key] = value
            self._dirty = True
            return True

    def kv_get(self, key: bytes, namespace: str = "default") -> Optional[bytes]:
        with self._lock:
            return self._kv.get(namespace, {}).get(key)

    def kv_del(self, key: bytes, namespace: str = "default") -> bool:
        with self._lock:
            self._dirty = True
            return self._kv.get(namespace, {}).pop(key, None) is not None

    def kv_keys(self, prefix: bytes = b"", namespace: str = "default") -> List[bytes]:
        with self._lock:
            return [k for k in self._kv.get(namespace, {}) if k.startswith(prefix)]

    # ---------------------------------------------------------------- pubsub

    def subscribe(self, channel: str, address: Tuple[str, int]) -> None:
        with self._lock:
            subs = self._subs.setdefault(channel, [])
            if tuple(address) not in subs:
                subs.append(tuple(address))

    def publish(self, channel: str, message: Any) -> None:
        if channel == "worker_logs":
            # ring buffer for the dashboard's log viewer (reference: the
            # dashboard's log tailing endpoints)
            buf = getattr(self, "_recent_logs", None)
            if buf is None:
                import collections

                buf = self._recent_logs = collections.deque(maxlen=2000)
            ts = time.time()
            for entry in (message if isinstance(message, list) else ()):
                buf.append({**entry, "ts": ts})
        with self._lock:
            subs = list(self._subs.get(channel, []))
        for addr in subs:
            try:
                self._clients.get(addr).notify("on_published", channel, message)
            except Exception:
                pass

    def get_recent_logs(self, limit: int = 500) -> List[Dict[str, Any]]:
        buf = getattr(self, "_recent_logs", None)
        if not buf:
            return []
        return list(buf)[-limit:]

    # ------------------------------------------------------- placement groups

    def _assign_bundles(self, bundles: List[Dict[str, float]],
                        strategy: str) -> List[str]:
        """Pick a node per bundle (reference composite/bundle scheduling
        policies, scheduling/policy/bundle_scheduling_policy.h):
        PACK = first-fit onto the fewest nodes, SPREAD = round-robin with
        overflow, STRICT_PACK = one node or fail, STRICT_SPREAD =
        distinct nodes or fail. Must hold the lock. Raises ValueError
        when infeasible; mutates nothing."""
        order = [self._head_node_id] + sorted(
            nid for nid, n in self._nodes.items()
            if nid != self._head_node_id and n.alive)
        # quarantined/draining hosts are excluded from gang formation;
        # an all-excluded cluster falls back to the full list (liveness)
        kept = [nid for nid in order
                if not self._fd_tracker.is_excluded(nid)]
        if kept:
            order = kept
        avail = {nid: dict(self._nodes[nid].available) for nid in order}

        def fits(nid, b):
            return all(avail[nid].get(k, 0.0) >= v for k, v in b.items())

        def take(nid, b):
            for k, v in b.items():
                avail[nid][k] = avail[nid].get(k, 0.0) - v

        if strategy == "STRICT_PACK":
            for nid in order:
                trial = dict(avail[nid])
                ok = True
                for b in bundles:
                    if all(trial.get(k, 0.0) >= v for k, v in b.items()):
                        for k, v in b.items():
                            trial[k] = trial.get(k, 0.0) - v
                    else:
                        ok = False
                        break
                if ok:
                    return [nid] * len(bundles)
            raise ValueError(
                "STRICT_PACK infeasible: no single node fits all bundles")

        if strategy == "STRICT_SPREAD":
            assignment: List[str] = []
            used: set = set()
            for b in bundles:
                placed = next((nid for nid in order
                               if nid not in used and fits(nid, b)), None)
                if placed is None:
                    raise ValueError(
                        "STRICT_SPREAD infeasible: needs "
                        f"{len(bundles)} distinct nodes with capacity, "
                        f"have {len(order)}")
                take(placed, b)
                used.add(placed)
                assignment.append(placed)
            return assignment

        if strategy == "SPREAD":
            assignment = []
            start = 0
            for b in bundles:
                rotation = order[start:] + order[:start]
                placed = next((nid for nid in rotation if fits(nid, b)),
                              None)
                if placed is None:
                    raise ValueError(
                        f"SPREAD infeasible: no node fits bundle {b}")
                take(placed, b)
                assignment.append(placed)
                start = (order.index(placed) + 1) % len(order)
            return assignment

        # PACK: first-fit in fixed order keeps bundles on the fewest nodes
        assignment = []
        for b in bundles:
            placed = next((nid for nid in order if fits(nid, b)), None)
            if placed is None:
                raise ValueError(f"PACK infeasible: no node fits bundle {b}")
            take(placed, b)
            assignment.append(placed)
        return assignment

    def create_placement_group(self, bundles: List[Dict[str, float]],
                               strategy: str = "PACK",
                               name: Optional[str] = None) -> str:
        """Assign each bundle to a node per the strategy, then reserve
        atomically with rollback on partial failure (reference 2PC
        gcs_placement_group_scheduler.cc — single authority here, so the
        transaction is a lock-held reserve loop)."""
        pg_id = PlacementGroupID().hex()
        with self._cv:
            assignment = self._assign_bundles(bundles, strategy)
            reserved: List[Tuple[NodeRecord, Dict[str, float]]] = []
            ok = True
            for b, nid in zip(bundles, assignment):
                node = self._nodes[nid]
                if not self._acquire_resources(node, b):
                    ok = False
                    break
                reserved.append((node, b))
            if not ok:  # raced with a concurrent reservation: roll back
                for node, b in reserved:
                    self._release_resources(node, b)
                raise ValueError(
                    f"placement group infeasible: bundles {bundles} "
                    "no longer fit their assigned nodes")
            # expose per-PG bundle pools as synthetic resources ON THE
            # ASSIGNED NODES — leases carrying the _pg_ prefix can then
            # only be satisfied where the bundle actually lives
            for b, nid in zip(bundles, assignment):
                node = self._nodes[nid]
                for k, v in b.items():
                    pk = f"_pg_{pg_id}_{k}"
                    node.total[pk] = node.total.get(pk, 0) + v
                    node.available[pk] = node.available.get(pk, 0) + v
            self._pgs[pg_id] = PlacementGroupRecord(
                pg_id=pg_id, bundles=bundles, strategy=strategy, name=name,
                assignments=assignment)
            self._dirty = True
            self._notify_all_locked()
        return pg_id

    def placement_group_ready(self, pg_id: str) -> bool:
        with self._lock:
            pg = self._pgs.get(pg_id)
            return pg is not None and pg.state == "CREATED"

    def remove_placement_group(self, pg_id: str) -> None:
        with self._cv:
            pg = self._pgs.pop(pg_id, None)
            if pg is None:
                return
            assignments = pg.assignments or \
                [self._head_node_id] * len(pg.bundles)
            for b, nid in zip(pg.bundles, assignments):
                node = self._nodes.get(nid)
                if node is None:  # node died: its capacity died with it
                    continue
                for k in b:
                    pk = f"_pg_{pg_id}_{k}"
                    node.total.pop(pk, None)
                    node.available.pop(pk, None)
                self._release_resources(node, b)
            self._dirty = True
            self._notify_all_locked()

    def list_placement_groups(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{"pg_id": p.pg_id, "bundles": p.bundles,
                     "strategy": p.strategy, "state": p.state,
                     "name": p.name, "assignments": list(p.assignments)}
                    for p in self._pgs.values()]

    # ------------------------------------------------------------ task events

    def report_task_events(self, events: List[Dict[str, Any]]) -> None:
        with self._lock:
            self._task_events.extend(events)
            if len(self._task_events) > 100_000:
                del self._task_events[:len(self._task_events) - 100_000]

    def report_spans(self, spans: List[Dict[str, Any]]) -> None:
        """Tracing spans flushed by workers/drivers (reference: GCS task-
        event store aggregating OTel-style spans; util/tracing.py drain)."""
        with self._lock:
            self._spans.extend(spans)
            if len(self._spans) > 100_000:
                del self._spans[:len(self._spans) - 100_000]

    def get_spans(self, limit: int = 10_000) -> List[Dict[str, Any]]:
        with self._lock:
            return self._spans[-limit:]

    def get_task_events(self, limit: int = 10_000) -> List[Dict[str, Any]]:
        with self._lock:
            return self._task_events[-limit:]

    # ------------------------------------------------------ flight recorder
    # Gang-wide step telemetry (ray_tpu.observability): every rank's
    # StepTimer ships per-step records here; the per-run ring buffer is
    # the source for straggler detection (util.state.train_progress),
    # the dashboard /api/train route, and `ray_tpu train-status`.

    _TRAIN_STEPS_KEPT = 1024   # per-run step window
    _TRAIN_RUNS_KEPT = 16      # oldest runs evicted past this

    def report_train_steps(self, run_id: str, rank: int,
                           records: List[Dict[str, Any]]) -> None:
        with self._lock:
            run = self._train_runs.setdefault(
                run_id, {"steps": {}, "updated": 0.0})
            steps = run["steps"]
            for rec in records:
                step = int(rec.get("step", 0))
                steps.setdefault(step, {})[int(rank)] = rec
            if len(steps) > self._TRAIN_STEPS_KEPT:
                for s in sorted(steps)[:len(steps)
                                       - self._TRAIN_STEPS_KEPT]:
                    del steps[s]
            run["updated"] = time.time()
            if len(self._train_runs) > self._TRAIN_RUNS_KEPT:
                oldest = sorted(self._train_runs,
                                key=lambda r:
                                self._train_runs[r]["updated"])
                for r in oldest[:len(self._train_runs)
                                - self._TRAIN_RUNS_KEPT]:
                    del self._train_runs[r]

    def get_train_progress(self) -> Dict[str, Any]:
        """Per-run gang summaries (per-rank stats, skew, stragglers) —
        aggregation math lives in ray_tpu.observability.gang. Step
        records are write-once (inserted/replaced, never mutated), so a
        two-level shallow copy isolates the summarizer without paying a
        deep copy of up to 16k records inside the conductor lock."""
        from ray_tpu.observability import gang

        with self._lock:
            snapshot = {
                run_id: {s: dict(by_rank)
                         for s, by_rank in run["steps"].items()}
                for run_id, run in self._train_runs.items()}
        return {run_id: gang.summarize_run(steps)
                for run_id, steps in snapshot.items()}

    def get_train_steps(self, limit: int = 10_000) -> List[Dict[str, Any]]:
        """Raw step records, flattened newest-last with run_id attached —
        the merged-timeline source (observability.timeline). Only a
        two-level shallow snapshot happens under the lock (records are
        write-once, see get_train_progress); the flatten/sort over
        potentially ~1M records runs outside it."""
        with self._lock:
            snapshot = {
                run_id: {s: dict(by_rank)
                         for s, by_rank in run["steps"].items()}
                for run_id, run in self._train_runs.items()}
        out: List[Dict[str, Any]] = []
        for run_id, steps in snapshot.items():
            for step in sorted(steps):
                for rank, rec in sorted(steps[step].items()):
                    out.append(dict(rec, run_id=run_id, rank=rank))
        out.sort(key=lambda r: r.get("t_start") or 0.0)
        return out[-limit:]

    # --------------------------------------------------------- resilience
    # ray_tpu.resilience: the conductor is the authority for preemption
    # broadcast, failure-domain quarantine, and the resilience event log
    # (restart/preemption/quarantine markers for the merged timeline).

    _RESILIENCE_EVENTS_KEPT = 10_000

    def _resilience_record_locked(self, event: Dict[str, Any]) -> None:
        """Append an event + bump its kind counter. Must hold the lock."""
        event.setdefault("ts", time.time())
        self._resilience_events.append(event)
        if len(self._resilience_events) > self._RESILIENCE_EVENTS_KEPT:
            del self._resilience_events[
                :len(self._resilience_events)
                - self._RESILIENCE_EVENTS_KEPT]
        kind = str(event.get("kind", "other"))
        self._resilience_counters[kind] = \
            self._resilience_counters.get(kind, 0) + 1
        if kind == "recovery" and event.get("ttr_s") is not None:
            self._last_ttr_s = float(event["ttr_s"])
        try:
            counter, ttr = _resilience_metrics()
            counter.inc(tags={"kind": kind})
            if kind == "recovery" and self._last_ttr_s is not None:
                ttr.set(self._last_ttr_s)
        except Exception:  # noqa: BLE001 — metrics must never fail an
            pass           # event report

    def _record_failure(self, node_id: str, kind: str, detail: str = "",
                        worker_id: Optional[str] = None) -> None:
        """Charge `node_id`'s failure domain; emits a quarantine event
        on the not-quarantined -> quarantined transition."""
        was = self._fd_tracker.is_quarantined(node_id)
        score = self._fd_tracker.record(node_id, kind, detail=detail)
        with self._lock:
            self._resilience_record_locked(
                {"kind": kind, "node_id": node_id, "detail": detail,
                 "worker_id": worker_id, "score": round(score, 4)})
            if not was and self._fd_tracker.is_quarantined(node_id):
                self._resilience_record_locked(
                    {"kind": "quarantine", "node_id": node_id,
                     "detail": f"score {score:.2f} >= threshold "
                               f"{self._fd_tracker.threshold:g}"})

    def report_preemption(self, node_id: Optional[str] = None,
                          worker_id: Optional[str] = None,
                          grace_s: Optional[float] = None,
                          reason: str = "maintenance") -> Dict[str, Any]:
        """A host announced it is going away (maintenance event, spot
        reclaim, SIGTERM). Starts draining the host — no new leases or
        bundles land on it for the grace window — and broadcasts
        "checkpoint now, grace N seconds" on the `resilience` pubsub
        channel, where training sessions pick it up
        (ray_tpu.train.preemption_requested)."""
        from .config import config

        grace = config.preempt_grace_s if grace_s is None else \
            float(grace_s)
        with self._cv:
            if node_id is None and worker_id is not None:
                w = self._workers.get(worker_id)
                if w is not None:
                    node_id = w.lease_node_id or w.node_id
            if node_id is None:
                node_id = self._head_node_id
            self._fd_tracker.begin_drain(
                node_id, time.monotonic() + grace, reason)
            event = {"kind": "preemption", "ts": time.time(),
                     "node_id": node_id, "grace_s": grace,
                     "deadline": time.time() + grace, "reason": reason}
            self._resilience_record_locked(event)
            self._notify_all_locked()
        self.publish("resilience", event)
        return event

    def report_resilience_event(self, event: Dict[str, Any]) -> None:
        """Generic event sink for trainers/supervisors/chaos: restart,
        grace_checkpoint, gang_peer_death, elastic_reform, recovery
        (with time-to-recovery `ttr_s`), chaos injections."""
        if not isinstance(event, dict):
            return
        with self._lock:
            self._resilience_record_locked(dict(event))

    def quarantine_node(self, node_id: str, reason: str = "manual") -> None:
        """Operator pin: exclude a node until clear_quarantine."""
        self._fd_tracker.quarantine(node_id, reason)
        with self._cv:
            self._resilience_record_locked(
                {"kind": "quarantine", "node_id": node_id,
                 "detail": reason, "manual": True})
            self._notify_all_locked()

    def clear_quarantine(self, node_id: str) -> bool:
        cleared = self._fd_tracker.clear(node_id)
        with self._cv:
            if cleared:
                self._resilience_record_locked(
                    {"kind": "quarantine_cleared", "node_id": node_id})
            self._notify_all_locked()
        return cleared

    def get_resilience_status(self) -> Dict[str, Any]:
        """State-API/dashboard view: per-domain scores + quarantine/
        drain flags, excluded hosts, counters, recent events."""
        status = self._fd_tracker.status()
        with self._lock:
            return {
                "domains": status["domains"],
                "threshold": status["threshold"],
                "half_life_s": status["half_life_s"],
                "excluded": self._fd_tracker.excluded(),
                "head_node_id": self._head_node_id,
                "counters": dict(self._resilience_counters),
                "last_ttr_s": self._last_ttr_s,
                "recent_events": self._resilience_events[-50:],
            }

    def get_resilience_events(self, limit: int = 10_000
                              ) -> List[Dict[str, Any]]:
        """Raw event log, oldest first — the merged-timeline source."""
        with self._lock:
            return self._resilience_events[-limit:]

    def schedulable_resources(self) -> Dict[str, float]:
        """available_resources minus quarantined/draining hosts — what a
        gang re-form can actually get (elastic sizing input)."""
        with self._lock:
            # copy under the lock: other RPCs insert/pop _pg_ keys in
            # these dicts, and iterating them unlocked can raise
            # "dictionary changed size during iteration"
            nodes = [(n.node_id, dict(n.available))
                     for n in self._nodes.values() if n.alive]
        out: Dict[str, float] = {}
        for node_id, available in nodes:
            if self._fd_tracker.is_excluded(node_id):
                continue
            for k, v in available.items():
                out[k] = out.get(k, 0) + v
        return out

    # ------------------------------------------------------ weight fabric
    # ray_tpu.weights: the conductor is the version registry. Producers
    # publish their LOCAL shards into their own object stores and send
    # only a metadata fragment here; the version commits atomically when
    # every host's fragment is in. Keep-last-K GC and partial-publish
    # reaping notify producers over the `weights` pubsub channel so they
    # can free the dropped chunks they own.

    _WEIGHT_EVENTS_KEPT = 10_000

    def _weight_event_locked(self, event: Dict[str, Any]) -> None:
        event.setdefault("ts", time.time())
        self._weight_events.append(event)
        if len(self._weight_events) > self._WEIGHT_EVENTS_KEPT:
            del self._weight_events[
                :len(self._weight_events) - self._WEIGHT_EVENTS_KEPT]

    def report_weight_event(self, event: Dict[str, Any]) -> None:
        """Client-side markers (fetch, swap) for the merged timeline —
        publish/gc/reap events are recorded by the registry itself."""
        if not isinstance(event, dict):
            return
        with self._lock:
            self._weight_event_locked(dict(event))

    def get_weight_events(self, limit: int = 10_000) -> List[Dict[str, Any]]:
        with self._lock:
            return self._weight_events[-limit:]

    # ---------------------------------------------------------- telemetry
    # Every subsystem that only carries numbers from a component to a
    # reader is a row of _private/telemetry.py's SUBSYSTEMS, kept in one
    # TelemetryStore under a lock of its own: components push through
    # ray_tpu.util.telemetry.Pusher; util.state, `ray_tpu <x>`, the
    # dashboard's /api/<x> and the merged timeline read these four
    # methods, so every surface reports one set of numbers. No payload
    # (KV bytes, rollouts, adapters) ever lands here.

    def report_stats(self, subsystem: str, worker_id: str,
                     component_id: str, stats: Dict[str, Any]) -> None:
        self._telemetry.report_stats(subsystem, worker_id, component_id,
                                     stats)

    def report_event(self, subsystem: str, event: Dict[str, Any]) -> None:
        self._telemetry.report_event(subsystem, event)

    # servefault's markers (failover / replace / breaker_trip) are
    # recovery events: they land in the resilience log, beside the
    # preemption and restart markers, and its events are that slice
    _SERVEFAULT_EVENT_KINDS = ("failover", "replace", "breaker_trip",
                               "replica_death", "chaos", "serve_drain")

    def get_events(self, subsystem: str,
                   limit: int = 10_000) -> List[Dict[str, Any]]:
        if subsystem == "servefault":
            with self._lock:
                events = list(self._resilience_events)
            kinds = self._SERVEFAULT_EVENT_KINDS
            return [e for e in events if e.get("kind") in kinds][-limit:]
        return self._telemetry.events(subsystem, limit)

    def get_status(self, subsystem: str) -> Dict[str, Any]:
        out = self._telemetry.status(subsystem)
        if subsystem == "kvplane":
            out["directory"] = self._kvplane_directory_summary()
        return out

    def get_request_trace(self, request_id: str
                          ) -> Optional[Dict[str, Any]]:
        """Replay one request's full trace: the newest kept
        kind="trace" record under the id, with any kind="phase" child
        records remote tiers pushed merged in (attempt-tagged, so
        failover replays read as child spans under the same id)."""
        rid = str(request_id)
        events = self._telemetry.events("requesttrace")
        trace = None
        for ev in reversed(events):
            if ev.get("kind") == "trace" \
                    and str(ev.get("request_id")) == rid:
                trace = dict(ev)
                break
        if trace is None:
            return None
        remote = [dict(ev) for ev in events
                  if ev.get("kind") == "phase"
                  and str(ev.get("request_id")) == rid]
        if remote:
            trace["remote_phases"] = remote
        return trace

    # ------------------------------------------------- global KV plane
    # The cluster-wide PREFIX DIRECTORY lives here: (namespace,
    # digest-chain) -> holder + chunk descriptor — metadata only, the
    # weight-fabric registry pattern (atomic commit, TTL reap,
    # keep-last-K GC). Its commit / reap / GC markers ride the kvplane
    # row's event ring beside the replicas' own.

    _KVPLANE_DIR_KEPT = 4096

    def _kvplane_directory_summary(self) -> Dict[str, Any]:
        """Entries, bytes, per-namespace counts, commit/reap/GC
        counters. Directory entry payloads stay out: descriptors are
        metadata, but a status call is a human surface."""
        with self._lock:
            per_ns: Dict[str, int] = {}
            total_bytes = 0
            for (ns, _d), e in self._kvplane_dir.items():
                per_ns[ns] = per_ns.get(ns, 0) + 1
                total_bytes += int(e.get("nbytes", 0))
            return {"entries": len(self._kvplane_dir),
                    "nbytes": total_bytes,
                    "namespaces": per_ns,
                    "counters": dict(self._kvplane_dir_counters)}

    # ---- prefix directory (the weight-fabric registry pattern) ----

    def _kvplane_ttl_s(self) -> float:
        from ray_tpu.util import envknobs

        return envknobs.get_float("RAY_TPU_KVPLANE_T3_TTL_S", 600.0)

    def kvplane_publish(self, namespace: str, digest: str,
                        meta: Dict[str, Any]) -> Dict[str, Any]:
        """Atomic metadata-only commit of one published prefix: the
        entry is visible to lookups the instant it lands, or not at
        all. A digest already committed returns ``status: already`` —
        the FIRST holder keeps serving, the late publisher drops its
        refs (no torn ownership). Error dicts, never raises (the
        weights_publish_fragment contract)."""
        if not isinstance(meta, dict) or not meta.get("holder"):
            return {"error": "kvplane_publish needs a holder in meta"}
        if not digest:
            return {"error": "kvplane_publish needs a digest"}
        key = (str(namespace or ""), str(digest))
        now = time.time()
        with self._lock:
            existing = self._kvplane_dir.get(key)
            if existing is not None:
                existing["republished"] = now
                self._kvplane_dir_counters["republishes"] += 1
                return {"status": "already",
                        "holder": existing.get("holder")}
            entry = dict(meta, namespace=key[0], digest=key[1],
                         ts=now, started=time.monotonic(),
                         last_hit=None, hits=0)
            self._kvplane_dir[key] = entry
            self._kvplane_dir_counters["publishes"] += 1
            # overall bound: oldest by recency (last hit, else commit)
            # — a runaway publisher cannot grow the directory forever
            while len(self._kvplane_dir) > self._KVPLANE_DIR_KEPT:
                oldest = min(
                    self._kvplane_dir,
                    key=lambda k: (self._kvplane_dir[k].get("last_hit")
                                   or self._kvplane_dir[k]["ts"]))
                del self._kvplane_dir[oldest]
                self._kvplane_dir_counters["gced"] += 1
        self.report_event("kvplane", {
            "kind": "tier3_publish", "namespace": key[0],
            "digest": key[1][:16], "holder": meta.get("holder"),
            "tokens": meta.get("tokens"),
            "nbytes": meta.get("nbytes"), "ts": now})
        self.publish("kvplane", {"event": "publish", "digest": key[1],
                                 "namespace": key[0],
                                 "holder": meta.get("holder")})
        return {"status": "committed"}

    def kvplane_lookup(self, namespace: str,
                       digests: List[str]) -> Optional[Dict[str, Any]]:
        """Longest registered prefix among `digests` (caller orders
        longest-first — models/kvcache.prefix_digests' order). Expired
        entries (TTL over the monotonic commit clock) are treated as
        misses and dropped lazily."""
        ns = str(namespace or "")
        ttl = self._kvplane_ttl_s()
        now_m = time.monotonic()
        with self._lock:
            self._kvplane_dir_counters["lookups"] += 1
            for d in list(digests or [])[:64]:
                key = (ns, str(d))
                e = self._kvplane_dir.get(key)
                if e is None:
                    continue
                if ttl > 0 and now_m - e.get("started", now_m) > ttl:
                    del self._kvplane_dir[key]
                    self._kvplane_dir_counters["reaped"] += 1
                    continue
                e["last_hit"] = time.time()
                e["hits"] = int(e.get("hits", 0)) + 1
                self._kvplane_dir_counters["directory_hits"] += 1
                return {k: v for k, v in e.items() if k != "started"}
            self._kvplane_dir_counters["directory_misses"] += 1
        return None

    def kvplane_unpublish(self, namespace: str, digest: str) -> bool:
        """Holder-side retraction (replica draining / arena teardown
        drops its refs — the descriptor would dangle)."""
        key = (str(namespace or ""), str(digest))
        with self._lock:
            e = self._kvplane_dir.pop(key, None)
            if e is not None:
                self._kvplane_dir_counters["unpublished"] += 1
        return e is not None

    def kvplane_reap(self, max_age_s: Optional[float] = None) -> int:
        """Drop directory entries older than `max_age_s` (default: the
        RAY_TPU_KVPLANE_T3_TTL_S knob) on the monotonic commit clock —
        a published prefix nobody re-publishes eventually stops being
        routable, bounding how stale a holder claim can get."""
        ttl = self._kvplane_ttl_s() if max_age_s is None \
            else float(max_age_s)
        now_m = time.monotonic()
        reaped = []
        with self._lock:
            for key, e in list(self._kvplane_dir.items()):
                if now_m - e.get("started", now_m) >= ttl:
                    del self._kvplane_dir[key]
                    self._kvplane_dir_counters["reaped"] += 1
                    reaped.append(key)
        if reaped:
            self.report_event("kvplane",
                              {"kind": "reap", "entries": len(reaped)})
        return len(reaped)

    def kvplane_gc(self, keep: int,
                   namespace: Optional[str] = None) -> int:
        """Keep only the newest `keep` entries (by recency: last hit,
        else commit time) — per namespace, or over the whole directory
        when namespace is None. The operator keep-last-K analog of
        weights_gc."""
        keep = max(0, int(keep))
        dropped = 0
        with self._lock:
            keys = [k for k in self._kvplane_dir
                    if namespace is None or k[0] == str(namespace or "")]
            if len(keys) > keep:
                keys.sort(key=lambda k:
                          (self._kvplane_dir[k].get("last_hit")
                           or self._kvplane_dir[k]["ts"]),
                          reverse=True)
                for k in keys[keep:]:
                    del self._kvplane_dir[k]
                    self._kvplane_dir_counters["gced"] += 1
                    dropped += 1
        if dropped:
            self.report_event("kvplane",
                              {"kind": "gc", "entries": dropped})
        return dropped

    # ------------------------------------------------------ MPMD pipelines
    # ray_tpu.mpmd: stage registry, channel mailbox, per-stage stats and
    # instant markers. util.state.pipeline_status(), `ray_tpu pipeline`,
    # and the dashboard /api/pipeline all read get_pipeline_status so
    # every surface reports one set of numbers.

    _PIPELINE_EVENTS_KEPT = 10_000
    _PIPELINE_MAILBOX_CAP = 65_536
    _PIPELINES_KEPT = 16  # closed records retained (open ones never evict)

    def _pipeline_event_locked(self, event: Dict[str, Any]) -> None:
        event.setdefault("ts", time.time())
        self._pipeline_events.append(event)
        if len(self._pipeline_events) > self._PIPELINE_EVENTS_KEPT:
            del self._pipeline_events[
                :len(self._pipeline_events)
                - self._PIPELINE_EVENTS_KEPT]

    def pipeline_open(self, name: str,
                      spec: Dict[str, Any]) -> Dict[str, Any]:
        """Create (or replace) a pipeline registry entry. Reopening a
        name drops the previous generation's stages, stats, and any
        stale mailbox entries — a restarted driver must not deliver the
        dead run's activations."""
        num_stages = int(spec.get("num_stages", 0))
        if num_stages < 2:
            return {"error": f"num_stages must be >= 2, got "
                             f"{num_stages}"}
        if "/ch/" in name or name.endswith("/ch"):
            # "/ch/" delimits channel keys (pipeline_channel_put parses
            # the name back out of the key at its FIRST occurrence, and
            # a name ending in "/ch" would shift that occurrence)
            return {"error": f"pipeline name {name!r} must not "
                             "contain '/ch/' or end with '/ch'"}
        with self._lock:
            # "/ch/" delimiter (not a bare "/") so purging "train"
            # never touches a live "train/eval" pipeline's entries
            prefix = f"{name}/ch/"
            for key in [k for k in self._pipeline_mailbox
                        if k.startswith(prefix)]:
                del self._pipeline_mailbox[key]
            self._pipelines[name] = {
                "name": name,
                "num_stages": num_stages,
                "schedule": spec.get("schedule", "1f1b"),
                "num_microbatches": spec.get("num_microbatches"),
                "bubble_estimate": spec.get("bubble_estimate"),
                "run_id": spec.get("run_id", ""),
                "created": time.time(),
                "formed": False,
                "closed": False,
                "stages": {},
                "stats": {},
            }
            self._pipeline_event_locked(
                {"kind": "open", "pipeline": name,
                 "num_stages": num_stages,
                 "schedule": spec.get("schedule")})
        return {"ok": True}

    def pipeline_register_stage(self, name: str, stage: int,
                                info: Dict[str, Any]) -> Dict[str, Any]:
        """One stage-gang's registration. The pipeline flips formed=True
        atomically when the LAST of num_stages stages is in — partial
        pipelines are never visible as formed (the weights-fragment
        commit pattern)."""
        formed_now = False
        with self._lock:
            rec = self._pipelines.get(name)
            if rec is None or rec.get("closed"):
                return {"error": f"no open pipeline {name!r} — call "
                                 "pipeline_open first"}
            stage = int(stage)
            if not 0 <= stage < rec["num_stages"]:
                return {"error": f"stage {stage} out of range for "
                                 f"{rec['num_stages']}-stage pipeline"}
            reg_run = (info or {}).get("run_id")
            if rec.get("run_id") and reg_run is not None and \
                    reg_run != rec["run_id"]:
                # a stage from a DEAD generation (driver restarted and
                # reopened the name) must not count toward — or flip —
                # this generation's formation
                return {"error":
                        f"stage {stage} belongs to generation "
                        f"{reg_run!r}, not {rec['run_id']!r}"}
            rec["stages"][stage] = dict(info or {}, ts=time.time())
            self._pipeline_event_locked(
                {"kind": "stage_registered", "pipeline": name,
                 "stage": stage,
                 "slice_id": (info or {}).get("slice_id")})
            if not rec["formed"] and \
                    len(rec["stages"]) == rec["num_stages"]:
                rec["formed"] = True
                formed_now = True
                self._pipeline_event_locked(
                    {"kind": "formed", "pipeline": name,
                     "num_stages": rec["num_stages"]})
            formed = rec["formed"]
        if formed_now:
            self.publish("pipeline", {"kind": "formed", "name": name})
        return {"ok": True, "formed": formed}

    def pipeline_get(self, name: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._pipelines.get(name)
            if rec is None:
                return None
            out = dict(rec)
            out["stages"] = {s: dict(v)
                             for s, v in rec["stages"].items()}
            out["stats"] = {s: dict(v) for s, v in rec["stats"].items()}
            return out

    def pipeline_close(self, name: str) -> bool:
        """Mark the pipeline closed and drop its mailbox entries (the
        senders' chunk refs die with the stage actors)."""
        with self._lock:
            rec = self._pipelines.get(name)
            if rec is None:
                return False
            rec["closed"] = True
            prefix = f"{name}/ch/"
            dropped = [k for k in self._pipeline_mailbox
                       if k.startswith(prefix)]
            for key in dropped:
                del self._pipeline_mailbox[key]
            self._pipeline_event_locked(
                {"kind": "closed", "pipeline": name,
                 "dropped_mailbox": len(dropped)})
            # keep-last-K of CLOSED records (the weights-registry GC
            # pattern): a sweep of uniquely-named runs must not grow
            # the registry — and every status payload — forever
            closed = sorted(
                (n for n, r in self._pipelines.items()
                 if r.get("closed")),
                key=lambda n: self._pipelines[n].get("created", 0.0))
            for n in closed[:max(0, len(closed) - self._PIPELINES_KEPT)]:
                del self._pipelines[n]
        return True

    def pipeline_channel_put(self, key: str,
                             desc: Dict[str, Any]) -> Dict[str, Any]:
        """Register one microbatch payload's chunk descriptor
        (metadata only). Single-slot per key: the schedules never
        produce the same (step, mb, kind) twice."""
        if not isinstance(desc, dict):
            return {"error": "descriptor must be a dict"}
        from ray_tpu.util.runtime import pipeline_run_token

        name, _, rest = str(key).partition("/ch/")
        with self._lock:
            rec = self._pipelines.get(name)
            if rec is None or rec.get("closed"):
                # a stage-gang of a closed/GC-evicted (dead) generation
                # must fail its sends instead of leaking undeliverable
                # entries toward the global mailbox cap
                return {"error": f"pipeline {name!r} is not open — "
                                 "pipeline_open must precede channel "
                                 "sends"}
            run = rest.split("/", 1)[0]
            want = pipeline_run_token(str(rec["run_id"])) \
                if rec.get("run_id") else ""
            if want and run != want:
                # same generation fencing as stage registration: an
                # orphaned old gang's sends must fail fast, not pile
                # up as undeliverable entries under the live name
                return {"error":
                        f"channel key belongs to generation {run!r}, "
                        f"not {want!r}"}
            if len(self._pipeline_mailbox) >= self._PIPELINE_MAILBOX_CAP:
                return {"error":
                        f"pipeline mailbox full "
                        f"({self._PIPELINE_MAILBOX_CAP} entries) — "
                        "receiver stages dead or stuck?"}
            self._pipeline_mailbox[str(key)] = desc
        self.publish("pipeline", {"kind": "channel_put", "key": key})
        return {"ok": True}

    def pipeline_channel_pending(self, keys: List[str]) -> List[str]:
        """Which of `keys` are still undelivered (the sender-side
        drain barrier — see ActivationChannel.drain)."""
        with self._lock:
            return [k for k in keys if str(k) in self._pipeline_mailbox]

    def pipeline_channel_discard(self, keys: List[str]) -> None:
        """Drop undelivered descriptors whose chunks the sender is
        about to free (retention pruning / channel close): a
        descriptor naming freed chunks must not stay deliverable —
        a late recv would die in an opaque fetch timeout — nor leak
        toward the mailbox cap."""
        with self._lock:
            for k in keys:
                self._pipeline_mailbox.pop(str(k), None)

    def pipeline_channel_take(self, key: str) -> Optional[Dict[str, Any]]:
        """Pop a descriptor (None while not yet delivered — receivers
        poll with a pubsub wakeup)."""
        with self._lock:
            return self._pipeline_mailbox.pop(str(key), None)

    def report_pipeline_stats(self, name: str, stage: int,
                              stats: Dict[str, Any]) -> None:
        """Per-stage run summary (bubble fraction, channel bytes,
        steps) from the stage-gangs — the one set of numbers every
        surface reports."""
        if not isinstance(stats, dict):
            return
        with self._lock:
            rec = self._pipelines.get(name)
            if rec is None:
                return
            run = stats.get("run_id")
            if rec.get("run_id") and run is not None and \
                    run != rec["run_id"]:
                # a dead generation must not overwrite the live run's
                # numbers (generation fencing, as registration)
                return
            rec["stats"][int(stage)] = dict(stats, ts=time.time())

    def get_pipeline_status(self) -> Dict[str, Any]:
        """State-API/dashboard view: every pipeline's registry record
        plus cross-stage totals (activation bytes, mean/max bubble)."""
        with self._lock:
            pipelines = {}
            for name, rec in self._pipelines.items():
                out = dict(rec)
                out["stages"] = {s: dict(v)
                                 for s, v in rec["stages"].items()}
                out["stats"] = {s: dict(v)
                                for s, v in rec["stats"].items()}
                pipelines[name] = out
            mailbox_depth = len(self._pipeline_mailbox)
        for rec in pipelines.values():
            stats = rec["stats"].values()
            fracs = [s.get("bubble_fraction") for s in stats
                     if s.get("bubble_fraction") is not None]
            rec["totals"] = {
                "activation_bytes": sum(int(s.get("sent_bytes") or 0)
                                        for s in stats),
                "bubble_fraction_mean": (sum(fracs) / len(fracs)
                                         if fracs else None),
                "bubble_fraction_max": max(fracs) if fracs else None,
                "steps": max((int(s.get("steps") or 0) for s in stats),
                             default=0),
            }
        return {"pipelines": pipelines, "mailbox_depth": mailbox_depth}

    def report_pipeline_event(self, event: Dict[str, Any]) -> None:
        """Instant markers (formed / stage_report / stage_death /
        closed) for the merged timeline's pipeline lane."""
        if not isinstance(event, dict):
            return
        with self._lock:
            self._pipeline_event_locked(dict(event))

    def get_pipeline_events(self, limit: int = 10_000
                            ) -> List[Dict[str, Any]]:
        with self._lock:
            return self._pipeline_events[-limit:]

    def weights_publish_fragment(self, name: str, version: int, host: int,
                                 num_hosts: int, fragment: Dict[str, Any],
                                 run_id: str = "",
                                 step: Optional[int] = None
                                 ) -> Dict[str, Any]:
        """One host's share of a publish: per-leaf shard metadata (the
        chunk ObjectIDs live in that host's store). The version flips
        committed — and becomes fetchable — only when all `num_hosts`
        fragments are in; until then it is invisible to subscribers and
        a died-mid-publish producer leaves only a reapable pending
        entry, never a torn manifest."""
        version = int(version)
        publish_msg = None
        gc_msgs: List[Dict[str, Any]] = []
        with self._cv:
            by_ver = self._weights_committed.setdefault(name, {})
            if version in by_ver:
                return {"error": f"version {version} of {name!r} is "
                                 "already committed"}
            base_version = fragment.get("base_version")
            if base_version is not None \
                    and int(base_version) not in by_ver:
                # delta against a base this registry no longer holds
                # (GC'd between the publisher's probe and this call):
                # reject so the publisher's full fallback runs — an
                # inherit-from-nothing commit would be a torn manifest
                return {"error": f"delta base {base_version} of "
                                 f"{name!r} is gone"}
            key = (name, version)
            pend = self._weights_pending.get(key)
            if pend is not None and int(num_hosts) != pend["num_hosts"]:
                # a gang RESIZED between attempts (elastic re-form after
                # a crash that left this version partially published):
                # the stale pending entry can never complete under the
                # old num_hosts, and erroring here would crash-loop the
                # recovered gang until the TTL reaper ran — supersede
                # it, telling the old fragments' owners to free EXACTLY
                # those chunks (by object id: the new gang's in-flight
                # chunks share the version number and must survive)
                gc_msgs.append({
                    "kind": "reaped", "name": name,
                    "versions": [version],
                    "object_ids": self._weights_object_ids(
                        f["leaves"] for f in
                        pend["fragments"].values())})
                self._weight_event_locked(
                    {"kind": "reap", "name": name, "version": version,
                     "detail": f"superseded: num_hosts "
                               f"{pend['num_hosts']} -> {num_hosts}"})
                pend = None
            if pend is None:
                pend = self._weights_pending[key] = {
                    "fragments": {}, "num_hosts": int(num_hosts),
                    "run_id": run_id, "step": step,
                    "started": time.monotonic()}
            prev_frag = pend["fragments"].get(int(host))
            if prev_frag is not None:
                # fragment RESEND (publisher retry after an ambiguous
                # RPC timeout): the replaced fragment's chunks are
                # referenced by nothing from here on — reap-notice them
                # or the producer pins a full stale shard copy forever
                gc_msgs.append({
                    "kind": "reaped", "name": name,
                    "versions": [version],
                    "object_ids": self._weights_object_ids(
                        [prev_frag["leaves"]])})
            pend["fragments"][int(host)] = fragment
            self._dirty = True  # registry is a durable table: producers'
            # chunk refs depend on gc/reap notices that only a registry
            # remembering the version can ever send (conductor bounce)
            committed = len(pend["fragments"]) == pend["num_hosts"]
            error = None
            if committed:
                # delta commits inherit unchanged leaves from their base
                # manifests — every named base must still be here (a
                # fragment-time check passed, but another host's base
                # could have been GC'd while this publish was pending)
                gone = sorted({int(f["base_version"])
                               for f in pend["fragments"].values()
                               if f.get("base_version") is not None
                               and int(f["base_version"]) not in by_ver})
                if gone:
                    del self._weights_pending[key]
                    gc_msgs.append({
                        "kind": "reaped", "name": name,
                        "versions": [version],
                        "object_ids": self._weights_object_ids(
                            f["leaves"] for f in
                            pend["fragments"].values())})
                    self._weight_event_locked(
                        {"kind": "reap", "name": name,
                         "version": version,
                         "detail": f"delta base {gone} gone"})
                    error = (f"delta base {gone[0]} of {name!r} is "
                             "gone")
                else:
                    del self._weights_pending[key]
                    manifest = self._weights_commit_locked(name, version,
                                                           pend)
                    publish_msg = {"kind": "published", "name": name,
                                   "version": version, "step": step,
                                   "run_id": run_id,
                                   "total_bytes":
                                       manifest["total_bytes"]}
                    # EXTEND: a supersede notice queued above must still
                    # go out when the superseding fragment commits
                    # immediately
                    gc_msgs.extend(self._weights_gc_locked(name, None))
            self._notify_all_locked()
        if publish_msg is not None:
            self.publish("weights", publish_msg)
        for msg in gc_msgs:
            self.publish("weights", msg)
        if error is not None:
            return {"error": error}
        return {"committed": committed, "version": version}

    @staticmethod
    def _weights_object_ids(leaves_by_frag) -> List[str]:
        """Chunk object ids referenced by fragments or manifest leaves.
        gc/reap notices name EXPLICIT object ids so a publisher only
        ever frees the chunks the registry actually dropped — a
        version-scoped notice would also hit a NEW publish in flight
        under the same version number (gang resize supersede)."""
        out: List[str] = []
        for leaves in leaves_by_frag:
            for leaf in (leaves.values() if isinstance(leaves, dict)
                         else leaves):
                for sh in leaf.get("shards", ()):
                    out.append(sh["object_id"])
        return out

    @staticmethod
    def _weights_recency(manifest: Dict[str, Any]) -> Tuple[float, int]:
        """Ordering key for GC and 'latest': commit recency, version as
        tiebreak. By COMMIT TIME, not version number — a gang restarted
        from an older checkpoint legitimately republishes lower version
        numbers, and those are the weights subscribers should follow
        (max-version ordering would instantly GC the rollback's publish
        while 'latest' kept pointing at the dead attempt's weights)."""
        return (float(manifest.get("ts", 0.0)),
                int(manifest.get("version", 0)))

    def _weights_latest_locked(self, name: str) -> Optional[int]:
        by_ver = self._weights_committed.get(name, {})
        if not by_ver:
            return None
        return max(by_ver.values(), key=self._weights_recency)["version"]

    def weights_latest_version(self, name: str) -> Optional[int]:
        """O(1)-payload poll target for subscribers — the full manifest
        (per-chunk tables + treedef bytes) must not ship on every
        staleness check."""
        with self._lock:
            return self._weights_latest_locked(name)

    def weights_has_version(self, name: str, version: int) -> bool:
        """O(1) committed-version probe (publishers pre-check replayed
        steps before paying the local shard copy into the store)."""
        with self._lock:
            return int(version) in self._weights_committed.get(name, {})

    def _weights_commit_locked(self, name: str, version: int,
                               pend: Dict[str, Any]) -> Dict[str, Any]:
        """Merge host fragments into the version manifest. Must hold the
        lock; records the publish event.

        Delta fragments mark unchanged leaves ``from_base``: those
        inherit the named base manifest's chunk entries FOR THAT HOST
        (entries are host-tagged at commit exactly so this attribution
        survives the merge). The committed manifest is therefore always
        self-contained — chains of deltas collapse one link per commit,
        and a version stays fetchable no matter which of its ancestors
        GC later drops. ``delta_bytes`` records what the publish
        actually shipped; ``total_bytes`` stays the full resolved
        size."""
        frags = pend["fragments"]
        by_ver = self._weights_committed.get(name, {})
        n_leaves = max(int(f.get("n_leaves", 0)) for f in frags.values())
        leaves: List[Dict[str, Any]] = []
        total = 0
        delta_bytes = 0
        n_chunks = 0
        changed: List[int] = []
        any_delta = any(f.get("base_version") is not None
                        for f in frags.values())
        for i in range(n_leaves):
            meta = None
            shards: List[Dict[str, Any]] = []
            leaf_changed = False
            for host, f in sorted(frags.items()):
                m = f["leaves"].get(str(i))
                if m is None:
                    continue
                meta = meta or m
                if m.get("from_base"):
                    base = by_ver[int(f["base_version"])]
                    shards.extend(
                        s for s in base["leaves"][i]["shards"]
                        if s.get("host", host) == host)
                else:
                    own = [dict(s, host=host) for s in m["shards"]]
                    shards.extend(own)
                    if own:
                        leaf_changed = True
                        delta_bytes += sum(int(s["nbytes"])
                                           for s in own)
            total += sum(int(s["nbytes"]) for s in shards)
            n_chunks += len(shards)
            if leaf_changed:
                changed.append(i)
            leaves.append({"shape": meta["shape"], "dtype": meta["dtype"],
                           "hash": meta.get("hash"), "shards": shards})
        treedef = next((f["treedef"] for _, f in sorted(frags.items())
                        if f.get("treedef") is not None), None)
        manifest = {"name": name, "version": version,
                    "step": pend.get("step"), "run_id": pend.get("run_id"),
                    "ts": time.time(), "num_hosts": pend["num_hosts"],
                    "n_leaves": n_leaves, "n_chunks": n_chunks,
                    "total_bytes": total, "leaves": leaves,
                    "treedef": treedef,
                    "delta": any_delta,
                    "base_version": next(
                        (int(f["base_version"]) for f in frags.values()
                         if f.get("base_version") is not None), None),
                    "changed_leaves": changed if any_delta else None,
                    "delta_bytes": delta_bytes}
        self._weights_committed[name][version] = manifest
        self._weight_event_locked(
            {"kind": "publish", "name": name, "version": version,
             "step": pend.get("step"), "run_id": pend.get("run_id"),
             "num_hosts": pend["num_hosts"], "bytes": total,
             "delta_bytes": delta_bytes if any_delta else None,
             "changed_leaves": len(changed) if any_delta else None})
        return manifest

    def _weights_live_ids_locked(self, name: str) -> set:
        """Chunk object ids referenced by the KEPT manifests and pending
        fragments of `name`. Delta manifests inherit their base's chunk
        entries, so dropping a base version must free only the ids no
        kept manifest still points at."""
        live = set(self._weights_object_ids(
            m["leaves"] for m in
            self._weights_committed.get(name, {}).values()))
        for (n, _v), pend in self._weights_pending.items():
            if n == name:
                live.update(self._weights_object_ids(
                    f["leaves"] for f in pend["fragments"].values()))
        return live

    def _weights_gc_locked(self, name: str,
                           keep: Optional[int]) -> List[Dict[str, Any]]:
        """Drop committed versions beyond keep-last-K (config
        weights_keep when `keep` is None). Returns the pubsub messages
        telling producers which versions' chunks to free — publish them
        AFTER releasing the lock. Ids still referenced by a kept
        manifest (delta inheritance) are withheld from the notice."""
        from .config import config

        keep = config.weights_keep if keep is None else int(keep)
        by_ver = self._weights_committed.get(name, {})
        order = sorted(by_ver,
                       key=lambda v: self._weights_recency(by_ver[v]))
        drop = order[:-keep] if keep > 0 else order
        msgs = []
        for v in drop:
            manifest = by_ver.pop(v)
            self._dirty = True
            self._weight_event_locked(
                {"kind": "gc", "name": name, "version": v})
            live = self._weights_live_ids_locked(name)
            dead = [oid for oid in self._weights_object_ids(
                        [manifest["leaves"]])
                    if oid not in live]
            msgs.append({"kind": "gc", "name": name, "versions": [v],
                         "object_ids": dead})
        return msgs

    def weights_gc(self, name: str, keep: Optional[int] = None) -> int:
        """Operator GC (`ray_tpu weights gc`): keep only the newest
        `keep` versions of `name`. Returns the number dropped. Only an
        EXPLICIT keep=0 drops everything; a negative keep (operator
        typo) is rejected rather than read as drop-all."""
        if keep is not None and int(keep) < 0:
            raise ValueError(f"keep must be >= 0, got {keep}")
        with self._cv:
            msgs = self._weights_gc_locked(name, keep)
            self._notify_all_locked()
        for msg in msgs:
            self.publish("weights", msg)
        return len(msgs)

    def weights_reap(self, max_age_s: Optional[float] = None) -> int:
        """Drop pending publishes older than `max_age_s` (config
        weights_publish_ttl_s default) — a producer chaos-killed
        mid-publish must never leave a forever-pending entry, and its
        surviving peers' orphan chunks must be freed. Runs from the
        monitor loop; tests call it with 0 for determinism."""
        from .config import config

        ttl = config.weights_publish_ttl_s if max_age_s is None \
            else float(max_age_s)
        now = time.monotonic()
        msgs = []
        with self._cv:
            for key in [k for k, p in self._weights_pending.items()
                        if now - p["started"] >= ttl]:
                name, version = key
                pend = self._weights_pending.pop(key)
                self._dirty = True
                self._weight_event_locked(
                    {"kind": "reap", "name": name, "version": version})
                msgs.append({"kind": "reaped", "name": name,
                             "versions": [version],
                             "object_ids": self._weights_object_ids(
                                 f["leaves"] for f in
                                 pend["fragments"].values())})
            if msgs:
                self._notify_all_locked()
        for msg in msgs:
            self.publish("weights", msg)
        return len(msgs)

    def weights_get_manifest(self, name: str,
                             version: Optional[int] = None
                             ) -> Optional[Dict[str, Any]]:
        """The full manifest of `version` (latest committed when None),
        or None when nothing is committed / the version was GC'd."""
        with self._lock:
            by_ver = self._weights_committed.get(name, {})
            if not by_ver:
                return None
            v = self._weights_latest_locked(name) if version is None \
                else int(version)
            return by_ver.get(v)

    def get_weight_versions(self) -> Dict[str, Any]:
        """Registry state for util.state.weight_versions(), the
        `ray_tpu weights` CLI, and the dashboard's /api/weights — one
        summary per name, manifests without the per-shard chunk lists."""
        with self._lock:
            out: Dict[str, Any] = {}
            for name, by_ver in self._weights_committed.items():
                if not by_ver:
                    continue
                out[name] = {
                    "latest": self._weights_latest_locked(name),
                    "versions": [
                        {k: m.get(k) for k in (
                            "version", "step", "run_id", "ts",
                            "num_hosts", "n_leaves", "n_chunks",
                            "total_bytes", "delta", "base_version",
                            "delta_bytes")}
                        for m in sorted(
                            by_ver.values(),
                            key=self._weights_recency)],
                }
            pending = [{"name": n, "version": v,
                        "hosts_committed":
                            sorted(p["fragments"]),
                        "num_hosts": p["num_hosts"],
                        "age_s": round(time.monotonic() - p["started"], 3)}
                       for (n, v), p in self._weights_pending.items()]
            return {"names": out, "pending": pending}

    # ----------------------------------------------------------- metrics
    # Reference: src/ray/stats/metric_exporter.cc -> metrics agent ->
    # Prometheus; here workers push their registry snapshots and the
    # conductor is the aggregation point the exporter reads.

    def report_metrics(self, worker_id: str,
                       snapshot: List[Dict[str, Any]]) -> None:
        with self._lock:
            if not hasattr(self, "_metrics"):
                self._metrics: Dict[str, List[Dict[str, Any]]] = {}
            self._metrics[worker_id] = snapshot

    def get_metrics(self) -> Dict[str, List[Dict[str, Any]]]:
        with self._lock:
            return dict(getattr(self, "_metrics", {}))

    # ------------------------------------------------------------------ jobs
    # Reference: GcsJobManager (src/ray/gcs/gcs_server/gcs_job_manager) +
    # dashboard/modules/job JobManager — entrypoint drivers run as head-node
    # subprocesses with RAY_TPU_ADDRESS injected, logs captured per job.

    def submit_job(self, entrypoint: str,
                   env: Optional[Dict[str, str]] = None,
                   submission_id: Optional[str] = None,
                   working_dir: Optional[str] = None,
                   metadata: Optional[Dict[str, str]] = None) -> str:
        import uuid as _uuid

        job_id = submission_id or f"job_{_uuid.uuid4().hex[:12]}"
        with self._lock:
            if job_id in getattr(self, "_jobs", {}):
                raise ValueError(
                    f"job submission id {job_id!r} already exists "
                    "(reference JobManager rejects duplicates)")
        logs = os.path.join(self._session_dir, "logs")
        os.makedirs(logs, exist_ok=True)
        log_path = os.path.join(logs, f"{job_id}.log")
        host, port = self.address
        penv = dict(os.environ)
        penv.update(env or {})
        penv["RAY_TPU_ADDRESS"] = f"{host}:{port}"
        penv["RAY_TPU_JOB_ID"] = job_id
        log_f = open(log_path, "ab")
        try:
            proc = subprocess.Popen(
                entrypoint, shell=True, env=penv,
                cwd=working_dir or os.getcwd(),
                stdout=log_f, stderr=subprocess.STDOUT,
                start_new_session=True)
        finally:
            log_f.close()
        with self._lock:
            self._jobs[job_id] = {
                "job_id": job_id, "entrypoint": entrypoint,
                "start_time": time.time(), "end_time": None,
                "log_path": log_path, "proc": proc, "stopped": False,
                "metadata": dict(metadata or {})}
            self._dirty = True
        return job_id

    def _job_status_locked(self, rec: Dict[str, Any]) -> str:
        proc = rec["proc"]
        if proc is None:  # restored after a conductor restart
            return rec.get("status", "FAILED")
        code = proc.poll()
        if code is None:
            return "RUNNING"
        if rec["end_time"] is None:
            rec["end_time"] = time.time()
            self._dirty = True  # terminal status reached; persist it
        if rec["stopped"]:
            return "STOPPED"
        return "SUCCEEDED" if code == 0 else "FAILED"

    def get_job(self, job_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = getattr(self, "_jobs", {}).get(job_id)
            if rec is None:
                return None
            return {k: v for k, v in dict(
                rec, status=self._job_status_locked(rec)).items()
                if k != "proc"}

    def list_jobs(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{k: v for k, v in dict(
                r, status=self._job_status_locked(r)).items() if k != "proc"}
                for r in getattr(self, "_jobs", {}).values()]

    def stop_job(self, job_id: str) -> bool:
        with self._lock:
            rec = getattr(self, "_jobs", {}).get(job_id)
            if rec is None or rec["proc"] is None \
                    or rec["proc"].poll() is not None:
                return False
            rec["stopped"] = True
            proc = rec["proc"]
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except (OSError, ProcessLookupError):
            proc.terminate()
        return True

    def get_job_logs(self, job_id: str, tail_bytes: int = 1 << 20) -> str:
        with self._lock:
            rec = getattr(self, "_jobs", {}).get(job_id)
        if rec is None:
            raise KeyError(job_id)
        try:
            with open(rec["log_path"], "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - tail_bytes))
                return f.read().decode("utf-8", "replace")
        except FileNotFoundError:
            return ""

    def shutdown_cluster(self) -> bool:
        """Remote stop for `ray_tpu stop` — tears the head down shortly
        after replying."""

        def later():
            time.sleep(0.2)
            try:
                self.stop()
            finally:
                os._exit(0)

        threading.Thread(target=later, daemon=True).start()
        return True

    # ------------------------------------------------------------------ misc

    def ping(self) -> str:
        return "pong"

    def session_info(self) -> Dict[str, Any]:
        from .worker import _MACHINE_ID

        return {"session_dir": self._session_dir,
                "head_node_id": self._head_node_id,
                "machine": _MACHINE_ID}

    # ----------------------------------------------------------- persistence

    def _flush_state(self) -> None:
        """Write the durable tables to disk (atomic rename). Called by the
        monitor when dirty and on stop — mutations only mark dirty, so the
        hot path never pays the disk write."""
        import pickle

        with self._lock:
            if not self._dirty:
                return
            self._dirty = False
            jobs = {}
            for jid, r in self._jobs.items():
                meta = {k: v for k, v in r.items() if k != "proc"}
                meta["status"] = self._job_status_locked(r)
                jobs[jid] = meta
            blob = pickle.dumps({
                "kv": {ns: dict(d) for ns, d in self._kv.items()},
                "named_actors": dict(self._named_actors),
                "actors": list(self._actors.values()),
                "pgs": list(self._pgs.values()),
                "jobs": jobs,
                # weight registry (metadata only — chunks live in their
                # producers' stores and survive a conductor bounce; a
                # forgotten registry could never send the gc/reap
                # notices producers' chunk lifetimes depend on)
                "weights": {
                    "committed": {n: dict(bv) for n, bv in
                                  self._weights_committed.items()},
                    "pending": [
                        {"name": n, "version": v,
                         "num_hosts": p["num_hosts"],
                         "run_id": p.get("run_id", ""),
                         "step": p.get("step"),
                         "fragments": dict(p["fragments"])}
                        for (n, v), p in self._weights_pending.items()],
                },
                # a restarted conductor mints a fresh head node id: PG
                # bundle assignments pointing at THIS id must be remapped
                "head_node_id": self._head_node_id,
            })
        tmp = self._persist_path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._persist_path)
        except OSError:
            with self._lock:
                self._dirty = True  # retry next monitor tick

    def _restore_state(self) -> None:
        """Load a prior snapshot from this session dir (conductor restart).
        Actor records come back with their worker addresses, so handles
        keep working against surviving worker processes; those workers'
        records are reconstructed provisionally and confirmed (pid filled
        in) by their periodic re-registration."""
        import pickle

        if not os.path.exists(self._persist_path):
            return
        try:
            with open(self._persist_path, "rb") as f:
                state = pickle.load(f)
        except (OSError, pickle.UnpicklingError, EOFError):
            return
        # Restore runs from __init__, before the serving threads
        # start, but the same records are later mutated under the
        # lock; take it here too so every mutation site is covered.
        with self._lock:
            head = self._nodes[self._head_node_id]
            self._kv = {ns: dict(d) for ns, d in state.get("kv", {}).items()}
            self._named_actors = dict(state.get("named_actors", {}))
            now = time.monotonic()
            # PGs first: live actors scheduled inside one hold the PG's
            # synthetic `_pg_<id>_<k>` keys, which must exist to re-charge.
            # Head-assigned bundles re-reserve now; bundles assigned to agent
            # nodes re-reserve when their node re-registers
            # (_reapply_pg_reservations from register_node).
            old_head = state.get("head_node_id")
            for pg in state.get("pgs", []):
                if pg.state != "CREATED":
                    continue
                if not getattr(pg, "assignments", None):
                    pg.assignments = [self._head_node_id] * len(pg.bundles)
                else:
                    pg.assignments = [
                        self._head_node_id if nid == old_head else nid
                        for nid in pg.assignments]
                for b, nid in zip(pg.bundles, pg.assignments):
                    if nid != self._head_node_id:
                        continue
                    self._acquire_resources(head, b)
                    for k, v in b.items():
                        pk = f"_pg_{pg.pg_id}_{k}"
                        head.total[pk] = head.total.get(pk, 0) + v
                        head.available[pk] = head.available.get(pk, 0) + v
                self._pgs[pg.pg_id] = pg
            for rec in state.get("actors", []):
                self._actors[rec.actor_id] = rec
                if rec.state in ("ALIVE", "RESTARTING") and rec.worker_id:
                    # mirror lease_worker: a PG-scheduled actor's lease holds
                    # the bundle's prefixed keys, NOT head general capacity
                    if rec.placement_group_id:
                        held = {f"_pg_{rec.placement_group_id}_{k}": v
                                for k, v in rec.resources.items()}
                    else:
                        held = dict(rec.resources)
                    w = WorkerRecord(worker_id=rec.worker_id,
                                     node_id=self._head_node_id,
                                     address=rec.address, state="ACTOR",
                                     resources=held,
                                     lease_node_id=self._head_node_id,
                                     restored_at=now)
                    self._workers[w.worker_id] = w
                    self._acquire_resources(head, held)
            wstate = state.get("weights") or {}
            self._weights_committed = {
                n: {int(v): m for v, m in bv.items()}
                for n, bv in (wstate.get("committed") or {}).items()}
            for p in wstate.get("pending") or []:
                # fresh TTL clock: `started` is monotonic and does not
                # survive a restart; the reaper ages them out from now
                self._weights_pending[(p["name"], int(p["version"]))] = {
                    "fragments": dict(p["fragments"]),
                    "num_hosts": int(p["num_hosts"]),
                    "run_id": p.get("run_id", ""), "step": p.get("step"),
                    "started": now}
            for jid, meta in state.get("jobs", {}).items():
                meta = dict(meta, proc=None)
                if meta.get("status") == "RUNNING":
                    # the job driver was orphaned by the crash; we can no
                    # longer supervise it
                    meta["status"] = "FAILED"
                    meta["end_time"] = meta.get("end_time") or time.time()
                self._jobs[jid] = meta

    # --------------------------------------------------------------- monitor

    def _monitor_loop(self) -> None:
        """Reap dead worker processes; restart actors; detect dead agent
        nodes by heartbeat age (reference gcs_health_check_manager.cc +
        gcs_actor_manager worker-death path)."""
        from .config import config

        node_timeout = config.node_timeout
        restore_grace = config.restore_grace
        last_mem_check = 0.0
        while not self._stopped:
            time.sleep(0.2)
            self._flush_state()
            try:
                # partial weight publishes (producer died mid-publish)
                # age out of the registry here
                self.weights_reap()
            except Exception:  # noqa: BLE001 — monitor must not die
                pass
            refresh_ms = config.memory_monitor_refresh_ms
            if refresh_ms > 0 and \
                    time.monotonic() - last_mem_check >= refresh_ms / 1000.0:
                last_mem_check = time.monotonic()
                try:
                    self._maybe_oom_kill()
                except Exception:  # noqa: BLE001 — monitor must not kill
                    pass           # the reap loop
            dead: List[WorkerRecord] = []
            with self._cv:
                agent_nodes = {nid for nid, n in self._nodes.items()
                               if n.has_agent}
                for w in self._workers.values():
                    if w.state == "DEAD":
                        continue
                    alive = True
                    if w.restored_at is not None:
                        # snapshot-restored record: presumed alive until
                        # the re-register window passes with no announce
                        alive = (time.monotonic() - w.restored_at
                                 <= restore_grace)
                    elif w.proc is not None:
                        alive = w.proc.poll() is None
                    elif w.node_id in agent_nodes:
                        # remote pid: liveness arrives via the agent's
                        # heartbeat (node_heartbeat dead_worker_ids)
                        alive = self._nodes[w.node_id].alive
                    elif w.pid is not None:
                        try:
                            os.kill(w.pid, 0)
                        except OSError:
                            alive = False
                    if not alive:
                        w.state = "DEAD"
                        self._release_resources(self._lease_release_node(w),
                                                w.resources)
                        w.resources = {}
                        self._free_worker_chips(w)
                        dead.append(w)
                        if w.address:
                            self._clients.invalidate(w.address)
                # heartbeat-expired agent nodes: mark dead, free resources
                now = time.monotonic()
                for n in self._nodes.values():
                    if (n.has_agent and n.alive
                            and now - n.last_heartbeat > node_timeout):
                        n.alive = False
                self._notify_all_locked()
            for w in dead:
                self._on_worker_death(w)

    def _maybe_oom_kill(self) -> None:
        """Memory-monitor tick (reference memory_monitor.h:52 +
        worker_killing_policy.cc): above the threshold, SIGKILL the
        greediest LOCAL worker — task workers before actors before idle —
        recording 'oom: ...' as its death cause so the submitter raises
        OutOfMemoryError instead of a bare crash. Remote nodes police
        themselves (node agent) and report causes via heartbeat."""
        from .config import config
        from .memory_monitor import MemoryMonitor

        threshold = config.memory_usage_threshold
        mon = getattr(self, "_mem_monitor", None)
        if mon is None or mon.threshold != threshold:
            mon = MemoryMonitor(threshold)
            self._mem_monitor = mon
        with self._lock:
            cands = [(w.worker_id, w.proc.pid, w.state)
                     for w in self._workers.values()
                     if w.proc is not None and w.proc.poll() is None]
        res = mon.kill_greediest(cands, "head")
        if res is None:
            return
        worker_id, cause = res
        with self._lock:
            rec = self._workers.get(worker_id)
            if rec is not None:
                rec.death_cause = cause  # submitters re-query after a
                # short grace, covering the kill→record window

    def worker_death_cause(self, worker_id: str) -> Optional[str]:
        with self._lock:
            w = self._workers.get(worker_id)
            return w.death_cause if w is not None else None

    def _on_worker_death(self, w: WorkerRecord) -> None:
        if not w.expected_death:
            # unexpected death (crash, OOM, chaos kill, host loss):
            # charge the host's failure domain and log the event —
            # this is what eventually quarantines a flaky host
            self._record_failure(w.lease_node_id or w.node_id,
                                 "worker_death",
                                 detail=w.death_cause or "",
                                 worker_id=w.worker_id)
        restart: List[str] = []
        with self._cv:
            for rec in self._actors.values():
                if rec.worker_id == w.worker_id and rec.state == "ALIVE":
                    if rec.restarts_remaining != 0:
                        if rec.restarts_remaining > 0:
                            rec.restarts_remaining -= 1
                        rec.state = "RESTARTING"
                        rec.num_restarts += 1
                        restart.append(rec.actor_id)
                    else:
                        rec.state = "DEAD"
                        rec.death_cause = "worker process died"
            self._dirty = True
            self._notify_all_locked()
        for actor_id in restart:
            self.publish("actor_state",
                         {"actor_id": actor_id, "state": "RESTARTING"})
            threading.Thread(target=self._place_actor, args=(actor_id,),
                             daemon=True).start()
        for rec in list(self._actors.values()):
            if rec.state == "DEAD" and rec.worker_id == w.worker_id:
                self.publish("actor_state",
                             {"actor_id": rec.actor_id, "state": "DEAD"})

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            workers = list(self._workers.values())
            jobs = list(getattr(self, "_jobs", {}).values())
            agents = [n.address for n in self._nodes.values()
                      if n.has_agent and n.alive]
            self._notify_all_locked()
        for addr in agents:
            try:
                self._clients.get(addr).call("stop_node", timeout=5.0)
            except Exception:
                pass
        for rec in jobs:
            if rec["proc"] is not None and rec["proc"].poll() is None:
                try:
                    os.killpg(rec["proc"].pid, signal.SIGTERM)
                except (OSError, ProcessLookupError):
                    pass
        for w in workers:
            if w.proc is not None and w.proc.poll() is None:
                # RPC first: an rpc-handler thread can os._exit without
                # waiting for the MAIN thread to notice a signal flag —
                # on a contended 1-core host, SIGTERM-only teardown of
                # fork-server workers measured ~1.7s (the signal lands
                # on a non-main thread and the main thread must be
                # scheduled before the handler runs)
                if w.address:
                    try:
                        self._clients.get(tuple(w.address)).notify(
                            "shutdown_worker")
                    except Exception:  # noqa: BLE001 — already gone
                        pass
                try:
                    w.proc.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + 3.0
        for w in workers:
            if w.proc is not None:
                try:
                    w.proc.wait(max(0.0, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    try:
                        w.proc.kill()
                        # reap: an unreaped zombie still passes the
                        # sweeper's os.kill(pid, 0) liveness probe, so
                        # its leaked segments would be skipped
                        w.proc.wait(2.0)
                    except (OSError, subprocess.TimeoutExpired):
                        pass
        self._clients.close_all()
        self._flush_state()
        from .worker_spawn import stop_fork_server

        stop_fork_server(self._session_dir)
        # workers that needed SIGKILL leaked their shm arena segments
        from .object_store import cleanup_leaked_segments

        cleanup_leaked_segments()


class Conductor:
    """Hosts a ConductorHandler on an RpcServer (in-process head or
    standalone via conductor_main)."""

    def __init__(self, resources: Dict[str, float], session_dir: str,
                 host: str = "127.0.0.1", port: int = 0,
                 worker_env: Optional[Dict[str, str]] = None):
        self.handler = ConductorHandler(resources, session_dir,
                                        worker_env=worker_env)
        self.server = RpcServer(self.handler, host=host, port=port,
                                max_workers=32, warn_slow=True)
        self.handler.address = self.server.address
        self.handler._rpc_server = self.server

    def start(self) -> "Conductor":
        self.server.start()
        self.handler._monitor.start()
        # head-node log tailer: worker prints ride the worker_logs pubsub
        # channel to subscribed drivers (reference log_monitor.py)
        from .log_monitor import LogMonitor

        self._log_monitor = LogMonitor(
            os.path.join(self.handler._session_dir, "logs"),
            lambda batch: self.handler.publish("worker_logs", batch),
            node_label="head").start()
        # head-node preemption watcher: the maintenance-event channel
        # (RAY_TPU_MAINTENANCE_EVENT file) covers the head host too
        self._preemption_watcher = None
        from ray_tpu.resilience.preemption import (ENV_VAR,
                                                   PreemptionWatcher)

        if os.environ.get(ENV_VAR):
            h = self.handler
            self._preemption_watcher = PreemptionWatcher(
                lambda ev: h.report_preemption(
                    node_id=h._head_node_id, grace_s=ev.grace_s,
                    reason=ev.reason)).start()
        return self

    @property
    def address(self) -> Tuple[str, int]:
        return self.server.address

    def stop(self) -> None:
        if getattr(self, "_preemption_watcher", None) is not None:
            self._preemption_watcher.stop()
        self.handler.stop()
        self.server.stop()
