"""Disaggregated prefill/decode serving: dedicated prefill replicas
stream KV blocks to decode replicas over the chunk fabric.

Why (the ROADMAP serving-envelope item, and the Gemma-on-TPU serving
envelope PAPERS.md: arXiv 2605.25645 measures): with prefill and decode
sharing one replica, a long prefill stalls every in-flight decode tick —
TTFT p99 and tokens/s both degrade under load. Splitting the phases
turns prefill into horizontally scalable compute-bound work and keeps
decode ticks free of head-of-line blocking:

- **PrefillServer** runs ``engine._prefill_paged`` behind the paged KV
  prefix cache (models/kvcache.py — shared system prompts still
  amortize), then publishes the prompt's KV rows plus the first token
  through ``util.chunks``: each leaf goes into the SENDER's own object
  store and only a metadata descriptor travels the control plane.
  Same no-full-copy invariant as the weight fabric and the MPMD
  activation channels — the bytes move sender -> receiver exactly once
  (shm zero-copy same-host, 64MB-ranged streaming across hosts), the
  conductor never holds payload, and the sender's ObjectRefs ARE the
  chunks' lifetime (``ack()`` releases them; a bounded retention window
  reaps unacked transfers).
- **DecodeServer** pulls the KV point-to-point with a ``ChunkFetcher``
  (shm-vs-rpc accounting) and ADOPTS it into its engine's decode slab
  via ``ContinuousBatchingEngine.adopt_prefill`` — an O(prompt_len)
  splice between ticks, never an O(pool) copy — so a decode replica
  never executes a prefill program at all (its ``_prefill_paged``
  compile cache stays flat; asserted in tests/test_disagg.py).
- **DisaggRouter** dispatches: the prefill replica is chosen by
  prefix-cache AFFINITY (a stable hash of the prompt's first cache
  block, so prompts sharing a system prompt land on the replica that
  already holds its KV), the decode replica by free-slot count; with no
  prefill tier configured it falls back to today's colocated
  single-replica path, bit-identical. On top it does **admission
  control + load shedding**: per-replica in-flight is bounded at
  capacity + ``max_queue_depth``; past the knob the request is REJECTED
  with a ``RequestShedError`` carrying ``retry_after_s`` — shed at the
  router, before the engine wedges.

On top of dispatch the router owns **request-level fault tolerance**
(the serving-plane failover invariant: an ACCEPTED request is never
silently dropped — it either streams to completion or sheds with an
attributed cause):

- every request records its prompt, its sampled-token history, and a
  per-attempt deadline; decode streams cross the actor boundary as
  chunked pulls (``DecodeServer.start_decode``/``next_tokens``) so the
  router always holds the tokens produced so far;
- on decode-replica death mid-stream the router re-runs prefill with
  the dead replica's tokens EXTENDING the prompt (the prefix cache
  makes the replay a suffix-only prefill) on a healthy prefill replica
  and resumes decode on a survivor — bit-identical to an uninterrupted
  greedy run, the correctness oracle;
- prefill death before its transfer is acked retries on another
  prefill replica (the dead process's chunk refs die with it — no
  leak by construction);
- attempts are bounded (``RAY_TPU_FAILOVER_ATTEMPTS`` extra attempts,
  default 2); exhaustion sheds with cause ``failover``, a request past
  its ``deadline_s`` sheds with cause ``deadline``.

Surfaces (the full treatment): ``util.state.disagg_status()`` +
``util.state.servefault_status()``, ``ray_tpu disagg`` / ``ray_tpu
servefault`` CLI, dashboard ``/api/disagg`` + ``/api/servefault`` +
SPA tabs, lazy Prometheus (``ray_tpu_disagg_kv_bytes_total{direction}``,
``ray_tpu_disagg_transfers_total``, ``ray_tpu_serve_shed_total``,
``ray_tpu_disagg_queue_depth``,
``ray_tpu_servefault_failovers_total{phase}``,
``ray_tpu_servefault_sheds_total{cause}``), ``disagg`` instant markers
in the merged timeline plus ``failover`` markers in its resilience
lane. Knobs: ``RAY_TPU_DISAGG_QUEUE_DEPTH`` (router backlog
bound per decode replica, default 8), ``RAY_TPU_DISAGG_RETRY_AFTER_S``
(shed hint, default 1.0), ``RAY_TPU_FAILOVER_ATTEMPTS`` (bounded
failover budget, default 2), ``RAY_TPU_MAX_ADOPTIONS_PER_TICK`` (decode
adoption cap, models/engine.py), plus the kvcache knobs on the prefill
tier. The bit-identity and zero-dropped invariants are held by
``tests/test_disagg.py`` and ``tests/test_servefault.py``.
"""
from __future__ import annotations

import itertools
import os
import queue
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, \
    Tuple

import numpy as np

from ray_tpu.exceptions import ActorError, WorkerCrashedError
from ray_tpu.observability import requests as reqtrace
from ray_tpu.util.telemetry import Pusher, emit

from .autoscale import SlidingWindow, default_target_p99_ms
from .handle import RequestShedError, shed_counter

_SERVER_SEQ = itertools.count()

# Exception shapes that mean "the replica's process is gone" (actor
# death, worker crash, or the RPC plane losing the connection) — the
# failover wrapper removes the corpse from the replica set and retries
# elsewhere. Anything else is a REQUEST failure (bad KV layout, a bug):
# it still consumes a bounded failover attempt but the replica stays.
_DEATH_TYPES = (ActorError, WorkerCrashedError, ConnectionError,
                EOFError, OSError)


def _first_token_parts(src: Any) -> Dict[str, Optional[float]]:
    """The engine's own split of a first token's wait, as the parts of
    the flight recorder's ``decode_first_token``: read off the
    colocated engine's TokenStream, or off a decode replica's pull
    reply. The prefill ends with the phase, so it goes first
    (``RequestTrace.add_phase`` clips parts in order): what a
    disaggregated request queued while its ``kv_transfer`` was still
    open is not counted twice."""
    get = src.get if isinstance(src, dict) \
        else lambda key: getattr(src, key, None)
    return {"engine_prefill": get("prefill_ms"),
            "engine_queue": get("queue_ms")}


def _first_token_waited(src: Any) -> Dict[str, int]:
    """The other requests' prefills the engine ran in ``engine_queue``,
    a count and so an attribute of the phase, not a part of it."""
    waited = src.get("prefills_waited") if isinstance(src, dict) \
        else getattr(src, "prefills_waited", None)
    return {} if waited is None else {"prefills_waited": int(waited)}


def _is_pool_exhausted(e: BaseException) -> bool:
    """An adapter-pool-exhausted failure (serve/lora.py
    LoraPoolExhausted) — matched by name because the exception may
    arrive re-wrapped across the actor boundary. It is a CAPACITY
    condition (every pool row pinned by in-flight requests), not a
    replica fault: the router sheds cause=capacity immediately instead
    of burning failover attempts replaying it onto the same full
    pools."""
    return "LoraPoolExhausted" in repr(e)


# Deterministic tenant-CONFIGURATION failures (unknown tenant, adapter
# rank over the pool ceiling, tenant tag against a pool-less replica):
# retrying cannot help — affinity re-routes to the same healthy
# replica and the error reproduces — and shedding would mislabel a
# client/operator mistake as a serving fault. The router re-raises
# them to the caller as the ValueError they are. Substring-matched
# because they may arrive re-wrapped across the actor boundary.
_LORA_CONFIG_ERRORS = ("no adapter registered for tenant",
                       "exceeds the pool's rank_max",
                       "has no lora_pool",
                       "has no adapter pool",
                       "does not fit this model's target")


def _is_lora_config_error(e: BaseException) -> bool:
    r = repr(e)
    if any(m in r for m in _LORA_CONFIG_ERRORS):
        return True
    # fabric source, tenant never published: the subscriber's registry
    # miss. Matched in two pieces (quoting around the name varies with
    # repr nesting across the actor boundary), scoped to lora/* names
    # so unrelated weight fetches keep their failover semantics.
    return "no committed version" in r and "lora/" in r


class ReplicaDeadError(RuntimeError):
    """A tier-replica call failed because the replica died; carries the
    tier/rid so the failover path can attribute and re-route."""

    def __init__(self, tier: str, rid: str, cause: BaseException):
        super().__init__(f"{tier} replica {rid} died: "
                         f"{type(cause).__name__}: {cause}")
        self.tier = tier
        self.rid = rid
        self.cause = cause

# ----------------------------------------------------- prometheus (lazy)
# Created on first component construction, never at import (the
# weights / kvcache / online pattern — rebound ONCE to a complete dict).

_metrics: Optional[Dict[str, Any]] = None
_metrics_lock = threading.Lock()


def disagg_metrics() -> Dict[str, Any]:
    global _metrics
    m = _metrics
    if m is not None:
        return m
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            _metrics = dict(
                kv_bytes=Counter(
                    "ray_tpu_disagg_kv_bytes_total",
                    "KV-block bytes moved between prefill and decode "
                    "replicas over the chunk fabric",
                    tag_keys=("direction",)),
                transfers=Counter(
                    "ray_tpu_disagg_transfers_total",
                    "completed prefill->decode KV transfers (counted "
                    "when the decode replica's fetch finishes)"),
                queue_depth=Gauge(
                    "ray_tpu_disagg_queue_depth",
                    "requests in flight through a disagg router "
                    "(executing + queued at its decode tier)",
                    tag_keys=("router",)))
    return _metrics


# Serving-plane fault-tolerance metrics, shared with the self-healer in
# serve/autoscale.py (one lazy group so every servefault number has one
# Prometheus home).
_sf_metrics: Optional[Dict[str, Any]] = None
_sf_metrics_lock = threading.Lock()


def servefault_metrics() -> Dict[str, Any]:
    global _sf_metrics
    m = _sf_metrics
    if m is not None:
        return m
    with _sf_metrics_lock:
        if _sf_metrics is None:
            from ray_tpu.util.metrics import Counter

            _sf_metrics = dict(
                failovers=Counter(
                    "ray_tpu_servefault_failovers_total",
                    "request failover attempts after a tier-replica "
                    "failure (phase=prefill|decode)",
                    tag_keys=("phase",)),
                sheds=Counter(
                    "ray_tpu_servefault_sheds_total",
                    "requests shed with an attributed cause "
                    "(capacity|deadline|failover|draining)",
                    tag_keys=("cause",)),
                replacements=Counter(
                    "ray_tpu_servefault_replacements_total",
                    "dead tier replicas replaced by the self-healer "
                    "(serve/autoscale.py)",
                    tag_keys=("tier",)),
                breaker_trips=Counter(
                    "ray_tpu_servefault_breaker_trips_total",
                    "replacement circuit-breaker OPEN transitions (a "
                    "host whose replicas die repeatedly stops getting "
                    "replacements)"))
    return _sf_metrics


def _worker():
    from ray_tpu._private import worker as worker_mod

    return worker_mod.global_worker


def _notify_resilience(event: Dict[str, Any]) -> None:
    """Failovers are recovery events: mirror them into the resilience
    event log (the merged timeline's resilience lane, beside the PR-4
    preemption/restart markers)."""
    w = _worker()
    if w is None:
        return
    try:
        w.conductor.notify("report_resilience_event", dict(event))
    except Exception:  # noqa: BLE001 — cluster shutting down
        pass


def _call(target: Any, method: str, *args, block: bool = True, **kw):
    """Invoke `method` on a local component or a ray_tpu actor handle
    (the router accepts either, so tests and the load harness can run
    replicas in-process while deployments run them as actors)."""
    fn = getattr(target, method)
    remote = getattr(fn, "remote", None)
    if remote is not None:
        import ray_tpu

        ref = remote(*args, **kw)
        return ray_tpu.get(ref) if block else ref
    return fn(*args, **kw)


def own_params(params: Any) -> Any:
    """`params` is the parameter pytree, or a zero-argument callable that
    builds it here, in the replica's own process: actor replicas then
    make their weights from a seed on their own chip, and the driver that
    creates them neither ships the weights nor touches JAX (a driver
    that has touched JAX holds every chip its replicas need)."""
    return params() if callable(params) else params


def device_record() -> Dict[str, Any]:
    """Where this process computes, as JAX reports it. An actor replica
    created without num_tpus runs on a CPU-pinned worker and nothing
    else says so: describe() and stats() carry this, the router surfaces
    it, and chip_smoke.py asserts on it. Chips are renumbered from 0
    inside a bound process, so `visible_chips` (the conductor's binding)
    and `pid` are what tell two replicas apart."""
    import jax

    dev = jax.devices()[0]  # the default device: engines place nothing
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_id": int(dev.id),
            "local_devices": len(jax.local_devices()),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "pid": os.getpid()}


def runtime_record() -> Dict[str, Any]:
    """What this replica's process has compiled and how much device
    memory it holds (peak where the backend reports it): set-up cost and
    head-room a caller budgets with, read through stats().

    `compile_cache` is `compile_cache_counts()`, all numbers: `hits`,
    `misses`, `compiles` and `compile_s` (the hand-overs to the backend,
    fetches and compiles together), and of set-up's seconds `trace_s`
    and `lower_s` (Python's tracing and lowering, which a warm cache
    does not save), `fetch_s` (the hits' retrievals) and
    `miss_compile_s` (the hand-overs that compiled). `slowest_program`
    is the `name` and `seconds` (trace, lowering and hand-over) of the
    costliest program of the newest `compile_cache.RING`, None before
    the first."""
    import jax

    from ray_tpu.util.compile_cache import (compile_cache_counts,
                                            compile_cache_programs)

    mem = jax.devices()[0].memory_stats() or {}
    slowest = max(
        ((r["trace_s"] + r["lower_s"] + r["backend_s"], r["name"])
         for r in compile_cache_programs() if "backend_s" in r),
        default=None)
    return {"compile_cache": compile_cache_counts(),
            "slowest_program": slowest and {
                "name": slowest[1], "seconds": round(slowest[0], 3)},
            "peak_bytes_in_use": mem.get("peak_bytes_in_use")}


# ------------------------------------------------------------ prefill tier

class PrefillServer:
    """One prefill replica: compute-bound prefill behind the prefix
    cache, KV rows published as sender-owned chunks.

    ``prefill()`` returns a metadata-only record (safe to route through
    actors/the control plane): the first token, its logprob score, the
    prefix-cache outcome, and the chunk descriptor a DecodeServer
    fetches the KV from. The prompt's cache pins are released as soon as
    the KV is exported — blocks stay cached for future lookups."""

    def __init__(self, params: Any, config: Any, *,
                 prefix_cache: bool = True,
                 kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 kv_int8: Optional[bool] = None,
                 retain: int = 32,
                 server_id: Optional[str] = None,
                 chaos: Optional[str] = None,
                 chaos_replica: int = 0,
                 lora: Any = None,
                 lora_pool_slots: Optional[int] = None,
                 lora_rank_max: Optional[int] = None,
                 kvplane: Optional[bool] = None,
                 kvplane_arena_bytes: Optional[int] = None):
        from ray_tpu.models.family import refuse, slab_spec
        from ray_tpu.models.kvcache import (PagedKVCache,
                                            kv_int8_default,
                                            resolve_pool_config)

        import jax.numpy as jnp

        from ray_tpu.resilience.chaos import serve_monkey_from_spec
        from ray_tpu.util.chunks import local_machine_id

        from .lora import build_pool

        self.params = params = own_params(params)
        self.config = config
        self.server_id = server_id or \
            f"pf-{os.getpid()}-{next(_SERVER_SEQ)}"
        self.machine = local_machine_id()
        self.device = device_record()
        # scripted fault injection (resilience/chaos.py kill_replica):
        # meaningful on ACTOR replicas — the fire is an os._exit
        self._chaos = serve_monkey_from_spec(chaos, "prefill",
                                             chaos_replica)
        # int8 KV blocks (models/kvcache.py): halve the pool's bytes
        # per block -> doubled default pool -> higher prefix residency
        # on the tier that actually owns prefix reuse
        if kv_int8 is None:
            kv_int8 = kv_int8_default()
        self.kv_int8 = bool(kv_int8)
        # a transfer carries ck and cv, one stack of the prompt's
        # length: any other cache is refused before a pool is built
        spec = slab_spec(config, 1)
        refuse(spec, "transfer")
        block_size, pool_blocks = resolve_pool_config(
            config, kv_block_size, kv_pool_blocks, int8=self.kv_int8)
        self.kv_cache: Optional[PagedKVCache] = (
            PagedKVCache(config, block_size=block_size,
                         num_blocks=pool_blocks, int8=self.kv_int8)
            if prefix_cache else None)
        # global KV plane (serve/kvplane.py): the tier-2 host arena
        # catches HBM-evicted blocks instead of letting them die, and
        # tier 3 publishes cold hot-prompt prefixes to the chunk
        # fabric under the conductor's prefix directory
        from .kvplane import HostArena, kvplane_enabled
        if kvplane is None:
            kvplane = kvplane_enabled()
        self.kvplane = bool(kvplane) and self.kv_cache is not None
        self.arena: Optional[HostArena] = None
        if self.kvplane:
            self.arena = HostArena(max_bytes=kvplane_arena_bytes,
                                   replica=self.server_id)
            self.kv_cache.attach_arena(self.arena)
        # multi-tenant LoRA (serve/lora.py): prefill runs under each
        # request's tenant adapter, so the prefill tier pages adapters
        # exactly like the decode tier; an adapter hot-swap flushes
        # that tenant's (namespace-keyed) prefix-cache entries
        self.lora_pool = build_pool(config, lora, slots=lora_pool_slots,
                                    rank_max=lora_rank_max)
        if self.lora_pool is not None and self.kv_cache is not None:
            # namespaces are (tenant, version)-stamped — correctness
            # never needs this flush; it eagerly reclaims the
            # superseded version's blocks
            self.lora_pool.add_swap_listener(
                lambda tenant, old, _p=self.lora_pool:
                self.kv_cache.invalidate(
                    namespace=_p.cache_namespace(tenant, old)))
        self._empty_prefix = jnp.zeros(spec.stack_shape(0), spec.dtype)
        # retention bounds how many unacked transfers this server keeps
        # alive; size it past the decode tier's admitted bound
        # (decode_replicas * (max_batch + queue_depth)) — transfers are
        # held from publish until the router's post-decode ack, and
        # prefix affinity can route all of them here, so a smaller
        # window reaps chunks a decode replica is about to fetch
        self._retain = max(1, int(retain))
        self._lock = threading.Lock()
        # transfer_id -> chunk refs; holding them IS the chunks'
        # lifetime (ack() or retention-window reap drops them)
        self._held: "OrderedDict[str, List[Any]]" = OrderedDict()
        # tier-3 holder state: digest -> (namespace, chunk refs). The
        # refs ARE the published prefix's lifetime — keep-last-K so one
        # replica can never pin unbounded fabric bytes; evicting a
        # digest retracts its directory entry. _t3_known throttles
        # re-export attempts (committed OR lost to a racing holder).
        self._t3_refs: "OrderedDict[str, tuple]" = OrderedDict()
        self._t3_known: "OrderedDict[str, bool]" = OrderedDict()
        self._t3_keep = 8
        self._kvp_stats = {k: 0 for k in (
            "tier3_publishes", "tier3_adopts", "tier3_adopted_blocks",
            "tier3_reused_tokens", "tier3_fetched_bytes",
            "evict_storms", "storm_evicted_blocks")}
        self._seq = itertools.count()
        self._stats = {k: 0 for k in (
            "prefills", "prefilled_tokens", "reused_tokens",
            "published_transfers", "published_bytes", "acked",
            "reaped_unacked")}
        self._pusher = Pusher("disagg", self.server_id)
        self._kv_pusher = Pusher("kvcache", self.server_id)
        self._kvplane_pusher = Pusher("kvplane", self.server_id)
        disagg_metrics()  # lazy registration before the first event

    # ---------------------------------------------------------- data plane

    def prefill(self, prompt_tokens,
                tenant: Optional[str] = None,
                kvplane_hint: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Any]:
        """Prefill one prompt (suffix-only on a cache hit) and publish
        its KV rows. Returns the transfer record for a DecodeServer.
        `tenant` (multi-tenant LoRA): prefill under that tenant's
        adapter — paged through this server's pool — with the prefix
        cache keyed by (tenant, prompt); the record carries the tag so
        the decode tier adopts under the same adapter.
        `kvplane_hint` (serve/kvplane.py): a prefix-directory entry
        whose holder the router could not dispatch to — this replica
        fetches the published prefix over the transfer plane and
        adopts it BEFORE the cache lookup, so the prefill is
        suffix-only anyway (a failed fetch just prefills from scratch:
        tier 3 is an accelerator, not a dependency)."""
        from ray_tpu.models.engine import _prefill_with_cache
        from ray_tpu.util import chunks

        if self._chaos is not None:
            self._chaos.on_request()  # may os._exit (kill_replica)
            storm = self._chaos.take_storm()
            if storm and self.kv_cache is not None:
                # scripted eviction storm (chaos evict_storm): with the
                # arena attached the evicted blocks SPILL to tier 2
                # instead of dying — the chaos test's whole point
                evicted = self.kv_cache.force_evict(storm)
                with self._lock:
                    self._kvp_stats["evict_storms"] += 1
                    self._kvp_stats["storm_evicted_blocks"] += evicted
                emit("kvplane", {"kind": "evict_storm",
                                 "replica": self.server_id,
                                 "blocks": evicted,
                                 "requested": storm})
        prompt = np.asarray(prompt_tokens, np.int32).reshape(1, -1)
        plen = prompt.shape[1]
        if plen < 1:
            raise ValueError("empty prompt")
        adapter = None
        namespace = None
        if tenant is not None:
            if self.lora_pool is None:
                raise ValueError(
                    f"request for tenant {tenant!r} but this prefill "
                    f"server has no adapter pool (lora= ctor arg)")
            adapter, aver = self.lora_pool.adapter_slice(
                self.lora_pool.acquire(tenant), with_version=True)
            namespace = self.lora_pool.cache_namespace(tenant, aver)
        kvp_info: Dict[str, Any] = {}
        if self.arena is not None:
            # bracket the prefill: tier-2 re-adoptions inside the cache
            # lookup accumulate into this request's attribution
            self.arena.begin_request()
        if kvplane_hint is not None and self.kvplane \
                and _worker() is not None:
            t3 = self._adopt_t3(kvplane_hint, prompt[0], namespace)
            if t3 is not None:
                kvp_info["tier3"] = t3
        try:
            ck, cv, _state, table, first, score, outcome, reused, \
                suffix_len, _counts = _prefill_with_cache(
                                    self.params, self.config,
                                    self.kv_cache, prompt,
                                    self._empty_prefix, adapter=adapter,
                                    namespace=namespace)
        finally:
            if adapter is not None:
                # the adapter pin covers exactly the prefill compute;
                # refcount-0 adapters stay resident for the next
                # request (the pool's LRU owns reclamation)
                self.lora_pool.release(tenant)
        if self.kv_cache is not None:
            # pins drop NOW: the KV is exported below, and refcount-0
            # blocks stay cached for the next prompt's lookup
            self.kv_cache.release(table)
        if self.arena is not None:
            t2 = self.arena.end_request()
            if t2.get("blocks"):
                kvp_info["tier2"] = t2
        # the transfer payload: exactly the prompt's KV rows, host-side
        # (this is the ONLY materialization outside the fill itself —
        # the same single-copy the colocated splice reads on-device)
        kv_k = np.asarray(ck[:, :plen])
        kv_v = np.asarray(cv[:, :plen])
        del ck, cv
        rec: Dict[str, Any] = {
            "transfer_id": f"{self.server_id}-{next(self._seq)}",
            "plen": plen, "first_token": first, "score": score,
            "outcome": outcome, "reused_tokens": int(reused),
            "prefill_server": self.server_id,
            # the prompt's actual tokens ride the (metadata) record so
            # the decode tier's speculative proposer drafts from the
            # same context the colocated engine would — tiny next to
            # the KV payload, and the adopting engine's n-gram lookup
            # is useless over the zero placeholder prompt otherwise
            "prompt_tokens": [int(t) for t in prompt[0]],
        }
        if tenant is not None:
            rec["tenant"] = tenant
        if kvp_info:
            # rides the metadata record back to the router, which turns
            # it into kvplane_tier2/3_fetch flight-recorder phases
            rec["kvplane"] = kvp_info
        nbytes = int(kv_k.nbytes + kv_v.nbytes)
        w = _worker()
        if w is not None:
            refs, desc = chunks.put_tree(w, {"k": kv_k, "v": kv_v})
            rec["kv"] = desc
            reaped = []
            with self._lock:
                self._held[rec["transfer_id"]] = refs
                while len(self._held) > self._retain:
                    reaped.append(self._held.popitem(last=False))
                self._stats["reaped_unacked"] += len(reaped)
        else:
            # clusterless (unit tests / in-process harness): the arrays
            # ride the record directly — no chunk plane to publish to
            rec["kv_inline"] = (kv_k, kv_v)
        # send is counted for BOTH paths: the receiver counts recv for
        # inline adoptions too, and a consumer cross-checking
        # send == recv must see the totals agree in either mode
        disagg_metrics()["kv_bytes"].inc(
            nbytes, tags={"direction": "send"})
        with self._lock:
            self._stats["prefills"] += 1
            self._stats["prefilled_tokens"] += suffix_len
            self._stats["reused_tokens"] += int(reused)
            self._stats["published_transfers"] += 1
            self._stats["published_bytes"] += nbytes
        emit("disagg", {"kind": "kv_publish", "server": self.server_id,
                        "transfer_id": rec["transfer_id"],
                        "bytes": nbytes, "plen": plen,
                        "outcome": outcome})
        if w is not None and self.kvplane:
            self._maybe_publish_t3(prompt[0], namespace)
        self.publish_telemetry()
        return rec

    # -------------------------------------------- global KV plane (tier 3)

    def _adopt_t3(self, entry: Dict[str, Any], tokens,
                  namespace: Optional[str]) -> Optional[Dict[str, Any]]:
        """Fetch a directory entry's published prefix over the chunk
        fabric and adopt it into the HBM pool ahead of the lookup.
        Returns the fetch attribution (for the flight recorder) or
        None when nothing crossed the wire."""
        from . import kvplane as kvp

        t0 = time.perf_counter()
        try:
            adopted, fst = kvp.fetch_and_adopt(
                _worker(), self.kv_cache, entry, tokens, namespace)
        except Exception:  # noqa: BLE001 — never fail the prefill
            return None
        ms = (time.perf_counter() - t0) * 1e3
        fetched = int(fst.get("fetched_bytes", 0))
        reused = int(adopted) * self.kv_cache.block_size
        with self._lock:
            if adopted:
                self._kvp_stats["tier3_adopts"] += 1
                self._kvp_stats["tier3_adopted_blocks"] += int(adopted)
                self._kvp_stats["tier3_reused_tokens"] += reused
            self._kvp_stats["tier3_fetched_bytes"] += fetched
        if adopted:
            emit("kvplane", {"kind": "tier3_adopt",
                             "replica": self.server_id,
                             "blocks": int(adopted),
                             "tokens": reused, "nbytes": fetched,
                             "namespace": namespace})
        if not adopted and not fetched:
            return None
        return {"blocks": int(adopted), "tokens": reused,
                "nbytes": fetched, "ms": round(ms, 3)}

    def _maybe_publish_t3(self, tokens, namespace: Optional[str]
                          ) -> None:
        """Publish the prompt's longest cached full-block prefix to
        tier 3 — chunk-fabric objects plus the conductor's prefix
        directory commit — at most once per digest from this replica.
        The held refs are the published object's lifetime: keep-last-K,
        and an evicted digest retracts its directory entry so lookups
        stop routing to bytes that are gone. Best-effort throughout:
        tier 3 is an accelerator, never a dependency."""
        from ray_tpu.models.kvcache import prefix_digests

        from . import kvplane as kvp

        if not kvp.directory_enabled() or self.kv_cache is None:
            return
        digs = prefix_digests(tokens, self.kv_cache.block_size,
                              namespace)
        if len(digs) < kvp.t3_min_blocks():
            return  # prompt too short to ever clear the publish floor
        head = digs[0]  # longest chain — the dedup/throttle key
        with self._lock:
            if head in self._t3_known:
                self._t3_known.move_to_end(head)
                return
        w = _worker()
        if w is None:
            return
        try:
            out = kvp.publish_prefix(w, self.kv_cache, tokens,
                                     namespace, self.server_id,
                                     machine=self.machine)
        except Exception:  # noqa: BLE001 — directory outage
            return
        dropped: List[tuple] = []
        with self._lock:
            self._t3_known[head] = out is not None
            while len(self._t3_known) > 4 * self._t3_keep:
                self._t3_known.popitem(last=False)
            if out is not None:
                digest_hex, refs = out
                self._t3_refs[digest_hex] = (namespace, refs)
                self._kvp_stats["tier3_publishes"] += 1
                while len(self._t3_refs) > self._t3_keep:
                    old_digest, (old_ns, _refs) = \
                        self._t3_refs.popitem(last=False)
                    dropped.append((old_digest, old_ns))
        for old_digest, old_ns in dropped:
            try:
                # the refs just died — retract the directory entry so
                # lookups stop routing fetches at a gone object
                w.conductor.call("kvplane_unpublish", old_ns or "",
                                 old_digest, timeout=5.0)
            except Exception:  # noqa: BLE001 — best-effort retract
                pass

    def kvplane_stats(self) -> Dict[str, Any]:
        """This replica's kvplane snapshot (tier-2 arena + tier-3
        holder counters + per-caller fabric attribution) — one
        component of the `kvplane` telemetry row's aggregate."""
        from ray_tpu.util import chunks

        s: Dict[str, Any] = {"role": "prefill",
                             "server_id": self.server_id,
                             "enabled": self.kvplane}
        if self.arena is not None:
            s.update(self.arena.stats())
        with self._lock:
            s.update(self._kvp_stats)
            s["t3_held_refs"] = len(self._t3_refs)
        s["fabric"] = chunks.caller_totals("kvplane")
        return s

    def set_retention(self, retain: int) -> None:
        """Raise the retention window (routers push the decode tier's
        admitted bound at construction so the default can never reap an
        in-flight transfer); never shrinks below the constructor
        value."""
        with self._lock:
            self._retain = max(self._retain, int(retain))

    def ack(self, transfer_id: str) -> bool:
        """Receiver finished fetching: drop the chunks' refs (their
        lifetime). Returns False if retention already reaped them."""
        with self._lock:
            held = self._held.pop(transfer_id, None)
            if held is not None:
                self._stats["acked"] += 1
        return held is not None

    def describe(self) -> Dict[str, Any]:
        """Registration record for a router: identity + host (the
        decode-side placement-affinity input)."""
        return {"server_id": self.server_id, "role": "prefill",
                "machine": self.machine, "device": self.device,
                "lora": self.lora_pool is not None,
                "kvplane": self.kvplane,
                # the router computes directory digests with OUR block
                # size — digest chains only match when they agree
                "kv_block_size": (self.kv_cache.block_size
                                  if self.kv_cache is not None
                                  else None)}

    def publish_adapter(self, tenant: str,
                        adapter: Dict[str, Any]) -> int:
        """Publish/replace a tenant's adapter on this replica's LOCAL
        source (actor-friendly — the in-process twin of a weight-fabric
        publish; fabric-backed pools take publishes through
        serve.lora.publish_adapter instead). The pool sees the tenant
        dirty and hot-swaps on the next acquire."""
        if self.lora_pool is None:
            raise ValueError("this prefill server has no adapter pool")
        return int(self.lora_pool.source.publish(tenant, adapter))

    def refresh_adapter(self, tenant: str) -> bool:
        """Force the resident adapter to the newest published version
        now (the dirty flag does it lazily on the next request)."""
        if self.lora_pool is None:
            return False
        return self.lora_pool.refresh(tenant)

    def prepare_for_shutdown(self, timeout_s: float = 30.0) -> bool:
        """Grace drain (the serve/replica.py shape, reused by autoscale
        scale-down): wait until every published transfer has been acked
        — a decode replica may still be fetching our chunks — then
        report whether the drain completed. The chunks' refs are this
        object's lifetime either way; the caller frees them by dropping
        the server."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        while True:
            with self._lock:
                held = len(self._held)
            if held == 0 or time.monotonic() >= deadline:
                break
            time.sleep(0.05)
        # retract this holder's directory entries: the tier-3 refs die
        # with the replica, so lookups must stop routing fetches here
        # (a stale entry is only a wasted fetch, but why leave one)
        w = _worker()
        with self._lock:
            t3 = list(self._t3_refs.items())
            self._t3_refs.clear()
        if w is not None:
            for digest_hex, (ns, _refs) in t3:
                try:
                    w.conductor.call("kvplane_unpublish", ns or "",
                                     digest_hex, timeout=5.0)
                except Exception:  # noqa: BLE001 — conductor gone too
                    pass
        self.publish_telemetry(force=True)
        return held == 0

    # ------------------------------------------------------------ telemetry

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            s: Dict[str, Any] = dict(self._stats)
            s["held_transfers"] = len(self._held)
        s["role"] = "prefill"
        s["server_id"] = self.server_id
        s["device"] = self.device
        s["runtime"] = runtime_record()
        if self.kv_cache is not None:
            s["prefix_cache"] = self.kv_cache.stats()
        if self.lora_pool is not None:
            s["lora"] = self.lora_pool.stats()
        return s

    def kv_stats(self) -> Dict[str, Any]:
        """Engine-shaped snapshot for the kvcache surface (the prefill
        tier is where prefix reuse happens under disaggregation)."""
        s: Dict[str, Any] = (self.kv_cache.stats() if self.kv_cache
                             else {"enabled": False})
        with self._lock:
            s.update(engine_id=self.server_id, phase="prefill",
                     prefill_calls=self._stats["prefills"],
                     admitted=self._stats["prefills"],
                     prefill_admitted=self._stats["prefills"],
                     adopted=0)
        return s

    def publish_telemetry(self, force: bool = False) -> None:
        if not self._pusher.push(self.stats, force=force):
            return
        if self.lora_pool is not None:
            self.lora_pool.publish_telemetry(force=force)

        def kv_events():
            kv = (self.kv_cache.drain_events()
                  if self.kv_cache is not None else [])
            for ev in kv:
                ev.setdefault("engine", self.server_id)
            return kv

        self._kv_pusher.push(self.kv_stats, kv_events, force=True)
        if self.arena is not None:
            self._kvplane_pusher.push(self.kvplane_stats,
                                      self.arena.drain_events, force=True)


# ------------------------------------------------------------- decode tier

class _CountedStream:
    """Iterates an adopted TokenStream and folds the drained token count
    into the owning DecodeServer's ``decoded_tokens`` (in the finally, so
    an abandoned/failed stream still accounts what it actually yielded).
    Everything else proxies to the underlying stream."""

    def __init__(self, server: "DecodeServer", stream: Any):
        self._server = server
        self._stream = stream

    def __iter__(self):
        n = 0
        try:
            for tok in self._stream:
                n += 1
                yield tok
        finally:
            self._server._count_decoded(n)

    def __getattr__(self, name: str) -> Any:
        return getattr(self._stream, name)


class DecodeServer:
    """One decode replica: a prefix-cache-DISABLED batching engine that
    only ever adopts prefilled KV — it never runs a prefill program
    (``prefill_programs()`` reports this process's `_prefill_paged`
    compile-cache size so tests can assert it stays flat)."""

    def __init__(self, params: Any, config: Any, *,
                 max_batch: int = 8,
                 server_id: Optional[str] = None,
                 chaos: Optional[str] = None,
                 chaos_replica: int = 0,
                 lora: Any = None,
                 lora_pool_slots: Optional[int] = None,
                 lora_rank_max: Optional[int] = None,
                 **engine_kw):
        from ray_tpu.models.engine import ContinuousBatchingEngine

        from ray_tpu.resilience.chaos import serve_monkey_from_spec
        from ray_tpu.util.chunks import local_machine_id

        from .lora import build_pool

        engine_kw.setdefault("prefix_cache", False)
        # multi-tenant LoRA: the decode tick applies each slot's
        # adapter, so the decode tier pages adapters through its own
        # pool (the engine pins at adoption, releases at slot-free)
        self.lora_pool = build_pool(config, lora, slots=lora_pool_slots,
                                    rank_max=lora_rank_max)
        if self.lora_pool is not None:
            engine_kw.setdefault("lora_pool", self.lora_pool)
        self.engine = ContinuousBatchingEngine(own_params(params), config,
                                               max_batch=max_batch,
                                               **engine_kw)
        self.server_id = server_id or \
            f"dec-{os.getpid()}-{next(_SERVER_SEQ)}"
        self.machine = local_machine_id()
        self.device = device_record()
        self._chaos = serve_monkey_from_spec(chaos, "decode",
                                             chaos_replica)
        self._lock = threading.Lock()
        # open chunked-pull streams (start_decode/next_tokens): handle
        # -> [TokenStream, last-activity]. Done streams clean themselves
        # up; abandoned ones (a router that shed on deadline mid-pull)
        # are reaped by IDLE age — every pull refreshes the stamp, so a
        # slow client's long stream is never reaped mid-request — and a
        # handle can never leak an engine request object forever.
        self._streams: Dict[str, List[Any]] = {}
        self._stats = {k: 0 for k in (
            "transfers", "kv_fetched_bytes", "shm_bytes", "rpc_bytes",
            "chunks_local", "decoded_tokens")}
        self._pusher = Pusher("disagg", self.server_id)
        disagg_metrics()

    _STREAM_REAP_S = 600.0

    # ---------------------------------------------------------- data plane

    def _adopt(self, rec: Dict[str, Any], max_new_tokens: int,
               eos_token: Optional[int], timeout_s: float):
        from ray_tpu.util import chunks

        if self._chaos is not None:
            self._chaos.on_request()  # may os._exit (kill_replica)
        t_fetch0 = time.perf_counter()
        desc = rec.get("kv")
        if desc is not None:
            w = _worker()
            if w is None:
                raise RuntimeError(
                    "a chunk-published transfer needs a live cluster "
                    "(ray_tpu.init) on the decode side")
            fetcher = chunks.ChunkFetcher(w, caller="kv")
            tree = chunks.fetch_tree(w, desc, fetcher)
            kv_k, kv_v = tree["k"], tree["v"]
            acc = fetcher.stats()
        else:
            kv_k, kv_v = rec["kv_inline"]
            acc = {"chunks_local": 2, "chunks_fetched": 0,
                   "fetched_bytes": 0, "shm_bytes": 0, "rpc_bytes": 0}
        fetch_ms = (time.perf_counter() - t_fetch0) * 1e3
        nbytes = int(kv_k.nbytes + kv_v.nbytes)
        # adopt (which VALIDATES length bounds and KV layout) before any
        # accounting: a rejected adoption must not leave transfers >
        # adopted or a kv_transfer marker with no decode behind it — the
        # surfaces assert one set of numbers
        stream = self.engine.adopt_prefill(
            rec["plen"], rec["first_token"], kv_k, kv_v,
            max_new_tokens, eos_token, score=rec.get("score", 0.0),
            cache_outcome=rec.get("outcome"),
            reused_tokens=rec.get("reused_tokens", 0),
            adapter_id=rec.get("tenant"),
            prompt_tokens=rec.get("prompt_tokens"),
            timeout_s=timeout_s)
        with self._lock:
            self._stats["transfers"] += 1
            self._stats["kv_fetched_bytes"] += acc["fetched_bytes"]
            self._stats["shm_bytes"] += acc["shm_bytes"]
            self._stats["rpc_bytes"] += acc["rpc_bytes"]
            self._stats["chunks_local"] += acc["chunks_local"]
        m = disagg_metrics()
        m["transfers"].inc()
        m["kv_bytes"].inc(nbytes, tags={"direction": "recv"})
        emit("disagg", {"kind": "kv_transfer", "server": self.server_id,
                        "transfer_id": rec.get("transfer_id"),
                        "bytes": nbytes, "plen": rec["plen"],
                        "shm_bytes": acc["shm_bytes"],
                        "rpc_bytes": acc["rpc_bytes"],
                        "outcome": rec.get("outcome")})
        # flight recorder: in-process routers have the request trace
        # active on THIS thread (the open kv_transfer span absorbs the
        # fetch breakdown); an actor-mode replica has no thread-local
        # and pushes the breakdown as a remote child phase instead
        rt = rec.get("_reqtrace")
        if reqtrace.current_trace() is not None:
            reqtrace.annotate(kv_fetch_ms=round(fetch_ms, 3),
                              kv_bytes=nbytes,
                              shm_bytes=int(acc["shm_bytes"]),
                              rpc_bytes=int(acc["rpc_bytes"]),
                              chunks_local=int(acc["chunks_local"]))
        elif isinstance(rt, dict) and rt.get("request_id"):
            reqtrace.push_remote_phase(
                rt["request_id"], "kv_transfer_remote", fetch_ms,
                attempt=int(rt.get("attempt", 1)),
                server=self.server_id, kv_bytes=nbytes,
                shm_bytes=int(acc["shm_bytes"]),
                rpc_bytes=int(acc["rpc_bytes"]))
        return stream

    def stream_from(self, rec: Dict[str, Any], max_new_tokens: int,
                    eos_token: Optional[int] = None,
                    timeout_s: float = 120.0):
        """Adopt a transfer and return the live token stream (in-process
        callers only — streams do not cross the actor boundary). The
        stream proxies the underlying TokenStream (``cache_outcome``
        etc.) and folds drained tokens into ``decoded_tokens`` so the
        streaming path reports the same one set of numbers as
        ``decode_from``."""
        return _CountedStream(
            self, self._adopt(rec, max_new_tokens, eos_token, timeout_s))

    def decode_from(self, rec: Dict[str, Any], max_new_tokens: int,
                    eos_token: Optional[int] = None,
                    timeout_s: float = 120.0) -> List[int]:
        """Adopt a transfer and decode it to completion (actor-friendly:
        returns the full token list, first token included)."""
        stream = self._adopt(rec, max_new_tokens, eos_token, timeout_s)
        toks = list(stream)
        self._count_decoded(len(toks))
        return toks

    # ------------------------------------------- chunked-pull streaming
    # Streams cannot cross the actor boundary, but a blocking
    # decode_from loses every already-produced token when the replica
    # dies mid-request. The router therefore pulls tokens in bounded
    # chunks: it always holds the history produced so far, which is
    # exactly what the failover replay extends the prompt with.

    def start_decode(self, rec: Dict[str, Any], max_new_tokens: int,
                     eos_token: Optional[int] = None,
                     timeout_s: float = 120.0) -> str:
        """Adopt a transfer and open a pull handle for it. The handle's
        first pulled token is the transfer's first token."""
        now = time.monotonic()
        stream = self._adopt(rec, max_new_tokens, eos_token, timeout_s)
        hid = f"{self.server_id}-h{next(_SERVER_SEQ)}"
        reaped: List[Any] = []
        with self._lock:
            self._streams[hid] = [stream, now]
            for k, (st, last) in list(self._streams.items()):
                if now - last > self._STREAM_REAP_S:
                    del self._streams[k]  # abandoned by a dead router
                    reaped.append(st)
        for st in reaped:
            # the abandoned request must not decode to completion —
            # same early-free as cancel_decode (KV + adapter pins drop
            # at the next tick boundary)
            self.engine.cancel_slot(st, "idle_reap")
        return hid

    def next_tokens(self, hid: str, max_tokens: int = 64,
                    wait_s: float = 2.0) -> Dict[str, Any]:
        """Pull up to `max_tokens` from an open handle: blocks up to
        `wait_s` for the FIRST token, then drains whatever is already
        produced. ``{"tokens": [...], "done": bool}`` — an empty pull
        with done=False is a keep-alive (the caller owns timeout and
        deadline policy)."""
        from ray_tpu.models.engine import _DONE

        with self._lock:
            entry = self._streams.get(hid)
            if entry is not None:
                entry[1] = time.monotonic()  # the idle-reap stamp
        if entry is None:
            raise KeyError(f"unknown decode stream {hid!r} "
                           f"(finished, cancelled, or reaped)")
        req = entry[0]._req
        toks: List[int] = []
        done = False
        try:
            tok = req.out.get(timeout=max(0.0, float(wait_s)))
            while True:
                if tok is _DONE:
                    done = True
                    break
                toks.append(int(tok))
                if len(toks) >= max(1, int(max_tokens)):
                    break
                tok = req.out.get_nowait()
        except queue.Empty:
            pass
        if toks:
            # counts the tokens AND consults chaos: a scripted
            # kill_replica at=token:K fires here, losing this pull's
            # reply — the mid-stream death the failover path replays
            self._count_decoded(len(toks))
        if done:
            with self._lock:
                self._streams.pop(hid, None)
            self.publish_telemetry()
            # per-request speculation accounting rides the final pull
            # so the router's decode_steady span can carry
            # accept/reject counts without an extra round trip
            out = {"tokens": toks, "done": True,
                   "spec_proposed": int(getattr(req, "spec_proposed",
                                                0)),
                   "spec_accepted": int(getattr(req, "spec_accepted",
                                                0))}
        else:
            out = {"tokens": toks, "done": done}
        if toks:
            # the engine's split of the first token's wait, for the
            # router's decode_first_token phase (three numbers a pull)
            out["queue_ms"] = entry[0].queue_ms
            out["prefill_ms"] = entry[0].prefill_ms
            out["prefills_waited"] = entry[0].prefills_waited
        return out

    def cancel_decode(self, hid: str,
                      reason: Optional[str] = None) -> bool:
        """Abandon a pull handle (router shed the request on deadline,
        failed it over, or PREEMPTED it for an interactive request):
        the engine CANCELS the slot — it frees, with its KV pins and
        adapter pin, at the next tick boundary instead of decoding the
        abandoned request to completion (the PR-12 known limit: those
        ticks were pure waste). The freed slot is immediately
        re-admittable. `reason` tags the engine's cancel accounting
        (``cancelled_by_reason``) so a preemption never reads as a
        deadline shed."""
        with self._lock:
            entry = self._streams.pop(hid, None)
        if entry is None:
            return False
        self.engine.cancel_slot(entry[0], reason)
        return True

    def _count_decoded(self, n: int) -> None:
        with self._lock:
            self._stats["decoded_tokens"] += n
        if self._chaos is not None:
            self._chaos.on_tokens(n)  # may os._exit (kill_replica)
        self.publish_telemetry()

    # -------------------------------------------------------- control plane

    def capacity(self) -> int:
        return self.engine.max_batch

    def free_slots(self) -> int:
        return self.engine.free_slots

    def prefill_programs(self) -> int:
        """`_prefill_paged` compile-cache size in THIS process — must
        stay flat on a pure decode replica (0 when it runs alone)."""
        from ray_tpu.models.engine import _prefill_paged

        return _prefill_paged._cache_size()

    def describe(self) -> Dict[str, Any]:
        """Registration record for a router: identity, capacity, host
        (the decode-side placement-affinity anchor)."""
        return {"server_id": self.server_id, "role": "decode",
                "capacity": self.engine.max_batch,
                "machine": self.machine, "device": self.device,
                "lora": self.lora_pool is not None}

    def publish_adapter(self, tenant: str,
                        adapter: Dict[str, Any]) -> int:
        """Local-source adapter publish (see PrefillServer twin)."""
        if self.lora_pool is None:
            raise ValueError("this decode server has no adapter pool")
        return int(self.lora_pool.source.publish(tenant, adapter))

    def refresh_adapter(self, tenant: str) -> bool:
        if self.lora_pool is None:
            return False
        return self.lora_pool.refresh(tenant)

    def prepare_for_shutdown(self, timeout_s: float = 30.0) -> bool:
        """Grace drain (the serve/replica.py shape, reused by autoscale
        scale-down): wait until every decode slot has finished its
        stream, then stop the engine. Returns whether the drain
        completed inside the window — the engine stops either way, so
        the caller may safely drop/kill the replica afterwards."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        while True:
            drained = self.engine.free_slots == self.engine.max_batch
            if drained or time.monotonic() >= deadline:
                break
            time.sleep(0.05)
        self.stop()
        return drained

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            s: Dict[str, Any] = dict(self._stats)
        s.update(role="decode", server_id=self.server_id,
                 device=self.device, runtime=runtime_record(),
                 capacity=self.engine.max_batch,
                 free_slots=self.engine.free_slots,
                 adopted=self.engine.adopted,
                 cancelled=self.engine.cancelled,
                 prefill_programs=self.prefill_programs())
        if self.engine.speculate_k:
            s["speculation"] = self.engine.speculation_stats()
        if self.lora_pool is not None:
            s["lora"] = self.lora_pool.stats()
        return s

    def publish_telemetry(self, force: bool = False) -> None:
        if not self._pusher.push(self.stats, force=force):
            return
        if self.lora_pool is not None:
            self.lora_pool.publish_telemetry(force=force)
        # the engine's own kvcache push carries the adoption counters
        # to the kvcache surface (per-phase truthfulness)
        self.engine.publish_kv_telemetry(force=True)

    def stop(self) -> None:
        self.engine.stop()
        self.publish_telemetry(force=True)


# ----------------------------------------------------------------- router

class _TierReplica:
    """One router-side replica slot. generate() holds the OBJECT (not an
    index) across its whole lifetime, so the replica set can grow,
    drain, and shrink mid-traffic without invalidating in-flight
    bookkeeping."""

    __slots__ = ("target", "rid", "cap", "inflight", "draining",
                 "machine", "lora", "device")

    def __init__(self, target: Any, rid: str, cap: int,
                 machine: Optional[str] = None, lora: bool = False,
                 device: Optional[Dict[str, Any]] = None):
        self.target = target
        self.rid = rid
        self.cap = int(cap)
        self.inflight = 0
        self.draining = False
        self.machine = machine
        self.lora = bool(lora)
        # the replica's own device_record(): where it really computes
        self.device = device

    def snapshot(self) -> Dict[str, Any]:
        return {"rid": self.rid, "target": self.target, "cap": self.cap,
                "inflight": self.inflight, "draining": self.draining,
                "machine": self.machine, "device": self.device}


# cache-outcome weights for the router's recent hit-rate signal: a full
# hit skips the prefill entirely, a partial roughly halves it, a miss
# pays it all — the policy reads "fraction of prefill work the cache is
# absorbing right now"
_OUTCOME_WEIGHT = {"hit": 1.0, "partial": 0.5, "miss": 0.0}


class _PreemptSlot:
    """One PREEMPTIBLE in-flight request (priority class ``batch``,
    serve/qos.py) as the admission path sees it. Registered for the
    request's whole lifetime; ``cancel_fn`` is armed only while a
    decode stream is actually live (it cancels that stream's engine
    slot, reason-tagged ``preempt``). An interactive arrival that finds
    every decode slot taken picks the victim with the FEWEST delivered
    tokens — the cheapest replay — marks it ``preempted`` under the
    router lock, and fires the cancel outside it. The victim's pull
    loop notices (its stream ends early, or errors) and resumes
    through the SAME replay-with-history path as a replica-death
    failover: prompt+history re-prefills (a suffix-only prefill thanks
    to the prefix cache) and decode continues for the remaining
    budget, so the greedy bit-identity oracle covers preemption for
    free."""

    __slots__ = ("key", "tenant", "rep", "tokens", "preempted",
                 "cancel_fn", "live")

    def __init__(self, key: int, tenant: Optional[str] = None):
        self.key = key
        self.tenant = tenant
        self.rep: Optional[_TierReplica] = None
        self.tokens = 0
        self.preempted = False
        self.cancel_fn: Optional[Callable[[], None]] = None
        # a live stream whose tokens leave by a sink: its owner sleeps
        # and counts them into `tokens` at the stream's end
        self.live: Any = None

    def delivered(self) -> int:
        """Tokens delivered so far, a sleeping owner's stream's too."""
        live = self.live
        return self.tokens + (live.produced if live is not None else 0)


class DisaggRouter:
    """Dispatch + admission control over a prefill tier and a decode
    tier (each a sequence of in-process servers or actor handles).

    With an empty prefill tier the router degrades to the colocated
    single-engine path — same engine code, bit-identical outputs — so
    one deployment shape serves both modes.

    The replica sets are LIVE: ``add_prefill``/``add_decode`` admit a
    new replica to dispatch immediately, ``begin_drain`` stops
    dispatching to one while its in-flight requests finish and its KV
    transfers get acked, and ``remove`` retires it once ``drained`` —
    the serve/autoscale.py control loop drives exactly this API
    mid-traffic. Dispatch policy: decode by free-slot count, prefill by
    prefix-cache affinity WITHIN the subset co-located with the chosen
    decode replica's host (when one exists), so KV transfers stay on
    shm — the ``shm_affinity`` split in stats() reports how often that
    held."""

    def __init__(self, decode: Sequence[Any] = (),
                 prefill: Sequence[Any] = (), *,
                 colocated: Any = None,
                 max_queue_depth: Optional[int] = None,
                 retry_after_s: Optional[float] = None,
                 affinity_tokens: int = 16,
                 failover_attempts: Optional[int] = None,
                 failover_wait_s: float = 15.0,
                 stream_chunk_tokens: int = 32,
                 router_id: Optional[str] = None):
        # every combination generate() cannot serve is rejected HERE,
        # not per-request after a prefill was already published
        if prefill and not decode:
            raise ValueError(
                "a prefill tier needs a decode tier to stream KV to")
        if not prefill and colocated is None:
            raise ValueError(
                "need a prefill+decode pair or a colocated engine")
        self._colocated = colocated
        # the deployment SHAPE is fixed at construction: a disagg
        # router whose whole prefill tier momentarily died waits for
        # the self-healer's replacement (it never falls through to a
        # colocated engine it may not have)
        self._disagg_mode = bool(prefill)
        if max_queue_depth is None:
            max_queue_depth = int(os.environ.get(
                "RAY_TPU_DISAGG_QUEUE_DEPTH", "8"))
        self.max_queue_depth = max(0, int(max_queue_depth))
        if retry_after_s is None:
            retry_after_s = float(os.environ.get(
                "RAY_TPU_DISAGG_RETRY_AFTER_S", "1.0"))
        self.retry_after_s = float(retry_after_s)
        # bounded failover budget: EXTRA attempts after the first (so
        # the default survives any single replica failure with one
        # retry to spare); exhaustion sheds with cause "failover"
        if failover_attempts is None:
            failover_attempts = int(os.environ.get(
                "RAY_TPU_FAILOVER_ATTEMPTS", "2"))
        self.failover_attempts = max(0, int(failover_attempts))
        # how long a failed-over request waits for a survivor (or a
        # self-healer replacement) when a whole tier momentarily has
        # zero live replicas
        self.failover_wait_s = max(0.0, float(failover_wait_s))
        self.stream_chunk_tokens = max(1, int(stream_chunk_tokens))
        # prompts sharing their first `affinity_tokens` tokens (the
        # system prompt's first cache block) land on one prefill replica
        self.affinity_tokens = max(1, int(affinity_tokens))
        self.router_id = router_id or \
            f"router-{os.getpid()}-{next(_SERVER_SEQ)}"
        self._lock = threading.Lock()
        # global KV plane (serve/kvplane.py): prefer the replica that
        # HAS the prefix (conductor directory) over the one the hash
        # says probably does; block size learned from prefill describe()
        from .kvplane import directory_enabled as _kvp_dir_enabled
        self._kvplane_dir = _kvp_dir_enabled()
        self._kv_block_size: Optional[int] = None
        self._decode: List[_TierReplica] = [
            self._register(d, "decode") for d in decode]
        self._prefill: List[_TierReplica] = [
            self._register(p, "prefill") for p in prefill]
        if not self._decode:
            self._decode = [_TierReplica(
                colocated, f"{self.router_id}-colocated",
                int(colocated.max_batch))]
        self._push_retention_hint()
        # recent-signal windows (serve/autoscale.SlidingWindow): the
        # policy — and `recent` in stats() — reads these, not lifetime
        # counters, so a load shift shows up within the window
        self._ttft_win = SlidingWindow()
        self._depth_win = SlidingWindow()
        self._pf_inflight_win = SlidingWindow()
        self._cache_win = SlidingWindow()
        self._pf_inflight = 0
        self._stats = {k: 0 for k in (
            "dispatched", "completed", "shed", "max_pending",
            "shm_affinity_hits", "shm_affinity_total",
            "tenant_affinity_hits", "tenant_affinity_total",
            "tier_wakeups", "preemptions", "preempted_requests",
            "directory_hits", "directory_misses",
            "directory_fallbacks")}
        # QoS preemption (serve/qos.py classes): batch-class requests
        # register here while in flight; an interactive arrival that
        # finds every slot taken cancels the cheapest one and rides
        # its freed slot — the victim resumes via the failover replay
        self._preempt_seq = itertools.count()
        self._preempt_reg: Dict[int, _PreemptSlot] = {}
        # scale-from-zero hook (serve/autoscale.py): called with the
        # tier name when an arrival finds that tier EMPTY — the
        # autoscaler's waker spawns a replica through the tier factory
        # outside hysteresis, and the arrival waits for it
        self._tier_waker: Optional[Callable[[str], None]] = None
        # multi-tenant LoRA (serve/lora.py): per-tenant shed/SLO/
        # latency isolation — one tenant's overload or failure must
        # never read as another's. LRU-capped so a tenant sweep can't
        # grow the router without bound; the SLO line is the same
        # TTFT target the autoscale policy chases.
        self._tenant_stats: "OrderedDict[str, Dict[str, Any]]" = \
            OrderedDict()
        self._tenant_decode: "OrderedDict[str, str]" = OrderedDict()
        self._tenant_cap = 512
        self._tenant_slo_ms = default_target_p99_ms()
        # serving-fault-tolerance accounting (the servefault surface):
        # failover attempts per phase, requests that survived >= 1
        # failover, sheds by attributed cause, corpses removed
        self._sf = {
            "failovers": {"prefill": 0, "decode": 0},
            "failover_requests": 0,
            "sheds_by_cause": {},
            "removed_dead": {"prefill": 0, "decode": 0},
        }
        # recovery cost of each failover: ms from failure detection to
        # the resumed stream's re-prefill landing (the chaos benchmark
        # reports this window's summary as the recovery impact)
        self._failover_win = SlidingWindow()
        self._pusher = Pusher("disagg", self.router_id)
        self._kvplane_pusher = Pusher("kvplane", self.router_id)
        self._lora_pusher = Pusher("lora", self.router_id)
        self._sf_pusher = Pusher("servefault", self.router_id)
        disagg_metrics()
        servefault_metrics()

    # ----------------------------------------------------- replica set ops

    def _register(self, target: Any, tier: str) -> _TierReplica:
        try:
            info = _call(target, "describe")
        except Exception:  # noqa: BLE001 — pre-describe replica impls
            info = {}
        rid = info.get("server_id") or \
            f"{tier}-{self.router_id}-{next(_SERVER_SEQ)}"
        cap = int(info.get("capacity")
                  or (_call(target, "capacity") if tier == "decode"
                      else 0))
        if tier == "prefill" and self._kv_block_size is None:
            bs = info.get("kv_block_size")
            if bs:
                self._kv_block_size = int(bs)
        return _TierReplica(target, rid, cap, info.get("machine"),
                            bool(info.get("lora")), info.get("device"))

    def _push_retention_hint(self) -> None:
        """Every admissible request can be in flight at once and
        affinity can route ALL of them to one prefill server — push the
        bound so its retention window can never reap a transfer a
        decode replica is about to fetch. Re-pushed whenever the
        replica set grows."""
        with self._lock:
            prefill = list(self._prefill)
            hint = 2 * sum(r.cap + self.max_queue_depth
                           for r in self._decode)
        for pf in prefill:
            try:
                # best-effort hint, supervised by the except below
                _call(pf.target, "set_retention", hint, block=False)  # shardlint: disable=unsupervised-actor-call
            except Exception:  # noqa: BLE001 — replica mid-restart
                pass

    def add_decode(self, target: Any) -> str:
        """Admit a new decode replica — it becomes dispatchable the
        moment this returns."""
        rep = self._register(target, "decode")
        with self._lock:
            self._decode.append(rep)
        self._push_retention_hint()
        self.publish_telemetry(force=True)
        return rep.rid

    def add_prefill(self, target: Any) -> str:
        """Admit a new prefill replica (affinity re-hashes over the
        grown set on the next dispatch)."""
        rep = self._register(target, "prefill")
        with self._lock:
            self._prefill.append(rep)
        self._push_retention_hint()
        self.publish_telemetry(force=True)
        return rep.rid

    def set_tier_waker(self,
                       fn: Optional[Callable[[str], bool]]) -> None:
        """Attach the scale-from-zero hook (serve/autoscale.py): called
        with the tier name ("prefill"|"decode") when a request arrives
        to an EMPTY tier; returns whether a wake was actually initiated
        (only a min_replicas=0 tier wakes — for any other tier the
        arrival must keep the pre-existing behavior: shed immediately
        on decode, or wait for the self-healer on prefill). Must be
        non-blocking — the waker spawns its replica off-thread while
        the arrival waits."""
        self._tier_waker = fn

    def _wake_tier(self, tier: str) -> bool:
        """Fire the waker; True only when it reports a wake is coming —
        the caller's cue to wait for the replica instead of shedding.
        Bookkeeping (counter + event) only on actual wakes."""
        waker = self._tier_waker
        if waker is None:
            return False
        try:
            woke = bool(waker(tier))
        except Exception:  # noqa: BLE001 — treat as no wake coming
            return False
        if woke:
            with self._lock:
                self._stats["tier_wakeups"] += 1
            emit("disagg", {"kind": "tier_wake",
                            "router": self.router_id, "tier": tier})
        return woke

    def _lora_enabled(self) -> bool:
        """Whether this deployment can serve tenant-tagged requests:
        any tier replica advertised an adapter pool (describe()'s
        `lora` field), or the colocated engine holds one. Live — a
        LoRA-enabled replica added mid-traffic enables the tenant
        default from then on."""
        if self._colocated is not None and \
                getattr(self._colocated, "lora_pool", None) is not None:
            return True
        with self._lock:
            return any(r.lora for r in self._prefill + self._decode)

    def _tier(self, tier: str) -> List[_TierReplica]:
        if tier not in ("prefill", "decode"):
            raise ValueError(f"unknown tier {tier!r}")
        return self._prefill if tier == "prefill" else self._decode

    def tier_replicas(self, tier: str) -> List[Dict[str, Any]]:
        with self._lock:
            return [r.snapshot() for r in self._tier(tier)]

    def begin_drain(self, tier: str, rid: str, *,
                    allow_empty: bool = False) -> bool:
        """Stop dispatching to one replica; its in-flight requests keep
        running and its KV transfers still get acked. Refuses to drain
        the LAST active replica of a tier (the router must stay able
        to serve) unless ``allow_empty`` — the scale-to-zero path,
        where the attached tier waker makes an empty tier serveable
        again on the next arrival. Returns whether the drain
        started."""
        with self._lock:
            reps = self._tier(tier)
            active = [r for r in reps if not r.draining]
            for r in reps:
                if r.rid == rid and not r.draining:
                    if len(active) <= 1 and not (
                            allow_empty and self._tier_waker is not None):
                        return False
                    r.draining = True
                    break
            else:
                return False
        self.publish_telemetry(force=True)
        return True

    def drained(self, tier: str, rid: str) -> bool:
        """True when a draining replica has zero in-flight left (its
        dispatch stopped at begin_drain; this is the router-side half of
        the grace drain — the replica-side prepare_for_shutdown
        double-checks engine slots and unacked transfers)."""
        with self._lock:
            for r in self._tier(tier):
                if r.rid == rid:
                    return r.draining and r.inflight == 0
        return True  # already removed

    def remove(self, tier: str, rid: str) -> Optional[Any]:
        """Retire a draining replica from the set; returns its target
        so the caller can tear it down (grace-drain first — see
        serve/autoscale.py)."""
        with self._lock:
            reps = self._tier(tier)
            for i, r in enumerate(reps):
                if r.rid == rid:
                    if not r.draining:
                        raise ValueError(
                            f"{tier} replica {rid} is not draining — "
                            "begin_drain() first so dispatch stops "
                            "before the replica disappears")
                    del reps[i]
                    return r.target
        return None

    def remove_dead(self, tier: str, rid: str) -> bool:
        """Remove a DEAD replica immediately — distinct from the drain
        flow: no grace, no draining precondition (a corpse mid-drain is
        reaped too), its in-flight requests have already failed over or
        are about to. Called by the failover wrapper on an observed
        death and by the serve/autoscale.py self-healer on an
        actor-death event. Idempotent."""
        with self._lock:
            reps = self._tier(tier)
            for i, r in enumerate(reps):
                if r.rid == rid:
                    del reps[i]
                    self._sf["removed_dead"][tier] += 1
                    break
            else:
                return False
        self.publish_telemetry(force=True)
        self.publish_servefault(force=True)
        return True

    # ------------------------------------------------------- failover core

    def _tier_call(self, rep: _TierReplica, tier: str, method: str,
                   *args, block: bool = True, **kw):
        """THE supervised path for data-plane calls on a tier replica
        (shardlint's unsupervised-actor-call rule flags bare calls that
        bypass it): a death-shaped failure removes the corpse from the
        replica set, emits the failover markers, and re-raises as
        ReplicaDeadError so generate()'s bounded retry can re-route."""
        try:
            return _call(rep.target, method, *args, block=block, **kw)
        except _DEATH_TYPES as e:
            self.remove_dead(tier, rep.rid)
            raise ReplicaDeadError(tier, rep.rid, e) from e

    def _count_failover(self, phase: str, rid: str, attempt: int,
                        detail: str) -> None:
        with self._lock:
            self._sf["failovers"][phase] += 1
        servefault_metrics()["failovers"].inc(tags={"phase": phase})
        _notify_resilience({"kind": "failover", "phase": phase,
                            "router": self.router_id, "replica": rid,
                            "attempt": attempt, "detail": detail[:200]})
        self.publish_servefault()

    def _tenant_rec_locked(self, tenant: str) -> Dict[str, Any]:
        rec = self._tenant_stats.get(tenant)
        if rec is None:
            rec = {"dispatched": 0, "completed": 0, "shed": 0,
                   "sheds_by_cause": {}, "slo_misses": 0,
                   "ttft": SlidingWindow(), "latency": SlidingWindow()}
            self._tenant_stats[tenant] = rec
            while len(self._tenant_stats) > self._tenant_cap:
                self._tenant_stats.popitem(last=False)
        self._tenant_stats.move_to_end(tenant)
        return rec

    def _shed(self, cause: str, message: str,
              tenant: Optional[str] = None) -> RequestShedError:
        """Count + build an attributed shed (the caller raises it):
        every shed path reports the same one set of numbers. `tenant`
        charges the shed to that tenant's isolated counters too."""
        with self._lock:
            self._stats["shed"] += 1
            by = self._sf["sheds_by_cause"]
            by[cause] = by.get(cause, 0) + 1
            if tenant is not None:
                trec = self._tenant_rec_locked(tenant)
                trec["shed"] += 1
                tby = trec["sheds_by_cause"]
                tby[cause] = tby.get(cause, 0) + 1
        shed_counter().inc(tags={"app": "disagg",
                                 "deployment": self.router_id})
        servefault_metrics()["sheds"].inc(tags={"cause": cause})
        emit("disagg", {"kind": "shed", "router": self.router_id,
                        "cause": cause,
                        "retry_after_s": self.retry_after_s})
        self.publish_telemetry()
        self.publish_servefault()
        return RequestShedError(message, retry_after_s=self.retry_after_s,
                                cause=cause)

    # ------------------------------------------------------------ admission

    def _admit_or_shed(self, tenant: Optional[str] = None,
                       deadline: Optional[float] = None,
                       priority: Optional[str] = None) -> _TierReplica:
        """Reserve a decode replica or shed. Sheds when EVERY active
        replica's in-flight estimate has reached capacity +
        max_queue_depth — the bound that keeps queue depth finite
        (draining replicas receive nothing, so they neither admit nor
        extend the bound). The bound check and the in-flight
        reservation happen under ONE lock acquisition (check-then-act
        would let N racing callers all pass the check before any
        reserves, exceeding the bound by N-1); shed-side metrics and
        the conductor notify run after release so overload never
        serializes healthy admissions behind a socket write.

        `tenant` adds TENANT-AFFINITY beside the load policy: the
        replica that served this tenant last already holds its adapter
        resident (serve/lora.py pool), so it is preferred while it has
        admission headroom — a cross-replica spray would page the same
        adapter into every pool.

        Scale-from-zero (serve/autoscale.py min_replicas=0): when the
        decode tier is EMPTY (drained to zero, not merely full) and a
        tier waker is attached, the FIRST arrival is the scale-up
        signal — the waker spawns a replica through the tier factory
        and this admission waits up to ``failover_wait_s`` for it to
        register instead of shedding. A full-but-live tier still sheds
        immediately (that is load, not absence).

        `priority` (serve/qos.py classes): an ``interactive`` arrival
        that finds every replica full PREEMPTS the cheapest registered
        batch-class request instead of shedding — it rides the
        victim's replica (deliberately one reservation past the bound:
        the parked victim keeps its own reservation while it waits to
        resume, so nothing leaks when both complete). The victim
        resumes through the failover replay, bit-identical."""
        affinity_hit = False
        wake_until: Optional[float] = None
        while True:
            victim: Optional[_PreemptSlot] = None
            with self._lock:
                open_reps = [r for r in self._decode if not r.draining
                             and r.inflight < r.cap
                             + self.max_queue_depth]
                pending = sum(r.inflight for r in self._decode)
                if not open_reps and priority == "interactive":
                    victim = self._pick_victim_locked()
                    if victim is not None:
                        rep = victim.rep
                        rep.inflight += 1
                        pending += 1
                        self._stats["dispatched"] += 1
                        self._stats["preemptions"] += 1
                        self._stats["max_pending"] = max(
                            self._stats["max_pending"], pending)
                        if tenant is not None:
                            self._tenant_rec_locked(
                                tenant)["dispatched"] += 1
                if open_reps:
                    # probe-free first cut: least estimated in-flight,
                    # reserved NOW so the bound holds under concurrency
                    rep = min(open_reps, key=lambda r: r.inflight)
                    if tenant is not None:
                        self._stats["tenant_affinity_total"] += 1
                        want = self._tenant_decode.get(tenant)
                        for r in open_reps:
                            if r.rid == want:
                                rep = r
                                affinity_hit = True
                                self._stats["tenant_affinity_hits"] += 1
                                break
                        self._tenant_rec_locked(
                            tenant)["dispatched"] += 1
                    rep.inflight += 1
                    pending += 1
                    self._stats["dispatched"] += 1
                    self._stats["max_pending"] = max(
                        self._stats["max_pending"], pending)
                tier_empty = not any(not r.draining
                                     for r in self._decode)
            if victim is not None:
                # cancel fires OUTSIDE the lock (it's an RPC); the
                # probe refinement below is naturally skipped — the
                # preemptor must ride exactly the slot it just freed
                self._fire_preemption(victim)
                self._depth_win.add(pending)
                break
            if open_reps:
                self._depth_win.add(pending)
                break
            if tier_empty and self._tier_waker is not None:
                # one wake attempt per admission; the wait engages only
                # when the waker reports a replica is actually coming
                # (min_replicas=0 tier) — a dead min_replicas>=1 tier
                # keeps the pre-existing immediate shed
                if wake_until is None and self._wake_tier("decode"):
                    wake_until = time.monotonic() + self.failover_wait_s
                if wake_until is not None \
                        and time.monotonic() < wake_until:
                    self._check_deadline(deadline, tenant)
                    time.sleep(0.1)
                    continue
            self._depth_win.add(pending)
            # _shed pushes the snapshot NOW (0.5s-throttled): under
            # sustained overload nothing completes, and a completion-
            # only push would freeze the conductor surfaces — queue
            # depth aging out to 0 — during exactly the storm they
            # exist to show
            raise self._shed(
                "capacity",
                f"disagg router {self.router_id}: every decode "
                f"replica is at capacity + queue depth "
                f"{self.max_queue_depth} (pending {pending}); retry "
                f"after {self.retry_after_s:.1f}s", tenant)
        if self._prefill and len(open_reps) > 1 and not affinity_hit:
            # refine by live free-slot count (the decode-pick policy);
            # the in-flight estimate breaks ties and covers probe lag.
            # The probes are ISSUED before any is awaited so N actor
            # replicas answer concurrently — sequential blocking gets
            # here would add N x RPC latency to every dispatch.
            # Moving the reservation re-checks the target's bound under
            # the lock — a refinement may not overfill a replica that
            # filled up while we probed.
            try:
                from ray_tpu._private.object_store import ObjectRef

                import ray_tpu

                # read-only probe, supervised by the except below
                probes = [(r, _call(r.target, "free_slots",  # shardlint: disable=unsupervised-actor-call
                                    block=False)) for r in open_reps]
                # expected free slots once in-transit dispatches land:
                # the probe already excludes EXECUTING requests, which
                # are also in this router's in-flight estimate, so
                # subtracting the full estimate would double-count them
                # and rank a deep backlog above a busy-but-shallower
                # replica. cap - inflight is that expectation for load
                # this router dispatched; min() with the probe keeps it
                # honest about slots held by load we never saw.
                frees = [(min(int(ray_tpu.get(v)
                                  if isinstance(v, ObjectRef) else v),
                              r.cap - r.inflight), i)
                         for i, (r, v) in enumerate(probes)]
                best = probes[max(frees)[1]][0]
            except Exception:  # noqa: BLE001 — replica mid-restart
                best = rep
            if best is not rep:
                with self._lock:
                    if not best.draining and best.inflight < \
                            best.cap + self.max_queue_depth:
                        rep.inflight -= 1
                        best.inflight += 1
                        rep = best
        if tenant is not None:
            # record the replica that will ACTUALLY serve (after the
            # probe refinement above) — it's the one paging the
            # tenant's adapter, so it's the one affinity must point at
            self._note_tenant_decode(tenant, rep.rid)
        disagg_metrics()["queue_depth"].set(
            pending, tags={"router": self.router_id})
        self.publish_telemetry()
        return rep

    # ----------------------------------------------------- qos preemption

    def _preempt_register(self, priority: Optional[str],
                          tenant: Optional[str]
                          ) -> Optional[_PreemptSlot]:
        """Make a batch-class request visible to interactive
        admission. Non-batch (and unclassified) requests return None —
        they are never preemption victims."""
        if priority != "batch":
            return None
        slot = _PreemptSlot(next(self._preempt_seq), tenant)
        with self._lock:
            self._preempt_reg[slot.key] = slot
        return slot

    def _preempt_unregister(self, slot: Optional[_PreemptSlot]) -> None:
        if slot is None:
            return
        with self._lock:
            self._preempt_reg.pop(slot.key, None)

    def _pick_victim_locked(self) -> Optional[_PreemptSlot]:
        """Cheapest-replay victim: the live batch stream with the
        fewest delivered tokens (its replay re-prefills the least
        history). Caller holds the router lock; marking ``preempted``
        here makes the pick exactly-once under racing interactive
        arrivals."""
        cands = [s for s in self._preempt_reg.values()
                 if s.cancel_fn is not None and s.rep is not None
                 and not s.preempted]
        if not cands:
            return None
        victim = min(cands, key=_PreemptSlot.delivered)
        victim.preempted = True
        return victim

    def _fire_preemption(self, victim: _PreemptSlot) -> None:
        """Cancel the victim's live decode stream (reason-tagged
        ``preempt`` down in the engine) and count the preemption into
        the gateway surface. The victim's pull loop notices its stream
        ending early and resumes via replay-with-history; its
        reservation never moves, so slot accounting stays balanced
        when both requests complete."""
        fn = victim.cancel_fn
        if fn is not None:
            try:
                fn()
            except Exception:  # noqa: BLE001 — victim mid-teardown
                pass
        try:
            from .qos import gateway_metrics

            gateway_metrics()["preemptions"].inc()
            emit("gateway", {"kind": "preempt",
                             "router": self.router_id,
                             "victim_tenant": victim.tenant,
                             "tokens_done": victim.delivered()})
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        emit("disagg", {"kind": "preempt", "router": self.router_id,
                        "victim_tenant": victim.tenant})
        self.publish_telemetry()

    def _check_abort(self, deadline: Optional[float],
                     tenant: Optional[str] = None,
                     cancel_event: Any = None) -> None:
        """_check_deadline plus the gateway's client-disconnect
        signal: a set cancel_event sheds with cause ``disconnect`` —
        an abandoned decode must stop burning ticks for a socket
        nobody reads."""
        self._check_deadline(deadline, tenant)
        if cancel_event is not None and cancel_event.is_set():
            raise self._shed(
                "disconnect",
                f"disagg router {self.router_id}: client disconnected "
                f"mid-request; decode cancelled", tenant)

    def _note_tenant_decode(self, tenant: str, rid: str) -> None:
        with self._lock:
            self._tenant_decode[tenant] = rid
            self._tenant_decode.move_to_end(tenant)
            while len(self._tenant_decode) > self._tenant_cap:
                self._tenant_decode.popitem(last=False)

    def _complete(self, rep: _TierReplica, ok: bool = True, *,
                  tenant: Optional[str] = None,
                  wall_ms: Optional[float] = None) -> None:
        """Release a request's reservation; `completed` counts only
        requests that RETURNED tokens — a shed-after-admission
        (deadline, failover exhaustion) or an error releases the slot
        without counting, so completed + shed + errors reconciles with
        dispatched instead of double-counting the shed ones."""
        with self._lock:
            if rep.inflight > 0:
                rep.inflight -= 1
            if ok:
                self._stats["completed"] += 1
                if tenant is not None:
                    trec = self._tenant_rec_locked(tenant)
                    trec["completed"] += 1
                    if wall_ms is not None:
                        trec["latency"].add(wall_ms)
            pending = sum(r.inflight for r in self._decode)
        disagg_metrics()["queue_depth"].set(
            pending, tags={"router": self.router_id})
        self.publish_telemetry()

    # ------------------------------------------------------------- dispatch

    def _directory_entry(self, prompt: np.ndarray,
                         tenant: Optional[str]
                         ) -> Optional[Dict[str, Any]]:
        """Ask the conductor's KV-plane prefix directory who HOLDS this
        prompt's longest published prefix. Returns None when the lookup
        was not attempted (directory off, no cluster, block size not
        yet learned from a prefill replica, or a tenant-tagged request
        — the tenant namespace folds in the adapter VERSION, which only
        the replica's adapter pool knows) and ``{}`` when it ran and
        found nothing; any entry is advisory — a miss always falls back
        to the affinity hash, bit-identically."""
        if not self._kvplane_dir or tenant is not None:
            return None
        bs = self._kv_block_size
        w = _worker()
        if bs is None or w is None:
            return None
        from .kvplane import directory_lookup
        try:
            # namespace None, not "": the digest chain must be rooted
            # exactly like the replicas' default-namespace index (the
            # conductor-side directory key maps None -> "" itself)
            entry = directory_lookup(w, None, [int(t) for t in prompt],
                                     bs)
        except Exception:  # noqa: BLE001 — conductor unreachable
            return None
        return entry if entry is not None else {}

    def _pick_prefill(self, prompt: np.ndarray,
                      decode_machine: Optional[str],
                      tenant: Optional[str] = None
                      ) -> Tuple[_TierReplica,
                                 Optional[Dict[str, Any]]]:
        """Prefix-cache affinity WITHIN the host-local subset: among
        prefill replicas co-located with the chosen decode replica (so
        the KV transfer rides shm, never RPC), the prompt's first cache
        block hashes to one stable choice; with no co-located replica
        the hash falls back to the whole active set. On one host the
        subset IS the whole set, so single-host affinity (and
        bit-identity) is unchanged. The TENANT joins the hash beside
        the prompt head: a tenant's prompts land on the replica that
        already holds its adapter (and its namespace-keyed KV) — the
        tenant-affinity half of the multi-tenant routing policy.

        With the global KV plane on, the conductor's prefix directory
        upgrades the hash from "who PROBABLY has it" to "who HAS it":
        a live holder wins outright; a holder that has left the pool
        degrades to the hash plus a tier-3 hint the chosen replica can
        fetch through the transfer plane. Returns ``(replica, hint)``
        where hint is None except on that fallback path."""
        dir_entry = self._directory_entry(prompt, tenant)
        head = (tenant,) + tuple(
            int(t) for t in prompt[:self.affinity_tokens])
        hint: Optional[Dict[str, Any]] = None
        outcome: Optional[str] = None
        with self._lock:
            cands = [r for r in self._prefill if not r.draining]
            if not cands:  # every prefill draining: keep serving
                cands = list(self._prefill)
            if not cands:  # every prefill DEAD: caller waits/sheds
                raise LookupError("no live prefill replica")
            rep = None
            if dir_entry:
                holder = dir_entry.get("holder")
                by_rid = {r.rid: r for r in cands}
                if holder in by_rid:
                    rep = by_rid[holder]
                    outcome = "hit"
                    self._stats["directory_hits"] += 1
                else:
                    # entry survives its holder (death, drain): route
                    # by hash but hand the replica the tier-3 pointer
                    hint = dir_entry
                    outcome = "fallback"
                    self._stats["directory_fallbacks"] += 1
            elif dir_entry is not None:  # lookup ran, found nothing
                outcome = "miss"
                self._stats["directory_misses"] += 1
            if rep is None:
                local = [r for r in cands
                         if decode_machine is not None
                         and r.machine == decode_machine]
                pool = local or cands
                rep = pool[hash(head) % len(pool)]
            self._stats["shm_affinity_total"] += 1
            if decode_machine is not None \
                    and rep.machine == decode_machine:
                self._stats["shm_affinity_hits"] += 1
        if outcome is not None:
            from .kvplane import kvplane_metrics
            kvplane_metrics()["directory"].inc(
                tags={"outcome": outcome})
            if outcome == "hit":
                emit("kvplane", {
                    "kind": "directory_hit", "router": self.router_id,
                    "replica": rep.rid,
                    "digest": dir_entry.get("digest"),
                    "blocks": dir_entry.get("blocks")})
        return rep, hint

    def _check_deadline(self, deadline: Optional[float],
                        tenant: Optional[str] = None) -> None:
        """Shed with cause `deadline` the moment the request outlives
        its budget — it must never occupy a decode slot (or a failover
        attempt) past it."""
        if deadline is not None and time.perf_counter() > deadline:
            raise self._shed(
                "deadline",
                f"disagg router {self.router_id}: request outlived its "
                f"deadline; retry after {self.retry_after_s:.1f}s",
                tenant)

    def _ack_transfer(self, pf: _TierReplica, rec: Dict[str, Any]
                      ) -> None:
        """Release the sender's chunk refs, consumed or abandoned: an
        un-acked record pins them until the retention window overflows
        — which on a quiet tier is never. The prefill replica may
        itself be dead by now; then its refs died with it."""
        try:
            # fire-and-forget on a possibly-dead replica — failure here
            # must not consume a failover attempt
            _call(pf.target, "ack", rec["transfer_id"], block=False)  # shardlint: disable=unsupervised-actor-call
        except Exception:  # noqa: BLE001 — replica already dead
            pass

    def _shed_pool_exhausted(self, phase: str,
                             tenant: Optional[str],
                             e: BaseException) -> RequestShedError:
        """The one adapter-pool-exhausted shed (colocated submit,
        prefill, and decode paths all raise through here): a CAPACITY
        condition, attributed to the tenant, never a failover."""
        return self._shed(
            "capacity",
            f"disagg router {self.router_id}: {phase} adapter pool "
            f"exhausted (every row pinned); retry after "
            f"{self.retry_after_s:.1f}s", tenant)

    def _check_request_fault(self, tenant: Optional[str],
                             e: BaseException) -> None:
        """Classify a data-plane failure that is NOT a replica death:
        tenant-configuration errors re-raise to the caller (retrying
        reproduces them; shedding would mislabel a client mistake as a
        serving fault), everything else returns so the bounded
        failover budget applies."""
        if _is_lora_config_error(e):
            raise ValueError(
                f"tenant {tenant!r} is misconfigured for this "
                f"deployment: {str(e)[:240]}") from e

    def _attempt_failed(self, phase: str, rid: str, attempt: int,
                        err: BaseException,
                        tenant: Optional[str] = None) -> None:
        """Account one failed attempt; sheds with cause `failover` when
        the bounded budget is exhausted."""
        self._count_failover(phase, rid, attempt,
                             f"{type(err).__name__}: {err}")
        if attempt > self.failover_attempts:
            raise self._shed(
                "failover",
                f"disagg router {self.router_id}: {phase} failure on "
                f"attempt {attempt}/{1 + self.failover_attempts} "
                f"({type(err).__name__}: {str(err)[:160]}); failover "
                f"budget exhausted", tenant) from err

    def _pick_prefill_or_wait(self, prompt: np.ndarray,
                              decode_machine: Optional[str],
                              deadline: Optional[float],
                              tenant: Optional[str] = None
                              ) -> Tuple[_TierReplica,
                                         Optional[Dict[str, Any]]]:
        """_pick_prefill, waiting out a momentarily-empty tier (every
        prefill replica dead — self-healer replacement in flight — or
        drained to zero: the first LookupError fires the scale-from-
        zero waker) up to ``failover_wait_s`` before shedding with
        cause failover."""
        wait_until = time.monotonic() + self.failover_wait_s
        woke = False
        while True:
            try:
                return self._pick_prefill(prompt, decode_machine,
                                          tenant)
            except LookupError:
                if not woke:
                    self._wake_tier("prefill")
                    woke = True
            self._check_deadline(deadline, tenant)
            if time.monotonic() >= wait_until:
                raise self._shed(
                    "failover",
                    f"disagg router {self.router_id}: no live prefill "
                    f"replica after {self.failover_wait_s:.0f}s",
                    tenant)
            time.sleep(0.25)

    def _reserve_survivor(self, old: _TierReplica,
                          deadline: Optional[float],
                          tenant: Optional[str] = None
                          ) -> _TierReplica:
        """Move an ACCEPTED request's reservation off a failed decode
        replica onto a survivor. Failover never re-runs admission —
        the request was accepted and the dead replica's slot vanished
        with it — so the survivor is chosen by least in-flight without
        re-checking the shed bound. Waits out a momentarily-empty tier
        (self-healer replacement in flight) like the prefill twin. The
        swap is atomic under the lock: `old` keeps its reservation
        until the survivor holds one, so the caller's release-on-exit
        always has exactly one reservation to release."""
        wait_until = time.monotonic() + self.failover_wait_s
        while True:
            with self._lock:
                cands = [r for r in self._decode if not r.draining]
                if cands:
                    rep = min(cands, key=lambda r: r.inflight)
                    rep.inflight += 1
                    if old.inflight > 0:
                        old.inflight -= 1
                else:
                    rep = None
            if rep is not None:
                if tenant is not None:
                    # failover moved the request (and its adapter
                    # page-in) to the survivor — affinity follows
                    self._note_tenant_decode(tenant, rep.rid)
                return rep
            self._check_deadline(deadline, tenant)
            if time.monotonic() >= wait_until:
                raise self._shed(
                    "failover",
                    f"disagg router {self.router_id}: no live decode "
                    f"replica after {self.failover_wait_s:.0f}s",
                    tenant)
            time.sleep(0.25)

    def generate(self, prompt_tokens, max_new_tokens: int,
                 eos_token: Optional[int] = None, *,
                 timeout_s: float = 120.0,
                 deadline_s: Optional[float] = None,
                 on_first_token=None,
                 token_sleep_s: float = 0.0,
                 tenant: Optional[str] = None,
                 priority: Optional[str] = None,
                 on_tokens=None,
                 cancel_event: Any = None,
                 sink: Any = None) -> List[int]:
        """One request end-to-end. `on_first_token()` (optional) fires
        the moment the first token exists — at prefill completion under
        disaggregation — which is what the harness's TTFT measures.
        `token_sleep_s` simulates a slow client consuming the stream:
        decode ticks must keep serving OTHER requests while this one
        drains slowly.
        `deadline_s` bounds the request's total wall time — past it the
        request sheds with cause ``deadline`` instead of occupying a
        slot forever.

        `tenant` (multi-tenant LoRA, serve/lora.py): serve the request
        under that tenant's adapter — tenant-affinity placement,
        (tenant, prompt)-keyed prefix cache, per-tenant shed/SLO/
        latency counters. Defaults to the current serve request's
        multiplexed-model-id (serve/multiplex.py), so a multiplexed
        deployment is tenant-tagged with no extra plumbing.

        The failover invariant: once this method ADMITS a request, it
        either returns the complete token list — bit-identical to an
        uninterrupted greedy run, surviving any single tier-replica
        death via bounded replay — or raises a RequestShedError with an
        attributed cause. It never silently drops.

        QoS (serve/qos.py, the HTTP front door): `priority` names the
        request's class — ``"batch"`` registers it as a preemption
        victim candidate, ``"interactive"`` lets it preempt a batch
        stream when every slot is taken (the victim resumes via the
        failover replay, bit-identical; the preemptor rides the freed
        slot). `on_tokens(list)` streams each delivered chunk to the
        caller as it lands (the gateway's SSE bridge). `cancel_event`
        (a threading.Event) aborts the request with shed cause
        ``disconnect`` when set — the gateway sets it when the HTTP
        client goes away. `sink` (a ``models.engine.StreamSink``, the
        gateway's for a streamed request) takes the tokens by HAND-OVER
        where the engine is colocated and no `token_sleep_s` is set:
        the engine's loop hands the sink every token, once a pass, and
        `on_tokens` is not called; this thread sleeps on ``sink.wake``
        from the first token to the stream's end (`_generate_colocated`).
        Everywhere else the sink is ignored and `on_tokens` streams as
        ever. All four default to None: in-process callers are
        byte-for-byte unaffected."""
        if priority is not None and priority not in ("interactive",
                                                     "batch"):
            raise ValueError(
                f"unknown priority class {priority!r}; expected "
                f"'interactive' or 'batch'")
        if tenant is None and self._lora_enabled():
            # the implicit multiplexed-model-id default applies ONLY to
            # LoRA-enabled deployments: a plain multiplexed deployment
            # routing through a pool-less router must keep working
            # exactly as before (an EXPLICIT tenant= on a pool-less
            # tier still fails loudly — that is a misconfiguration)
            from .multiplex import request_tenant

            tenant = request_tenant()
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        deadline = (None if deadline_s is None
                    else time.perf_counter() + float(deadline_s))
        # flight recorder: adopt the gateway's trace when one is active
        # on this thread; mint our own for direct callers (and then we
        # own the finish). Either way the trace rides the thread-local
        # so every tier hop below stamps phases without plumbing.
        tr = reqtrace.current_trace()
        owned = tr is None
        if owned:
            tr = reqtrace.start_trace(source="router", tenant=tenant,
                                      cls=priority)
        try:
            with reqtrace.activate(tr):
                self._check_deadline(deadline, tenant)  # arrived expired
                # rep_box[0] is the decode replica currently holding
                # this request's reservation — failover swaps it, and
                # release-on-exit must decrement whichever replica
                # holds it NOW (releasing the original after a swap
                # would steal another request's reservation and leak
                # the survivor's)
                with reqtrace.phase("queue_reserve"):
                    rep_box = [self._admit_or_shed(tenant, deadline,
                                                   priority)]
                t_admit = time.perf_counter()
                pslot = self._preempt_register(priority, tenant)
                ok = False
                try:
                    if not self._disagg_mode:
                        out = self._generate_colocated(
                            prompt, max_new_tokens, eos_token,
                            timeout_s, deadline, on_first_token,
                            token_sleep_s, t_admit, tenant, pslot,
                            on_tokens, cancel_event, rep_box,
                            sink if token_sleep_s <= 0 else None)
                    else:
                        out = self._generate_disagg(
                            rep_box, prompt, max_new_tokens, eos_token,
                            timeout_s, deadline, on_first_token,
                            token_sleep_s, t_admit, tenant, pslot,
                            on_tokens, cancel_event)
                    ok = True
                    if owned and tr is not None:
                        tr.finish("ok", tokens=len(out))
                    return out
                finally:
                    self._preempt_unregister(pslot)
                    self._complete(rep_box[0], ok, tenant=tenant,
                                   wall_ms=(time.perf_counter()
                                            - t_admit) * 1e3)
        except RequestShedError as e:
            if owned and tr is not None:
                cause = getattr(e, "cause", None)
                outcome = {"deadline": "deadline",
                           "disconnect": "disconnect",
                           "preempt": "preempt"}.get(cause, "shed")
                tr.finish(outcome, cause=cause)
            raise
        except BaseException as e:
            if owned and tr is not None:
                tr.finish("error", cause=type(e).__name__)
            raise

    def stream_sink(self, call: Callable[[List[tuple]], None],
                    tag: Any = None) -> Any:
        """A ``StreamSink`` for ONE streamed request of a consumer whose
        one callable is `call` (``generate(sink=)``), where this
        router's engine hands over: the colocated engine. None where it
        does not (the disaggregated tiers pull chunks from a replica):
        the consumer's `on_tokens` streams there."""
        if self._disagg_mode:
            return None
        from ray_tpu.models.engine import StreamSink

        return StreamSink(call, tag)

    def _record_tenant_ttft(self, tenant: Optional[str],
                            ttft_ms: float) -> None:
        if tenant is None:
            return
        with self._lock:
            rec = self._tenant_rec_locked(tenant)
            rec["ttft"].add(ttft_ms)
            if ttft_ms > self._tenant_slo_ms:
                rec["slo_misses"] += 1

    def _generate_colocated(self, prompt, max_new_tokens, eos_token,
                            timeout_s, deadline, on_first_token,
                            token_sleep_s, t_admit, tenant=None,
                            pslot=None, on_tokens=None,
                            cancel_event=None,
                            rep_box=None, sink=None) -> List[int]:
        """Single-engine path — now a replay LOOP mirroring
        _generate_disagg: a preempted batch stream ends early at the
        engine's tick boundary (cancelled slots drain through _DONE)
        and resumes here from prompt+history for the remaining budget,
        bit-identical under greedy decode.

        With a `sink` (``generate``) this thread does not iterate: the
        engine's loop hands the sink EVERY token of the stream, the
        first too, so one thread alone orders them. This thread wakes
        ONCE at the first token (a signal that carries none: the TTFT
        window, the ``decode_first_token`` phase, `on_first_token`),
        then sleeps until the stream's end, the deadline or the
        `cancel_event`, whichever is first: a TIMED wait on
        ``sink.wake`` that ends at the deadline and that whoever sets
        the `cancel_event` sets too, so a shed is no later than a
        token's time, never a poll's. At the end it takes the history
        from the stream (``TokenStream.tokens()``)."""
        history: List[int] = []
        first_emitted = False
        had_preempt = False
        tr = reqtrace.current_trace()
        while True:
            remaining = max_new_tokens - len(history)
            if remaining <= 0:
                break
            if eos_token is not None and history \
                    and history[-1] == int(eos_token):
                break  # complete before the cancel landed
            replay = (np.concatenate(
                [prompt, np.asarray(history, np.int32)])
                if history else prompt)
            try:
                stream = self._colocated.stream(
                    replay, remaining, eos_token, timeout_s=timeout_s,
                    adapter_id=tenant,
                    **({} if sink is None else {"sink": sink}))
            except Exception as e:  # noqa: BLE001 — submit-time failure
                if _is_pool_exhausted(e):
                    raise self._shed_pool_exhausted("colocated", tenant,
                                                    e) from e
                raise
            if pslot is not None:
                # arm preemption for the live stream: the cancel is
                # reason-tagged so engine accounting attributes it
                with self._lock:
                    pslot.rep = rep_box[0] if rep_box else None
                    pslot.cancel_fn = (
                        lambda s=stream: self._colocated.cancel_slot(
                            s, "preempt"))
                    if sink is not None:
                        pslot.live = stream
            t_dec = time.perf_counter()
            t_first_tok: Optional[float] = None
            n_attempt_toks = 0

            def first_token() -> None:
                """The attempt's first token exists."""
                nonlocal t_first_tok, first_emitted
                t_first_tok = time.perf_counter()
                if tr is not None:
                    # the engine's own split of this wait rides the
                    # phase as its parts
                    tr.add_phase(
                        "decode_first_token",
                        (t_first_tok - t_dec) * 1e3,
                        parts=_first_token_parts(stream),
                        **_first_token_waited(stream))
                if not first_emitted:
                    first_emitted = True
                    ttft = (time.perf_counter() - t_admit) * 1e3
                    self._ttft_win.add(ttft)
                    self._record_tenant_ttft(tenant, ttft)
                    if on_first_token is not None:
                        on_first_token()

            try:
                if sink is not None:
                    while True:
                        # to a hair past the deadline, where there is
                        # one nearer than the engine's own timeout
                        wait = timeout_s if deadline is None else min(
                            timeout_s, max(0.0, deadline + 1e-3
                                           - time.perf_counter()))
                        woke = sink.wake.wait(wait)
                        # cleared BEFORE the stream is read: what is
                        # set after this is seen by the next wait
                        sink.wake.clear()
                        ended, before = stream.ended, n_attempt_toks
                        n_attempt_toks = stream.produced
                        if t_first_tok is None and n_attempt_toks:
                            first_token()
                        if ended:
                            break
                        self._check_abort(deadline, tenant, cancel_event)
                        if not woke and wait >= timeout_s \
                                and n_attempt_toks == before:
                            # the engine stalled: what the iterator's
                            # get raises after `timeout_s` and no token
                            raise queue.Empty
                    # the engine's own history of the stream, whole now
                    history.extend(stream.tokens())
                    if pslot is not None:
                        pslot.tokens, pslot.live = len(history), None
                else:
                    for tok in stream:
                        if t_first_tok is None:
                            first_token()
                        n_attempt_toks += 1
                        history.append(tok)
                        if pslot is not None:
                            pslot.tokens = len(history)
                        if on_tokens is not None:
                            try:
                                on_tokens([tok])
                            except Exception:  # noqa: BLE001 — caller's
                                pass
                        if token_sleep_s > 0:
                            time.sleep(token_sleep_s)
                        self._check_abort(deadline, tenant, cancel_event)
            except RequestShedError as e:
                # deadline/disconnect shed mid-stream: cancel the
                # engine slot so the abandoned request stops burning
                # ticks (freed + pins released at the tick boundary)
                if tr is not None:
                    tr.add_phase(
                        "decode_steady" if t_first_tok is not None
                        else "decode_first_token",
                        (time.perf_counter()
                         - (t_first_tok or t_dec)) * 1e3,
                        tokens=n_attempt_toks,
                        error=getattr(e, "cause", None) or "shed")
                cancel = getattr(self._colocated, "cancel_slot", None)
                if callable(cancel):
                    cancel(stream, getattr(e, "cause", None))
                raise
            finally:
                if pslot is not None:
                    with self._lock:
                        pslot.cancel_fn = None
                        pslot.live = None
            if tr is not None and t_first_tok is not None:
                tr.add_phase("decode_steady",
                             (time.perf_counter() - t_first_tok) * 1e3,
                             tokens=n_attempt_toks)
            if pslot is not None and pslot.preempted \
                    and len(history) < max_new_tokens \
                    and not (eos_token is not None and history
                             and history[-1] == int(eos_token)):
                # the stream ended early because an interactive
                # request took the slot — resume, don't return short
                with self._lock:
                    pslot.preempted = False
                had_preempt = True
                if tr is not None:
                    tr.mark_preempt()
                time.sleep(0.1)  # let the preemptor actually land
                continue
            break
        if had_preempt:
            with self._lock:
                self._stats["preempted_requests"] += 1
        return history

    def _generate_disagg(self, rep_box, prompt, max_new_tokens,
                         eos_token, timeout_s, deadline, on_first_token,
                         token_sleep_s, t_admit, tenant=None,
                         pslot=None, on_tokens=None,
                         cancel_event=None) -> List[int]:
        """The failover loop. `history` holds every token delivered so
        far; a replay prefills prompt+history (a suffix-only prefill
        thanks to the prefix cache — the dead replica's tokens EXTEND
        the prompt) and resumes decode for the remaining budget, so the
        concatenated stream is bit-identical to an uninterrupted greedy
        run. `rep_box[0]` tracks the decode replica holding the
        request's reservation across swaps; the caller releases it.

        A QoS preemption (`pslot` marked preempted, its stream
        cancelled under it) rides the SAME loop: the victim's pull
        ends early — done short of budget from an in-flight pull, or
        KeyError once the handle is popped — and the next iteration
        replays exactly like a failover, without consuming a failover
        attempt or moving the reservation."""
        history: List[int] = []
        attempt = 0
        first_emitted = False
        fail_detected: Optional[float] = None
        had_failover = False
        had_preempt = False
        tr = reqtrace.current_trace()

        def _preempt_resume() -> bool:
            """True exactly once per fired preemption: the stream
            ended early because an interactive request took the slot
            (not death, not completion) — resume, don't fail over."""
            nonlocal had_preempt
            if pslot is None or not pslot.preempted:
                return False
            if len(history) >= max_new_tokens or (
                    eos_token is not None and history
                    and history[-1] == int(eos_token)):
                return False  # complete anyway; nothing to resume
            with self._lock:
                pslot.preempted = False
            had_preempt = True
            if tr is not None:
                # the replay's phases become a child span set under
                # the same request id, tagged with the new attempt
                tr.mark_preempt()
            time.sleep(0.1)  # let the preemptor actually land
            return True

        while True:
            rep = rep_box[0]
            attempt += 1
            self._check_abort(deadline, tenant, cancel_event)
            remaining = max_new_tokens - len(history)
            if remaining <= 0:
                return history  # died between last token and DONE
            if eos_token is not None and history \
                    and history[-1] == int(eos_token):
                # the eos token was already delivered — the replica
                # died between the eos pull and the done pull. The
                # request IS complete; replaying would decode past eos
                # and break bit-identity.
                return history
            replay = (np.concatenate(
                [prompt, np.asarray(history, np.int32)])
                if history else prompt)
            # ---- prefill phase (retryable: nothing emitted from rec
            # until decode pulls it)
            pf, kv_hint = self._pick_prefill_or_wait(
                replay, rep.machine, deadline, tenant)
            with self._lock:
                self._pf_inflight += 1
                pf.inflight += 1
            self._pf_inflight_win.add(self._pf_inflight)
            try:
                # the tier-3 hint rides as an extra positional only
                # when present — pre-kvplane replicas (and test
                # doubles) keep their two-argument prefill surface
                pf_args = (replay.tolist(), tenant) \
                    if kv_hint is None \
                    else (replay.tolist(), tenant, kv_hint)
                with reqtrace.phase("prefill", replica=pf.rid,
                                    prompt_tokens=int(replay.size)):
                    rec = self._tier_call(pf, "prefill", "prefill",
                                          *pf_args)
            except Exception as e:  # noqa: BLE001 — dead or broken
                if _is_pool_exhausted(e):
                    raise self._shed_pool_exhausted("prefill", tenant,
                                                    e) from e
                self._check_request_fault(tenant, e)
                fail_detected = time.perf_counter()
                had_failover = True
                self._attempt_failed("prefill", pf.rid, attempt, e,
                                     tenant)
                if tr is not None:
                    tr.begin_attempt()
                continue
            finally:
                with self._lock:
                    self._pf_inflight -= 1
                    if pf.inflight > 0:
                        pf.inflight -= 1
            try:
                if tr is not None and rec.get("kvplane"):
                    # tier-2/3 fetch sub-phases: the flight recorder
                    # attributes KV-plane time inside the prefill span
                    for tier_n, ph in (("tier2",
                                        "kvplane_tier2_fetch"),
                                       ("tier3",
                                        "kvplane_tier3_fetch")):
                        tinfo = rec["kvplane"].get(tier_n)
                        if tinfo:
                            tr.add_phase(
                                ph, float(tinfo.get("ms", 0.0)),
                                replica=pf.rid,
                                blocks=int(tinfo.get("blocks", 0)),
                                tokens=int(tinfo.get("tokens", 0)),
                                kv_bytes=int(tinfo.get("nbytes", 0)))
                if not first_emitted:
                    # the first token exists NOW — this is the TTFT
                    # the recent window (and the policy's queueing-
                    # delay signal) reads
                    first_emitted = True
                    ttft = (time.perf_counter() - t_admit) * 1e3
                    self._ttft_win.add(ttft)
                    self._record_tenant_ttft(tenant, ttft)
                    self._cache_win.add(
                        _OUTCOME_WEIGHT.get(rec.get("outcome"), 0.0))
                    if on_first_token is not None:
                        on_first_token()
                if fail_detected is not None:
                    # recovery cost: failure detection -> replayed
                    # prefill landed (the stream is about to resume)
                    self._failover_win.add(
                        (time.perf_counter() - fail_detected) * 1e3)
                    fail_detected = None
            except BaseException:
                # a raising caller callback must not strand the
                # just-published transfer un-acked (it would pin the
                # sender's chunk refs forever on a quiet tier)
                self._ack_transfer(pf, rec)
                raise
            # ---- decode phase: chunked pulls so the router holds the
            # history the next replay would need
            hid = None
            # slow-client pacing sleeps token_sleep_s * chunk between
            # pulls; cap the chunk so the inter-pull gap stays well
            # inside the replica's idle-reap window (the reap stamp
            # refreshes on every pull) — without this, pacing past
            # _STREAM_REAP_S / chunk would reap a healthy live stream
            chunk = self.stream_chunk_tokens
            if token_sleep_s > 0:
                chunk = max(1, min(chunk,
                                   int(120.0 / token_sleep_s) or 1))
            t_dec: Optional[float] = None
            t_first_tok: Optional[float] = None
            n_attempt_toks = 0
            spec_attrs: Dict[str, int] = {}
            try:
                if tr is not None:
                    # remote decode tiers (actor mode) push their KV
                    # adoption breakdown to the conductor as a child
                    # phase under this id; local tiers annotate the
                    # open kv_transfer span directly
                    rec["_reqtrace"] = {"request_id": tr.request_id,
                                        "attempt": attempt}
                with reqtrace.phase("kv_transfer", replica=rep.rid):
                    hid = self._tier_call(rep, "decode", "start_decode",
                                          rec, remaining, eos_token,
                                          timeout_s)
                t_dec = time.perf_counter()
                if pslot is not None:
                    # arm preemption for the LIVE stream only: an
                    # interactive arrival cancels exactly this handle
                    # (reason-tagged so engine accounting attributes
                    # it) and rides the freed slot
                    with self._lock:
                        pslot.rep = rep
                        pslot.cancel_fn = (
                            lambda r=rep, h=hid: _call(  # shardlint: disable=unsupervised-actor-call
                                r.target, "cancel_decode", h,
                                "preempt", block=False))
                last_progress = time.perf_counter()
                while True:
                    out = self._tier_call(
                        rep, "decode", "next_tokens", hid, chunk,
                        min(2.0, max(0.1, timeout_s / 4)))
                    toks = out.get("tokens") or []
                    if toks:
                        if t_first_tok is None:
                            t_first_tok = time.perf_counter()
                            if tr is not None:
                                tr.add_phase(
                                    "decode_first_token",
                                    (t_first_tok - t_dec) * 1e3,
                                    replica=rep.rid,
                                    parts=_first_token_parts(out),
                                    **_first_token_waited(out))
                        n_attempt_toks += len(toks)
                        history.extend(int(t) for t in toks)
                        if pslot is not None:
                            pslot.tokens = len(history)
                        if on_tokens is not None:
                            # the gateway's SSE bridge; its bugs (or a
                            # closed queue) must not kill the decode
                            try:
                                on_tokens([int(t) for t in toks])
                            except Exception:  # noqa: BLE001
                                pass
                        last_progress = time.perf_counter()
                        if token_sleep_s > 0:
                            time.sleep(token_sleep_s * len(toks))
                    if out.get("done"):
                        if tr is not None:
                            for k in ("spec_proposed", "spec_accepted"):
                                if out.get(k) is not None:
                                    spec_attrs[k] = int(out[k])
                            tr.add_phase(
                                "decode_steady",
                                (time.perf_counter()
                                 - (t_first_tok or t_dec)) * 1e3,
                                replica=rep.rid, tokens=n_attempt_toks,
                                **spec_attrs)
                        self._ack_transfer(pf, rec)
                        if _preempt_resume():
                            # the cancel landed mid-pull: this "done"
                            # is the cancelled slot draining, not
                            # completion — replay from history
                            break
                        if had_failover:
                            with self._lock:
                                self._sf["failover_requests"] += 1
                            self.publish_servefault()
                        if had_preempt:
                            with self._lock:
                                self._stats[
                                    "preempted_requests"] += 1
                        return history
                    try:
                        self._check_abort(deadline, tenant,
                                          cancel_event)
                    except RequestShedError as e:
                        # abandon the stream: the engine frees the slot
                        # on its own; the transfer is still acked so
                        # the sender's chunk refs never leak
                        if tr is not None and t_dec is not None:
                            tr.add_phase(
                                "decode_steady"
                                if t_first_tok is not None
                                else "decode_first_token",
                                (time.perf_counter()
                                 - (t_first_tok or t_dec)) * 1e3,
                                replica=rep.rid,
                                tokens=n_attempt_toks,
                                error=getattr(e, "cause", None)
                                or "shed")
                        try:
                            self._tier_call(rep, "decode",
                                            "cancel_decode", hid,
                                            getattr(e, "cause", None),
                                            block=False)
                        except Exception:  # noqa: BLE001 — dead too
                            pass
                        self._ack_transfer(pf, rec)
                        raise
                    if time.perf_counter() - last_progress > timeout_s:
                        raise TimeoutError(
                            f"decode stream stalled > {timeout_s:.0f}s "
                            f"on {rep.rid}")
            except RequestShedError:
                raise
            except Exception as e:  # noqa: BLE001 — death or stall
                if tr is not None and t_dec is not None:
                    # the failed attempt's partial decode IS a child
                    # span — the failover breakdown needs it
                    tr.add_phase(
                        "decode_steady" if t_first_tok is not None
                        else "decode_first_token",
                        (time.perf_counter()
                         - (t_first_tok or t_dec)) * 1e3,
                        replica=rep.rid, tokens=n_attempt_toks,
                        error=type(e).__name__)
                if _preempt_resume():
                    # not a fault: the pull handle vanished because an
                    # interactive request took the slot (cancel_decode
                    # pops it -> this KeyError). Resume WITHOUT
                    # consuming a failover attempt or moving the
                    # reservation — the replica is alive.
                    self._ack_transfer(pf, rec)
                    continue
                if _is_pool_exhausted(e):
                    self._ack_transfer(pf, rec)
                    raise self._shed_pool_exhausted("decode", tenant,
                                                    e) from e
                try:
                    self._check_request_fault(tenant, e)
                except ValueError:
                    self._ack_transfer(pf, rec)
                    raise
                fail_detected = time.perf_counter()
                had_failover = True
                if hid is not None:
                    # a LIVE-but-stalled replica keeps its abandoned
                    # stream (and the engine slot behind it) unless we
                    # cancel; on a dead replica this is a no-op throw
                    try:
                        _call(rep.target, "cancel_decode", hid,  # shardlint: disable=unsupervised-actor-call
                              "failover", block=False)
                    except Exception:  # noqa: BLE001 — replica dead
                        pass
                self._ack_transfer(pf, rec)
                self._attempt_failed("decode", rep.rid, attempt, e,
                                     tenant)
                if tr is not None:
                    tr.begin_attempt()
                rep_box[0] = self._reserve_survivor(rep, deadline,
                                                    tenant)
                continue
            finally:
                if pslot is not None:
                    with self._lock:
                        pslot.cancel_fn = None

    # ------------------------------------------------------------ telemetry

    def signals(self) -> Dict[str, Any]:
        """The autoscale policy's input snapshot (recent windows; keys
        absent when there is no evidence yet — see
        serve/autoscale.DisaggPolicy for what each drives)."""
        sig: Dict[str, Any] = {}
        ttft = self._ttft_win.summary()
        if ttft["n"]:
            sig["ttft_p99_ms"] = ttft["p99"]
        depth = self._depth_win.summary()
        if depth["n"]:
            sig["queue_depth_p99"] = depth["p99"]
        pf = self._pf_inflight_win.summary()
        if pf["n"]:
            sig["prefill_inflight_p99"] = pf["p99"]
        cache = self._cache_win.summary()
        if cache["n"]:
            sig["cache_hit_rate"] = cache["mean"]
        return sig

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            s: Dict[str, Any] = dict(self._stats)
            s["pending"] = sum(r.inflight for r in self._decode)
            s["failovers"] = dict(self._sf["failovers"])
            s["failover_requests"] = self._sf["failover_requests"]
            s["sheds_by_cause"] = dict(self._sf["sheds_by_cause"])
            decode = list(self._decode)
            prefill = list(self._prefill)
        s.update(role="router", router_id=self.router_id,
                 mode="disagg" if self._disagg_mode else "colocated",
                 decode_replicas=sum(1 for r in decode
                                     if not r.draining),
                 prefill_replicas=sum(1 for r in prefill
                                      if not r.draining),
                 draining_replicas=sum(
                     1 for r in decode + prefill if r.draining),
                 capacity=sum(r.cap for r in decode if not r.draining),
                 max_queue_depth=self.max_queue_depth,
                 retry_after_s=self.retry_after_s,
                 replica_devices={
                     tier: {r.rid: r.device for r in reps}
                     for tier, reps in (("prefill", prefill),
                                        ("decode", decode))})
        if s["shm_affinity_total"]:
            s["shm_affinity_hit_rate"] = round(
                s["shm_affinity_hits"] / s["shm_affinity_total"], 4)
        if s["tenant_affinity_total"]:
            s["tenant_affinity_hit_rate"] = round(
                s["tenant_affinity_hits"] / s["tenant_affinity_total"],
                4)
        tenants = self.tenant_stats()
        if tenants:
            s["tenants"] = tenants
        # recent trailing-window summaries beside the lifetime counters
        # (`serve status`/CLI show both; the autoscale policy reads the
        # same derivation through signals())
        s["recent"] = {
            "ttft_ms": self._ttft_win.summary(),
            "queue_depth": self._depth_win.summary(),
            "prefill_inflight": self._pf_inflight_win.summary(),
            "cache_hit_rate": self._cache_win.summary(),
        }
        return s

    def tenant_stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant isolated counters (dispatched/completed/shed by
        cause/SLO misses + recent TTFT/latency windows) — the router's
        contribution to the lora surface, and the bench's isolation
        evidence."""
        with self._lock:
            out: Dict[str, Dict[str, Any]] = {}
            for t, rec in self._tenant_stats.items():
                out[t] = {
                    "dispatched": rec["dispatched"],
                    "completed": rec["completed"],
                    "shed": rec["shed"],
                    "sheds_by_cause": dict(rec["sheds_by_cause"]),
                    "slo_misses": rec["slo_misses"],
                    "ttft_ms": rec["ttft"].summary(),
                    "latency_ms": rec["latency"].summary(),
                }
        return out

    def kvplane_stats(self) -> Dict[str, Any]:
        """The router's KV-plane contribution: directory routing
        outcomes (hit = routed to the holder, fallback = holder gone,
        hashed + tier-3 hint, miss = nothing published). Rates and
        totals merge with the replicas' tier stats on the conductor."""
        with self._lock:
            s: Dict[str, Any] = {
                k: self._stats[k] for k in
                ("directory_hits", "directory_misses",
                 "directory_fallbacks")}
        s.update(role="router", router_id=self.router_id,
                 enabled=self._kvplane_dir,
                 kv_block_size=self._kv_block_size)
        probes = (s["directory_hits"] + s["directory_misses"]
                  + s["directory_fallbacks"])
        if probes:
            s["directory_hit_rate"] = round(
                s["directory_hits"] / probes, 4)
        return s

    def publish_telemetry(self, force: bool = False) -> None:
        if not self._pusher.push(self.stats, force=force):
            return
        if self._disagg_mode and self._kvplane_dir:
            self._kvplane_pusher.push(self.kvplane_stats, force=True)

        def tenant_counters():
            # the router's tenant counters ride the lora surface too,
            # beside the pools' paging stats (one aggregate, every
            # surface reads the same numbers)
            tenants = self.tenant_stats()
            if not tenants:
                return None
            return {"role": "router", "router_id": self.router_id,
                    "tenant_affinity_hits":
                        self._stats["tenant_affinity_hits"],
                    "tenant_affinity_total":
                        self._stats["tenant_affinity_total"],
                    "tenants": tenants}

        self._lora_pusher.push(tenant_counters, force=True)

    def servefault_stats(self) -> Dict[str, Any]:
        """The fault-tolerance snapshot this router contributes to the
        servefault surface (state API == CLI == dashboard ==
        Prometheus == timeline read the same numbers)."""
        with self._lock:
            sf: Dict[str, Any] = {
                "failovers": dict(self._sf["failovers"]),
                "failover_requests": self._sf["failover_requests"],
                "sheds_by_cause": dict(self._sf["sheds_by_cause"]),
                "removed_dead": dict(self._sf["removed_dead"]),
            }
        sf.update(role="router", router_id=self.router_id,
                  recent_failover_recovery_ms=
                  self._failover_win.summary())
        return sf

    def publish_servefault(self, force: bool = False) -> None:
        self._sf_pusher.push(self.servefault_stats, force=force)


__all__ = ["DecodeServer", "DisaggRouter", "PrefillServer",
           "ReplicaDeadError", "RequestShedError", "disagg_metrics",
           "servefault_metrics"]
