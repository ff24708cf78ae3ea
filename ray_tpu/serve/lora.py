"""Multi-tenant LoRA serving: a paged adapter pool over one resident
base model (the ROADMAP's scenario-diversity item — millions of users
means per-tenant fine-tunes, not one monolith; S-LoRA's serving shape
rebuilt on machinery this repo already owns).

The pieces, and where each lives:

- **AdapterPool** (here): adapters page through a refcounted LRU pool
  exactly like KV blocks page through ``models/kvcache.py`` — device-
  resident stacks ``A [P, L, in, r_max]`` / ``B [P, L, r_max, out]``
  per LoRA-target leaf (``models.generate.lora_targets``), one pool
  row per adapter, row 0 reserved as the NULL adapter (zero A/B,
  scale 0 — the base model). ``acquire(tenant)`` pins a resident
  adapter (hit) or pages it in (miss: fetch → zero-pad to ``r_max`` →
  write its row), evicting the least-recently-used UNPINNED row under
  pressure; pinned rows are never evicted. Acquisition runs on the
  SUBMITTING thread (models/engine.py submit/adopt_prefill), so a cold
  tenant's page-in can never stall another tenant's decode tick. Pool
  row writes are DONATED jits — O(row) in place, never an O(pool)
  stack copy (the models/kvcache.py write discipline; at 64 slots x
  32 layers a copying write moves the whole pool per page-in). The
  donation is tick-safe the same way the kvcache's is: every read of
  the stacks (the decode tick via ``dispatch_tick``, the prefill
  merge's ``adapter_slice``) and every donated write dispatches under
  the pool lock, so same-device stream order makes dispatch the only
  critical section while the compute overlaps freely. shardlint's
  ``undonated-pool-write`` rule guards the discipline.
- **Cross-tenant batched decode** (models/engine.py ``_tick(lora=)`` +
  the model families' ``*_decode(lora=)``): one decode tick serves
  mixed tenants via per-slot adapter indices gathering each slot's
  A/B out of these stacks — ``base @ x + scatter-gathered (B·A) @ x``
  at the target leaves. Null-adapter slots are bit-identical to the
  base-only engine (the correctness oracle, asserted in
  tests/test_lora.py).
- **Paging source**: :class:`FabricAdapterSource` fetches adapters on
  demand through :class:`~ray_tpu.weights.WeightSubscriber` from the
  weight fabric's (delta) publications under ``lora/<tenant>`` — a
  tenant's publish marks it dirty (pubsub) and the next acquire
  hot-swaps the new version into its row BETWEEN ticks, without
  touching the base or any other tenant's in-flight requests.
  :class:`LocalAdapterSource` is the clusterless twin (tests, the
  in-process load harness).
- **Tenant routing** (serve/disagg.py): ``DisaggRouter.generate``
  carries a ``tenant`` tag (defaulting to serve/multiplex.py's
  multiplexed-model-id — the request-side plumbing reused as the
  tenant tag), adds tenant-affinity beside prefix-affinity, keeps
  per-tenant shed/SLO/latency counters, and the prefix cache keys
  entries by (tenant, prompt) (``models/kvcache.py`` namespaces).
- **Per-tenant online loop** (online/lora.py ``TenantLoraTrainer``):
  adapter-only gradients against the frozen base, published as deltas
  that hot-swap through the dirty-tenant path above.

Surfaces (the full treatment): ``util.state.lora_status()``,
``ray_tpu lora`` CLI, dashboard ``/api/lora`` + tab, lazy Prometheus
``ray_tpu_lora_adapter_{hits,misses,evictions}_total{tenant}`` +
``ray_tpu_lora_pool_utilization``, and a ``lora`` merged-timeline lane
with page_in / evict / swap instant markers. Knobs:
``RAY_TPU_LORA_POOL_SLOTS`` (adapter rows beside the null row, default
8), ``RAY_TPU_LORA_RANK_MAX`` (pool rank ceiling, default 8). Paging
and isolation are held by ``tests/test_lora.py`` (pool and router tests).
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.util.telemetry import Pusher

_POOL_SEQ = itertools.count()
_EVENTS_KEPT = 512


def default_pool_slots() -> int:
    return max(1, int(os.environ.get("RAY_TPU_LORA_POOL_SLOTS", "8")))


def default_rank_max() -> int:
    return max(1, int(os.environ.get("RAY_TPU_LORA_RANK_MAX", "8")))


def tenant_weights_name(tenant: str, prefix: str = "lora/") -> str:
    """The weight-fabric name a tenant's adapter publishes under — the
    ONE convention the pool's fabric source, the per-tenant online
    trainer, and the CLI all share."""
    return f"{prefix}{tenant}"


# ----------------------------------------------------- prometheus (lazy)
# Created on first pool construction, never at import (the weights /
# kvcache / disagg pattern — rebound ONCE to a complete dict).

_metrics: Optional[Dict[str, Any]] = None
_metrics_lock = threading.Lock()


def lora_metrics() -> Dict[str, Any]:
    global _metrics
    m = _metrics
    if m is not None:
        return m
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            _metrics = dict(
                hits=Counter(
                    "ray_tpu_lora_adapter_hits_total",
                    "adapter-pool acquisitions served by a resident "
                    "adapter", tag_keys=("tenant",)),
                misses=Counter(
                    "ray_tpu_lora_adapter_misses_total",
                    "adapter-pool acquisitions that paged the adapter "
                    "in", tag_keys=("tenant",)),
                evictions=Counter(
                    "ray_tpu_lora_adapter_evictions_total",
                    "unpinned adapters LRU-evicted from the pool under "
                    "pressure", tag_keys=("tenant",)),
                swaps=Counter(
                    "ray_tpu_lora_adapter_swaps_total",
                    "resident adapters hot-swapped to a newer "
                    "published version", tag_keys=("tenant",)),
                utilization=Gauge(
                    "ray_tpu_lora_pool_utilization",
                    "fraction of adapter-pool rows holding a resident "
                    "adapter"))
    return _metrics


def _worker():
    from ray_tpu._private import worker as worker_mod

    return worker_mod.global_worker


# -------------------------------------------------- donated row writes

_row_write_jit = None


def _row_write():
    """The ONE donated pool-row writer (lazy so importing serve.lora
    never touches jax): ``write(stack, row, leaf)`` lowers to an
    in-place O(leaf) update of ``stack[row]`` with the stack donated —
    one compiled program per stack shape, shared by every A/B leaf and
    the scale vector. Callers must hold the pool lock across the
    dispatch (see AdapterPool)."""
    global _row_write_jit
    if _row_write_jit is None:
        import functools

        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def write(stack, row, leaf):
            return jax.lax.dynamic_update_slice(
                stack, leaf[None], (row,) + (0,) * leaf.ndim)

        _row_write_jit = write
    return _row_write_jit


# ------------------------------------------------------- host adapters

def make_lora_adapter(config: Any, rank: int, *, seed: int = 0,
                      scale: float = 1.0) -> Dict[str, Any]:
    """A host-side adapter tree for `config`'s LoRA-target leaves:
    ``{"scale": f32[], "targets": {name: {"a": [L, in, r],
    "b": [L, r, out]}}}`` — the pytree shape the weight fabric
    publishes and the pool pages. Both A and B are random (classic
    LoRA inits B = 0 — a no-op adapter — which would make every
    isolation test vacuous), in the model's compute dtype."""
    from ray_tpu.models.generate import lora_targets

    rng = np.random.default_rng(seed)
    layers = len_blocks(config)
    targets: Dict[str, Any] = {}
    for name, d_in, d_out in lora_targets(config):
        targets[name] = {
            "a": (rng.standard_normal((layers, d_in, rank))
                  * 0.05).astype(np.float32),
            "b": (rng.standard_normal((layers, rank, d_out))
                  * 0.05).astype(np.float32),
        }
    return {"scale": np.float32(scale), "targets": targets}


def len_blocks(config: Any) -> int:
    return int(config.num_layers)


def adapter_nbytes(adapter: Dict[str, Any]) -> int:
    """Host bytes of one adapter tree (the bench's paging-amortization
    denominator)."""
    n = 0
    for ab in adapter["targets"].values():
        n += int(np.asarray(ab["a"]).nbytes)
        n += int(np.asarray(ab["b"]).nbytes)
    return n


def adapter_rank(adapter: Dict[str, Any]) -> int:
    ab = next(iter(adapter["targets"].values()))
    return int(np.asarray(ab["a"]).shape[-1])


def publish_adapter(tenant: str, adapter: Dict[str, Any], *,
                    prefix: str = "lora/", delta: bool = True) -> int:
    """Publish a tenant's adapter to the weight fabric under
    ``lora/<tenant>`` (delta publication by default — an adapter
    refresh usually touches a subset of leaves). Every subscribed
    AdapterPool sees the pubsub notice, marks the tenant dirty, and
    hot-swaps on its next acquire. Returns the committed version."""
    from ray_tpu.weights import publish

    return int(publish(adapter,
                       name=tenant_weights_name(tenant, prefix),
                       delta=delta))


# ------------------------------------------------------ adapter sources

class LocalAdapterSource:
    """Clusterless paging source: a host-side dict of adapter trees.
    ``publish()`` bumps the tenant's version and marks it dirty — the
    in-process stand-in for a weight-fabric publication (tests and the
    inline load harness use it; `fetch_delay_s` simulates a slow fetch
    so the no-stall tests can prove page-ins never block ticks)."""

    def __init__(self, adapters: Optional[Dict[str, Any]] = None, *,
                 fetch_delay_s: float = 0.0):
        self._lock = threading.Lock()
        self._adapters: Dict[str, Tuple[int, Dict[str, Any]]] = {
            t: (1, a) for t, a in (adapters or {}).items()}
        self._dirty: set = set()
        self.fetch_delay_s = float(fetch_delay_s)

    def publish(self, tenant: str, adapter: Dict[str, Any]) -> int:
        with self._lock:
            ver = self._adapters.get(tenant, (0, None))[0] + 1
            self._adapters[tenant] = (ver, adapter)
            self._dirty.add(tenant)
        return ver

    def fetch(self, tenant: str) -> Tuple[int, Dict[str, Any], int]:
        if self.fetch_delay_s > 0:
            time.sleep(self.fetch_delay_s)
        with self._lock:
            entry = self._adapters.get(tenant)
            if entry is None:
                raise KeyError(f"no adapter registered for tenant "
                               f"{tenant!r}")
            self._dirty.discard(tenant)
            ver, adapter = entry
        return ver, adapter, adapter_nbytes(adapter)

    def dirty(self, tenant: str) -> bool:
        with self._lock:
            return tenant in self._dirty


class FabricAdapterSource:
    """Weight-fabric paging source: each tenant's adapter lives under
    ``lora/<tenant>`` in the versioned registry (delta publications —
    PR 8's changed-leaves machinery — so an adapter refresh ships only
    what changed). One :class:`WeightSubscriber` per tenant, created
    lazily; the shared ``weights`` pubsub channel marks tenants dirty
    the moment a new version commits, so the next acquire hot-swaps
    without polling."""

    def __init__(self, prefix: str = "lora/"):
        self.prefix = str(prefix)
        self._lock = threading.Lock()
        self._subs: Dict[str, Any] = {}
        self._dirty: set = set()
        w = _worker()
        if w is None:
            raise RuntimeError(
                "FabricAdapterSource needs a live cluster "
                "(ray_tpu.init); use LocalAdapterSource clusterless")
        self._worker_ref = w
        w.subscribe_channel("weights", self._on_weights_msg)

    def _on_weights_msg(self, msg: Any) -> None:
        if not isinstance(msg, dict) or msg.get("kind") != "published":
            return
        name = str(msg.get("name") or "")
        if name.startswith(self.prefix):
            with self._lock:
                self._dirty.add(name[len(self.prefix):])

    def _sub(self, tenant: str):
        from ray_tpu.weights import WeightSubscriber

        with self._lock:
            sub = self._subs.get(tenant)
        if sub is not None:
            return sub
        # construct OUTSIDE the lock: the subscriber's setup talks to
        # the conductor, and holding this lock across an RPC would let
        # a slow registry stall every dirty() probe (which the pool
        # calls on its hot acquire path). Double-checked insert; a
        # racing duplicate is closed, the winner kept.
        sub = WeightSubscriber(tenant_weights_name(tenant, self.prefix))
        with self._lock:
            cur = self._subs.get(tenant)
            if cur is None:
                self._subs[tenant] = sub
                return sub
        sub.close()
        return cur

    def fetch(self, tenant: str) -> Tuple[int, Dict[str, Any], int]:
        sub = self._sub(tenant)
        with self._lock:
            self._dirty.discard(tenant)
        adapter = sub.fetch()  # numpy leaves via the producer treedef
        stats = sub.last_stats
        ver = int(stats.version) if stats else 0
        moved = int(stats.fetched_bytes) if stats else 0
        return ver, adapter, moved

    def dirty(self, tenant: str) -> bool:
        with self._lock:
            return tenant in self._dirty

    def close(self) -> None:
        try:
            self._worker_ref.unsubscribe_channel("weights",
                                                 self._on_weights_msg)
        except Exception:  # noqa: BLE001 — worker already torn down
            pass
        with self._lock:
            subs, self._subs = dict(self._subs), {}
        for sub in subs.values():
            try:
                sub.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass


def build_pool(config: Any, lora: Any, *, slots: Optional[int] = None,
               rank_max: Optional[int] = None,
               prefix: str = "lora/") -> Optional["AdapterPool"]:
    """The one `lora=` ctor-knob parser every replica shares
    (PrefillServer / DecodeServer / the colocated engine builders):
    ``None``/``False`` → no pool; ``True`` → page from the weight
    fabric (FabricAdapterSource); a dict of host adapter trees →
    LocalAdapterSource; an AdapterPool → used as-is (shared pool); any
    other object → treated as a source."""
    if lora is None or lora is False:
        return None
    if isinstance(lora, AdapterPool):
        return lora
    if lora is True:
        source: Any = FabricAdapterSource(prefix)
    elif isinstance(lora, dict):
        source = LocalAdapterSource(lora)
    else:
        source = lora
    return AdapterPool(config, slots=slots, rank_max=rank_max,
                       source=source)


class LoraPoolExhausted(RuntimeError):
    """Every pool row is pinned by an in-flight request — the caller
    should shed (cause `capacity`) or retry; admission control sizes
    concurrency below this in a healthy deployment."""


class _Resident:
    __slots__ = ("tenant", "row", "version", "rank", "ref", "last_used",
                 "nbytes")

    def __init__(self, tenant: str, row: int):
        self.tenant = tenant
        self.row = row
        self.version = 0
        self.rank = 0
        self.ref = 0
        self.last_used = 0
        self.nbytes = 0


class AdapterPool:
    """Refcounted LRU pool of device-resident LoRA adapters for one
    engine (or prefill server). Thread-safe; fetches run OUTSIDE the
    lock (single-flight per tenant) so a cold page-in never blocks the
    decode loop's ``tick_args`` read or another tenant's acquire."""

    def __init__(self, config: Any, *, slots: Optional[int] = None,
                 rank_max: Optional[int] = None,
                 source: Any = None,
                 pool_id: Optional[str] = None):
        import jax.numpy as jnp

        from ray_tpu.models.generate import lora_targets

        self.config = config
        self.slots = int(slots) if slots else default_pool_slots()
        self.rank_max = int(rank_max) if rank_max else default_rank_max()
        if self.slots < 1 or self.rank_max < 1:
            raise ValueError("slots and rank_max must be >= 1")
        self.source = source if source is not None \
            else LocalAdapterSource()
        self.pool_id = pool_id or f"lorapool-{os.getpid()}-" \
                                  f"{next(_POOL_SEQ)}"
        self.targets = lora_targets(config)
        self.dtype = config.dtype
        layers = len_blocks(config)
        rows = self.slots + 1  # row 0: the null/base adapter
        # the device stacks the mixed-tenant tick gathers from; zeros
        # everywhere means every row starts as the null adapter
        self._a = {name: jnp.zeros((rows, layers, d_in, self.rank_max),
                                   self.dtype)
                   for name, d_in, _ in self.targets}
        self._b = {name: jnp.zeros((rows, layers, self.rank_max, d_out),
                                   self.dtype)
                   for name, _, d_out in self.targets}
        self._scale = jnp.zeros((rows,), jnp.float32)
        self._lock = threading.Lock()
        self._by_tenant: Dict[str, _Resident] = {}
        self._free: List[int] = list(range(rows - 1, 0, -1))
        self._loading: Dict[str, threading.Event] = {}
        # last version ever installed per tenant — SURVIVES eviction.
        # A tenant evicted, republished, and paged back in arrives at a
        # DIFFERENT version than its (still-cached, version-blind)
        # namespace-keyed KV was computed under; comparing against this
        # map is what makes the swap listeners (the engine's scoped KV
        # invalidation) fire on that path too, not just on a
        # resident-row hot-swap. One int per tenant ever seen — tiny.
        self._seen_versions: Dict[str, int] = {}
        self._tick = itertools.count(1)
        self._swap_listeners: List[Callable[[str], None]] = []
        self._events: List[Dict[str, Any]] = []
        self._stats: Dict[str, int] = {k: 0 for k in (
            "acquires", "hits", "misses", "evictions", "swaps",
            "page_in_bytes", "releases")}
        self._tenant_stats: Dict[str, Dict[str, int]] = {}
        self._pusher = Pusher("lora", self.pool_id)
        lora_metrics()  # lazy registration before the first event

    # ----------------------------------------------------------- helpers

    def add_swap_listener(self,
                          fn: Callable[[str, Optional[int]], None]
                          ) -> None:
        """Called (outside the pool lock) as ``fn(tenant,
        old_version)`` when a tenant moves to a new adapter version —
        resident hot-swap or evict→republish→re-page-in. The engine
        hooks EAGER reclamation of the old version's (version-stamped)
        KV namespace here; correctness never depends on it — a stale
        version's namespace simply stops being looked up (see
        ``cache_namespace``) and its blocks LRU out."""
        self._swap_listeners.append(fn)

    @staticmethod
    def cache_namespace(tenant: str, version: Optional[int]) -> str:
        """The prefix-cache namespace for one (tenant, adapter-version)
        pair. Stamping the VERSION into the namespace is what makes
        hot-swaps race-free by construction: a prefill that captured
        the v1 adapter commits into ``t@v1`` even if the row hot-swaps
        to v2 mid-compute, and every post-swap lookup reads ``t@v2`` —
        old-version KV can never be served under a newer adapter, with
        no ordering requirements between swaps and in-flight
        commits."""
        return f"{tenant}@v{0 if version is None else int(version)}"

    def _tenant_locked(self, tenant: str) -> Dict[str, int]:
        ts = self._tenant_stats.get(tenant)
        if ts is None:
            ts = {k: 0 for k in ("hits", "misses", "evictions",
                                 "swaps")}
            self._tenant_stats[tenant] = ts
        return ts

    def _event_locked(self, ev: Dict[str, Any]) -> None:
        ev.setdefault("ts", time.time())
        ev.setdefault("pool", self.pool_id)
        self._events.append(ev)
        if len(self._events) > _EVENTS_KEPT:
            del self._events[:len(self._events) - _EVENTS_KEPT]

    def _pad(self, arr: np.ndarray, rank_axis: int) -> np.ndarray:
        """Zero-pad an adapter leaf's rank dimension to ``rank_max`` —
        the padded columns of A (rows of B) multiply to exact-zero
        contributions, so a rank-r adapter in a rank_max pool computes
        the same delta it would at its native rank."""
        r = arr.shape[rank_axis]
        if r > self.rank_max:
            raise ValueError(
                f"adapter rank {r} exceeds the pool's rank_max "
                f"{self.rank_max} (RAY_TPU_LORA_RANK_MAX)")
        if r == self.rank_max:
            return arr
        pad = [(0, 0)] * arr.ndim
        pad[rank_axis] = (0, self.rank_max - r)
        return np.pad(arr, pad)

    def _write_row_locked(self, row: int,
                          adapter: Dict[str, Any]) -> None:
        """Write one adapter into pool row `row` through the DONATED
        row writer — an in-place O(row) update per leaf, never an
        O(pool) stack copy (the ROADMAP's 64-slot x 32-layer scale
        bug). Caller holds the pool lock: every stack read (the tick's
        ``dispatch_tick``, the prefill merge's ``adapter_slice``)
        dispatches under the same lock, so the donation can never
        invalidate an array a concurrent reader is about to hand to
        XLA — same-device stream order serializes the rest."""
        import jax.numpy as jnp

        write = _row_write()
        rw = np.int32(row)
        layers = len_blocks(self.config)
        for name, d_in, d_out in self.targets:
            a = self._pad(np.asarray(adapter["targets"][name]["a"]), 2)
            b = self._pad(np.asarray(adapter["targets"][name]["b"]), 1)
            if a.shape != (layers, d_in, self.rank_max) \
                    or b.shape != (layers, self.rank_max, d_out):
                raise ValueError(
                    f"adapter leaf {name!r} shaped a={a.shape} "
                    f"b={b.shape} does not fit this model's target "
                    f"({layers}, {d_in}->{d_out})")
            self._a[name] = write(self._a[name], rw,
                                  jnp.asarray(a, self.dtype))
            self._b[name] = write(self._b[name], rw,
                                  jnp.asarray(b, self.dtype))
        # ravel()[0]: the fabric's 0-d -> 1-d chunk promotion may hand
        # the scale back as a [1] array
        self._scale = write(
            self._scale, rw,
            jnp.asarray(float(np.asarray(adapter.get("scale", 1.0))
                              .ravel()[0]), jnp.float32))

    # ------------------------------------------------------------ paging

    def acquire(self, tenant: str) -> int:
        """Pin `tenant`'s adapter and return its pool row (the per-slot
        index the decode tick gathers by). Hit: resident and current —
        bump the pin. Miss: page in (fetch outside the lock,
        single-flight per tenant), evicting the LRU unpinned row when
        the pool is full. Dirty (a newer version was published):
        re-fetch and hot-swap the SAME row — other tenants' rows are
        untouched. Raises LoraPoolExhausted when every row is pinned."""
        tenant = str(tenant)
        while True:
            # the dirty probe runs OUTSIDE the pool lock: tick_args()
            # blocks on that lock, and a source implementation may take
            # its own lock here — nesting them would let a slow source
            # transitively stall the decode loop. Non-atomic is fine: a
            # publish landing between this check and the return is
            # caught by the tenant's next acquire.
            dirty = self.source.dirty(tenant)
            with self._lock:
                r = self._by_tenant.get(tenant)
                if r is not None and not dirty:
                    r.ref += 1
                    r.last_used = next(self._tick)
                    self._stats["acquires"] += 1
                    self._stats["hits"] += 1
                    self._tenant_locked(tenant)["hits"] += 1
                    lora_metrics()["hits"].inc(tags={"tenant": tenant})
                    return r.row
                loading = self._loading.get(tenant)
                if loading is None:
                    self._loading[tenant] = threading.Event()
                    break
            # another thread is paging this tenant in: wait, re-check
            loading.wait(timeout=120.0)
        try:
            version, adapter, moved = self.source.fetch(tenant)
            row, prev_version, evicted = self._install(tenant, version,
                                                       adapter, moved)
        finally:
            with self._lock:
                ev = self._loading.pop(tenant, None)
            if ev is not None:
                ev.set()
        if prev_version is not None:
            self._fire_swap_listeners(tenant, prev_version)
        self.publish_telemetry()
        return row

    def _fire_swap_listeners(self, tenant: str,
                             old_version: int) -> None:
        for fn in self._swap_listeners:
            try:
                fn(tenant, old_version)
            except Exception:  # noqa: BLE001 — listener's problem
                pass

    def _install(self, tenant: str, version: int,
                 adapter: Dict[str, Any], moved: int
                 ) -> Tuple[int, Optional[int], Optional[str]]:
        """Returns ``(row, superseded_version, evicted_tenant)``.
        `superseded_version` is the tenant's previous adapter version
        when this install moved it to a NEW one (resident hot-swap OR
        evict→republish→re-page-in) — the caller fires the swap
        listeners with it so the old version's KV namespace gets
        eagerly reclaimed; None when nothing was superseded."""
        rank = adapter_rank(adapter)
        nbytes = adapter_nbytes(adapter)
        with self._lock:
            now = next(self._tick)
            r = self._by_tenant.get(tenant)
            swapped = r is not None
            prev_version = self._seen_versions.get(tenant)
            superseded = (prev_version
                          if prev_version is not None
                          and prev_version != int(version) else None)
            evicted: Optional[str] = None
            if r is None:
                if self._free:
                    row = self._free.pop()
                else:
                    victim = min(
                        (c for c in self._by_tenant.values()
                         if c.ref == 0),
                        key=lambda c: c.last_used, default=None)
                    if victim is None:
                        raise LoraPoolExhausted(
                            f"adapter pool {self.pool_id}: all "
                            f"{self.slots} rows pinned by in-flight "
                            f"requests (RAY_TPU_LORA_POOL_SLOTS)")
                    evicted = victim.tenant
                    del self._by_tenant[victim.tenant]
                    row = victim.row
                    self._stats["evictions"] += 1
                    self._tenant_locked(evicted)["evictions"] += 1
                    self._event_locked({"kind": "evict",
                                        "tenant": evicted,
                                        "row": row})
                r = _Resident(tenant, row)
                self._by_tenant[tenant] = r
            # the DONATED write dispatches under the lock — the same
            # lock every stack read dispatches under, so stream order
            # makes the in-place update tick-safe
            self._write_row_locked(r.row, adapter)
            r.version = int(version)
            r.rank = rank
            r.nbytes = nbytes
            r.last_used = now
            r.ref += 1
            self._seen_versions[tenant] = int(version)
            self._stats["acquires"] += 1
            if swapped:
                self._stats["swaps"] += 1
                self._tenant_locked(tenant)["swaps"] += 1
                self._event_locked({"kind": "swap", "tenant": tenant,
                                    "row": r.row, "version": version})
            else:
                self._stats["misses"] += 1
                self._tenant_locked(tenant)["misses"] += 1
                self._event_locked({"kind": "page_in", "tenant": tenant,
                                    "row": r.row, "version": version,
                                    "bytes": moved or nbytes,
                                    "superseded": superseded})
            self._stats["page_in_bytes"] += moved or nbytes
            row = r.row
            util = len(self._by_tenant) / self.slots
        m = lora_metrics()
        if swapped:
            m["swaps"].inc(tags={"tenant": tenant})
        else:
            m["misses"].inc(tags={"tenant": tenant})
        if evicted is not None:
            m["evictions"].inc(tags={"tenant": evicted})
        m["utilization"].set(util)
        return row, superseded, evicted

    def release(self, tenant: str) -> None:
        """Drop one pin. Refcount-0 adapters STAY resident (that is the
        cache) and are reclaimed only by LRU eviction under pressure —
        the kvcache refcount discipline."""
        with self._lock:
            r = self._by_tenant.get(str(tenant))
            if r is not None and r.ref > 0:
                r.ref -= 1
            self._stats["releases"] += 1

    def refresh(self, tenant: str) -> bool:
        """Hot-swap `tenant`'s resident adapter to the newest published
        version NOW (the publish path's dirty flag does this lazily on
        the next acquire; tests and operators force it). No-op when the
        tenant is not resident. Existing pins keep counting — the swap
        changes the row's CONTENT between ticks, never its identity."""
        tenant = str(tenant)
        with self._lock:
            if tenant not in self._by_tenant:
                return False
        version, adapter, moved = self.source.fetch(tenant)
        with self._lock:
            r = self._by_tenant.get(tenant)
            if r is None or r.version == int(version):
                return False
            old_version = r.version
            self._write_row_locked(r.row, adapter)
            r.version = int(version)
            r.rank = adapter_rank(adapter)
            r.nbytes = adapter_nbytes(adapter)
            self._seen_versions[tenant] = int(version)
            self._stats["swaps"] += 1
            self._stats["page_in_bytes"] += moved or r.nbytes
            self._tenant_locked(tenant)["swaps"] += 1
            self._event_locked({"kind": "swap", "tenant": tenant,
                                "row": r.row, "version": version})
        lora_metrics()["swaps"].inc(tags={"tenant": tenant})
        self._fire_swap_listeners(tenant, old_version)
        self.publish_telemetry()
        return True

    # -------------------------------------------------------- device API

    def _tick_args_locked(self, slot_adapter: np.ndarray
                          ) -> Dict[str, Any]:
        import jax.numpy as jnp

        out: Dict[str, Any] = {
            "idx": jnp.asarray(slot_adapter, jnp.int32),
            "scale": self._scale,
        }
        for name, _, _ in self.targets:
            out[name] = (self._a[name], self._b[name])
        return out

    def dispatch_tick(self, fn: Callable[[Dict[str, Any]], Any],
                      slot_adapter: np.ndarray) -> Any:
        """Build the mixed-tenant tick's `lora` argument (per-slot pool
        rows + the stacks, models/llama.py ``llama_decode(lora=)``
        layout) and dispatch ``fn(args)`` UNDER the pool lock. Pool-row
        writes are donated jits dispatched under this same lock, so a
        page-in racing a tick can never donate away an array the tick
        is about to hand to XLA — dispatch is the only critical
        section (the kvcache gather/commit discipline); the tick's
        compute still overlaps page-in fetches freely."""
        with self._lock:
            return fn(self._tick_args_locked(slot_adapter))

    def tick_args(self, slot_adapter: np.ndarray) -> Dict[str, Any]:
        """Snapshot of the tick argument for INSPECTION (tests,
        debugging). Dispatching a jit on these references outside
        ``dispatch_tick`` races the donated row writes — the engine
        always goes through ``dispatch_tick``."""
        with self._lock:
            return self._tick_args_locked(slot_adapter)

    def adapter_slice(self, row: int, with_version: bool = False):
        """ONE adapter's device arrays (for the single-tenant prefill
        merge): ``{"scale", "targets": {name: {"a": [L,in,r_max],
        "b": [L,r_max,out]}}}``. With ``with_version`` also returns
        the row's resident adapter version, read under the SAME lock
        as the arrays — the pair the versioned cache namespace needs
        (a swap landing between a separate read and the slice would
        stamp v1 KV with v2's namespace)."""
        with self._lock:
            sl = {
                "scale": self._scale[row],
                "targets": {name: {"a": self._a[name][row],
                                   "b": self._b[name][row]}
                            for name, _, _ in self.targets},
            }
            if not with_version:
                return sl
            version = next((r.version
                            for r in self._by_tenant.values()
                            if r.row == row), None)
            return sl, version

    def resident_version(self, tenant: str) -> Optional[int]:
        with self._lock:
            r = self._by_tenant.get(str(tenant))
            return None if r is None else r.version

    # -------------------------------------------------- stats / telemetry

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            s: Dict[str, Any] = dict(self._stats)
            residents = {t: {"row": r.row, "version": r.version,
                             "rank": r.rank, "ref": r.ref,
                             "nbytes": r.nbytes}
                         for t, r in self._by_tenant.items()}
            s.update(
                role="pool",
                pool_id=self.pool_id,
                slots=self.slots,
                rank_max=self.rank_max,
                resident=len(residents),
                pinned=sum(1 for r in self._by_tenant.values()
                           if r.ref > 0),
                utilization=len(residents) / self.slots,
                residents=residents,
                tenants={t: dict(v)
                         for t, v in self._tenant_stats.items()},
            )
        acq = s["acquires"]
        s["hit_rate"] = s["hits"] / acq if acq else 0.0
        return s

    def drain_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self._events = self._events, []
        return out

    def publish_telemetry(self, force: bool = False) -> None:
        """Best-effort push of pool stats + pending timeline events to
        the conductor (no-op without a live cluster); throttled unless
        forced — the one-set-of-numbers source for every lora
        surface."""
        self._pusher.push(self.stats, self.drain_events, force=force)


__all__ = ["AdapterPool", "FabricAdapterSource", "LocalAdapterSource",
           "LoraPoolExhausted", "adapter_nbytes", "adapter_rank",
           "build_pool", "default_pool_slots", "default_rank_max",
           "lora_metrics", "make_lora_adapter", "publish_adapter",
           "tenant_weights_name"]
