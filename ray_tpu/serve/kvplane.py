"""Global KV plane — the tiered prefix cache (HBM -> host RAM -> object
store) with a cluster-wide prefix directory.

int8 KV blocks doubled a single replica's prefix pool; this subsystem
adds the next multiplier, hierarchy: cache residency stops being
bounded by one replica's HBM.

- **Tier 1** is the engine's paged HBM pool (models/kvcache.py),
  unchanged.
- **Tier 2** (``HostArena``) is a bounded per-replica host-RAM arena.
  A block evicted from the HBM pool under pressure spills its
  int8+per-block-channel-scales wire-format payload (``_payload_locked``'s
  layout) here instead of dying; a later lookup whose chain walk breaks
  re-adopts the block through the pool's normal insert path. LRU within
  the arena, byte-bounded (``RAY_TPU_KVPLANE_ARENA_BYTES``). int8 pools
  round-trip bit-exactly; fp pools re-enter within the int8 tolerance
  contract.
- **Tier 3** persists cold hot-prompt prefixes as ``util/chunks``
  objects ANY replica can adopt, with a conductor-side **prefix
  directory**: digest-chain -> holder + descriptor, namespaced by
  tenant/adapter version, the same metadata-only atomic-commit registry
  pattern as the weight fabric, TTL-reaped
  (``RAY_TPU_KVPLANE_T3_TTL_S``) and keep-last-K GC'd. The
  ``DisaggRouter``'s prefix-affinity routing upgrades from "hash to the
  replica that PROBABLY has it" to "look up who HAS it, or fetch it
  over the transfer plane" — a directory miss falls back to the
  affinity hash bit-identically (``RAY_TPU_KVPLANE_DIRECTORY=0`` turns
  the lookup off wholesale).

Correctness invariant (asserted in tests/test_kvplane.py): with int8
pools a block's spill/readopt round trip through ANY tier is
byte-for-byte the pool bytes that were evicted, so engine outputs with
the KV plane enabled are bit-identical to the single-tier engine. The
namespace scoping of the hash chains carries through every tier — one
tenant's spilled or published KV can never match another tenant's
prompt, because the digests themselves are namespace-rooted.

Surfaces (the full treatment every subsystem gets):
``util.state.kvplane_status()``, CLI ``ray_tpu kvplane [--json
--events]``, dashboard ``/api/kvplane`` + SPA tab, the lazy
``ray_tpu_kvplane_*`` Prometheus family (per-tier hits / evictions /
spills / fetched bytes / reused tokens), ``kvplane`` markers in the
merged timeline (spill / tier2_hit / tier3_publish / tier3_adopt /
directory_hit), and per-request flight-recorder phases
``kvplane_tier2_fetch`` / ``kvplane_tier3_fetch`` so p99 attribution
can name the KV plane.

Knobs (all read through util/envknobs): ``RAY_TPU_KVPLANE`` (master
enable, default 1), ``RAY_TPU_KVPLANE_ARENA_BYTES`` (tier-2 bound,
default 128 MiB), ``RAY_TPU_KVPLANE_DIRECTORY`` (directory lookups +
tier-3 publication, default 1), ``RAY_TPU_KVPLANE_T3_TTL_S`` (directory
entry TTL, default 600), ``RAY_TPU_KVPLANE_T3_MIN_BLOCKS`` (smallest
prefix worth publishing, default 2).
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

_EVENTS_KEPT = 512


# ------------------------------------------------------------ env knobs

def kvplane_enabled() -> bool:
    """Master enable — gates the arena attach AND tier-3 publication."""
    from ray_tpu.util import envknobs

    return envknobs.get_str("RAY_TPU_KVPLANE", "1") == "1"


def arena_bytes_default() -> int:
    """Tier-2 host-arena byte bound (``RAY_TPU_KVPLANE_ARENA_BYTES``)."""
    from ray_tpu.util import envknobs

    return envknobs.get_int("RAY_TPU_KVPLANE_ARENA_BYTES", 128 << 20)


def directory_enabled() -> bool:
    """Prefix-directory lookups + tier-3 publication
    (``RAY_TPU_KVPLANE_DIRECTORY``) — off falls back to the affinity
    hash bit-identically."""
    from ray_tpu.util import envknobs

    return envknobs.get_str("RAY_TPU_KVPLANE_DIRECTORY", "1") == "1"


def t3_ttl_s() -> float:
    """Directory-entry TTL (``RAY_TPU_KVPLANE_T3_TTL_S``) the conductor
    reaper enforces; 0 disables the age check."""
    from ray_tpu.util import envknobs

    return envknobs.get_float("RAY_TPU_KVPLANE_T3_TTL_S", 600.0)


def t3_min_blocks() -> int:
    """Smallest full-block prefix worth publishing to tier 3
    (``RAY_TPU_KVPLANE_T3_MIN_BLOCKS``)."""
    from ray_tpu.util import envknobs

    return envknobs.get_int("RAY_TPU_KVPLANE_T3_MIN_BLOCKS", 2)


# ----------------------------------------------------- prometheus (lazy)
# Created on first arena construction / directory use, never at import
# (the kvcache_metrics pattern — rebound ONCE to a complete dict).

_metrics: Optional[Dict[str, Any]] = None
_metrics_lock = threading.Lock()


def kvplane_metrics() -> Dict[str, Any]:
    global _metrics
    m = _metrics
    if m is not None:
        return m
    with _metrics_lock:
        if _metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            _metrics = dict(
                hits=Counter(
                    "ray_tpu_kvplane_hits_total",
                    "prefix blocks re-adopted from a lower tier",
                    tag_keys=("tier",)),
                spills=Counter(
                    "ray_tpu_kvplane_spills_total",
                    "HBM-evicted blocks spilled into the tier-2 host "
                    "arena instead of dying"),
                evictions=Counter(
                    "ray_tpu_kvplane_evictions_total",
                    "blocks dropped OUT of a kvplane tier (arena LRU, "
                    "directory TTL/GC)",
                    tag_keys=("tier",)),
                fetched_bytes=Counter(
                    "ray_tpu_kvplane_fetched_bytes_total",
                    "wire-format bytes pulled back out of a tier on a "
                    "hit",
                    tag_keys=("tier",)),
                reused_tokens=Counter(
                    "ray_tpu_kvplane_reused_tokens_total",
                    "prompt tokens whose prefill was recovered from a "
                    "kvplane tier",
                    tag_keys=("tier",)),
                directory=Counter(
                    "ray_tpu_kvplane_directory_total",
                    "prefix-directory routing decisions",
                    tag_keys=("outcome",)),
                arena_bytes=Gauge(
                    "ray_tpu_kvplane_arena_bytes",
                    "tier-2 host-arena resident bytes"))
    return _metrics


# ------------------------------------------------------------- tier 2

class HostArena:
    """Bounded host-RAM spill arena for one replica's HBM pool (tier 2).

    Keys ARE the pool's index keys — ``("full", digest)`` /
    ``("partial", parent_digest, tokens)`` — with the digests already
    namespace-rooted, so tenant isolation is inherited, not re-checked.
    ``take_*`` POPS (a hit moves the block back to tier 1; no double
    residency). LRU within the byte bound. Thread-safe: accept() is
    called under the pool lock, stats()/drain_events() from telemetry
    threads."""

    def __init__(self, max_bytes: Optional[int] = None,
                 replica: Optional[str] = None):
        self.max_bytes = int(arena_bytes_default()
                             if max_bytes is None else max_bytes)
        self.replica = replica
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, Dict[str, Any]]" \
            = OrderedDict()
        # parent_digest -> {tokens: key} for the partial-tail probe
        self._partials: Dict[bytes, Dict[Tuple[int, ...], tuple]] = {}
        self._bytes = 0
        self._events: List[Dict[str, Any]] = []
        self._stats: Dict[str, int] = {
            k: 0 for k in ("spills", "spill_bytes", "tier2_hits",
                           "tier2_probes", "tier2_reused_tokens",
                           "tier2_fetched_bytes", "arena_evictions")}
        self._tl = threading.local()
        kvplane_metrics()  # lazy registration, before the first event

    @staticmethod
    def _payload_bytes(p: Dict[str, Any]) -> int:
        return int(p["qk"].nbytes + p["qv"].nbytes
                   + p["sk"].nbytes + p["sv"].nbytes)

    def _event_locked(self, ev: Dict[str, Any]) -> None:
        ev.setdefault("ts", time.time())
        if self.replica is not None:
            ev.setdefault("replica", self.replica)
        self._events.append(ev)
        if len(self._events) > _EVENTS_KEPT:
            del self._events[:len(self._events) - _EVENTS_KEPT]

    def _insert_locked(self, key: tuple, payload: Dict[str, Any],
                       size: int) -> None:
        self._entries[key] = payload
        self._entries.move_to_end(key)
        self._bytes += size
        if key[0] == "partial":
            self._partials.setdefault(key[1], {})[key[2]] = key
        while self._bytes > self.max_bytes and self._entries:
            old_key, old = self._entries.popitem(last=False)
            self._bytes -= self._payload_bytes(old)
            self._drop_partial_locked(old_key)
            self._stats["arena_evictions"] += 1
            kvplane_metrics()["evictions"].inc(tags={"tier": "2"})

    def _drop_partial_locked(self, key: tuple) -> None:
        if key[0] != "partial":
            return
        by_tok = self._partials.get(key[1])
        if by_tok is not None:
            by_tok.pop(key[2], None)
            if not by_tok:
                del self._partials[key[1]]

    def accept(self, payload: Dict[str, Any]) -> None:
        """Spill sink — an HBM eviction's wire-format payload enters
        the arena (refreshing recency if the identity already lives
        here). Called under the pool lock: dict work only."""
        key = payload.get("index_key")
        if key is None:
            return
        size = self._payload_bytes(payload)
        if size > self.max_bytes:
            return  # a block bigger than the arena can never fit
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= self._payload_bytes(old)
                self._drop_partial_locked(key)
            self._insert_locked(key, payload, size)
            self._stats["spills"] += 1
            self._stats["spill_bytes"] += size
            self._event_locked({"kind": "spill",
                                "block_tokens": payload.get("filled"),
                                "nbytes": size,
                                "namespace": payload.get("ns")})
        m = kvplane_metrics()
        m["spills"].inc()
        m["arena_bytes"].set(self._bytes)

    def give_back(self, payload: Dict[str, Any]) -> None:
        """Return a popped payload whose re-adoption failed (pool had
        no allocatable block) — not a new spill, no counters."""
        key = payload.get("index_key")
        if key is None:
            return
        with self._lock:
            if key not in self._entries:
                self._insert_locked(key, payload,
                                    self._payload_bytes(payload))

    def _hit_locked(self, key: tuple, payload: Dict[str, Any],
                    t0: float) -> Dict[str, Any]:
        size = self._payload_bytes(payload)
        self._bytes -= size
        self._drop_partial_locked(key)
        self._stats["tier2_hits"] += 1
        self._stats["tier2_reused_tokens"] += int(payload["filled"])
        self._stats["tier2_fetched_bytes"] += size
        self._event_locked({"kind": "tier2_hit",
                            "block_tokens": payload.get("filled"),
                            "nbytes": size,
                            "namespace": payload.get("ns")})
        acc = getattr(self._tl, "acc", None)
        if acc is not None:
            acc["blocks"] += 1
            acc["tokens"] += int(payload["filled"])
            acc["nbytes"] += size
            acc["ms"] += (time.perf_counter() - t0) * 1e3
        m = kvplane_metrics()
        m["hits"].inc(tags={"tier": "2"})
        m["reused_tokens"].inc(int(payload["filled"]), tags={"tier": "2"})
        m["fetched_bytes"].inc(size, tags={"tier": "2"})
        m["arena_bytes"].set(self._bytes)
        return payload

    def take_full(self, digest: bytes,
                  blk_tokens: Tuple[int, ...]) -> Optional[Dict[str, Any]]:
        """Pop the full block keyed by `digest` iff its exact token
        tuple matches (a digest collision must never re-adopt wrong
        KV). Called under the pool lock from the lookup chain walk."""
        t0 = time.perf_counter()
        key = ("full", digest)
        with self._lock:
            self._stats["tier2_probes"] += 1
            payload = self._entries.get(key)
            if payload is None or payload["tokens"] != blk_tokens:
                return None
            del self._entries[key]
            return self._hit_locked(key, payload, t0)

    def take_partial(self, digest: bytes, remainder,
                     budget: int) -> Optional[Dict[str, Any]]:
        """Pop the LONGEST spilled partial tail under `digest` whose
        tokens prefix-match `remainder` within `budget` tokens."""
        t0 = time.perf_counter()
        rem = tuple(int(t) for t in np.asarray(remainder).reshape(-1))
        with self._lock:
            self._stats["tier2_probes"] += 1
            best_key: Optional[tuple] = None
            best_len = 0
            for ptoks, key in self._partials.get(digest, {}).items():
                k = len(ptoks)
                if (k > best_len and k <= budget
                        and rem[:k] == ptoks):
                    best_key, best_len = key, k
            if best_key is None:
                return None
            payload = self._entries.pop(best_key)
            return self._hit_locked(best_key, payload, t0)

    # --------------------------------------- per-request accounting
    # The arena is hit from inside PagedKVCache.lookup(), deep under
    # the engine — a thread-local accumulator lets the replica bracket
    # one request's prefill and attribute its tier-2 traffic to the
    # flight recorder (each actor request runs on its own thread).

    def begin_request(self) -> None:
        self._tl.acc = {"blocks": 0, "tokens": 0, "nbytes": 0,
                        "ms": 0.0}

    def end_request(self) -> Dict[str, Any]:
        acc = getattr(self._tl, "acc", None) \
            or {"blocks": 0, "tokens": 0, "nbytes": 0, "ms": 0.0}
        self._tl.acc = None
        return acc

    # ------------------------------------------------ stats / events

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            s: Dict[str, Any] = dict(self._stats)
            s.update(entries=len(self._entries), bytes=self._bytes,
                     max_bytes=self.max_bytes)
        probes = s["tier2_probes"]
        s["tier2_hit_rate"] = (s["tier2_hits"] / probes
                               if probes else 0.0)
        return s

    def drain_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self._events = self._events, []
        return out


# ------------------------------------------------------------- tier 3

def prefix_digests(tokens, block_size: int,
                   namespace: Optional[str] = None,
                   max_blocks: int = 32) -> List[str]:
    """Directory keys for a prompt — re-exported from models/kvcache so
    router code needs no kvcache import."""
    from ray_tpu.models import kvcache

    return kvcache.prefix_digests(tokens, block_size, namespace,
                                  max_blocks)


def directory_lookup(worker, namespace: Optional[str], tokens,
                     block_size: int,
                     max_blocks: int = 32) -> Optional[Dict[str, Any]]:
    """Ask the conductor's prefix directory who HOLDS the longest
    published prefix of `tokens`. Returns the directory entry (holder,
    descriptor, matched digest) or None — every failure path is a None,
    so a directory outage degrades to the affinity hash, never to an
    error."""
    digests = prefix_digests(tokens, block_size, namespace, max_blocks)
    if not digests:
        return None
    try:
        entry = worker.conductor.call("kvplane_lookup",
                                      namespace or "", digests,
                                      timeout=5.0)
    except Exception:  # noqa: BLE001 — pre-kvplane conductor / outage
        return None
    if not isinstance(entry, dict) or entry.get("error"):
        return None
    return entry


def publish_prefix(worker, cache, tokens, namespace: Optional[str],
                   holder: str, machine: Optional[str] = None,
                   min_blocks: Optional[int] = None,
                   max_blocks: int = 32) -> Optional[Tuple[str, list]]:
    """Persist the longest cached full-block prefix of `tokens` as
    chunk-fabric objects and commit it to the conductor's prefix
    directory (metadata only — the atomic-commit registry pattern).
    Returns ``(digest_hex, refs)`` — the caller OWNS the refs, they are
    the object lifetime — or None when nothing was published."""
    from ray_tpu.util import chunks

    mb = t3_min_blocks() if min_blocks is None else int(min_blocks)
    out = cache.export_prefix(tokens, namespace, max_blocks)
    if out is None:
        return None
    packed, n_tokens, digest_hex = out
    if n_tokens < mb * cache.block_size:
        return None
    refs, desc = chunks.put_tree(worker, packed)
    meta = {"desc": desc, "holder": holder, "machine": machine,
            "tokens": int(n_tokens),
            "nbytes": int(desc.get("total_bytes", 0)),
            "namespace": namespace}
    # the directory commit is the REGISTRATION step shardlint's
    # unregistered-prefix-publish rule checks for
    res = worker.conductor.call("kvplane_publish", namespace or "",
                                digest_hex, meta, timeout=10.0)
    if not isinstance(res, dict) or res.get("error") \
            or res.get("status") == "already":
        return None  # refs die here; the existing holder keeps serving
    return digest_hex, refs


def fetch_and_adopt(worker, cache, entry: Dict[str, Any], tokens,
                    namespace: Optional[str]) -> Tuple[int, Dict[str, Any]]:
    """Pull a directory entry's tier-3 object over the transfer plane
    and adopt it into `cache`. Returns ``(blocks_adopted,
    fetcher_stats)`` — 0 blocks on any fetch failure (the caller just
    prefills from scratch; tier 3 is an accelerator, not a
    dependency)."""
    from ray_tpu.util import chunks

    fetcher = chunks.ChunkFetcher(worker, caller="kvplane")
    try:
        packed = chunks.fetch_tree(worker, entry["desc"],
                                   fetcher=fetcher)
    except Exception:  # noqa: BLE001 — holder died, refs reaped, ...
        return 0, fetcher.stats()
    adopted = cache.import_prefix(tokens, packed, namespace)
    st = fetcher.stats()
    if adopted:
        m = kvplane_metrics()
        m["hits"].inc(tags={"tier": "3"})
        m["reused_tokens"].inc(adopted * cache.block_size,
                               tags={"tier": "3"})
        m["fetched_bytes"].inc(int(st.get("fetched_bytes", 0)),
                               tags={"tier": "3"})
    return adopted, st
