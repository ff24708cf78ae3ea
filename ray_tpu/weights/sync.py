"""WeightSync: keep a serving engine on the latest published weights.

A background thread (pubsub-nudged, poll-backed) watches the registry;
on a new version it reshards-on-fetch under the consumer's own template
shardings and queues a hot swap that the continuous-batching engine
applies BETWEEN decode ticks — in-flight requests keep their KV caches
and complete, nothing restarts, nothing drops. The per-replica staleness
gauge (latest published version minus serving version) updates on every
cycle, and each applied swap lands a marker in the conductor's weight
event log (merged timeline)."""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, Optional

from .metrics import weight_metrics
from .subscriber import WeightSubscriber

logger = logging.getLogger("ray_tpu.weights")


class WeightSync:
    """Drives one engine (anything with ``update_params(params, version)``
    and a ``params_version`` attribute — models.ContinuousBatchingEngine)
    from one named weight set."""

    def __init__(self, engine: Any, name: str = "default", *,
                 template: Any = None, consumer: str = "",
                 poll_interval_s: float = 0.5,
                 subscriber: Optional[WeightSubscriber] = None,
                 prefetch: bool = False):
        self.engine = engine
        self.name = name
        # the reshard target: defaults to the engine's current params
        # (their shardings/dtypes ARE the serving layout)
        self.template = template if template is not None else engine.params
        self.consumer = consumer or f"pid-{os.getpid()}"
        self.poll_interval_s = poll_interval_s
        self._sub = subscriber or WeightSubscriber(
            name, cache_chunks=prefetch)
        self._stop = threading.Event()
        self._swapped = threading.Condition()
        self.swap_count = 0
        self.last_error: Optional[str] = None
        # staleness high-water mark over this sync's lifetime (poll-
        # cycle sampled) — the online loop's <= 1 invariant reads it
        self.max_staleness: Optional[int] = None
        # False the moment a registry probe fails; True again on the
        # next successful cycle. status() exposes it so a caller can
        # tell "fresh" apart from "the registry stopped answering and
        # `latest` is whatever we last learned".
        self.registry_reachable = True
        # subscriber prefetch: a pubsub "published" notice immediately
        # pulls the new version's chunk bytes into this process's store
        # on a side thread, while the engine still decodes the old
        # version — by the time the sync loop assembles + swaps, every
        # chunk is local and the critical section is apply-only
        self.prefetch = prefetch
        self.prefetch_bytes = 0
        self.prefetched_version: Optional[int] = None
        self._prefetch_lock = threading.Lock()
        if prefetch:
            self._sub._worker.subscribe_channel("weights",
                                                self._on_published)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"weight-sync-{name}")
        self._thread.start()

    # ----------------------------------------------------------- prefetch

    def _on_published(self, msg: Any) -> None:
        if not isinstance(msg, dict) or msg.get("name") != self.name \
                or msg.get("kind") != "published":
            return
        version = msg.get("version")
        t = threading.Thread(target=self._prefetch_one, args=(version,),
                             daemon=True,
                             name=f"weight-prefetch-{self.name}")
        t.start()

    def _prefetch_one(self, version) -> None:
        with self._prefetch_lock:  # one transfer at a time; a burst of
            # publishes degrades to prefetching the newest last, which
            # is the one the sync loop will swap to
            if self._stop.is_set():
                return
            try:
                st = self._sub.prefetch(version=version)
            except Exception:  # noqa: BLE001 — version GC'd/reaped
                return         # between notice and pull; fetch retries
            self.prefetch_bytes += st.fetched_bytes
            self.prefetched_version = st.version

    # ------------------------------------------------------------- status

    def status(self) -> Dict[str, Any]:
        latest = None
        try:
            latest = self._sub.latest_version()
            self.registry_reachable = True
        except Exception as e:  # noqa: BLE001 — conductor unreachable
            self.last_error = str(e)
            self.registry_reachable = False
        serving = getattr(self.engine, "params_version", None)
        # staleness is unknowable (None), not huge, until the engine is
        # actually serving a fabric version — versions are step numbers,
        # so "latest - 0" would trip every staleness alert at boot.
        # Equally unknowable with the registry unreachable: `latest` is
        # then stale knowledge, not a freshness certificate.
        staleness = None
        if latest is not None and serving is not None \
                and self.registry_reachable:
            staleness = latest - serving
            self.max_staleness = staleness if self.max_staleness is None \
                else max(self.max_staleness, staleness)
        st = self._sub.last_stats
        return {"name": self.name, "consumer": self.consumer,
                "serving_version": serving, "latest_version": latest,
                "registry_reachable": self.registry_reachable,
                "staleness_versions": staleness,
                "max_staleness_versions": self.max_staleness,
                "swap_count": self.swap_count,
                "fetched_bytes": st.fetched_bytes if st else 0,
                "rpc_bytes": st.rpc_bytes if st else 0,
                "shm_bytes": st.shm_bytes if st else 0,
                "max_read_bytes": st.max_read_bytes if st else 0,
                "leaf_read_bytes": list(st.leaf_read_bytes) if st else [],
                "prefetch_bytes": self.prefetch_bytes,
                "prefetched_version": self.prefetched_version,
                "last_error": self.last_error}

    def wait_for_swap(self, min_version: int, timeout: float = 30.0
                      ) -> int:
        """Block until the ENGINE serves a version >= min_version (the
        swap has been applied between ticks, not merely queued)."""
        deadline = time.monotonic() + timeout
        with self._swapped:
            while True:
                v = getattr(self.engine, "params_version", None)
                if v is not None and v >= min_version:
                    return v
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"engine still serving {v} (< {min_version}) "
                        f"after {timeout}s; last_error={self.last_error}")
                self._swapped.wait(min(remaining, 0.2))

    # --------------------------------------------------------------- loop

    def _gauge(self, latest: Optional[int]) -> None:
        serving = getattr(self.engine, "params_version", None)
        if latest is None or serving is None \
                or not self.registry_reachable:
            # unknown staleness: emit nothing — neither a bogus delta
            # nor a reassuring 0 while the registry is unreachable (the
            # gauge keeps its LAST known value; the reachability flag is
            # what tells the operator it may be stale)
            return
        staleness = latest - serving
        self.max_staleness = staleness if self.max_staleness is None \
            else max(self.max_staleness, staleness)
        weight_metrics()["staleness"].set(
            float(staleness),
            tags={"name": self.name, "consumer": self.consumer})

    def _engine_stopped(self) -> bool:
        stopped = getattr(self.engine, "_stopped", None)
        return stopped is not None and stopped.is_set()

    def _loop(self) -> None:
        failed_cycles = 0
        while not self._stop.is_set():
            if self._engine_stopped():
                # nothing left to swap into — a queued swap would never
                # apply and every cycle would refetch the full model
                self.last_error = "engine stopped; weight sync idle"
                return
            try:
                latest = self._sub.latest_version()
                self.registry_reachable = True
                serving = getattr(self.engine, "params_version", None)
                # follow whatever the registry calls latest (committed
                # most recently) rather than `>`: a gang restarted from
                # an older checkpoint republishes LOWER version numbers,
                # and those are the live weights
                if latest is not None and latest != serving:
                    if self.prefetch:
                        # barrier on the pubsub-kicked transfer (or do
                        # the pull now — idempotent: chunks already
                        # local cost nothing): after this, fetch() is
                        # pure assembly
                        self._prefetch_one(latest)
                    params = self._sub.fetch(version=latest,
                                             like=self.template)
                    applied = self.engine.update_params(params,
                                                        version=latest)
                    if applied is not None and \
                            not applied.wait(timeout=60.0):
                        # swap queued but not applied (stuck or stopped
                        # decode loop): surface through the except path
                        # — status/staleness must keep telling the
                        # truth, not record the version as served
                        raise RuntimeError(
                            f"swap to v{latest} not applied within 60s "
                            "(decode loop stuck or engine stopped)")
                    # re-point the reshard template at the weights now
                    # being served (same shapes/dtypes/shardings):
                    # keeping the ORIGINAL params alive as the template
                    # would pin a dead full copy of the model forever
                    self.template = params
                    self.swap_count += 1
                    st = self._sub.last_stats
                    try:
                        self._sub._worker.conductor.notify(
                            "report_weight_event", {
                                "kind": "swap", "name": self.name,
                                "version": latest,
                                "consumer": self.consumer,
                                "fetched_bytes":
                                    st.fetched_bytes if st else 0})
                    except Exception:  # noqa: BLE001 — telemetry only
                        pass
                    with self._swapped:
                        self._swapped.notify_all()
                self._gauge(latest)
                failed_cycles = 0
                self.last_error = None  # any healthy cycle clears it —
                # status() must not report a long-resolved blip forever
            except Exception as e:  # noqa: BLE001 — keep serving on a
                # failed cycle (registry mid-restart, version GC'd
                # between list and fetch); next cycle retries
                failed_cycles += 1
                self.last_error = f"{type(e).__name__}: {e}"
                self.registry_reachable = False
                logger.debug("weight sync cycle failed: %s", e)
            # pubsub publish notices wake the subscriber cv; this wait
            # piggybacks on it so swaps start promptly without a hot
            # loop. Failed cycles back off — a repeatedly-failing fetch
            # of a large model must not retry at poll cadence.
            wait_s = self.poll_interval_s if not failed_cycles else \
                min(self.poll_interval_s * (2 ** failed_cycles), 30.0)
            with self._sub._cv:
                self._sub._cv.wait(wait_s)

    def stop(self) -> None:
        self._stop.set()
        if self.prefetch:
            try:
                self._sub._worker.unsubscribe_channel(
                    "weights", self._on_published)
            except Exception:  # noqa: BLE001 — worker already torn down
                pass
        with self._sub._cv:
            self._sub._cv.notify_all()
        self._thread.join(timeout=10.0)
        self._sub.close()
