"""Rollout buffer: the bounded, version-tagged queue between sampler
actors and the learner gang.

The buffer is a single actor holding rollout DICTS (prompt/completion
token arrays, per-token scores, the weights version that produced them
— small host arrays, never device buffers). Flow control is explicit:

- ``put`` accepts up to the free capacity and REJECTS the rest
  (returning the accepted count) — a full buffer pushes back on the
  samplers, which pause generation instead of flooding the object
  plane. Rollouts that an engine already produced are never dropped
  from the buffer side; the sampler retries the same batch.
- ``get_batch`` pops FIFO, so two learner hosts pulling through
  ``streaming_split`` consume disjoint rollouts by construction.

:func:`from_rollouts` exposes the buffer through the Data
streaming-split contract the Train-equivalent expects
(``streaming_split(world)[rank]`` → per-host iterator): each shard's
``iter_batches`` runs a background prefetch thread that pulls (and
collates) the NEXT batch while the learner's device step runs on the
current one — ingestion overlaps compute, and the residual wait the
learner actually observes lands in the flight recorder's ``data_wait``
phase. On shutdown, rollouts accumulated but not yet collated are
handed back to the buffer; an already-collated batch parked in the
prefetch queue (at most ``prefetch`` batches) is the one thing a
stopping learner discards.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.util.telemetry import Pusher

from .metrics import online_metrics


class RolloutBuffer:
    """Actor body for the rollout queue (spawn via
    ``ray_tpu.remote(RolloutBuffer).options(name=...).remote(...)``)."""

    def __init__(self, capacity: int = 256, name: str = "rollouts"):
        if capacity <= 0:
            raise ValueError(f"capacity must be > 0, got {capacity}")
        self.capacity = int(capacity)
        self.name = name
        self._items: "collections.deque[Dict[str, Any]]" = \
            collections.deque()
        self._lock = threading.Lock()
        self.puts = 0
        self.rejected = 0
        self.gets = 0
        self.total_in = 0
        self.total_out = 0
        self._versions: Dict[int, int] = {}  # weights_version -> queued
        self._pusher = Pusher("online", f"buffer/{self.name}")

    # ------------------------------------------------------------- queue

    def put(self, rollouts: List[Dict[str, Any]]) -> int:
        """Enqueue up to the free capacity; returns how many were
        accepted (the backpressure signal — 0 means "full, hold on")."""
        with self._lock:
            free = self.capacity - len(self._items)
            accepted = rollouts[:max(0, free)]
            for r in accepted:
                self._items.append(r)
                v = r.get("weights_version")
                if v is not None:
                    self._versions[int(v)] = \
                        self._versions.get(int(v), 0) + 1
            self.puts += 1
            self.total_in += len(accepted)
            n_rej = len(rollouts) - len(accepted)
            self.rejected += n_rej
        if n_rej:
            online_metrics()["buffer_rejected"].inc(
                n_rej, tags={"buffer": self.name})
        self._publish_telemetry()
        return len(accepted)

    def get_batch(self, max_items: int) -> List[Dict[str, Any]]:
        """Pop up to `max_items` FIFO (non-blocking: the consumer owns
        its wait policy)."""
        out: List[Dict[str, Any]] = []
        with self._lock:
            while self._items and len(out) < max_items:
                r = self._items.popleft()
                v = r.get("weights_version")
                if v is not None:
                    left = self._versions.get(int(v), 0) - 1
                    if left > 0:
                        self._versions[int(v)] = left
                    else:
                        self._versions.pop(int(v), None)
                out.append(r)
            self.gets += 1
            self.total_out += len(out)
        self._publish_telemetry()
        return out

    def size(self) -> int:
        with self._lock:
            return len(self._items)

    # --------------------------------------------------------- telemetry

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "role": "buffer", "buffer": self.name,
                "capacity": self.capacity,
                "occupancy": len(self._items),
                "puts": self.puts, "gets": self.gets,
                "rejected": self.rejected,
                "total_in": self.total_in, "total_out": self.total_out,
                "versions_queued": dict(self._versions),
            }

    def _publish_telemetry(self, force: bool = False) -> None:
        def stats():
            st = self.stats()
            online_metrics()["buffer_occupancy"].set(
                st["occupancy"], tags={"buffer": self.name})
            return st

        self._pusher.push(stats, force=force)


# --------------------------------------------------- learner-side stream


class RolloutShard:
    """One learner host's iterator over the shared buffer (the
    ``get_dataset_shard`` handle). Destructive FIFO pops make shards
    disjoint without any partitioning metadata."""

    def __init__(self, buffer: Any, *, batch_size: int,
                 min_items: Optional[int] = None,
                 poll_interval_s: float = 0.01,
                 collate_fn: Optional[Callable[[List[Dict[str, Any]]],
                                               Any]] = None,
                 prefetch: int = 1):
        self._buffer = buffer
        self.batch_size = int(batch_size)
        self.min_items = self.batch_size if min_items is None \
            else int(min_items)
        if not 0 < self.min_items <= self.batch_size:
            # min_items > batch_size would spin forever requesting 0
            raise ValueError(
                f"min_items must be in [1, batch_size={self.batch_size}]"
                f", got {self.min_items}")
        self.poll_interval_s = poll_interval_s
        self._collate = collate_fn
        self._prefetch = max(0, int(prefetch))

    def _pull_batch(self, stop: Optional[threading.Event] = None) -> Any:
        """Accumulate min_items..batch_size rollouts (polling — the
        buffer never blocks its actor loop), then collate."""
        import ray_tpu

        items: List[Dict[str, Any]] = []
        while len(items) < self.min_items:
            if stop is not None and stop.is_set():
                if items:
                    # stopped mid-accumulation: the pops were
                    # destructive, so hand the rollouts back (best
                    # effort — a full buffer genuinely drops them)
                    try:
                        self._buffer.put.remote(items)
                    except Exception:  # noqa: BLE001 — buffer gone
                        pass
                return None
            got = ray_tpu.get(self._buffer.get_batch.remote(
                self.batch_size - len(items)), timeout=60.0)
            items.extend(got)
            if len(items) >= self.min_items:
                break
            time.sleep(self.poll_interval_s)
        return self._collate(items) if self._collate else items

    def iter_batches(self, **_ignored):
        """Endless batch stream with background prefetch: the NEXT
        batch is pulled and collated while the caller computes on the
        current one (the ingestion-overlaps-device-step contract)."""
        import queue as _q

        if self._prefetch == 0:
            while True:
                yield self._pull_batch()
            return
        out: "_q.Queue" = _q.Queue(maxsize=self._prefetch)
        stop = threading.Event()

        def feed():
            try:
                while not stop.is_set():
                    batch = self._pull_batch(stop)
                    if batch is None:
                        return
                    while not stop.is_set():
                        try:
                            out.put(batch, timeout=0.2)
                            break
                        except _q.Full:
                            continue
            except Exception as e:  # noqa: BLE001 — surface via queue
                out.put(e)

        t = threading.Thread(target=feed, daemon=True,
                             name="rollout-prefetch")
        t.start()
        try:
            while True:
                batch = out.get()
                if isinstance(batch, Exception):
                    raise batch
                yield batch
        finally:
            stop.set()

    # Dataset-protocol conveniences (a RolloutShard is its own shard)
    def count(self) -> int:
        import ray_tpu

        return ray_tpu.get(self._buffer.size.remote(), timeout=30.0)


class RolloutStream:
    """The ``datasets={"rollouts": from_rollouts(buffer)}`` object: the
    trainer's ``_shard_datasets`` calls ``streaming_split(world)`` and
    hands each rank one :class:`RolloutShard`."""

    def __init__(self, buffer: Any, *, batch_size: int = 8,
                 min_items: Optional[int] = None,
                 collate_fn: Optional[Callable] = None,
                 prefetch: int = 1):
        self._buffer = buffer
        self._kw = dict(batch_size=batch_size, min_items=min_items,
                        collate_fn=collate_fn, prefetch=prefetch)

    def streaming_split(self, n: int, *, equal: bool = False,
                        locality_hints=None) -> List[RolloutShard]:
        return [RolloutShard(self._buffer, **self._kw) for _ in range(n)]

    @property
    def buffer(self) -> Any:
        return self._buffer


def from_rollouts(buffer: Any, *, batch_size: int = 8,
                  min_items: Optional[int] = None,
                  collate_fn: Optional[Callable] = None,
                  prefetch: int = 1) -> RolloutStream:
    """Expose a :class:`RolloutBuffer` actor to the learner through the
    Data streaming-split contract. `collate_fn(list_of_rollouts)` runs
    on the prefetch thread (padding/packing overlaps the device step
    too); without one, batches are lists of rollout dicts."""
    return RolloutStream(buffer, batch_size=batch_size,
                         min_items=min_items, collate_fn=collate_fn,
                         prefetch=prefetch)
