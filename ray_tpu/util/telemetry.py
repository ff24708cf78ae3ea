"""The component's side of the telemetry channel: a ``Pusher`` sends a
component's newest stats snapshot and its instant markers to the
conductor's ``report_stats`` / ``report_event`` (one row of
``ray_tpu._private.telemetry.SUBSYSTEMS`` a subsystem). Best-effort:
fire-and-forget notifies, a no-op without a live cluster.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, Optional, Union

from ray_tpu._private import worker as _worker_mod

# a component pushes at most this often unless forced
PUSH_INTERVAL_S = 0.5

Stats = Union[Dict[str, Any], Callable[[], Dict[str, Any]], None]
Events = Union[Iterable[Dict[str, Any]],
               Callable[[], Iterable[Dict[str, Any]]]]


def _send(subsystem: str, component_id: Optional[str], stats: Stats,
          events: Events) -> None:
    # the buffers are drained with or without a cluster, so that they
    # stay bounded
    if callable(events):
        events = events()
    w = _worker_mod.global_worker
    if w is None:
        return
    try:
        if callable(stats):
            stats = stats()
        if stats is not None:
            w.conductor.notify("report_stats", subsystem, w.worker_id,
                               component_id, stats)
        for ev in events:
            w.conductor.notify("report_event", subsystem, dict(ev))
    except Exception:  # noqa: BLE001 — cluster shutting down
        pass


def emit(subsystem: str, event: Dict[str, Any]) -> None:
    """One instant marker into the subsystem's event ring (its lane of
    the merged timeline)."""
    _send(subsystem, None, None, (event,))


class Pusher:
    """One component's throttled push. ``stats`` and ``events`` may be
    callables: the snapshot is then built only when there is a cluster
    to send it to, and the events are drained only when the push is
    due."""

    def __init__(self, subsystem: str, component_id: str) -> None:
        self.subsystem = subsystem
        self.component_id = str(component_id)
        self._last_push = 0.0

    def push(self, stats: Stats, events: Events = (),
             force: bool = False) -> bool:
        """False when the throttle held the push back."""
        now = time.monotonic()
        if not force and now - self._last_push < PUSH_INTERVAL_S:
            return False
        self._last_push = now
        _send(self.subsystem, self.component_id, stats, events)
        return True


__all__ = ["PUSH_INTERVAL_S", "Pusher", "emit"]
