"""What the readers of the engine's own clock share (PR 24): the loop
ring of `ray_tpu.models.engine`, cut to the requests the cell measured,
and the parts of a flight-recorder phase.

The engine leaves one record per iteration of its loop in the flight
recorder's process-local store (`reqtrace.store().loop_records()`; the
fields are in the engine's module docstring). The ring also holds the
reference check, the warm-up, the drain and the replays, so it is cut by
one rule:

    keep the iterations whose `ts` lies in the window of offered load:
    from the earliest start of the requests the cell measured to the
    cell's `seconds` later,

where the requests the cell measured are the ones `obs["phases"]` holds:
the store's newest `len(obs["phases"])` summaries (the harness takes
`obs["phases"]` from the same summaries and nothing finishes after it),
a request starts at its summary's `ts`, on `time.time()`, the clock a
record's `ts` is on too, and `seconds` is `obs["cell"]["seconds"]`. The
drain is left out because the engine empties in it: on the chip a third
of a chat cell's iterations fell there with one slot decoding (the last
long answer, then the replays one after another), which halved the mean
occupancy and moved with the seed (my chip runs, PR 24).

Against a program without the ring, or whose summaries carry no `ts`
(the parent of PR 24), every function here returns an empty list, and a
reader built on `readers.mean`/`readers.percentile` then returns None.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence


def cut(records: Sequence[Dict[str, Any]],
        summaries: Sequence[Dict[str, Any]], seconds: float
        ) -> List[Dict[str, Any]]:
    """The rule above, on plain data."""
    starts = [float(s["ts"]) for s in summaries if s.get("ts") is not None]
    if not starts:
        return []
    first = min(starts)
    return [r for r in records if first <= r["ts"] <= first + seconds]


def window(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The loop records of the window in which `obs["phases"]`'s
    requests were offered."""
    from ray_tpu.observability import requests as reqtrace

    n = len(obs.get("phases") or [])
    store = reqtrace.store()
    fetch = getattr(store, "loop_records", None)
    if fetch is None or not n:
        return []
    return cut(fetch(), store.summaries_since(0)[-n:],
               float(obs["cell"]["seconds"]))


def decoding(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The iterations that began with a slot decoding: what happens in
    one of them, every live stream waits for."""
    return [r for r in window(obs) if r["live"] >= 1]


def admissions(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [a for r in window(obs) for a in r["admissions"]]


def part_ms(obs: Dict[str, Any], key: str) -> List[float]:
    """Per request, the flight recorder's `phase_ms[key]`, over the
    requests that have it (`readers.phase_ms` reads a missing key as 0,
    which a program without the part would then report)."""
    return [float(p[key]) for p in obs.get("phases") or [] if key in p]
