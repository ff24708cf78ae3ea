"""What the readers of the engine's gap ledger share (PR 35): the gaps
the decode loop made between two landings of a tick, as its ring has
them, and the reductions over them.

A record of `ray_tpu.models.engine`'s loop ring whose pass read a tick
back carries what the gap that ended there held (the engine's module
docstring, "a ledger of the gaps"): `gap_ms`, the time since the landing
before, where at least one request took a token from both ticks;
`gap_streams`, how many did; `gap_admissions`, the admissions whose
programs were launched in it; `gap_blocked_ms`, the time the loop was
blocked on the chip's work; `gap_empty_ms` with `gap_empty_by`, the
time the chip was starved and the host's step that ran meanwhile. All
readers cut the ring to the window of offered load by
`loop_records.window`, and count a gap as the clients do: once a stream
that felt it. A STREAM-GAP is a record's `gap_ms` counted `gap_streams`
times, so a percentile over stream-gaps is the clients' percentile over
their inter-token gaps as the engine made them, before the router, the
gateway and the SSE writer add theirs. (Not in it: a request's own first
gap, from its prefill's token to its first tick's, one in a hundred.)

Against a program without the ledger (the parent of PR 35: its records
have no `gap_ms`) `gaps` is empty and every reduction here returns None.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .common import log
from .loop_records import admissions, window

Record = Dict[str, Any]


def gaps(obs: Dict[str, Any],
         keep: Optional[Callable[[Record], bool]] = None) -> List[Record]:
    """The window's records that ended a gap a stream felt, or those of
    them `keep` holds true of."""
    return [r for r in window(obs) if "gap_ms" in r
            and (keep is None or keep(r))]


def held_admission(r: Record) -> bool:
    return r["gap_admissions"] >= 1


def steady(r: Record) -> bool:
    return r["gap_admissions"] == 0


def stream_gaps(records: List[Record]) -> np.ndarray:
    """`gap_ms` of each record, once a stream that felt it."""
    return np.repeat(
        np.asarray([r["gap_ms"] for r in records], np.float64),
        np.asarray([r["gap_streams"] for r in records], np.int64))


def streams(records: List[Record]) -> int:
    return int(sum(r["gap_streams"] for r in records))


def stream_gap_percentile(records: List[Record], q: float
                          ) -> Optional[float]:
    values = stream_gaps(records)
    return float(np.percentile(values, q)) if values.size else None


def stream_gap_mean(records: List[Record]) -> Optional[float]:
    values = stream_gaps(records)
    return float(values.mean()) if values.size else None


def empty_share(obs: Dict[str, Any], name: str) -> Optional[float]:
    """Of the time in which a stream decoded (the sum of `gap_ms`), the
    percentage the chip was starved for; logs, under the reader's
    `name`, the starved seconds by the host's step."""
    records = gaps(obs)
    total_ms = sum(r["gap_ms"] for r in records)
    if not total_ms:
        return None
    by: Dict[str, float] = {}
    for r in records:
        for step, ms in r["gap_empty_by"].items():
            by[step] = by.get(step, 0.0) + ms
    table = ", ".join(f"{step} {ms / 1e3:.4f}" for step, ms in
                      sorted(by.items(), key=lambda kv: -kv[1]))
    blocked_ms = sum(r["gap_blocked_ms"] for r in records)
    log(f"{name}: chip starved {sum(by.values()) / 1e3:.4f} s of "
        f"{total_ms / 1e3:.3f} s of gaps (the loop blocked on it "
        f"{blocked_ms / 1e3:.3f} s); by step, s: {table or 'none'}")
    return 100.0 * sum(r["gap_empty_ms"] for r in records) / total_ms


def collision_share(obs: Dict[str, Any]) -> Optional[float]:
    """Of the window's admissions, the percentage that waited behind
    another request's prefill (`prefills_waited` >= 1)."""
    waited = [a["prefills_waited"] for a in admissions(obs)
              if "prefills_waited" in a]
    if not waited:
        return None
    return 100.0 * sum(w >= 1 for w in waited) / len(waited)
