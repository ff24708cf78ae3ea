"""What the readers of set-up's clock share (PR 52): the records
`ray_tpu.util.compile_cache` keeps of every program handed to the
backend (its module docstring has the fields), cut to set-up, and the
reductions over them.

`obs` holds no end-of-set-up stamp, so set-up's records are cut by one
rule:

    serving: the records whose `t0` lies before the earliest `ts` of the
    requests the cell measured (the rule `loop_records.window` starts
    its window by: `obs["phases"]` holds the store's newest summaries, a
    summary's `ts` and a record's `t0` are both on `time.time()`);
    training, and a cell that measured no request: every record of the
    process (a `correct` run compiles nothing in the window and runs
    nothing after it).

So `programs.setup` is `cache_at_setup["compiles"]` of the run's record
file wherever nothing compiled between the warm-up and the first
request. A record with `parent` was made inside its parent's trace or
lowering: its `backend_s` and `fetch_s` are its own, its `trace_s` and
`lower_s` lie in the parent's and are left out of a sum (`outermost`).
The `unattributed` record (traces that led to no hand-over) is set-up's
where it began before the first request.

Against a program without the clock (the parent of PR 52: its
`compile_cache` has no `compile_cache_programs` and no `import_spans`)
`split` and `import_s` return None, and so does every reader.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .common import log

Record = Dict[str, Any]


def measured(obs: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The summaries of the requests the cell measured: the store's
    newest `len(obs["phases"])`."""
    from ray_tpu.observability import requests as reqtrace

    n = len(obs.get("phases") or [])
    return reqtrace.store().summaries_since(0)[-n:] if n else []


def first_request_ts(obs: Dict[str, Any]) -> float:
    """The earliest start of the requests the cell measured; infinity
    where it measured none (training)."""
    return min((float(s["ts"]) for s in measured(obs)
                if s.get("ts") is not None), default=math.inf)


def cut(records: Iterable[Record], first_ts: float
        ) -> Tuple[List[Record], List[Record]]:
    """The rule above, on plain data: (set-up's records, the others)."""
    setup: List[Record] = []
    after: List[Record] = []
    for r in records:
        (setup if r["t0"] < first_ts else after).append(r)
    return setup, after


def split(obs: Dict[str, Any]
          ) -> Optional[Tuple[List[Record], List[Record]]]:
    """The process's records as `cut` parts them."""
    from ray_tpu.util import compile_cache

    fetch = getattr(compile_cache, "compile_cache_programs", None)
    if fetch is None:
        return None
    return cut(fetch(), first_request_ts(obs))


def programs(records: Iterable[Record]) -> List[Record]:
    """The records of programs handed to the backend (`unattributed`
    is none)."""
    return [r for r in records if "backend_s" in r]


def outermost(records: Iterable[Record]) -> List[Record]:
    """The records whose trace and lowering lie in no other's."""
    return [r for r in records if "parent" not in r]


def total_s(r: Record) -> float:
    return r["trace_s"] + r["lower_s"] + r["backend_s"]


def describe(r: Record) -> str:
    how = f"fetch {r['fetch_s']:.3f}" if r.get("hit") else "compiled"
    return (f"{r['name']} {total_s(r):.3f} (trace {r['trace_s']:.3f}, "
            f"lower {r['lower_s']:.3f}, backend {r['backend_s']:.3f}: "
            f"{how})")


def union_s(spans: Iterable[Tuple[float, float]]) -> float:
    """The length of the union of (start, end) intervals."""
    total, reach = 0.0, -math.inf
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def import_s() -> Optional[float]:
    """The union of the packages' import spans, in seconds."""
    from ray_tpu.util import compile_cache

    fetch = getattr(compile_cache, "import_spans", None)
    if fetch is None:
        return None
    spans = fetch()
    log("import_s.setup: " + (", ".join(
        f"{package} {t1 - t0:.3f} s" for package, t0, t1 in spans)
        or "no package stamped"))
    return union_s((t0, t1) for _package, t0, t1 in spans)


def covering(r: Record, summaries: Iterable[Dict[str, Any]]) -> List[str]:
    """The ids of the requests whose trace overlaps the record's span."""
    return [str(s.get("request_id")) for s in summaries
            if s.get("ts") is not None and float(s["ts"]) <= r["t1"]
            and float(s["ts"]) + float(s.get("total_ms", 0.0)) / 1e3
            >= r["t0"]]
