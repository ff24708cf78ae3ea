"""JAX's persistent compilation cache, enabled the same way by every
process that will own a chip (chip_smoke.py's children, the benchmark,
chip workers): compiled programs are shared between those processes and
found again by the next run.

The directory is part of each entry's key, so it must not move: where
JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no directory is
set here; otherwise it is `<checkout>/.xla_cache` (git-ignored), never a
name made from a pid, a time or tempfile.

The clock of set-up (PR 52). `enable_compile_cache` also listens to what
JAX reports of its compile path, each span with the function's name and
its start and end on `time.time()` (the clock of the flight recorder's
`ts` and of the engine's loop ring):

  `jaxpr_trace_duration`            Python traces a `jit`'s function
  `jaxpr_to_mlir_module_duration`   the jaxpr is lowered to a module
  `backend_compile_duration`        the module is handed to the backend:
                                    compiled, or fetched from the cache
  `cache_retrieval_time_sec`        inside the last, on a hit: the entry
                                    read, unpacked and loaded

and keeps ONE record for every program handed to the backend, the newest
`RING` of them (`compile_cache_programs`):

  `name`       the jit's name (`_tick`, `_prefill_paged`, `step`, ...)
  `t0`, `t1`   its first span's start, the hand-over's end
  `trace_s`, `lower_s`, `backend_s`   seconds of each kind
  `hit`, `fetch_s`   whether the cache held it, and then the retrieval
  `thread`     the thread that compiled
  `parent`     where the whole program was made INSIDE another's trace or
               lowering (a value the tracing needed at once): that span's
               name. Its `trace_s` and `lower_s` lie inside the parent's
               and count there
  `inner`      the largest `INNER_KEPT` of the functions traced inside
               this one's trace or lowering and handed over with it (an
               inner `jit`, a `jnp` function): `name`, `parent` (the span
               it lay in), `n` and `trace_s`, which the outer's holds

Spans on one thread nest and never overlap otherwise, so a sum given here
is the length of a UNION of spans on that thread, never a sum of
durations: only a span that lies in no other adds to `trace_s` or
`lower_s`, less the hand-overs inside it. A trace or a lowering that
leads to no hand-over (`jax.eval_shape`, a `.lower()` alone) goes to one
record named `unattributed`, so the totals hold every second JAX
reported.

What a hit's `backend_s - fetch_s` is (`jax/_src/compiler.py`
`compile_or_get_cached`): the making of the cache key before the cache
is asked, the module serialised and hashed with the compile options and
the backend's version. It is NOT the executable's deserialisation and
load: `fetch_s` covers those with the entry's read and decompression
(`_cache_read` -> `get_executable_and_time`). On the chip the key costs
0.4 to 1.3 ms a small program (a tenth of its 3 to 10 ms) and 7 to 20 ms
the largest (under 1% of their 1 to 3.4 s): 0.04 to 0.09 s of the 3.7 to
9.6 s a warm set-up hands over, 1% (my chip runs, PR 52). So `compile_s`
on a warm machine IS the retrieval time, to one part in a hundred.

The listeners run on the compile path alone, under one lock: 24,000 to
33,000 calls a set-up (every `jnp` function traced inside a program
reports a trace of its own, three calls each) at 5 to 10 us, 0.14 to
0.32 s, under 1% of a warm `setup_s` (my chip runs, PR 52). This module
imports no `jax` until the cache is enabled, so that the packages' import
stamps (`import_spans`) cost nothing.
"""
from __future__ import annotations

import collections
import os
import threading
from typing import Any, Deque, Dict, List, Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".xla_cache")

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_KIND = {_TRACE: "trace_s", _LOWER: "lower_s", _BACKEND: "backend_s"}
RING = 1024
INNER_KEPT = 8

Record = Dict[str, Any]


class _Thread:
    """One thread's compile path: the spans open on it, outermost first,
    as [event, name, seconds of hand-overs inside], and by depth the
    program whose spans have been seen and whose hand-over has not."""
    __slots__ = ("open", "pending")

    def __init__(self) -> None:
        self.open: List[List[Any]] = []
        self.pending: Dict[int, Record] = {}


_lock = threading.Lock()
_counts: Dict[str, float] = {
    "hits": 0, "misses": 0, "compiles": 0, "compile_s": 0.0,
    "trace_s": 0.0, "lower_s": 0.0, "fetch_s": 0.0, "miss_compile_s": 0.0}
_ring: Deque[Record] = collections.deque(maxlen=RING)
_unattributed: Record = {}
_threads: Dict[int, _Thread] = {}
_imports: Dict[str, Tuple[float, float]] = {}
_listening = False


def _name(fun_name: Any) -> str:
    """`jit(_tick)`, as the lowering and the hand-over have it, is the
    `_tick` the trace has."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1:-1]
    return name


def _fresh(name: str, start: float) -> Record:
    return {"name": name, "t0": float(start), "t1": float(start),
            "trace_s": 0.0, "lower_s": 0.0, "inner": {}}


def _unattribute(into: Record, p: Record) -> None:
    """`p` lay in no span and never reached the backend."""
    into["n"] = into.get("n", 0) + 1
    into["t0"] = min(into.get("t0", p["t0"]), p["t0"])
    into["t1"] = max(into.get("t1", p["t1"]), p["t1"])
    for key in ("trace_s", "lower_s"):
        into[key] = into.get(key, 0.0) + p[key]


def _fold(inner: Dict[Tuple[str, Optional[str]], List[float]],
          key: Tuple[str, Optional[str]], n: float, seconds: float) -> None:
    entry = inner.get(key)
    if entry is None:
        inner[key] = [n, seconds]
    else:
        entry[0] += n
        entry[1] += seconds


def _drop(th: _Thread, depth: int, span: Optional[str] = None) -> None:
    """What is pending at `depth` led to no hand-over: to `unattributed`
    if it lay in no span, else to the `inner` of the program whose span
    (named `span`) it lay in, as [n, seconds] by (name, parent)."""
    p = th.pending.pop(depth, None)
    if p is None:
        return
    if depth == 0:
        _unattribute(_unattributed, p)
        return
    outer = th.pending.get(depth - 1)
    if outer is None:  # the listeners came while its span was open
        return
    inner = outer["inner"]
    _fold(inner, (p["name"], span), 1, p["trace_s"] + p["lower_s"])
    for key, (n, seconds) in p["inner"].items():
        _fold(inner, key, n, seconds)


def _thread() -> _Thread:
    """This thread's state; the caller holds the lock."""
    ident = threading.get_ident()
    th = _threads.get(ident)
    if th is None:
        th = _threads[ident] = _Thread()
    return th


def _on_start(event: str, start: float, fun_name: Any = None, **_kw) -> None:
    if event not in _KIND:
        return
    name = _name(fun_name)
    with _lock:
        th = _thread()
        depth = len(th.open)
        p = th.pending.get(depth)
        if p is not None and (event == _TRACE or p["name"] != name):
            # a new program begins where one was pending
            _drop(th, depth, th.open[-1][1] if depth else None)
        if depth not in th.pending:
            th.pending[depth] = _fresh(name, start)
        th.open.append([event, name, 0.0])


def _on_span(event: str, start: float, end: float, fun_name: Any = None,
             **_kw) -> None:
    if event not in _KIND:
        return
    name, seconds = _name(fun_name), float(end) - float(start)
    with _lock:
        th = _thread()
        inside = 0.0
        if th.open and th.open[-1][0] == event:
            inside = th.open.pop()[2]
        depth = len(th.open)
        p = th.pending.get(depth)
        if p is None:  # the listeners came while the span was open
            p = th.pending[depth] = _fresh(name, start)
        _drop(th, depth + 1, name)  # traced inside this span: its `inner`
        p["t1"] = float(end)
        if event != _BACKEND:
            p[_KIND[event]] += seconds - inside
            if depth == 0:
                _counts[_KIND[event]] += seconds - inside
            return
        del th.pending[depth]
        for frame in th.open:
            frame[2] += seconds
        _counts["compiles"] += 1
        _counts["compile_s"] += seconds
        hit = bool(p.pop("hit", False))
        if not hit:
            _counts["miss_compile_s"] += seconds
        inner = sorted(p.pop("inner").items(),
                       key=lambda kv: -kv[1][1])[:INNER_KEPT]
        p.update(name=name, backend_s=seconds, hit=hit,
                 thread=threading.current_thread().name)
        if depth:
            p["parent"] = th.open[-1][1]
        if inner:
            p["inner"] = [{"name": key[0], "parent": key[1], "n": n,
                           "trace_s": seconds}
                          for key, (n, seconds) in inner]
        _ring.append(p)
        if not th.open and not th.pending:
            del _threads[threading.get_ident()]


def _handing_over() -> Optional[Record]:
    """The program this thread is handing to the backend, if one is."""
    th = _threads.get(threading.get_ident())
    if th is None or not th.open or th.open[-1][0] != _BACKEND:
        return None
    return th.pending.get(len(th.open) - 1)


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _counts["hits"] += 1
            p = _handing_over()
            if p is not None:
                p["hit"] = True
    elif event == "/jax/compilation_cache/cache_misses":
        with _lock:
            _counts["misses"] += 1


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _FETCH:
        with _lock:
            _counts["fetch_s"] += float(duration)
            p = _handing_over()
            if p is not None:
                p["fetch_s"] = float(duration)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    global _listening
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # keep every program: the serving programs (decode tick, per-length
    # prefills) compile in well under JAX's default one-second threshold,
    # and a replica process otherwise compiles them cold each start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_scalar_listener(_on_start)
            jax.monitoring.register_event_time_span_listener(_on_span)
            _listening = True
    return cache_dir


def compile_cache_counts() -> Dict[str, float]:
    """This process since `enable_compile_cache`: persistent-cache `hits`
    and `misses` (entries written), programs handed to the backend
    (`compiles`) and the seconds that took (`compile_s`, set-up time);
    of those seconds, the retrievals of the hits (`fetch_s`) and the
    hand-overs that compiled (`miss_compile_s`); and before them Python's
    tracing (`trace_s`) and lowering (`lower_s`), each the length of the
    union of its spans on a thread, summed over the threads."""
    with _lock:
        out = dict(_counts)
    return {k: round(v, 3 if k == "compile_s" else 6)
            for k, v in out.items()}


def compile_cache_programs(since: float = 0.0) -> List[Record]:
    """The ring's records (the module's docstring) whose `t0` is at or
    after `since`, a `time.time()`, oldest first; after them the
    `unattributed` record (`n` spans, `t0`, `t1`, `trace_s`, `lower_s`),
    where it holds anything that ended since: the traces and lowerings
    that led to no hand-over, those still waiting for one included."""
    with _lock:
        out = [dict(r) for r in _ring if r["t0"] >= since]
        rest = dict(_unattributed)
        for th in _threads.values():
            if not th.open and 0 in th.pending:
                _unattribute(rest, th.pending[0])
    if rest and rest["t1"] >= since:
        out.append(dict(rest, name="unattributed"))
    return out


def _stamp_import(package: str, t0: float, t1: float) -> None:
    with _lock:
        _imports[package] = (float(t0), float(t1))


def import_spans() -> List[Tuple[str, float, float]]:
    """`(package, t0, t1)` on `time.time()` for `ray_tpu` and
    `ray_tpu.models`, from the first to the last line of each
    `__init__.py`: inclusive of what they import (jax, where nothing
    imported it before), and one may lie inside the other."""
    with _lock:
        return [(p, t0, t1) for p, (t0, t1) in _imports.items()]
