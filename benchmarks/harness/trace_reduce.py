"""From a profiler trace to busy time, per-program time, per-kernel time
and idle gaps. Everything here works on plain tuples, so the tests feed it
a synthetic trace; `load_xplane` is the one adapter to JAX's reader.

A trace is a list of planes `(plane_name, [(line_name, [(event_name,
start_ns, duration_ns), ...]), ...])`. All times are on the trace's own
clock, in nanoseconds.

The window is the interval between two marker events the run puts into the
trace itself (`MARK_T0`, `MARK_T1`), never the host's clock: `busy_s` is the
length of the union of the operation intervals of ONE line of ONE device
plane, each clipped to the window, so `0 <= busy_s <= window_s` holds by
construction and overlapping events are not counted twice.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns
Line = Tuple[str, List[Event]]
Plane = Tuple[str, List[Line]]
Interval = Tuple[float, float]              # start_ns, end_ns

MARK_T0 = "bench_window_t0"
MARK_T1 = "bench_window_t1"


class TraceError(RuntimeError):
    """The trace cannot give the numbers asked of it: the run fails."""


def load_xplane(path: str) -> List[Plane]:
    """Read an `.xplane.pb` with JAX's own reader into plain tuples."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: List[Plane] = []
    for plane in data.planes:
        lines: List[Line] = []
        for line in plane.lines:
            lines.append((line.name, [
                (short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)) for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def short_name(name: str) -> str:
    """The chip's operation events are named by their whole HLO line
    (`%fusion.3 = f32[...] fusion(...)`): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def find_window(planes: Sequence[Plane]) -> Interval:
    """[end of the first MARK_T0, start of the last MARK_T1], wherever
    the host planes hold them."""
    t0: Optional[float] = None
    t1: Optional[float] = None
    for _pname, lines in planes:
        for _lname, events in lines:
            for name, start, dur in events:
                if name == MARK_T0:
                    end = start + dur
                    t0 = end if t0 is None else min(t0, end)
                elif name == MARK_T1:
                    t1 = start if t1 is None else max(t1, start)
    if t0 is None or t1 is None:
        raise TraceError(
            f"the trace holds no {MARK_T0}/{MARK_T1} marker pair "
            f"(t0={t0}, t1={t1}): no window to reduce")
    if t1 <= t0:
        raise TraceError(f"marker t1 ({t1}) is not after t0 ({t0})")
    return (t0, t1)


def pick_line(planes: Sequence[Plane], plane_prefix: str,
              line_prefix: str) -> Optional[List[Event]]:
    """The events of the first line whose name starts with `line_prefix`
    on the first plane (in name order) whose name starts with
    `plane_prefix`; None where there is none."""
    for pname, lines in sorted(planes, key=lambda p: p[0]):
        if not pname.startswith(plane_prefix):
            continue
        for lname, events in lines:
            if lname.startswith(line_prefix):
                return events
    return None


def device_planes(planes: Sequence[Plane], plane_prefix: str
                  ) -> List[Plane]:
    return sorted((p for p in planes if p[0].startswith(plane_prefix)),
                  key=lambda p: p[0])


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """Events cut to the window; those wholly outside it are dropped."""
    t0, t1 = window
    out: List[Event] = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Interval]:
    """The merged intervals covered by the events, in time order."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    merged: List[List[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(events: Iterable[Event], window: Interval) -> float:
    return sum(b - a for a, b in union(clip(events, window))) / 1e9


def gaps(events: Iterable[Event], window: Interval
         ) -> List[Tuple[float, float, str, str]]:
    """Idle gaps of one line inside the window: (start_ns, duration_ns,
    name of the event that ended before it, name of the one that starts
    after it). The window's edges count as events named `window`."""
    inside = sorted(clip(events, window), key=lambda e: e[1])
    t0, t1 = window
    out: List[Tuple[float, float, str, str]] = []
    edge, prev = t0, "window"
    for name, start, dur in inside:
        if start > edge:
            out.append((edge, start - edge, prev, name))
        if start + dur > edge:
            edge, prev = start + dur, name
    if t1 > edge:
        out.append((edge, t1 - edge, prev, "window"))
    return out


def program_name(event_name: str) -> str:
    """`jit__tick(1234)` -> `_tick`; other names are kept."""
    m = re.match(r"^jit_(.+?)(\(\d+\))?$", event_name)
    return m.group(1) if m else event_name


def by_name(events: Iterable[Event], window: Interval
            ) -> Dict[str, List[Event]]:
    """Events whose START lies in the window, whole, grouped by name: a
    mean duration must not be made of clipped pieces."""
    t0, t1 = window
    out: Dict[str, List[Event]] = {}
    for ev in events:
        if t0 <= ev[1] < t1:
            out.setdefault(ev[0], []).append(ev)
    return out


def top_ops(events: Iterable[Event], window: Interval, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The n operation names with the most clipped time, in seconds."""
    total: Dict[str, float] = {}
    for name, _s, dur in clip(events, window):
        total[name] = total.get(name, 0.0) + dur / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def name_gap(start_ns: float, dur_ns: float,
             host_spans: Sequence[Tuple[str, float, float]]) -> str:
    """What the host was doing in a gap: the names of the host spans
    (name, start_ns, end_ns on the trace's clock) that cover most of it,
    largest cover first, joined by `+`; `none` where nothing covers it."""
    a, b = start_ns, start_ns + dur_ns
    cover: Dict[str, float] = {}
    for name, s, e in host_spans:
        lap = min(b, e) - max(a, s)
        if lap > 0:
            cover[name] = cover.get(name, 0.0) + lap
    names = [n for n, c in sorted(cover.items(), key=lambda kv: -kv[1])
             if c >= 0.25 * dur_ns][:2]
    return "+".join(names) if names else "none"


def reduce_trace(planes: Sequence[Plane], *, plane_prefix: str,
                 ops_line: str, modules_line: str,
                 host_spans: Sequence[Tuple[str, float, float]] = (),
                 ) -> Dict[str, object]:
    """Everything the readers and the last line take from one trace.

    `busy_s` is averaged over the device planes found (one per chip);
    programs, operations and gaps are those of the first device plane.
    Raises TraceError where the window holds no device operation."""
    window = find_window(planes)
    window_s = (window[1] - window[0]) / 1e9
    devs = device_planes(planes, plane_prefix)
    if not devs:
        raise TraceError(
            f"no plane named {plane_prefix}* in the trace; it has "
            f"{[p[0] for p in planes]}")
    busy: List[float] = []
    for pname, lines in devs:
        ops = next((ev for ln, ev in lines if ln.startswith(ops_line)),
                   None)
        if ops is None:
            raise TraceError(
                f"plane {pname} has no line {ops_line}*; it has "
                f"{[ln for ln, _ in lines]}")
        busy.append(busy_seconds(ops, window))
    busy_s = sum(busy) / len(busy)
    if not busy_s > 0.0:
        raise TraceError(
            f"no device operation ran inside the traced window of "
            f"{window_s:.3f} s (planes {[p[0] for p in devs]})")
    if busy_s > window_s:
        raise TraceError(f"busy_s {busy_s} exceeds window_s {window_s}")
    first = [devs[0]]
    ops = pick_line(first, plane_prefix, ops_line) or []
    modules = pick_line(first, plane_prefix, modules_line) or []
    # one program name may stand for several compiled programs (one
    # `_prefill_paged` per prompt length): their events are put together
    programs: Dict[str, List[Event]] = {}
    for key, events in by_name(modules, window).items():
        programs.setdefault(program_name(key), []).extend(events)
    # gaps between programs where the trace has a module line, else
    # between operations
    gap_src = modules if modules else ops
    gap_list = gaps([(program_name(n), s, d) for n, s, d in gap_src],
                    window)
    labelled = [(start, dur, prev, nxt,
                 name_gap(start, dur, host_spans))
                for start, dur, prev, nxt in gap_list]
    idle: Dict[str, float] = {}
    for _start, dur, prev, nxt, host in labelled:
        key = f"{prev}>{nxt}|{host}"
        idle[key] = idle.get(key, 0.0) + dur / 1e9
    return {
        "window": window, "window_s": window_s, "busy_s": busy_s,
        "programs": programs,
        "ops": by_name(ops, window),
        # (start_ns, dur_ns, program before, program after, host spans)
        "gaps": labelled,
        "breakdown": {
            "device_ops": [[n, s] for n, s in top_ops(ops, window)],
            "idle_gaps": [[n, s] for n, s in sorted(
                idle.items(), key=lambda kv: -kv[1])[:10]],
        },
    }
