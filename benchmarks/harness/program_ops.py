"""Which device operations ran inside which program, from the reduced
trace (`trace_reduce.reduce_trace`): the trace names an operation by its
HLO instruction (`ragged-dot-none.3`, `mla_prefill_t4096.1`) and a program
by its jitted function, on one device clock, so an operation belongs to
the program event whose interval holds its start. For readers whose unit
of work is a kernel INSIDE one program (the grouped products of a prefill,
as against those of a tick) and whose work depends on that program's
shape."""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns
PROMPT_KERNEL = re.compile(r"mla_prefill_t(\d+)")


def named(trace: Dict[str, Any], substrings: Sequence[str]) -> List[Event]:
    """The operation events whose name holds any of the substrings."""
    return [ev for name, evs in trace["ops"].items()
            if any(s in name for s in substrings) for ev in evs]


def whole_programs(trace: Dict[str, Any], program: str) -> List[Event]:
    """The program's events that start AND end inside the traced window:
    of one the window cuts, only some operations were kept."""
    end = trace["window"][1]
    return [ev for ev in trace["programs"].get(program, [])
            if ev[1] + ev[2] <= end]


def inside(events: Sequence[Event], program_event: Event) -> List[Event]:
    _name, start, dur = program_event
    return [ev for ev in events if start <= ev[1] < start + dur]


def prompt_tokens(events: Sequence[Event]) -> Optional[int]:
    """The prompt's length, which the prompt form's kernel carries in its
    name; None where none of the events is that kernel."""
    for name, _s, _d in events:
        m = PROMPT_KERNEL.search(name)
        if m:
            return int(m.group(1))
    return None
