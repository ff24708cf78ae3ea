"""Mean `splice_ms` over the admissions of the window that carried state
(`state_bytes` in the entry: a family whose slots own state with no
sequence axis): what writing an admission's rows AND the whole of its
state (21 MB a slot in `nemotron-3-super-reason`) into the decode slab
holds the engine's thread for, which every live stream waits. None
against a program, or in a cell, whose admissions carry no state."""
from benchmarks.harness.loop_records import admissions
from benchmarks.harness.readers import mean


def read(obs):
    return mean([a["splice_ms"] for a in admissions(obs)
                 if a.get("state_bytes")])
