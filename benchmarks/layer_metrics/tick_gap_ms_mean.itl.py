"""Mean gap on the device between the end of a `_tick` program and the
start of the next program, whichever it is, over the gaps during which
some request was decoding (`decode_steady` covers the gap): the engine's
host loop (read the tokens back, walk the slots, emit, admit)."""
from benchmarks.harness.readers import mean


def read(obs):
    return mean([dur / 1e6 for _s, dur, prev, _nxt, host
                 in obs.get("host") or []
                 if prev == "_tick" and "decode_steady" in host])
