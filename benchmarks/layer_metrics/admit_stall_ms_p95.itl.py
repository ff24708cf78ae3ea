"""95th percentile of `admit_ms` over the engine's loop iterations that
began with a slot decoding, zeros included (`loop_records.decoding`): the
time inside `_admit` (lookup, prefill, pool commit, splice, first emit) is
what every live stream waits beyond a tick, so this is about `itl_p95_ms`
less the tick period."""
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import percentile


def read(obs):
    return percentile([r["admit_ms"] for r in decoding(obs)], 95)
