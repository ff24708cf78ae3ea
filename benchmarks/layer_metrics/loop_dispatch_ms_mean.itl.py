"""Mean `dispatch_ms` over the engine's loop iterations that began with a
slot decoding: from the end of `_admit` to the return of the `_tick` call,
the two `jnp.asarray` uploads and the dispatch. A part of
`loop_host_ms_mean.itl`."""
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean


def read(obs):
    return mean([r["dispatch_ms"] for r in decoding(obs)])
