"""Mean over the engine's loop iterations that began with a slot decoding
of `total_ms - readback_ms - admit_ms`: the host's own work in one pass of
`_loop` (uploads and dispatch, the walk over the slots, bookkeeping) with
the wait for the device and the admissions taken out. `tick_gap_ms_mean.itl`
less this is transfer and launch latency, which only a pipelined tick
removes (S1)."""
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean


def read(obs):
    return mean([r["total_ms"] - r["readback_ms"] - r["admit_ms"]
                 for r in decoding(obs)])
