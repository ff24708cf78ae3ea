"""Mean `emit_ms` over the engine's loop iterations that began with a
slot decoding: the walk over the slots after the tokens are back (`_emit`,
`_finish`, queue puts). A part of `loop_host_ms_mean.itl`; it grows with
`max_batch`."""
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean


def read(obs):
    return mean([r["emit_ms"] for r in decoding(obs)])
