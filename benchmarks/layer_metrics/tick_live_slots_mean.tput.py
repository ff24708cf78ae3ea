"""Mean `live` over the engine's loop iterations that began with a slot
decoding: the slots in flight when the pass began, of the cell's
`max_batch`. An empty slot is paid for in every tick (static shapes), so
higher is better at a given tick time."""
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean


def read(obs):
    return mean([r["live"] for r in decoding(obs)])
