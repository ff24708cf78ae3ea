"""What of the decode slab a tick reads is someone's: the rows of keys
and values the live slots of a tick need (`position` rows of every global
layer, `min(position, window)` of every window layer: `live_rows` and
`live_rows_window` of the engine's loop ring) over the rows the slab
holds (`max_batch` slots of `harness/smallthinker_cost.slab_rows`), as a
mean over the decode passes of the window of offered load. A slab of one
length for every layer would hold `max_seq_len` rows in the window
layers too, and this share would be that much lower. None against a
program, or in a cell, whose ring lacks `live_rows_window`."""
from benchmarks.harness.configs import model_shape
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean
from benchmarks.harness.smallthinker_cost import live_rows_read, slab_rows


def read(obs):
    passes = [r for r in decoding(obs) if "live_rows_window" in r]
    if not passes:
        return None
    shape = model_shape(obs["cell"]["conf"])
    traffic = obs["cell"]["traffic"]
    held = int(traffic["max_batch"]) * slab_rows(
        shape, int(traffic["max_seq_len"]))
    return 100.0 * mean([live_rows_read(shape, r["live_rows"],
                                        r["live_rows_window"])
                         for r in passes]) / held
