"""What of the decode slab a tick's attention READS: the rows one layer's
walk over the slab visits, all slots at the positions the tick was
launched with (`slab_rows_read` of the engine's loop ring: whole blocks,
a dead slot's one block, counted on the host by the function the
kernel's walk uses, `ray_tpu/ops/swa.py` `decode_rows_read`), over the
rows the slab holds (`max_batch` x `max_seq_len`), as a mean over the
decode passes of the window of offered load, in %. A program whose tick
reads every row of every slot reports its whole slab, 100. Lower is
better at a given load: beside `tick_live_slots_mean.itl` it says how
far the read follows the streams. None against a program whose ring
lacks the field."""
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean


def read(obs):
    passes = [r for r in decoding(obs) if "slab_rows_read" in r]
    if not passes:
        return None
    traffic = obs["cell"]["traffic"]
    held = int(traffic["max_batch"]) * int(traffic["max_seq_len"])
    return 100.0 * mean([r["slab_rows_read"] for r in passes]) / held
