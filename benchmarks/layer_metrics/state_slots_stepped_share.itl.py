"""What of the slots' recurrent state a tick STEPS: the slot-states one
layer's state step visits (`state_slots_stepped` of the engine's loop
ring: the slots the chip held live at the tick's launch, counted on the
host from what `_set_rows` last wrote, where the step walks the live
slots, `ray_tpu/ops/mamba2.py` `ssd_step`; `max_batch` for a program
whose step reads and writes every slot's state), over the slots the
slab holds (`max_batch`), as a mean over the decode passes of the window
of offered load, in %. A program that steps every slot reports 100.
Lower is better at a given load: beside `tick_live_slots_mean.itl` (over
`max_batch`) it says how far the step follows the streams; it reads a
little above it, since a slot whose budget ends with the tick ahead is
live on the chip for one launch more. None against a program whose ring
lacks the field."""
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean


def read(obs):
    passes = [r for r in decoding(obs) if "state_slots_stepped" in r]
    if not passes:
        return None
    slots = int(obs["cell"]["traffic"]["max_batch"])
    return 100.0 * mean([r["state_slots_stepped"] for r in passes]) / slots
