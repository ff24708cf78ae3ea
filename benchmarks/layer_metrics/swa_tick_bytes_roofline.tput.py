"""The decode tick's share of its memory roofline where the slab holds
window layers beside global ones: the least time the bytes of a tick need
at the chip's peak bandwidth (`harness/smallthinker_cost.tick_bytes`: the
weights every token reads once, an expert's matrices for each expert that
got a row, and for each live slot its `position` rows of every global
layer and `min(position, window)` rows of every window layer), over the
mean device time of the `_tick` program in the traced window. What the
tick met comes from the engine's loop ring (`moe_experts_hit`,
`live_rows`, `live_rows_window`), as means over the decode passes of the
window of offered load, in which the trace's three seconds lie (the
trace's clock is not the ring's). Dead slots and unread experts count
nothing: a lower reading. None against a program, or in a cell, whose
ring lacks the counters."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import (mean, op_count, op_seconds,
                                        program_mean_ms)
from benchmarks.harness.smallthinker_cost import tick_bytes


def read(obs):
    tick_ms = program_mean_ms(obs, "_tick")
    passes = [r for r in decoding(obs)
              if "moe_experts_hit" in r and "live_rows_window" in r]
    if not tick_ms or not passes:
        return None
    shape = model_shape(obs["cell"]["conf"])
    least_s = mean([tick_bytes(shape, r["moe_experts_hit"], r["live_rows"],
                               r["live_rows_window"]) for r in passes]) \
        / obs["cell"]["peaks"]["hbm_bytes_per_s"]
    log(f"swa_tick_bytes_roofline.tput: tick {tick_ms:.3f} ms, its bytes "
        f"need {1e3 * least_s:.3f} ms (experts hit "
        f"{mean([r['moe_experts_hit'] for r in passes]):.1f}, live rows "
        f"{mean([r['live_rows'] for r in passes]):.0f}, of the windows "
        f"{mean([r['live_rows_window'] for r in passes]):.0f}); in the "
        f"trace {op_count(obs, ['grouped_stream'])} grouped_stream events "
        f"took {op_seconds(obs, ['grouped_stream']):.3f} s")
    return 100.0 * least_s * 1e3 / tick_ms
