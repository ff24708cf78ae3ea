"""The decode tick's share of its memory roofline: the least time the
bytes of a tick need at the chip's peak bandwidth
(`harness/kimi_linear_cost.tick_bytes`: the weights every token reads
once, an expert's matrices for each held expert that got a row, each live
slot's recurrence state read and written, each cache row a live slot
holds), over the mean device time of the `_tick` program in the traced
window. What the tick met comes from the engine's loop ring
(`moe_experts_hit`, `live`, `live_rows`), as means over the decode passes
of the window of offered load, in which the trace's three seconds lie
(the trace's clock is not the ring's, so the ring cannot be cut to them).
Dead slots and unread experts count nothing: a lower reading. None
against a program, or in a cell, whose ring lacks the counters."""
from benchmarks.harness.configs import model_shape
from benchmarks.harness.kimi_linear_cost import tick_bytes
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean, program_mean_ms


def read(obs):
    tick_ms = program_mean_ms(obs, "_tick")
    passes = [r for r in decoding(obs)
              if "moe_experts_hit" in r and "live_rows" in r]
    if not tick_ms or not passes:
        return None
    shape = model_shape(obs["cell"]["conf"])
    least_s = mean([tick_bytes(shape, r["moe_experts_hit"], r["live"],
                               r["live_rows"]) for r in passes]) \
        / obs["cell"]["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s * 1e3 / tick_ms
