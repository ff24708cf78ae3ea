"""The decode tick's share of its memory roofline where nine layers of
ten carry a float32 state of megabytes and every layer routes ten of 72
narrow experts beside a shared MLP: the least time the bytes of a tick
need at the chip's peak bandwidth
(`harness/granite_hybrid_cost.tick_bytes`: the matrices of the experts
that got a row, the weights every tick reads, the head, the rows the
live slots hold, their states and tails read and written), over the mean
device time of the `_tick` program in the traced window. What the tick
met comes from the engine's loop ring (`moe_experts_hit`, `live`,
`live_rows`), as means over the decode passes of the window of offered
load, in which the trace's three seconds lie (the trace's clock is not
the ring's). Dead slots and unread experts count nothing: a lower
reading. None against a program whose ring lacks the counters, or in a
cell whose family's `shape()` lacks the sizes."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.granite_hybrid_cost import tick_bytes
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean, program_mean_ms


def read(obs):
    tick_ms = program_mean_ms(obs, "_tick")
    passes = [r for r in decoding(obs)
              if "moe_experts_hit" in r and "live_rows" in r]
    shape = model_shape(obs["cell"]["conf"])
    if not tick_ms or not passes or "dense_bytes" not in shape:
        return None
    hit = mean([r["moe_experts_hit"] for r in passes])
    live = mean([r["live"] for r in passes])
    rows = mean([r["live_rows"] for r in passes])
    least_s = tick_bytes(shape, hit, live, rows) \
        / obs["cell"]["peaks"]["hbm_bytes_per_s"]
    log(f"granite_tick_bytes_roofline.tput: tick {tick_ms:.3f} ms, its "
        f"bytes need {1e3 * least_s:.3f} ms ({hit:.1f} experts hit over "
        f"the layers, {live:.2f} slots live with {rows:.0f} rows)")
    return 100.0 * least_s * 1e3 / tick_ms
