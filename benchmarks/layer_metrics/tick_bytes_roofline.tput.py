"""The decode tick's share of its memory roofline in a cell judged on
tokens per second: the least time the bytes of a tick need at the chip's
peak bandwidth (`harness/deepseek_v2_cost.tick_bytes`: the weights every
token reads once, an expert's matrices for each held expert that got a
row, each cache row a live slot holds as the slab holds it), over the mean
device time of the `_tick` program in the traced window. What the tick met
comes from the engine's loop ring (`moe_experts_hit`, `live_rows`), as
means over the decode passes of the window of offered load, in which the
trace's three seconds lie (the trace's clock is not the ring's). Dead
slots and unread experts count nothing: a lower reading.

The absorbed attention of this family does 218 FLOPs for each byte of a
row, at the chip's ridge, so the bytes alone no longer say how near the
tick is to what the chip can do: the line this reader logs gives, beside
the share, `tick_mla_flops` over the peak FLOP/s over the same tick time,
the absorbed attention's share of the compute peak. None against a
program, or in a cell, whose ring lacks the counters."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.deepseek_v2_cost import tick_bytes, tick_mla_flops
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean, program_mean_ms


def read(obs):
    tick_ms = program_mean_ms(obs, "_tick")
    passes = [r for r in decoding(obs)
              if "moe_experts_hit" in r and "live_rows" in r]
    if not tick_ms or not passes:
        return None
    shape = model_shape(obs["cell"]["conf"])
    peaks = obs["cell"]["peaks"]
    least_s = mean([tick_bytes(shape, r["moe_experts_hit"], r["live_rows"])
                    for r in passes]) / peaks["hbm_bytes_per_s"]
    mla_s = mean([tick_mla_flops(shape, r["live_rows"])
                  for r in passes]) / peaks["flops_bf16"]
    log(f"tick_bytes_roofline.tput: tick {tick_ms:.3f} ms, its bytes "
        f"need {1e3 * least_s:.3f} ms, the absorbed attention's "
        f"operations {1e3 * mla_s:.3f} ms "
        f"({100.0 * mla_s * 1e3 / tick_ms:.1f}% of the compute peak)")
    return 100.0 * least_s * 1e3 / tick_ms
