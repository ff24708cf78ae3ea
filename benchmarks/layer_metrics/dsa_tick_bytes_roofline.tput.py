"""The decode tick's share of its memory roofline where the full layers
attend the rows an indexer picks: the least time the bytes of a tick need
at the chip's peak bandwidth (`harness/dsa_cost.tick_bytes`: the weights
every token reads once, an expert's matrices for each held expert that
got a row, the index key of every visible row and the latent row of
every SELECTED row of each full layer, the ring rows inside the window of
each sliding layer), over the mean device time of the `_tick` program in
the traced window. What the tick met comes from the engine's loop ring
(`moe_experts_hit`, `dsa_rows_visible`, `dsa_rows_selected`,
`ring_rows_read`), as means over the decode passes of the window of
offered load, in which the trace's three seconds lie (the trace's clock
is not the ring's). The program scores every row of the slab, and dead
slots and unread experts count nothing: a lower reading. None against a
program, or in a cell, whose ring lacks the counters."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.dsa_cost import tick_bytes
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean, program_mean_ms


def read(obs):
    tick_ms = program_mean_ms(obs, "_tick")
    passes = [r for r in decoding(obs)
              if "dsa_rows_selected" in r and "moe_experts_hit" in r]
    if not tick_ms or not passes:
        return None
    shape = model_shape(obs["cell"]["conf"])
    least_s = mean([tick_bytes(shape, r["moe_experts_hit"],
                               r["dsa_rows_visible"], r["dsa_rows_selected"],
                               r["ring_rows_read"]) for r in passes]) \
        / obs["cell"]["peaks"]["hbm_bytes_per_s"]
    log(f"dsa_tick_bytes_roofline.tput: tick {tick_ms:.3f} ms, its bytes "
        f"need {1e3 * least_s:.3f} ms (experts hit "
        f"{mean([r['moe_experts_hit'] for r in passes]):.1f}, rows visible "
        f"{mean([r['dsa_rows_visible'] for r in passes]):.0f}, selected "
        f"{mean([r['dsa_rows_selected'] for r in passes]):.0f}, of the "
        f"rings {mean([r['ring_rows_read'] for r in passes]):.0f})")
    return 100.0 * least_s * 1e3 / tick_ms
