"""The decode tick's share of its memory roofline where a slot owns a
per-channel recurrence state beside its rows of keys and values: the
least time the bytes of a tick need at the chip's peak bandwidth
(`harness/jamba_cost.tick_bytes`: every parameter once, the tied
embedding once as the head, for each live slot its state read AND written
and its `position` rows), over the mean device time of the `_tick`
program in the traced window. What the tick met comes from the engine's
loop ring (`live`, `live_rows`), as means over the decode passes of the
window of offered load, in which the trace's three seconds lie (the
trace's clock is not the ring's). The program steps every slot's state
and reads every row whatever is live, so dead slots read as lost time: a
lower reading. None against a program whose ring lacks the counters, or
in a cell whose family has no state of this kind."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.jamba_cost import tick_bytes
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean, program_mean_ms


def read(obs):
    tick_ms = program_mean_ms(obs, "_tick")
    passes = [r for r in decoding(obs) if "live_rows" in r]
    shape = model_shape(obs["cell"]["conf"])
    if not tick_ms or not passes or "float32_params" not in shape:
        return None
    live = mean([r["live"] for r in passes])
    rows = mean([r["live_rows"] for r in passes])
    least_s = tick_bytes(shape, live, rows) \
        / obs["cell"]["peaks"]["hbm_bytes_per_s"]
    log(f"ssm_tick_bytes_roofline.tput: tick {tick_ms:.3f} ms, its bytes "
        f"need {1e3 * least_s:.3f} ms ({live:.2f} slots live with "
        f"{rows:.0f} rows, {shape['state_bytes']} B of state a slot)")
    return 100.0 * least_s * 1e3 / tick_ms
