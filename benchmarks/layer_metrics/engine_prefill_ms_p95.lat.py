"""95th percentile of `decode_first_token.engine_prefill` over requests:
the part of the first token's wait from the pop in `_admit` to the first
token's `_emit` on the engine's own clock (`ray_tpu/models/engine.py`):
this request's own lookup, prefill, pool commit and splice, what S2 would
shorten. With the queue part it sums to `decode_first_token` less the
wake-up of the router's thread."""
from benchmarks.harness.loop_records import part_ms
from benchmarks.harness.readers import percentile


def read(obs):
    return percentile(part_ms(obs, "decode_first_token.engine_prefill"), 95)
