"""95th percentile of `decode_first_token.engine_queue` over requests:
the part of the first token's wait that passes on the engine's own clock
between `submit()` and the pop in `_admit` (`ray_tpu/models/engine.py`),
handed to the flight recorder by the router. It is the wait for a tick
boundary, a free slot and the prefills ahead: what chunked prefill (S3)
would shorten."""
from benchmarks.harness.loop_records import part_ms
from benchmarks.harness.readers import percentile


def read(obs):
    return percentile(part_ms(obs, "decode_first_token.engine_queue"), 95)
