"""95th percentile of `decode_first_token` (flight recorder, host clock):
from the router's submit to the first token, which in a colocated engine
is the wait in the engine's own queue plus the prefill. One prefill is
admitted a tick. It is the larger part of the time to first token, which
`request_ms_per_token` holds whole."""
from benchmarks.harness.readers import percentile, phase_ms


def read(obs):
    return percentile(phase_ms(obs, ("decode_first_token",)), 95)
