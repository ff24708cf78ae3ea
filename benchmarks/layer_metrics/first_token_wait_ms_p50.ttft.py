"""Median of `decode_first_token` (flight recorder, host clock): the wait
for a prefill turn plus the prefill."""
from benchmarks.harness.readers import percentile, phase_ms


def read(obs):
    return percentile(phase_ms(obs, ("decode_first_token",)), 50)
