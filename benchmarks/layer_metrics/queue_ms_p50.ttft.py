"""Median over requests of `qos_admission` + `queue_reserve` (flight
recorder, host clock)."""
from benchmarks.harness.readers import percentile, phase_ms


def read(obs):
    return percentile(phase_ms(obs, ("qos_admission", "queue_reserve")), 50)
