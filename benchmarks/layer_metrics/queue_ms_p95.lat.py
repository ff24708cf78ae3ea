"""95th percentile over requests of `qos_admission` + `queue_reserve`
(flight recorder, host clock): the wait before the router hands a request
to the engine. Under 1 ms in a colocated deployment, where the router
reserves or sheds and never queues; kept so that a front door that starts
to hold requests shows."""
from benchmarks.harness.readers import percentile, phase_ms


def read(obs):
    return percentile(phase_ms(obs, ("qos_admission", "queue_reserve")), 95)
