"""Mean host-clock time a step waited for its batch (the benchmark's own
span around fetching the next batch from the input pipeline)."""
from benchmarks.harness.readers import mean


def read(obs):
    waits = (obs.get("train") or {}).get("data_wait_s") or []
    return mean([1e3 * s for s in waits])
