"""Median host-clock time of a step, from the call to
`block_until_ready` on its loss."""
from benchmarks.harness.readers import percentile


def read(obs):
    steps = (obs.get("train") or {}).get("step_s") or []
    return percentile([1e3 * s for s in steps], 50)
