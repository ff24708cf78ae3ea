"""Of the window's admissions, the percentage whose request waited behind
another's prefill (`admissions[].prefills_waited` >= 1: the engine's count
of prefills admitted between the request's hand-in and its own pop). In a
closed loop a time to first token is a whole number of prefill turns; this
share decides which stair `ttft_p75_ms` stands on."""
from benchmarks.harness.gap_ledger import collision_share


def read(obs):
    return collision_share(obs)
