"""Of the window's stream-gaps, the percentage whose gap held an admission
(`gap_admissions` >= 1: a prefill, or an adoption's splice, was launched
between the two landings). Over 5, `itl_p95_ms` stands on the admissions'
step (a tick's rest plus a prefill plus a tick); under it, on the plain
tick period's tail; near it, the p95 moves with the seed."""
from benchmarks.harness.gap_ledger import gaps, held_admission, streams


def read(obs):
    every = streams(gaps(obs))
    if not every:
        return None
    return 100.0 * streams(gaps(obs, held_admission)) / every
