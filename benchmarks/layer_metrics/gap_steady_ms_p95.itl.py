"""95th percentile over the window's stream-gaps that held NO admission:
the plain tick period's tail (a tick on the chip, the lookahead queued
behind it), which is `itl_p95_ms` where admissions are rare."""
from benchmarks.harness.gap_ledger import gaps, steady, stream_gap_percentile


def read(obs):
    return stream_gap_percentile(gaps(obs, steady), 95)
