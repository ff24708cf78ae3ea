"""95th percentile over the window's stream-gaps (`harness/gap_ledger.py`:
the time between two landings of a tick on the `cb-engine` thread, counted
once a request that took a token from both): `itl_p95_ms` as the engine
made it. The client's own less this is what the router's pulls, the
gateway and the SSE writer add."""
from benchmarks.harness.gap_ledger import gaps, stream_gap_percentile


def read(obs):
    return stream_gap_percentile(gaps(obs), 95)
