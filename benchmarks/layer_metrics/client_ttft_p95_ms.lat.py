"""The client's 95th percentile of time to first token, from the due time,
recorded and not judged: over the 163 to 530 requests of a 51 s window it
spreads by 8 to 15% between runs of the same code (PERF.md section 2),
more than any bound may allow. Every request's time to first token is part
of `request_ms_per_token`, which is judged."""
from benchmarks.harness.readers import percentile, ttft_ms


def read(obs):
    return percentile(ttft_ms(obs), 95)
