"""The client's median time to first token, from the due time, recorded
and not judged: in a closed loop over prompts of 1,024 to 8,192 tokens a
first token waits for whole prefills ahead of it, each 50 to 600 ms, so
the quantiles stand on stairs (PERF.md section 6, PR 32) and move with the
order the seed gives the prompts."""
from benchmarks.harness.readers import percentile, ttft_ms


def read(obs):
    return percentile(ttft_ms(obs), 50)
