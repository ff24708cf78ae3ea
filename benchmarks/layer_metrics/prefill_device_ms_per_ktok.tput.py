"""Device time of `_prefill_paged` in the traced window per 1,000 prompt
tokens prefilled in it. The tokens are the engine's `prefilled_tokens`
counter read at the two window markers, so a prefill that straddles an
edge is counted on one side only."""
from benchmarks.harness.readers import counter, program_events


def read(obs):
    events = program_events(obs, "_prefill_paged")
    tokens = counter(obs, "prefilled_tokens_in_trace")
    if not events or not tokens:
        return None
    return sum(d for _n, _s, d in events) / 1e6 / tokens * 1e3
