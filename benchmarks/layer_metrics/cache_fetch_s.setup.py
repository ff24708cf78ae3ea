"""Seconds of set-up spent fetching programs from the persistent cache:
the sum of `fetch_s` (the entry read, unpacked and loaded:
`/jax/compilation_cache/cache_retrieval_time_sec`) over set-up's records
that were hits."""
from benchmarks.harness import setup_clock


def read(obs):
    parts = setup_clock.split(obs)
    if parts is None:
        return None
    return sum(r["fetch_s"] for r in setup_clock.programs(parts[0])
               if r["hit"])
