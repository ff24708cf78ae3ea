"""Seconds of set-up the backend spent compiling: the sum of `backend_s`
over set-up's records the cache did not hold. 0.0 on a warm machine (where
`compile_cache_misses.setup` is 0), most of `first_setup_s` on a cold
one."""
from benchmarks.harness import setup_clock


def read(obs):
    parts = setup_clock.split(obs)
    if parts is None:
        return None
    return sum(r["backend_s"] for r in setup_clock.programs(parts[0])
               if not r["hit"])
