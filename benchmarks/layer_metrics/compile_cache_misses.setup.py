"""Persistent-cache misses (programs compiled, not fetched) at the end of
set-up: 0 in every run but the first in a checkout."""
from benchmarks.harness.readers import counter


def read(obs):
    return counter(obs, "cache_misses_setup")
