"""Seconds the process spent importing `ray_tpu` and `ray_tpu.models`
(`compile_cache.import_spans()`, each package's `__init__.py` from its
first line to its last, jax included where nothing imported it before):
the union of the two spans, so a package imported inside the other counts
once."""
from benchmarks.harness import setup_clock


def read(obs):
    return setup_clock.import_s()
