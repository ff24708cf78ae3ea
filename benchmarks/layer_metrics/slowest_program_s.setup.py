"""The most one program cost set-up: the largest `trace_s + lower_s +
backend_s` of one of set-up's records. Logs the five largest by name with
their three parts."""
from benchmarks.harness import setup_clock
from benchmarks.harness.common import log


def read(obs):
    parts = setup_clock.split(obs)
    if parts is None:
        return None
    ranked = sorted(setup_clock.programs(parts[0]),
                    key=setup_clock.total_s, reverse=True)
    if not ranked:
        return None
    log("slowest_program_s.setup: " + "; ".join(
        setup_clock.describe(r) for r in ranked[:5]))
    return setup_clock.total_s(ranked[0])
