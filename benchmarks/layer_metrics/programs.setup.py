"""Programs handed to the backend (compiled or fetched) during set-up:
the records of `compile_cache.compile_cache_programs()` that
`setup_clock`'s rule gives to set-up, `cache_at_setup["compiles"]` of the
run's record file. Logs the name and `t0` of every record AFTER set-up
beside the requests whose flight-recorder trace covers it: what
`compiles_in_window.*` counts and cannot name."""
from benchmarks.harness import setup_clock
from benchmarks.harness.common import log


def read(obs):
    parts = setup_clock.split(obs)
    if parts is None:
        return None
    setup, after = parts
    summaries = setup_clock.measured(obs)
    for r in setup_clock.programs(after):
        rids = setup_clock.covering(r, summaries)
        log(f"programs.setup: AFTER set-up {setup_clock.describe(r)} "
            f"at t0 {r['t0']:.3f} on {r['thread']}, under requests "
            f"{', '.join(rids) or 'none'}")
    return len(setup_clock.programs(setup))
