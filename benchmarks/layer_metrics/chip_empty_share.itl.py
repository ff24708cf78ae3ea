"""Of the time in which a stream decoded (the sum of `gap_ms` over the
window of offered load), the percentage the chip was starved for by the
engine's own reckoning (`gap_empty_ms`): the idle share as the loop sees
it over the whole window, beside `device_idle_share.itl`'s traced 3 s. It
counts the small programs of an admission's chain as starved and cannot
see launch-to-launch time."""
from benchmarks.harness.gap_ledger import empty_share


def read(obs):
    return empty_share(obs, "chip_empty_share.itl")
