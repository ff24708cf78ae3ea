"""As `chip_empty_share.itl`, in a cell judged on tokens per second: of the
time in which a stream decoded, the percentage the chip was starved for by
the engine's own reckoning, beside `device_idle_share.tput`."""
from benchmarks.harness.gap_ledger import empty_share


def read(obs):
    return empty_share(obs, "chip_empty_share.tput")
