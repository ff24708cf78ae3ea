"""Mean `gap_ms` over the window's stream-gaps that held an admission: what
an admission costs every stream that decodes through it, whatever share of
the gaps it is (a percentile sits on, under or over that step; this mean
does not move with the share)."""
from benchmarks.harness.gap_ledger import (gaps, held_admission,
                                           stream_gap_mean)


def read(obs):
    return stream_gap_mean(gaps(obs, held_admission))
