"""Mean `gap_empty_ms` over the window's gaps that held an admission (each
gap once, not once a stream): the chip's starved time an admission, from
the return of the read of the prefill's logits, behind which nothing was
launched, to the return of the next `_tick`'s launch: the host's chain of
first token, splice, `_set_rows` and dispatch."""
from benchmarks.harness.gap_ledger import gaps, held_admission
from benchmarks.harness.readers import mean


def read(obs):
    return mean([r["gap_empty_ms"] for r in gaps(obs, held_admission)])
