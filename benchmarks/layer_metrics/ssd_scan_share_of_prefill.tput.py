"""What of a prefill the chunked Mamba-2 scan is: the summed device time
of the operations the program names `ssd_scan` (inside `mamba2`, around
the scan alone: the chunks' decay terms, the three batched products a
chunk and the carried state, whatever implements them) inside the whole
`_prefill_paged` events of the traced window, over those events' own
time, in per cent (`harness/granite_hybrid_cost.prefill_share`, which
says how an operation is told to be the scan's). None without a device
trace, or against a program that names no such scope."""
from benchmarks.harness.granite_hybrid_cost import prefill_share


def read(obs):
    return prefill_share(obs, "ssd_scan")
