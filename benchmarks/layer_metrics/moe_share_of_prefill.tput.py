"""What of a prefill the routed experts are: the summed device time of
the operations the program names `moe` (the router, the sort of the
token-expert pairs, the gather, both grouped products, the scatter and
the weighted sum) inside the whole `_prefill_paged` events of the traced
window, over those events' own time, in per cent
(`harness/granite_hybrid_cost.prefill_share`). None without a device
trace, or against a program that names no such scope."""
from benchmarks.harness.granite_hybrid_cost import prefill_share


def read(obs):
    return prefill_share(obs, "moe")
