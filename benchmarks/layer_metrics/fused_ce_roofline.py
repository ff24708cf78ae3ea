"""The fused cross-entropy kernels' share of their roofline: the least
time the chip could take for the logits, dx and dW of one step (compute
holds), a third of it for each `fused_ce_fwd`, `fused_ce_dx` and
`fused_ce_dw` event that starts in the traced window, over the summed
device time of those events."""
from benchmarks.harness.readers import op_count, op_seconds
from benchmarks.harness.roofline import fused_ce_cost, least_seconds

KERNELS = ("fused_ce_fwd", "fused_ce_dx", "fused_ce_dw")


def read(obs):
    seconds = op_seconds(obs, KERNELS)
    if not seconds:
        return None
    c = obs["cell"]
    ops, nbytes = fused_ce_cost(c["batch"] * c["seq"], c["d_model"],
                                c["vocab"])
    least, _bound = least_seconds(ops, nbytes, c["peaks"])
    return 100.0 * least * op_count(obs, KERNELS) / len(KERNELS) / seconds
