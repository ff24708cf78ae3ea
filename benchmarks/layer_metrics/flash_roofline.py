"""The flash-attention kernels' share of their roofline: the least time
the chip could take for one layer's attention, forward and backward (the
larger of operations over peak FLOP/s and bytes over peak bytes/s; here
compute holds), a third of it for each `flash_fwd`, `flash_bwd_dq` and
`flash_bwd_dkv` event that starts in the traced window, over the summed
device time of those events."""
from benchmarks.harness.readers import op_count, op_seconds
from benchmarks.harness.roofline import flash_attention_cost, least_seconds

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(obs):
    seconds = op_seconds(obs, KERNELS)
    if not seconds:
        return None
    c = obs["cell"]
    ops, nbytes = flash_attention_cost(
        c["batch"] * c["heads"], c["seq"], c["head_dim"])
    least, _bound = least_seconds(ops, nbytes, c["peaks"])
    return 100.0 * least * op_count(obs, KERNELS) / len(KERNELS) / seconds
