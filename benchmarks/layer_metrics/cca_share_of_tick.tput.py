"""What of a decode tick the compressed convolutional attention is: the
summed device time of the operations the program names `cca` (the
sublayer's norm, the in-projection, the two convolutions, the query-key
mean, the normalisation and the rotation, the write of the row, the walk
`gqa_decode_t1`, the out-projection and the residual-scaled sum) inside
the whole `_tick` events of the traced window, over those events' own
time, in per cent (`harness/zaya_cost.tick_share`, which says how an
operation is told to be the attention's). None without a device trace,
or against a program that names no such scope."""
from benchmarks.harness.zaya_cost import tick_share


def read(obs):
    return tick_share(obs, "cca")
