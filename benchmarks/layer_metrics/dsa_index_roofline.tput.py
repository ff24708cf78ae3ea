"""The indexer's share of its roofline over the prompts of the window:
the least time ONE full layer's index score over a prompt of T tokens
needs (`harness/dsa_cost.index_flops` and `index_bytes`: the visible
pairs alone; the larger of operations over the compute peak and bytes
over the bandwidth), shared out over the kernel's calls for that layer
(`index_calls`: a block of queries a call), for each `dsa_index_t<T>`
event that starts in the traced window (the kernel carries the PROMPT's
length in its name), over the summed device time of those events. The
matrix unit does 2 x 64 x 128 operations a pair and the vector unit a
ReLU, a product and a sum for each of the 64 heads, which the harness has
no peak for and does not count: a lower reading. None against a program
without the kernel, or a backend without Mosaic."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.dsa_cost import (index_bytes, index_calls,
                                         index_flops, kernel_events)
from benchmarks.harness.roofline import least_seconds


def read(obs):
    lengths = kernel_events(obs, "index")
    seconds = sum(took for _n, took in lengths.values())
    shape = model_shape(obs["cell"]["conf"])
    if not seconds or "index_block" not in shape:
        return None
    least, parts = 0.0, []
    for t, (n, took) in sorted(lengths.items()):
        layers = n / index_calls(shape, t)      # layer-prompts' worth
        need, bound = least_seconds(index_flops(shape, t),
                                    index_bytes(shape, t),
                                    obs["cell"]["peaks"])
        least += layers * need
        parts.append(f"t{t} {n} events of {1e3 * took / n:.3f} ms at "
                     f"{100.0 * layers * need / took:.1f}% ({bound})")
    log("dsa_index_roofline.tput: " + "; ".join(parts))
    return 100.0 * least / seconds
