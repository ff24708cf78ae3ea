"""What of the grouped-query selected prompt form's time its SELECTED
pairs need: the least time ONE layer's attention over the `min(t + 1,
topk)` rows each query attends needs (`harness/gqa_dsa_cost.selected_flops`
and `selected_bytes`: every query head's pairs, the keys and values of the
heads they share read once), shared out over the kernel's calls for that
layer (`dsa_cost.selected_calls`: `head_group` query heads a call), for
each `gqa_selected_t<T>` event that starts in the traced window (the
kernel carries the PROMPT's length in its name), over the summed device
time of those events. The first form computes every visible block of
scores and masks the unselected pairs, so this reads about `selected pairs
/ visible pairs` of what the same kernel reaches on dense attention, lower
the longer the prompt: the room a form that gathers the selected rows has.
None against a program without the kernel, or a backend without Mosaic."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.dsa_cost import selected_calls
from benchmarks.harness.gqa_dsa_cost import (kernel_events, selected_bytes,
                                             selected_flops)
from benchmarks.harness.roofline import least_seconds


def read(obs):
    lengths = kernel_events(obs, "selected")
    seconds = sum(took for _n, took in lengths.values())
    shape = model_shape(obs["cell"]["conf"])
    if not seconds or "kv_heads" not in shape or "index_keep" not in shape:
        return None
    least, parts = 0.0, []
    for t, (n, took) in sorted(lengths.items()):
        layers = n / selected_calls(shape)      # layer-prompts' worth
        need, bound = least_seconds(selected_flops(shape, t),
                                    selected_bytes(shape, t),
                                    obs["cell"]["peaks"])
        least += layers * need
        parts.append(f"t{t} {n} events of {1e3 * took / n:.3f} ms at "
                     f"{100.0 * layers * need / took:.1f}% ({bound})")
    log("gqa_selected_roofline.tput: " + "; ".join(parts))
    return 100.0 * least / seconds
