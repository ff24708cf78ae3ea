"""The selective scan's share of the memory roofline: the bytes ONE
layer's recurrence over a prompt of T tokens has to move
(`harness/jamba_cost.scan_bytes`: u', dt, z, B and C read once, y written
once, A, D and the state in and out once), shared out over the kernel's
calls for that layer (`scan_calls`: the program walks a prompt in blocks
of tokens), for each `selective_scan_t<T>` event that starts in the
traced window (the kernel carries the PROMPT's length in its name), over
the chip's peak bandwidth, over the summed device time of those events.
The work is elementwise on the vector unit, for which the harness has no
peak (`harness/peaks.py`), so this READS LOW by its nature: the log line
gives, by length, the share and the vector operations a second
(`scan_elementwise_ops`) the events reached, which is what to hold a
change to the kernel against. None against a program without the kernel,
or a backend without Mosaic."""
from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.jamba_cost import (scan_bytes, scan_calls,
                                           scan_elementwise_ops, scan_events)


def read(obs):
    lengths = scan_events(obs)
    seconds = sum(took for _n, took in lengths.values())
    if not seconds:
        return None
    shape = model_shape(obs["cell"]["conf"])
    if "token_block" not in shape:
        return None
    peak = obs["cell"]["peaks"]["hbm_bytes_per_s"]
    total = 0.0
    parts = []
    for t, (n, took) in sorted(lengths.items()):
        layers = n / scan_calls(shape, t)       # layer-prompts' worth
        moved = layers * scan_bytes(shape, t)
        total += moved
        parts.append(
            f"t{t} {n} events of {1e3 * took / n:.3f} ms at "
            f"{100.0 * moved / peak / took:.1f}%, "
            f"{layers * scan_elementwise_ops(shape, t) / took / 1e12:.3f} "
            "T vector ops/s")
    log("selective_scan_roofline.tput: " + "; ".join(parts))
    return 100.0 * total / peak / seconds
