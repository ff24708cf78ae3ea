"""The grouped-query prompt kernel's share of the compute peak: the
operations of one layer's attention over a prompt of T tokens under a
window of W (`harness/smallthinker_cost.gqa_prefill_flops`: the visible
pairs alone, the causal half for `w0`, the band for a window) for each
`gqa_prefill_w<W>_t<T>` event that starts in the traced window (the
kernel carries both in its name), over the chip's peak FLOP/s, over the
summed device time of those events. The kernel reads a key-value head's
keys and values once for its seven query heads, so the compute peak is
the roof at every length served. What it computes and masks in the
blocks the diagonal or the band's edge crosses reads as lost time. None
against a program without the kernel, or a backend without Mosaic."""
import re

from benchmarks.harness.common import log
from benchmarks.harness.configs import model_shape
from benchmarks.harness.smallthinker_cost import gqa_prefill_flops

KERNEL = re.compile(r"gqa_prefill_w(\d+)_t(\d+)")


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    shape = model_shape(obs["cell"]["conf"])
    by_kind = {}          # kernel name -> [events, seconds, operations]
    for name, events in trace["ops"].items():
        m = KERNEL.search(name)
        if m:
            kind = by_kind.setdefault(m.group(0), [0, 0.0, 0.0])
            kind[0] += len(events)
            kind[1] += sum(d for _n, _s, d in events) / 1e9
            kind[2] += len(events) * gqa_prefill_flops(
                shape, int(m.group(2)), int(m.group(1)))
    seconds = sum(took for _n, took, _w in by_kind.values())
    if not seconds:
        return None
    flops = sum(work for _n, _t, work in by_kind.values())
    peak = obs["cell"]["peaks"]["flops_bf16"]
    log("gqa_prefill_roofline.tput: " + "; ".join(
        f"{kind} {n} events of {1e3 * took / n:.3f} ms at "
        f"{100.0 * work / peak / took:.1f}%"
        for kind, (n, took, work) in sorted(by_kind.items())))
    return 100.0 * flops / peak / seconds
