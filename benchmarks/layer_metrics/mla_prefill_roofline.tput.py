"""The prompt form's kernel's share of the compute peak: the operations
of one layer's causal attention over a prompt of T tokens
(`harness/deepseek_v2_cost.mla_prefill_flops`: the causal half alone) for
each `mla_prefill_t<T>` event that starts in the traced window (the
kernel carries the prompt's length in its name), over the chip's peak
FLOP/s, over the summed device time of those events. The kernel reads
each head's keys and values once: 9 FLOPs a byte at 1,024 tokens would
still be compute-bound only from 2,048 up on a v5e (ridge 240), but the
bytes of q, k, v and o of a 1,024-token layer take 0.2 ms, under the
0.7 ms its operations take, so the compute peak is the roof throughout.
None against a program without the kernel, or a backend without Mosaic."""
from benchmarks.harness.configs import model_shape
from benchmarks.harness.deepseek_v2_cost import mla_prefill_flops
from benchmarks.harness.program_ops import PROMPT_KERNEL


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    shape = model_shape(obs["cell"]["conf"])
    flops = seconds = 0.0
    for name, events in trace["ops"].items():
        m = PROMPT_KERNEL.search(name)
        if m:
            flops += len(events) * mla_prefill_flops(shape, int(m.group(1)))
            seconds += sum(d for _n, _s, d in events) / 1e9
    if not seconds:
        return None
    return 100.0 * flops / obs["cell"]["peaks"]["flops_bf16"] / seconds
