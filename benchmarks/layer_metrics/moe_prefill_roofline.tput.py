"""The grouped products of the expert layers over a prompt, against their
roofline: for each `_prefill_paged` program that ran whole inside the
traced window, the least time its expert layers' products need (the
larger of `moe_prefill_flops` over the peak FLOP/s and
`moe_prefill_bytes` over the peak bandwidth,
`harness/deepseek_v2_cost.py`: compute holds from some 6,000 tokens up,
where a held expert sees 240 rows), summed, over the summed device time
of the `ragged-dot` operations that started inside those programs (the
tick's are left out by where they start). A program's prompt length is
the one its prompt-form kernel carries in its name; what its grouped
products met (`moe_pairs_held`, `moe_experts_hit`) is the mean over the
window's admissions of that length in the engine's loop ring (the trace's
clock is not the ring's, and the counts of two prompts of one length
differ by a few percent). None against a program without the counters or
the kernel."""
from benchmarks.harness import program_ops
from benchmarks.harness.configs import model_shape
from benchmarks.harness.deepseek_v2_cost import (moe_prefill_bytes,
                                                 moe_prefill_flops)
from benchmarks.harness.loop_records import admissions
from benchmarks.harness.readers import mean
from benchmarks.harness.roofline import least_seconds


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    grouped = program_ops.named(trace, ["ragged-dot"])
    kernels = program_ops.named(trace, ["mla_prefill_t"])
    met = [a for a in admissions(obs) if a.get("moe_pairs_held")]
    shape = model_shape(obs["cell"]["conf"])
    least = seconds = 0.0
    for prog in program_ops.whole_programs(trace, "_prefill_paged"):
        tokens = program_ops.prompt_tokens(program_ops.inside(kernels, prog))
        same = [a for a in met if a["prompt_tokens"] == tokens]
        products = program_ops.inside(grouped, prog)
        if not same or not products:
            continue
        pairs = mean([a["moe_pairs_held"] for a in same])
        hit = mean([a["moe_experts_hit"] for a in same])
        least += least_seconds(moe_prefill_flops(shape, pairs),
                               moe_prefill_bytes(shape, pairs, hit),
                               obs["cell"]["peaks"])[0]
        seconds += sum(d for _n, _s, d in products) / 1e9
    if not seconds:
        return None
    return 100.0 * least / seconds
