"""Mean over the window's admissions of the engine's `prefill_ms` per
1,000 prompt tokens (loop ring: the `_prefill_paged` call to the read-back
of its logits, on the engine's own clock): what an admission holds every
live stream for, by prompt length: the chunked scan, the expanded
attention and the experts over a prompt. Adoptions (`prefill_ms` 0) are
left out. None against a program without the ring."""
from benchmarks.harness.loop_records import admissions
from benchmarks.harness.readers import mean


def read(obs):
    return mean([1e3 * a["prefill_ms"] / a["prompt_tokens"]
                 for a in admissions(obs)
                 if a["prefill_ms"] > 0 and a["prompt_tokens"]])
