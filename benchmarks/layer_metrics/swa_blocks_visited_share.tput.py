"""What the band leaves of the causal walk: the blocks of scores the
prompt form visited over a prompt, all layers (`attn_blocks` of the
admission's record), over what a causal walk with no window would have
visited (`attn_blocks_causal`), as a mean over the admissions of the
window. By hand at blocks of 512 and a window of 4,096 in three layers
of four: 100% up to 4,608 tokens, 98.6% at 5,120, 61.7% at 15,872. A walk
that stops skipping reads 100. None against a program whose admission
records lack the two counters."""
from benchmarks.harness.loop_records import admissions
from benchmarks.harness.readers import mean


def read(obs):
    met = [a for a in admissions(obs) if a.get("attn_blocks_causal")]
    if not met:
        return None
    return 100.0 * mean([a["attn_blocks"] / a["attn_blocks_causal"]
                         for a in met])
