"""Over the decode passes of the window: the most rows one held expert
got in a tick (`moe_rows_max`, the largest group of any expert layer's
grouped product) over the mean rows an expert got (`moe_pairs_held`
spread over the expert layers and the experts held, which the family's
`shape()` gives), averaged over the passes. 1.0 is an even spread; the
grouped product's tiles are padded to, and its time is set by, the
fullest expert. None against a program without the two counters."""
from benchmarks.harness.configs import model_shape
from benchmarks.harness.loop_records import decoding
from benchmarks.harness.readers import mean


def read(obs):
    passes = [r for r in decoding(obs) if r.get("moe_pairs_held")]
    if not passes:
        return None
    shape = model_shape(obs["cell"]["conf"])
    groups = shape["expert_layers"] * shape["experts_held"]
    return mean([r["moe_rows_max"] * groups / r["moe_pairs_held"]
                 for r in passes])
