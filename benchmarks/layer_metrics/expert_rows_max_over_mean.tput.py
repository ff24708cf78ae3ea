"""Over the prefills of the window: the most rows one held expert got
over a prompt in one expert layer (`moe_rows_max` of the admission's
record) over the mean rows an expert got (`moe_pairs_held` spread over
the expert layers and the experts held, which the family's `shape()`
gives), averaged over the admissions. 1.0 is an even spread; the fullest
expert sets the grouped product's time. None against a program whose
admission records lack the two counters."""
from benchmarks.harness.configs import model_shape
from benchmarks.harness.loop_records import admissions
from benchmarks.harness.readers import mean


def read(obs):
    met = [a for a in admissions(obs) if a.get("moe_pairs_held")]
    if not met:
        return None
    shape = model_shape(obs["cell"]["conf"])
    groups = shape["expert_layers"] * shape["experts_held"]
    return mean([a["moe_rows_max"] * groups / a["moe_pairs_held"]
                 for a in met])
