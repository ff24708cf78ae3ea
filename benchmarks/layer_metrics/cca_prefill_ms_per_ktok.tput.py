"""Device milliseconds the prompt form of the compressed convolutional
attention takes a 1,000 prompt tokens: the summed device time of the
operations the program names `cca` inside the whole `_prefill_paged`
events of the traced window, over those prefills' prompt tokens
(`harness/zaya_cost.prefill_scope_ms_per_ktok`: each prompt length is a
program of its own and its operations are told by its own compiled
text). None without a device trace, or against a program that names no
such scope."""
from benchmarks.harness.zaya_cost import prefill_scope_ms_per_ktok


def read(obs):
    return prefill_scope_ms_per_ktok(obs, "cca")
