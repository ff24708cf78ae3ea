"""Mean `commit_dispatches` over the admissions of the window: the
programs one pool commit launches (each block's extract, and its write or
copy-on-write). A count: it depends on the prompts admitted and not on
the clock."""
from benchmarks.harness.loop_records import admissions
from benchmarks.harness.readers import mean


def read(obs):
    return mean([a["commit_dispatches"] for a in admissions(obs)])
