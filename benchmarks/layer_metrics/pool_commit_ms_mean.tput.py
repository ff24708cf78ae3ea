"""Mean `commit_ms` over the admissions of the window: how long
`PagedKVCache.commit` holds the engine's thread for one prompt, dispatching
an `_extract_block` and a `_write_block` per 16-token block (S2)."""
from benchmarks.harness.loop_records import admissions
from benchmarks.harness.readers import mean


def read(obs):
    return mean([a["commit_ms"] for a in admissions(obs)])
