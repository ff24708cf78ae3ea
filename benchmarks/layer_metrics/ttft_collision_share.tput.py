"""As `ttft_collision_share.ttft`, in a cell judged on tokens per second:
the percentage of the window's admissions that waited behind another
request's prefill."""
from benchmarks.harness.gap_ledger import collision_share


def read(obs):
    return collision_share(obs)
