"""1 - busy_s / window_s of the traced window, in percent."""
from benchmarks.harness.readers import idle_share


def read(obs):
    return idle_share(obs)
