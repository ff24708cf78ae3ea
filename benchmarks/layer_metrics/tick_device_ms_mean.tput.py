"""Mean device duration of the `_tick` program in the traced window, in a
cell judged on tokens per second."""
from benchmarks.harness.readers import program_mean_ms


def read(obs):
    return program_mean_ms(obs, "_tick")
