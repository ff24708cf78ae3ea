"""Programs handed to the backend (compiled or fetched) between the first
and the last request of the window: `compile_cache_counts()["compiles"]`
after minus before. Predicted 0."""
from benchmarks.harness.readers import counter


def read(obs):
    return counter(obs, "compiles_in_window")
