"""The one general traffic generator. A traffic mix is a data file of
parameters; this module turns it, a seed and a window length into the
requests of one run. It imports no JAX: the client process uses it too.

Every seed gets the SAME set of sizes and arrival gaps, in another order:
the multiset of prompt lengths, output lengths and inter-arrival gaps is
fixed by the file and the window, and the seed only shuffles them and
chooses the token values. So two seeds offer the same work, and a run's
numbers differ by the order alone.
"""
from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Sequence

import numpy as np

# the benchmark's own directory, and the checkout that holds it
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(ROOT)
# requests to a block: each block holds the file's shares of lengths and
# gaps, so the mix and the rate are even all through the window
BLOCK = 20


def load_json(kind: str, name: str) -> Dict[str, Any]:
    """`benchmarks/<kind>/<name>.json`, found by the name in
    BENCHMARK.json."""
    path = os.path.join(ROOT, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def apportion(n: int, weights: Sequence[float]) -> List[int]:
    """n items split by weights, largest remainder first: the counts
    always add up to n and never depend on a seed."""
    total = float(sum(weights))
    exact = [n * w / total for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(weights)),
                   key=lambda i: (-(exact[i] - counts[i]), i))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def draw(n: int, dist: Dict[str, Any], rng: np.random.Generator
         ) -> List[int]:
    """n values from {"values": [...], "weights": [...]}: exact shares,
    seeded order."""
    values = [int(v) for v in dist["values"]]
    weights = dist.get("weights") or [1.0] * len(values)
    out: List[int] = []
    for v, c in zip(values, apportion(n, weights)):
        out.extend([v] * c)
    rng.shuffle(out)
    return out


def poisson_gaps(n: int, rate_rps: float) -> List[float]:
    """The n mid-quantiles of the exponential inter-arrival distribution
    at `rate_rps`: a Poisson process's gaps as a fixed set."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate_rps for i in range(n)]


def arrival_offsets(n: int, rate_rps: float, rng: np.random.Generator
                    ) -> List[float]:
    """Seconds from a block's start at which each of its n requests is
    due: the block's exponential gaps, in the seed's order."""
    gaps = poisson_gaps(n, rate_rps)
    rng.shuffle(gaps)
    return [float(x) for x in np.cumsum(gaps)]


def plan(traffic: Dict[str, Any], seed: int, seconds: float
         ) -> Dict[str, Any]:
    """The requests of one run: for an open loop `n = rate x seconds`
    requests with their due times; for a closed loop a pool the clients
    draw from in order, larger than the window can complete.

    The requests come in blocks of BLOCK: every block holds
    the file's shares of lengths and the exponential gaps of a block that
    long, shuffled by the seed inside the block. So the mix and the offered
    rate are even all through the window, whatever the seed, and a run is
    not made slow by a seed that happens to put its long prompts together."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    if traffic["loop"] == "open":
        n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    elif traffic["loop"] == "closed":
        n = max(int(traffic["clients"]) * 4, int(math.ceil(
            float(traffic["pool_requests_per_s"]) * seconds)))
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    prompts: List[int] = []
    outputs: List[int] = []
    due: List[float] = []
    start = 0.0
    for first in range(0, n, BLOCK):
        size = min(BLOCK, n - first)
        prompts += draw(size, traffic["prompt_tokens"], rng)
        outputs += draw(size, traffic["output_tokens"], rng)
        if traffic["loop"] == "open":
            offsets = arrival_offsets(size, float(traffic["rate_rps"]),
                                      rng)
            due += [start + x for x in offsets]
            start += size / float(traffic["rate_rps"])
        else:
            due += [0.0] * size
    return {"n": n, "requests": [
        {"i": i, "due_s": due[i], "prompt_len": prompts[i],
         "max_tokens": outputs[i]} for i in range(n)]}


def prompt_tokens(seed: int, i: int, length: int, vocab_size: int
                  ) -> List[int]:
    """Request i's prompt: unique random tokens, so that no prefix is
    shared."""
    rng = np.random.default_rng([int(seed), 0x70CC, int(i)])
    return [int(t) for t in rng.integers(1, vocab_size, int(length))]


def prompt_lengths(traffic: Dict[str, Any]) -> List[int]:
    return sorted({int(v) for v in traffic["prompt_tokens"]["values"]})
